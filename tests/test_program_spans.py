"""The program's own spans and scopes (docs/observability.md "Spans"): one
store behind `profiler.scope`, the `mx.*` names placed where the work
happens, and the scope map of the compiled step."""

import collections
import gc
import importlib
import json
import logging
import os
import re
import sys
import threading
import time
import weakref

import jax
import pytest

from mxnet_tpu import profiler
from mxnet_tpu.io.device_prefetch import DevicePrefetcher

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "benchmark_suite", "fixtures")
if REPO not in sys.path:
    sys.path.insert(0, REPO)


# -- the store ----------------------------------------------------------------
def test_a_span_records_its_parent_and_its_thread():
    t0 = time.perf_counter()
    with profiler.scope("t.parent") as parent:
        with profiler.scope("t.child"):
            pass
        other = threading.Thread(
            target=lambda: profiler.scope("t.elsewhere").__enter__()
            .__exit__(None, None, None))
        other.start()
        other.join()
    got = {s.name: s for s in profiler.spans(since=t0)
           if s.name.startswith("t.")}
    assert set(got) == {"t.parent", "t.child", "t.elsewhere"}
    assert got["t.child"].parent == got["t.parent"].id
    assert got["t.parent"].parent is None
    # a span on another thread is no child of what this thread has open
    assert got["t.elsewhere"].parent is None
    assert got["t.child"].thread == got["t.parent"].thread == \
        threading.get_ident() != got["t.elsewhere"].thread
    assert got["t.parent"].start <= got["t.child"].start <= \
        got["t.child"].end <= got["t.parent"].end
    assert (parent.start, parent.end) == (got["t.parent"].start,
                                          got["t.parent"].end)


def test_the_ring_is_bounded_and_the_oldest_leave_first():
    with profiler.scope("t.first"):
        pass
    for _ in range(profiler.SPAN_RING):
        with profiler.scope("t.filler"):
            pass
    kept = profiler.spans()
    assert len(kept) == profiler.SPAN_RING
    # (a full collection may fall among them: it is a span too)
    assert {s.name for s in kept} - {"mx.gc"} == {"t.filler"}
    # oldest first: in the order they closed
    assert [s.end for s in kept] == sorted(s.end for s in kept)
    profiler.reset()
    assert profiler.spans() == []


def test_a_span_closes_on_an_exception():
    t0 = time.perf_counter()
    with pytest.raises(KeyError):
        with profiler.scope("t.raises"):
            with profiler.scope("t.inner"):
                raise KeyError("x")
    names = [s.name for s in profiler.spans(since=t0)
             if s.name.startswith("t.")]
    assert names == ["t.inner", "t.raises"]
    # ... and left nothing open on this thread
    with profiler.scope("t.after"):
        pass
    assert profiler.spans()[-1].parent is None


def test_since_keeps_the_spans_that_ended_after_it():
    with profiler.scope("t.before"):
        pass
    cut = time.perf_counter()
    with profiler.scope("t.later"):
        pass
    names = [s.name for s in profiler.spans(since=cut)
             if s.name.startswith("t.")]
    assert names == ["t.later"]


def test_record_span_writes_the_same_store_only_while_running():
    profiler.reset()
    profiler.record_span("t.off", "operator", 1.0, 2.0)
    assert [s for s in profiler.spans() if s.name == "t.off"] == []
    profiler.set_config(filename="/tmp/_spans_unused.json")
    profiler.set_state("run")
    try:
        with profiler.scope("t.open"):
            profiler.record_span("t.on", "operator", 1.0, 2.5,
                                 args={"k": 1})
    finally:
        profiler.set_state("stop")
    got = {s.name: s for s in profiler.spans()}
    assert (got["t.on"].start, got["t.on"].end, got["t.on"].cat,
            got["t.on"].args) == (1.0, 2.5, "operator", {"k": 1})
    assert got["t.on"].parent == got["t.open"].id
    assert "t.on" in profiler.dumps() and "t.open" in profiler.dumps()


def test_dump_still_writes_spans_counters_and_markers(tmp_path):
    fn = str(tmp_path / "trace.json")
    profiler.reset()
    profiler.set_config(filename=fn)
    profiler.set_state("run")
    with profiler.scope("t.user_block"):
        pass
    with profiler.Task("t.task"):
        pass
    profiler.Counter("t.counter", value=3)
    profiler.Marker("t.marker").mark()
    assert profiler.dump() == fn
    with open(fn) as f:
        events = json.load(f)["traceEvents"]
    by_name = {e["name"]: e for e in events}
    assert by_name["t.user_block"]["ph"] == by_name["t.task"]["ph"] == "X"
    assert by_name["t.task"]["cat"] == "task"
    assert by_name["t.counter"]["ph"] == "C" and \
        by_name["t.counter"]["args"] == {"t.counter": 3}
    assert by_name["t.marker"]["ph"] == "i"
    assert any(e["name"].startswith("metrics/") for e in events)
    for e in events:
        assert "ts" in e and "ph" in e and "pid" in e
    x = by_name["t.user_block"]
    assert x["dur"] >= 0 and x["tid"] == threading.get_ident()


def test_a_full_collection_is_a_span_and_a_young_one_is_not():
    t0 = time.perf_counter()
    gc.collect(0)
    assert [s for s in profiler.spans(since=t0) if s.name == "mx.gc"] == []
    gc.collect()
    full = [s for s in profiler.spans(since=t0) if s.name == "mx.gc"]
    assert len(full) == 1 and full[0].end >= full[0].start >= t0
    assert full[0].thread == threading.get_ident()
    assert "collected" in full[0].args


def test_the_package_records_its_import():
    # (the ring may have been reset by an earlier test of this process:
    # ask a fresh interpreter)
    import subprocess
    code = ("import mxnet_tpu as mx; from mxnet_tpu import profiler; "
            "mx.cpu(0).jax_device; "
            "print([(s.name, s.end > s.start) for s in profiler.spans() "
            "if s.cat == 'setup'])")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == \
        "[('mx.import', True), ('mx.backend_init', True)]"


def test_a_span_lies_on_the_profilers_host_plane_while_it_traces(tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with profiler.scope("mx.test.annotated"):
            jax.numpy.ones((8,)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    import glob
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                         "*.xplane.pb"))[-1]
    names = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert "mx.test.annotated" in names


def test_a_failed_device_trace_is_said_not_swallowed(monkeypatch, caplog):
    def refuse(_dir):
        raise RuntimeError("no profiler here")
    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    profiler.set_config(filename="/tmp/_spans_unused.json",
                        profile_device=True)
    try:
        with caplog.at_level(logging.WARNING, logger="mxnet_tpu.profiler"):
            profiler.set_state("run")
        assert profiler.is_running()
    finally:
        profiler.set_state("stop")
        profiler.set_config(profile_device=False)
    said = [r.getMessage() for r in caplog.records]
    assert len(said) == 1 and "no device trace" in said[0] and \
        "RuntimeError: no profiler here" in said[0]


# -- the trainer's spans and the step's scope map -----------------------------
def _fixture_trainer(name):
    from benchmarks.models import common as models_common
    from benchmarks.reference import common as ref_common

    with open(os.path.join(FIXTURES, name + ".json")) as f:
        cfg = json.load(f)
    family = importlib.import_module("benchmarks.models." + cfg["family"])
    table = family.reference.param_table(cfg)
    net, loss = family.build(cfg)
    models_common.seeded_net(net, table, ref_common.init_params(table, 5))
    trainer = models_common.make_trainer(net, loss, cfg["train"],
                                         jax.devices()[:1])
    batches = family.batches(cfg, 5, 3, cfg["train"]["per_chip_batch"])
    return trainer, batches


@pytest.fixture(scope="module", params=["tiny_resnet", "tiny_lm"])
def built(request):
    """A fixture trainer after its first step, with the text its scope map
    was parsed from and the spans of set-up."""
    texts = []
    keep = profiler.set_scope_map

    def capture(program, text, *xla):
        texts.append((program, text, xla))
        keep(program, text, *xla)
    profiler.set_scope_map = capture
    t0 = time.perf_counter()
    try:
        trainer, batches = _fixture_trainer(request.param)
        trainer.fit_batch(*batches[0])
    finally:
        profiler.set_scope_map = keep
    assert [t[0] for t in texts] == ["parallel_step"]
    return {"trainer": trainer, "batches": batches, "text": texts[0][1],
            "xla": texts[0][2],
            "map": dict(profiler.scope_map("parallel_step")),
            "setup": profiler.spans(since=t0)}


def test_set_up_yields_the_named_spans_and_the_first_calls_split(built):
    names = collections.Counter(
        s.name for s in built["setup"] if s.cat == "setup")
    assert names["mx.initialize"] >= len(built["trainer"].param_names)
    for once in ("mx.trainer.trace", "mx.trainer.gather_state",
                 "mx.trainer.build_step", "mx.step.first_call"):
        assert names[once] == 1, once
    by_name = {s.name: s for s in built["setup"]}
    first = by_name["mx.step.first_call"]
    # the first fit_batch holds the build and the first call, and no
    # dispatch span of the steady path
    assert first.parent == by_name["mx.fit_batch"].id
    assert by_name["mx.trainer.trace"].parent == by_name["mx.fit_batch"].id
    assert "mx.fit_batch.dispatch" not in by_name
    assert set(first.args) == {
        "jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
        "backend_compile_duration", "cache_retrieval_time_sec",
        "lower_s", "call_s", "scope_map_s"}
    assert first.args["jaxpr_to_mlir_module_duration"] > 0
    assert first.args["lower_s"] + first.args["call_s"] + \
        first.args["scope_map_s"] == pytest.approx(
            first.end - first.start, rel=0.05)
    # the text is read from the executable the call built: no second
    # lowering, no second compile (each would dwarf this)
    assert first.args["scope_map_s"] < 0.5 * (first.end - first.start)


def test_a_step_after_warm_up_is_one_dispatch_and_the_named_spans(built):
    trainer, batches = built["trainer"], built["batches"]
    feed = DevicePrefetcher(_ring(batches), depth=2, mesh=trainer.mesh)
    events = []

    def listen(event, _secs, **_):
        events.append(event)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        batch = next(feed)
        trainer.fit_batch(batch.data[0], batch.label[0])
        time.sleep(0.2)             # the producer refills the ring
        del events[:]
        dispatched = profiler.counter_value("parallel_step_dispatches")
        compiled = profiler.counter_value("parallel_step_compiles")
        t0 = time.perf_counter()
        batch = next(feed)
        loss = trainer.fit_batch(batch.data[0], batch.label[0])
        here = [s for s in profiler.spans(since=t0)
                if s.thread == threading.get_ident()]
        assert float(loss) == float(loss)
    finally:
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(listen)
        feed.close()
    assert profiler.counter_value("parallel_step_dispatches") \
        == dispatched + 1
    assert profiler.counter_value("parallel_step_compiles") == compiled
    assert not [e for e in events if "compile" in e]
    assert [s.name for s in here] == [
        "mx.prefetch.wait", "mx.fit_batch.dispatch", "mx.fit_batch"]
    wait, dispatch, fit = here
    assert dispatch.parent == fit.id and fit.parent is None \
        and wait.parent is None
    assert fit.start <= dispatch.start <= dispatch.end <= fit.end


def _ring(batches):
    from benchmarks.models import common as models_common
    return models_common.RingIter(batches)


def test_producer_spans_carry_the_producers_thread(built):
    trainer, batches = built["trainer"], built["batches"]
    t0 = time.perf_counter()
    feed = DevicePrefetcher(_ring(batches), depth=2, mesh=trainer.mesh)
    try:
        for _ in range(3):
            next(feed)
        producer = feed._thread.ident
    finally:
        feed.close()
    got = collections.defaultdict(set)
    for s in profiler.spans(since=t0):
        if s.name.startswith("mx.prefetch."):
            got[s.name].add(s.thread)
    assert got["mx.prefetch.source_next"] == got["mx.prefetch.device_put"] \
        == {producer}
    assert got["mx.prefetch.wait"] == {threading.get_ident()}
    assert producer != threading.get_ident()


_ENTRY_ROW = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = .*?[\])}] ([\w\-]+)\(",
                        re.M)


def _phase(op_name):
    from benchmarks import program_spans
    return program_spans.phase(op_name)


def test_the_scope_map_gives_each_instruction_to_its_phase(built):
    """Counted on the entry computation's instructions, exact on the CPU.
    The transformer: XLA:CPU fuses no dot, so each contraction of the
    graph is one instruction forward and two backward (the input's and
    the weight's gradient).  The ResNet: XLA:CPU rewrites convolutions
    (layout changes, 1x1 ones into dots) and drops their op_name on the
    way, so the count is over the batch norms: every one of the 53 has
    instructions in the forward pass and in the backward pass."""
    scope_of, text = built["map"], built["text"]
    entry = text[text.index("\nENTRY "):]
    rows = _ENTRY_ROW.findall(entry)
    assert len(rows) > 100
    nodes = [n for n in built["trainer"]._graph._topo() if not n.is_var]
    graph = collections.Counter(n.op.name for n in nodes)
    by = collections.Counter(
        (_phase(scope_of.get(name)), opcode) for name, opcode in rows)
    if graph["Convolution"]:
        seen = collections.defaultdict(set)
        for name, _ in rows:
            op_name = scope_of.get(name) or ""
            for node in re.findall(r"[/(]BatchNorm:([^/()]+)", op_name):
                seen[_phase(op_name)].add(node)
        norms = {n.name for n in nodes if n.op.name == "BatchNorm"}
        assert len(norms) == 53
        assert seen["forward"] == seen["backward"] == norms
        assert set(seen) == {"forward", "backward"}
    else:
        dots = graph["FullyConnected"] + \
            2 * graph["_contrib_DotProductAttention"]
        assert by[("forward", "dot")] == dots == 17
        assert by[("backward", "dot")] == 2 * dots
        assert by[(None, "dot")] == 0
    assert by[("optimizer", "convolution")] == by[("optimizer", "dot")] == 0
    # the update: a fused elementwise pass or more, none of it in a node
    assert by[("optimizer", "fusion")] >= 1
    for name, _ in rows:
        op_name = scope_of.get(name) or ""
        if "/mx.optimizer" in op_name:
            assert "mx.loss" not in op_name and ":" not in op_name, op_name
        if "transpose(" in op_name:
            assert "/mx.loss/" in op_name, op_name


def test_every_graph_node_is_a_scope_of_the_step(built):
    """In the optimized program every node that computes appears; one
    that only relabels its input (a reshape, a transpose folded into the
    next contraction) may leave no instruction, and is looked for in the
    lowering the program was compiled from."""
    trainer, scope_of = built["trainer"], built["map"]
    nodes = [n for n in trainer._graph._topo() if not n.is_var]
    scoped = set(re.findall(r"[/(]([\w.]+:[^/()]+)",
                            " ".join(set(scope_of.values()))))
    relabel = {"Reshape", "transpose", "slice_like", "Flatten", "mean"}
    for n in nodes:
        if n.op.name not in relabel:
            assert "%s:%s" % (n.op.name, n.name) in scoped, n.name
    x, y = built["batches"][1]
    lowered = trainer._step_fn.lower(
        trainer._params, trainer._opt_state, trainer._aux,
        trainer._device_batch(x), trainer._label_batch(y), trainer._key,
        jax.numpy.float32(0.1), jax.numpy.int32(1)).as_text(debug_info=True)
    for n in nodes:
        # (a slice_like that cuts nothing lowers to nothing at all)
        assert "%s:%s" % (n.op.name, n.name) in lowered or \
            n.op.name == "slice_like", n.name
    assert "mx.loss" in lowered and "mx.optimizer" in lowered


def test_the_scope_map_holds_the_trainer_by_no_reference():
    trainer, batches = _fixture_trainer("tiny_lm")
    trainer.fit_batch(*batches[0])
    ref = weakref.ref(trainer)
    leaf = weakref.ref(next(iter(trainer._params.values())))
    assert profiler.scope_map("parallel_step")
    del trainer
    gc.collect()
    assert ref() is None and leaf() is None
    scope_of = profiler.scope_map("parallel_step")
    assert all(type(k) is str and type(v) is str
               for k, v in scope_of.items())


def test_the_cost_map_holds_the_trainer_by_no_reference():
    """The twin of the test above: what the profiler keeps for the cost
    map is the packed text, then plain records; the map is priced after
    the trainer is gone, on the first request."""
    trainer, batches = _fixture_trainer("tiny_lm")
    trainer.fit_batch(*batches[0])
    ref = weakref.ref(trainer)
    leaf = weakref.ref(next(iter(trainer._params.values())))
    kept = profiler._compiled["parallel_step"]
    assert kept.packed is not None and kept.costs is None
    del trainer
    gc.collect()
    assert ref() is None and leaf() is None
    costs_of = profiler.cost_map("parallel_step")
    assert kept.packed is None and costs_of
    for name, rec in costs_of.items():
        assert type(name) is str and all(
            type(v) in (str, int, float, dict, type(None))
            for v in rec.values()), name
        assert all(type(k) is str and type(v) is float
                   for k, v in rec["bytes_by_scope"].items()), name


def test_the_step_s_cost_map_prices_its_entry_and_agrees_with_xla(built):
    """Every instruction of the compiled step's ENTRY has a record, the
    scope map is what the old regular expression made of the text, and
    the map's sum is within a tenth of XLA's own `bytes accessed`."""
    from mxnet_tpu.observability import costs
    profiler.set_scope_map("parallel_step", built["text"], *built["xla"])
    old = {m.group(1): m.group(2) for m in re.finditer(
        r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*?\bop_name="([^"]*)"',
        built["text"], re.M)}
    assert built["map"] == old == profiler.scope_map("parallel_step")
    entry, comps = costs.parse_optimized_hlo(built["text"])
    records = profiler.cost_map("parallel_step")
    assert {i.name for i in comps[entry]} <= set(records)
    totals = profiler.cost_totals("parallel_step")
    assert totals["xla"]["flops"] > 0
    assert totals["bytes_read"] + totals["bytes_written"] == pytest.approx(
        totals["xla"]["bytes_accessed"], rel=0.1)
    # the update is under its scope, in whatever fusion it rides
    update = sum(b for rec in records.values()
                 for op, b in rec["bytes_by_scope"].items()
                 if "/mx.optimizer" in op)
    n = sum(int(v.size) for v in built["trainer"]._params.values())
    per_parameter = 18 if built["trainer"].multi_precision else 20
    assert update >= per_parameter * n


def test_the_flash_kernels_are_scoped_and_named_in_a_tpu_lowering():
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import flash_attention

    aval = jax.ShapeDtypeStruct((2, 4, 2048, 64), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
        aval, aval, aval).lower(lowering_platforms=("tpu",)).as_text(
            debug_info=True)
    for scope, kernel in (("mx.flash.fwd", "mx_flash_fwd"),
                          ("mx.flash.bwd", "mx_flash_bwd")):
        assert scope in text and kernel in text, scope
    # the pair the one backward kernel replaced is in no program
    assert "mx.flash.dkdv" not in text and "mx.flash.dq" not in text


def test_a_trainer_s_first_step_records_the_flash_plan_of_two_kernels():
    """The LM fixture's set-up holds one `mx.flash.plan` span a traced
    attention call, and it says of the one backward kernel where dq
    accumulates and what VMEM its call asks for."""
    t0 = time.perf_counter()
    trainer, batches = _fixture_trainer("tiny_lm")
    trainer.fit_batch(*batches[0])
    plans = [s for s in profiler.spans(since=t0)
             if s.name == "mx.flash.plan"]
    assert plans
    for span in plans:
        assert {k for k, v in span.args.items() if isinstance(v, dict)} == \
            {"fwd", "bwd"}
        bwd = span.args["bwd"]
        assert bwd["dq_accumulator"] == "vmem"
        assert bwd["vmem_limit_bytes"] >= max(bwd["vmem_bytes"], 16 << 20)
        assert set(span.args["fwd"]) == set(bwd) - {"dq_accumulator",
                                                     "vmem_limit_bytes"}
