"""The readers of the program's own spans and scopes
(benchmarks/program_spans.py and the layer_metrics files that use it): the
host ones on hand-made spans, the device ones and the clock fit on a clip
recorded on the chip (fixtures/program_spans_clip.json: one block boundary
of `resnet50_train`), each against a slow, separate count."""

import collections
import importlib
import json
import os

import pytest

import bench_suite_util as util
from benchmarks import program_spans, trace

Span = collections.namedtuple(
    "Span", "id name cat start end thread parent args")
MAIN, PRODUCER = 11, 22

#: the readers that read nothing but the program's spans and scopes; how
#: many cells list each is BENCHMARK.json's to say, not this file's
READERS = (
    "setup_import_s", "setup_state_s", "setup_program_s",
    "trainer_python_ms_per_step", "prefetch_wait_ms_per_step",
    "prefetch_put_ms_per_batch", "gc_pause_ms_per_block",
    "step_forward_ms", "step_backward_ms", "step_optimizer_ms",
    "step_unscoped_pct", "batchnorm_ms_per_step", "flash_fwd_ms_per_step",
    "flash_bwd_ms_per_step", "idle_outside_program_pct",
    "attention_ms_per_step", "moe_ms_per_step",
    "moe_expert_matmul_ms_per_step", "short_conv_ms_per_step",
    "mla_ms_per_step", "mla_assemble_ms_per_step", "moe_shared_ms_per_step")


def reader(name):
    return importlib.import_module("benchmarks.layer_metrics." + name)


class Outcome:
    """What a reader is handed, as far as these readers look."""

    class cell:
        started = 100.0

    def __init__(self, spans=None, scope_map=None, **facts):
        self.facts = dict(facts, program_spans=spans,
                          program_scope_map=scope_map)
        self.end_to_end = {"setup_s": 30.0}
        self.trace = None
        self.spans = None


def _span(i, name, start, end, parent=None, thread=MAIN, args=None):
    return Span(i, name, "x", start, end, thread, parent, args)


# -- set-up: process start 100.0, the window's first block at 130.0 -----------
SETUP = [
    _span(1, "mx.import", 103.0, 108.0),
    _span(2, "mx.backend_init", 108.5, 109.0),
    _span(3, "mx.initialize", 110.0, 110.5),
    _span(4, "mx.initialize", 110.5, 111.5),
    _span(6, "mx.trainer.trace", 112.0, 113.0, parent=5),
    # a parameter that was deferred materialises inside the gather: its
    # seconds are the gather's already, and are counted once
    _span(7, "mx.initialize", 113.2, 113.4, parent=8),
    _span(8, "mx.trainer.gather_state", 113.0, 115.0, parent=5),
    _span(9, "mx.trainer.build_step", 115.0, 115.25, parent=5),
    _span(10, "mx.step.first_call", 115.5, 125.5, parent=5,
          args={"lower_s": 4.0, "call_s": 5.5, "scope_map_s": 0.5}),
    _span(5, "mx.fit_batch", 111.9, 125.6),
    # after the window's start: not set-up
    _span(11, "mx.initialize", 131.0, 132.0),
]


@pytest.mark.parametrize("name, seconds", [
    ("setup_import_s", 5.0 + 0.5),
    ("setup_state_s", 0.5 + 1.0 + 2.0),
    ("setup_program_s", 1.0 + 0.25 + 10.0)])
def test_set_up_seconds_by_program_span(name, seconds, capsys):
    out = Outcome(SETUP)
    assert reader(name).read(out) == pytest.approx(seconds)
    printed = capsys.readouterr().out
    if name == "setup_import_s":
        assert "before mx.import" in printed and "3.000 s" in printed
    if name == "setup_program_s":
        assert "call_s 5.500" in printed and "lower_s 4.000" in printed
    # the three lie inside set-up, side by side
    total = sum(reader(n).read(Outcome(SETUP)) for n in (
        "setup_import_s", "setup_state_s", "setup_program_s"))
    assert total <= out.end_to_end["setup_s"]


# -- the window: 2 traced blocks of 2 steps, then 3 untraced ones -------------
def _window():
    spans, i = [], 100
    t = 130.0
    for step in range(10):
        # wait 1 ms, fit_batch 10 ms of which 7 in the dispatch
        spans.append(_span(i, "mx.prefetch.wait", t, t + 0.001))
        spans.append(_span(i + 2, "mx.fit_batch.dispatch", t + 0.003,
                           t + 0.010, parent=i + 1))
        spans.append(_span(i + 1, "mx.fit_batch", t + 0.001, t + 0.011))
        spans.append(_span(i + 3, "mx.prefetch.source_next", t, t + 0.002,
                           thread=PRODUCER))
        spans.append(_span(i + 4, "mx.prefetch.device_put", t + 0.002,
                           t + 0.004 + 0.001 * (step % 2),
                           thread=PRODUCER))
        i += 5
        t += 0.020
    # one full collection in a traced block, two in the untraced ones
    spans.append(_span(90, "mx.gc", 130.021, 130.031))
    spans.append(_span(91, "mx.gc", 130.085, 130.088))
    spans.append(_span(92, "mx.gc", 130.150, 130.156))
    return spans


WINDOW = dict(untraced_span=(130.080, 130.200), steps=10, traced_blocks=2,
              steps_per_block=2)


@pytest.mark.parametrize("name, value", [
    ("trainer_python_ms_per_step", 3.0),    # 10 ms less the child's 7
    ("prefetch_wait_ms_per_step", 1.0),
    ("prefetch_put_ms_per_batch", 2.5),     # 2 and 3 ms by turns
    ("gc_pause_ms_per_block", (3.0 + 6.0) / 3)])
def test_host_readers_count_the_untraced_blocks_only(name, value):
    out = Outcome(_window(), **WINDOW)
    assert reader(name).read(out) == pytest.approx(value)
    # a slow, separate count of the same spans
    lo, hi = WINDOW["untraced_span"]
    inside = [s for s in _window() if s.start >= lo and s.end <= hi]
    assert len([s for s in inside if s.name == "mx.fit_batch"]) == 6
    if name == "trainer_python_ms_per_step":
        own = sum((s.end - s.start) for s in inside
                  if s.name == "mx.fit_batch") - sum(
            (s.end - s.start) for s in inside
            if s.name == "mx.fit_batch.dispatch")
        assert value == pytest.approx(1e3 * own / 6)


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_program_without_spans_or_scopes_leaves_every_metric_out(name):
    """The parent commit: `profiler.spans` and `scope_map` do not exist;
    the readers return None and do not raise."""
    out = Outcome(None, None, **WINDOW)
    out.trace = object()        # a trace was taken all the same
    assert reader(name).read(out) is None


def test_the_readers_find_the_store_of_the_program_itself():
    from mxnet_tpu import profiler
    with profiler.scope("mx.test.reader"):
        pass
    out = Outcome()
    del out.facts["program_spans"], out.facts["program_scope_map"]
    assert "mx.test.reader" in {s.name for s in program_spans.records(out)}
    assert program_spans.records(out) is out.facts["program_spans"]
    profiler.set_scope_map(program_spans.STEP_PROGRAM,
                           '  %f.1 = f32[] add(%a, %b), metadata={'
                           'op_name="jit(parallel_step)/mx.optimizer/add"}')
    assert program_spans.scopes(out) == {
        "f.1": "jit(parallel_step)/mx.optimizer/add"}


# -- device scopes ------------------------------------------------------------
@pytest.mark.parametrize("op_name, phase", [
    ("jit(parallel_step)/mx.loss/jvp(Convolution:stage1_conv0)/conv",
     "forward"),
    ("jit(parallel_step)/mx.loss/transpose(jvp(BatchNorm:bn0))/mul",
     "backward"),
    ("jit(parallel_step)/mx.loss/jvp()/reduce_sum", "forward"),
    ("jit(parallel_step)/mx.optimizer/add", "optimizer"),
    ("jit(parallel_step)/mx.grad_clip/sqrt", "optimizer"),
    ("jit(f)/jvp(_contrib_DotProductAttention:att0)/cond/branch_0_fun/"
     "mx.flash.fwd/mx_flash_fwd/pallas_call", "forward"),
    ("jit(forward)/FullyConnected:fc1/dot_general", "forward"),
    ("jit(parallel_step)/reduce_sum", None),
    ("args[6]", None), ("", None), (None, None)])
def test_an_op_name_is_given_to_its_phase(op_name, phase):
    assert program_spans.phase(op_name) == phase


def test_an_event_is_looked_up_by_its_instruction():
    assert program_spans.instruction(
        "%fusion.12 = bf16[8,128]{1,0} fusion(%p), kind=kLoop") == \
        "fusion.12"
    assert program_spans.instruction("%copy-done.3") == "copy-done.3"
    assert program_spans.instruction("mx_flash_fwd.12") == "mx_flash_fwd.12"


@pytest.fixture(scope="module")
def clip():
    with open(os.path.join(util.FIXTURES, "program_spans_clip.json")) as f:
        return json.load(f)


def _clip_outcome(clip):
    out = Outcome([Span(*row) for row in clip["program_spans"]],
                  clip["scope_map"], traced_blocks=1, steps_per_block=1)
    out.trace = trace.Trace(clip["events"])

    class Records:
        records = [tuple(r) for r in clip["bench_spans"]]
    out.spans = Records
    return out


def _covered(events, lo, hi):
    """Nanoseconds of [lo, hi) covered by any of *events*, the slow way
    (as tests/benchmark_suite/test_bench_trace.py counts)."""
    cuts = sorted({lo, hi} | {min(max(t, lo), hi) for e in events
                              for t in (e["start_ns"],
                                        e["start_ns"] + e["dur_ns"])})
    starts = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"])
                    for e in events)
    total, j, reach = 0, 0, float("-inf")
    for a, b in zip(cuts, cuts[1:]):
        while j < len(starts) and starts[j][0] <= a:
            reach = max(reach, starts[j][1])
            j += 1
        if reach >= b:
            total += b - a
    return total


def test_the_phases_and_the_unscoped_share_account_for_the_busy_time(clip):
    out = _clip_outcome(clip)
    t = out.trace
    ops = [e for e in clip["events"] if e["plane"].startswith("/device")
           and not trace._WRAPPERS.match(trace.op_family(e["name"]))]
    by = collections.defaultdict(list)
    for e in ops:
        by[program_spans.phase(clip["scope_map"].get(
            program_spans.instruction(e["name"])))].append(e)
    assert set(by) == {"forward", "backward", "optimizer", None}
    ms = {}
    for phase in ("forward", "backward", "optimizer"):
        ms[phase] = reader("step_%s_ms" % phase).read(out)
        assert ms[phase] == pytest.approx(
            _covered(by[phase], t.lo, t.hi) * 1e-6, rel=1e-9)
        assert ms[phase] > 0
    unscoped = reader("step_unscoped_pct").read(out)
    assert unscoped == pytest.approx(
        100.0 * _covered(by[None], t.lo, t.hi) * 1e-9 / t.busy_s, rel=1e-9)
    # the clip holds the end of a step (backward, the update) and the
    # start of the next (forward): nearly all of its busy time is scoped
    assert unscoped < 10
    busy_ms = 1e3 * t.busy_s
    assert sum(ms.values()) + unscoped / 100 * busy_ms == \
        pytest.approx(busy_ms, rel=0.02)


def test_batch_norm_and_the_flash_kernels_are_read_by_scope(clip):
    out = _clip_outcome(clip)
    norm = reader("batchnorm_ms_per_step").read(out)
    ops = [e for e in clip["events"] if e["plane"].startswith("/device")
           and "BatchNorm:" in clip["scope_map"].get(
               program_spans.instruction(e["name"]), "")]
    assert norm == pytest.approx(
        _covered(ops, out.trace.lo, out.trace.hi) * 1e-6, rel=1e-9)
    assert 0 < norm < reader("step_forward_ms").read(out) + \
        reader("step_backward_ms").read(out)
    # ResNet-50 calls no flash kernel: nothing to read ...
    for k in ("fwd", "bwd"):
        assert reader("flash_%s_ms_per_step" % k).read(out) is None
    # ... a step that does is read kernel by kernel
    events = [e for e in clip["events"] if e["plane"] == "/host:CPU"]
    lo = min(e["start_ns"] for e in events)
    scope_map = {}
    for i, (k, dur) in enumerate((("fwd", 300), ("bwd", 500),
                                  ("bwd", 200), ("fwd", 100))):
        name = "mx_flash_%s.%d" % (k, i)
        events.append({"plane": "/device:TPU:0", "line": "XLA Ops",
                       "name": "%" + name, "start_ns": lo + 1000 * i,
                       "dur_ns": dur})
        scope_map[name] = (
            "jit(parallel_step)/mx.loss/%s/cond/branch_0_fun/mx.flash.%s/"
            "mx_flash_%s/pallas_call" % (
                "jvp(Att:a)" if k == "fwd" else "transpose(jvp(Att:a))",
                k, k))
    out = Outcome([], scope_map, traced_blocks=1, steps_per_block=2)
    out.trace = trace.Trace(events)
    assert [reader("flash_%s_ms_per_step" % k).read(out)
            for k in ("fwd", "bwd")] == [
        pytest.approx(400e-6 / 2), pytest.approx(700e-6 / 2)]
    assert reader("step_backward_ms").read(out) == pytest.approx(700e-6 / 2)


# -- the program's spans on the trace's clock ---------------------------------
def test_the_clocks_are_fitted_from_the_benchmarks_own_spans(clip):
    out = _clip_outcome(clip)
    offset = program_spans.clock_offset(out)
    off = []
    for (name, h0, h1), (t0, t1, tname) in zip(
            sorted(out.spans.records, key=lambda r: r[1]), out.trace.spans):
        assert name == tname
        off += [abs(t0 * 1e-9 - (h0 + offset)),
                abs(t1 * 1e-9 - (h1 + offset))]
    # as recorded: most ends lie within microseconds, the readback's end
    # (the thread was switched out between its two clock reads) 0.2 ms
    assert sorted(off)[len(off) // 2] < 1e-5 and max(off) < 1e-3
    assert sum(d < 1e-4 for d in off) >= 0.8 * len(off)
    # one pair split by a thread switch moves nothing
    first = out.spans.records[0]
    out.spans.records = [(first[0], first[1] - 0.005, first[2])] + \
        out.spans.records[1:]
    assert program_spans.clock_offset(out) == pytest.approx(offset, abs=5e-5)


def test_clocks_that_do_not_fit_are_an_error(clip):
    out = _clip_outcome(clip)
    # a host clock that runs 1% fast: the pairs drift apart
    out.spans.records = [(n, a * 1.01, b * 1.01 + 0.002 * i)
                         for i, (n, a, b) in enumerate(out.spans.records)]
    with pytest.raises(ValueError, match="do not fit"):
        program_spans.clock_offset(out)
    out = _clip_outcome(clip)
    out.spans.records = out.spans.records[1:]
    with pytest.raises(ValueError, match="no pairs"):
        program_spans.clock_offset(out)


def test_idle_gaps_are_given_to_the_program_span_at_their_middle(
        clip, capsys):
    out = _clip_outcome(clip)
    by = program_spans.idle_by_program_span(out)
    t = out.trace
    assert sum(by.values()) == pytest.approx(t.window_s - t.busy_s)
    assert set(by) <= {"(outside)", "mx.fit_batch", "mx.fit_batch.dispatch",
                       "mx.prefetch.wait", "mx.gc"}
    # the clip spans a block boundary: the device waits while the loop
    # reads the loss back (the benchmark's code, outside the program)
    # and then while the program dispatches the next step
    assert by["(outside)"] > 1e-3
    share = reader("idle_outside_program_pct").read(out)
    assert share == pytest.approx(100.0 * by["(outside)"] / t.window_s)
    assert 0 < share <= 100.0 * (1 - t.busy_s / t.window_s)
    printed = capsys.readouterr().out
    assert printed.startswith("bench: idle by program span ") and \
        "(outside) %.6f s" % by["(outside)"] in printed
    # the producer thread's spans name no gap
    assert not [k for k in by if k.startswith("mx.prefetch.source")
                or k.startswith("mx.prefetch.device_put")]


# -- BENCHMARK.json -----------------------------------------------------------
def test_every_new_per_layer_entry_has_its_file_and_its_cells():
    """Each reader this file tests is declared, from its own constants,
    for cells that exist (the rules for every entry, whoever tests its
    reader, are `test_bench_data_driven.py`'s)."""
    with open(os.path.join(util.REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entries = {m["name"]: m for m in spec["per_layer"]}
    cells = {w["name"] for w in spec["workloads"]}
    for name in READERS:
        m, r = entries[name], reader(name)
        assert (r.UNIT, r.BETTER, r.LAYER, r.MOVES, r.SOURCE) == \
            (m["unit"], m["better"], m["layer"], m["moves"], m["source"]), \
            name
        # each says which cells it reads
        assert m["workloads"] and set(m["workloads"]) <= cells, name
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.exists(os.path.join(
            util.REPO, "benchmarks", "layer_metrics", name + ".py"))
    # batch norm is ResNet-50's alone; the transformers trace none
    assert entries["batchnorm_ms_per_step"]["workloads"] == [
        "resnet50_train"]
