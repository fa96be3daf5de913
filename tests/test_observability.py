"""Observability subsystem tests: metrics registry, structured event
log, per-op cost attribution, and their wiring into profiler/module/
resilience (docs/observability.md).

The concurrency drills run real threads against shared instruments;
under ``pytest --graftsan`` the instrument locks come from the
sanitizer factories, so the same tests double as a race audit of the
registry itself (satellite requirement: zero reports)."""

import json
import os
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler as prof
from mxnet_tpu import sym
from mxnet_tpu.io import DataBatch
from mxnet_tpu.observability import costs, events, metrics
from mxnet_tpu.observability.metrics import MetricsRegistry


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("c", "a counter")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g")
    g.set(7)
    g.dec(2)
    assert g.value == 5
    h = reg.histogram("h", buckets=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    snap = h._snap()
    assert snap["count"] == 3
    assert snap["sum"] == 55.5
    assert snap["buckets"] == {"1": 1, "10": 2, "+Inf": 3}


def test_get_or_create_same_instance_and_kind_clash():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_histogram_timer():
    reg = MetricsRegistry()
    h = reg.histogram("t")
    with h.time():
        pass
    assert h.count == 1
    assert h.sum >= 0.0


def test_snapshot_is_json_roundtrippable_and_consistent():
    reg = MetricsRegistry()
    reg.counter("a").inc(3)
    reg.gauge("b").set(-2)
    reg.histogram("c").observe(0.01)
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["a"] == {"kind": "counter", "value": 3}
    assert snap["b"] == {"kind": "gauge", "value": -2}
    assert snap["c"]["count"] == 1
    # cumulative bucket counts are monotone and end at count
    vals = list(snap["c"]["buckets"].values())
    assert vals == sorted(vals) and vals[-1] == snap["c"]["count"]
    assert reg.snapshot(kind="counter") == {"a": snap["a"]}


def test_exposition_golden():
    reg = MetricsRegistry()
    reg.counter("steps_total", "finished steps").inc(2)
    reg.gauge("queue.depth").set(3)
    reg.histogram("lat-seconds", buckets=(0.1,)).observe(0.05)
    expo = reg.exposition()
    assert expo == (
        "# TYPE mxnet_lat_seconds histogram\n"
        'mxnet_lat_seconds_bucket{le="0.1"} 1\n'
        'mxnet_lat_seconds_bucket{le="+Inf"} 1\n'
        "mxnet_lat_seconds_sum 0.05\n"
        "mxnet_lat_seconds_count 1\n"
        "# TYPE mxnet_queue_depth gauge\n"
        "mxnet_queue_depth 3\n"
        "# HELP mxnet_steps_total finished steps\n"
        "# TYPE mxnet_steps_total counter\n"
        "mxnet_steps_total 2\n")
    # names are sanitized into the prometheus charset
    assert "queue.depth" not in expo


def test_concurrent_increments_are_exact():
    """16 threads x 500 increments + histogram observes: no lost
    updates (and, under --graftsan, no race reports)."""
    reg = MetricsRegistry()
    c = reg.counter("hits")
    h = reg.histogram("obs")
    g = reg.gauge("level")
    n_threads, per = 16, 500
    barrier = threading.Barrier(n_threads)

    def work(i):
        barrier.wait()
        for k in range(per):
            c.inc()
            h.observe(0.001 * (k % 7))
            g.inc()

    ts = [threading.Thread(target=work, args=(i,))
          for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == n_threads * per
    assert h.count == n_threads * per
    assert g.value == n_threads * per


def test_concurrent_get_or_create_single_instance():
    reg = MetricsRegistry()
    out = []
    barrier = threading.Barrier(8)

    def work():
        barrier.wait()
        out.append(reg.counter("same"))

    ts = [threading.Thread(target=work) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert all(o is out[0] for o in out)


def test_registry_reset_zeroes_but_keeps_instruments():
    reg = MetricsRegistry()
    c = reg.counter("a")
    c.inc(5)
    reg.reset()
    assert reg.counter("a") is c and c.value == 0


# ---------------------------------------------------------------------------
# profiler compatibility layer
# ---------------------------------------------------------------------------

def test_profiler_counters_are_registry_backed():
    prof.reset_counters()
    prof.bump_counter("obs_test_counter", 2)
    prof.bump_counter("obs_test_counter")
    assert prof.counter_value("obs_test_counter") == 3
    assert prof.counters()["obs_test_counter"] == 3
    # the same series is visible to a scraper
    assert metrics.REGISTRY.get("obs_test_counter").value == 3
    assert "mxnet_obs_test_counter 3" in metrics.exposition()
    prof.reset_counters()
    assert prof.counter_value("obs_test_counter") == 0


def test_profiler_dump_carries_registry_counter_events(tmp_path):
    prof.bump_counter("obs_dump_counter", 7)
    metrics.histogram("obs_dump_hist").observe(0.5)
    path = str(tmp_path / "trace.json")
    prof.set_config(filename=path)
    prof.set_state("run")
    with prof.scope("obs-span"):
        pass
    prof.dump()
    with open(path) as f:
        trace = json.load(f)
    by_name = {e["name"]: e for e in trace["traceEvents"]}
    assert "obs-span" in by_name                      # spans survive
    ce = by_name["metrics/obs_dump_counter"]
    assert ce["ph"] == "C"
    assert ce["args"]["obs_dump_counter"] == 7
    he = by_name["metrics/obs_dump_hist"]
    assert he["args"]["count"] == 1 and he["args"]["sum"] == 0.5
    prof.reset()


# ---------------------------------------------------------------------------
# events
# ---------------------------------------------------------------------------

@pytest.fixture
def obs_env(tmp_path, monkeypatch):
    """MXNET_OBS=all with a private events.jsonl; writer reset around
    the test."""
    path = str(tmp_path / "events.jsonl")
    monkeypatch.setenv("MXNET_OBS", "all")
    monkeypatch.setenv("MXNET_OBS_PATH", path)
    events.configure()
    yield path
    events.configure()
    monkeypatch.delenv("MXNET_OBS", raising=False)
    monkeypatch.delenv("MXNET_OBS_PATH", raising=False)


def test_obs_unset_means_no_events_no_file(tmp_path, monkeypatch):
    monkeypatch.delenv("MXNET_OBS", raising=False)
    path = str(tmp_path / "nope.jsonl")
    monkeypatch.setenv("MXNET_OBS_PATH", path)
    events.configure()
    assert not events.enabled()
    assert events.emit("guard", step=1) is False
    assert not os.path.exists(path)
    # watch_jit is the identity when compile events are off
    fn = lambda: None
    assert events.watch_jit(fn, "x") is fn


def test_obs_unset_means_plain_primitives(monkeypatch):
    """With MXNET_SAN unset the instrument locks must be the plain
    threading primitives (zero sanitizer overhead on the hot path)."""
    monkeypatch.delenv("MXNET_SAN", raising=False)
    reg = MetricsRegistry()
    lock = reg.counter("plain")._lock
    assert type(lock) is type(threading.Lock())


def test_emit_and_read_roundtrip(obs_env):
    assert events.emit("guard", step=3, loss="nan") is True
    assert events.emit("checkpoint", epoch=1) is True
    evs = events.read_events(obs_env)
    assert [e["ev"] for e in evs] == ["guard", "checkpoint"]
    assert evs[0]["step"] == 3 and evs[0]["seq"] == 1
    assert evs[1]["seq"] == 2
    for e in evs:
        assert {"ts", "ev", "pid", "seq"} <= set(e)


def test_category_filtering(tmp_path, monkeypatch):
    path = str(tmp_path / "events.jsonl")
    monkeypatch.setenv("MXNET_OBS", "guard,retry")
    monkeypatch.setenv("MXNET_OBS_PATH", path)
    events.configure()
    try:
        assert events.enabled("guard") and events.enabled("retry")
        assert not events.enabled("compile")
        events.emit("guard", a=1)
        events.emit("compile", b=2)     # filtered out
        events.emit("retry", c=3)
        assert [e["ev"] for e in events.read_events(path)] == \
            ["guard", "retry"]
    finally:
        events.configure()


def test_rate_cap_counts_drops(tmp_path, monkeypatch):
    path = str(tmp_path / "events.jsonl")
    monkeypatch.setenv("MXNET_OBS", "all")
    monkeypatch.setenv("MXNET_OBS_PATH", path)
    monkeypatch.setenv("MXNET_OBS_RATE", "5")
    events.configure()
    try:
        sent = [events.emit("guard", i=i) for i in range(20)]
        assert sum(sent) == 5
        evs = events.read_events(path)
        assert len(evs) == 5
        # a fresh window surfaces the dropped count on the next event
        w = events._get_writer()
        w._window_start -= 2.0
        assert events.emit("guard", i=99) is True
        last = events.read_events(path)[-1]
        assert last["dropped"] == 15
    finally:
        events.configure()


def test_unserializable_fields_degrade_to_repr(obs_env):
    class Weird:
        def __repr__(self):
            return "<weird>"
    assert events.emit("warning", obj=Weird()) is True
    assert events.read_events(obs_env)[0]["obj"] == "<weird>"


def test_concurrent_emit_no_torn_lines(obs_env):
    barrier = threading.Barrier(8)

    def work(i):
        barrier.wait()
        for k in range(40):
            events.emit("chaos", thread=i, k=k)

    ts = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    evs = events.read_events(obs_env)     # raises on any torn line
    assert len(evs) <= 8 * 40
    assert [e["seq"] for e in evs] == list(range(1, len(evs) + 1))


def test_guard_trip_event_from_module(obs_env):
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    net = sym.FullyConnected(data, num_hidden=8, name="fc")
    net = sym.SoftmaxOutput(net, label, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind([("data", (4, 3))], [("softmax_label", (4,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    mod.set_nonfinite_guard()
    rng = np.random.RandomState(0)
    good = DataBatch(
        data=[mx.nd.array(rng.randn(4, 3).astype(np.float32))],
        label=[mx.nd.array(rng.randint(0, 2, (4,)).astype(np.float32))])
    bad = DataBatch(
        data=[mx.nd.array(np.full((4, 3), np.nan, np.float32))],
        label=[mx.nd.array(rng.randint(0, 2, (4,)).astype(np.float32))])
    mod.forward_backward_update(good)
    mod.forward_backward_update(bad)
    assert mod.nonfinite_skipped == 1
    trips = [e for e in events.read_events(obs_env)
             if e["ev"] == "guard"]
    assert len(trips) == 1 and trips[0]["consecutive"] == 1


def test_compile_event_with_blame(obs_env):
    import jax
    import jax.numpy as jnp
    fn = events.watch_jit(jax.jit(lambda x: x * 2), "toy")
    fn(jnp.ones((2, 2), jnp.float32))
    fn(jnp.ones((2, 2), jnp.float32))           # cached
    fn(jnp.ones((3, 3), jnp.float32))           # shape churn
    evs = [e for e in events.read_events(obs_env)
           if e["ev"] == "compile"]
    assert len(evs) == 2
    assert evs[0]["warmup"] is True and "blame" not in evs[0]
    assert evs[1]["warmup"] is False
    assert any("(2, 2)" in line and "(3, 3)" in line
               for line in evs[1]["blame"])


def test_checkpoint_and_chaos_and_retry_events(obs_env, tmp_path):
    from mxnet_tpu.resilience import chaos
    from mxnet_tpu.resilience.checkpoint import CheckpointManager
    from mxnet_tpu.resilience.retry import retry_call
    mgr = CheckpointManager(str(tmp_path / "ck" / "model"))
    mgr.save_checkpoint(1, arg_params={"w": mx.nd.ones((2,))})
    # retry: one failure then success
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) == 1:
            raise OSError("transient")
        return 42
    assert retry_call(flaky, attempts=3, sleep=lambda s: None) == 42
    # chaos: one injected write failure
    chaos.configure(fail_file_writes=1)
    try:
        with pytest.raises(OSError):
            mgr.save_checkpoint(2, arg_params={"w": mx.nd.ones((2,))})
    finally:
        chaos.reset()
    kinds = [e["ev"] for e in events.read_events(obs_env)]
    assert "checkpoint" in kinds
    assert "retry" in kinds
    assert "chaos" in kinds
    snap = metrics.snapshot()
    assert snap["checkpoint_saves_total"]["value"] >= 1
    assert snap["checkpoint_save_seconds"]["count"] >= 1
    assert snap["retry_attempts_total"]["value"] >= 1
    assert snap["chaos_injections_total"]["value"] >= 1


# ---------------------------------------------------------------------------
# subsystem instruments (always-on)
# ---------------------------------------------------------------------------

def test_host_transfer_instruments():
    before = metrics.REGISTRY.get("host_transfers_total").value
    bytes_before = metrics.REGISTRY.get("host_transfer_bytes_total").value
    a = mx.nd.ones((4, 4), dtype="float32")
    a.asnumpy()
    assert metrics.REGISTRY.get("host_transfers_total").value == \
        before + 1
    assert metrics.REGISTRY.get("host_transfer_bytes_total").value == \
        bytes_before + 64


def test_kvstore_push_pull_bytes():
    kv = mx.kv.create("local")
    push_before = metrics.REGISTRY.get("kvstore_push_bytes_total").value
    pull_before = metrics.REGISTRY.get("kvstore_pull_bytes_total").value
    kv.init("w", mx.nd.zeros((8,)))
    kv.push("w", mx.nd.ones((8,)))
    out = mx.nd.zeros((8,))
    kv.pull("w", out=out)
    assert metrics.REGISTRY.get("kvstore_push_bytes_total").value == \
        push_before + 32
    assert metrics.REGISTRY.get("kvstore_pull_bytes_total").value == \
        pull_before + 32


def test_fused_step_latency_histogram():
    h_before = metrics.histogram("fused_step_dispatch_seconds").count
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    net = sym.FullyConnected(data, num_hidden=4, name="fc")
    net = sym.SoftmaxOutput(net, label, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind([("data", (4, 3))], [("softmax_label", (4,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    rng = np.random.RandomState(0)
    b = DataBatch(
        data=[mx.nd.array(rng.randn(4, 3).astype(np.float32))],
        label=[mx.nd.array(rng.randint(0, 2, (4,)).astype(np.float32))])
    for _ in range(3):
        mod.forward_backward_update(b)
    assert metrics.histogram("fused_step_dispatch_seconds").count == \
        h_before + 3


# ---------------------------------------------------------------------------
# per-op cost attribution
# ---------------------------------------------------------------------------

def test_parse_hlo_dot_and_conv_flops():
    import jax
    import jax.numpy as jnp

    def f(a, b, c, k):
        d = jnp.tanh(a @ b)
        e = jax.lax.conv_general_dilated(
            c, k, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jnp.sum(d) + jnp.sum(e)

    low = jax.jit(f).lower(
        jnp.ones((16, 32)), jnp.ones((32, 64)),
        jnp.ones((2, 8, 8, 3)), jnp.ones((3, 3, 3, 8)))
    rows = costs.parse_hlo_ops(low.as_text())
    by_op = {}
    for r in rows:
        by_op.setdefault(r["op"], []).append(r)
    # dot: 2 * 16*64 * 32
    assert by_op["dot_general"][0]["flops"] == 2 * 16 * 64 * 32
    # conv: 2 * prod(out 2x8x8x8) * 3*3 spatial * 3 in-channels
    assert by_op["convolution"][0]["flops"] == \
        2 * (2 * 8 * 8 * 8) * 9 * 3
    # bytes: dot reads 16x32 + 32x64 f32 and writes 16x64
    assert by_op["dot_general"][0]["bytes"] == \
        4 * (16 * 32 + 32 * 64 + 16 * 64)


def test_parse_hlo_grouped_conv_divides_by_its_groups_once():
    """A grouped convolution's kernel already holds the input's features
    of ONE group (`HWIO` with I = C / groups): the FLOPs are
    2 * out * window * I, not that over the groups again."""
    import jax
    import jax.numpy as jnp

    def f(c, k):
        return jax.lax.conv_general_dilated(
            c, k, (1, 1), "SAME", feature_group_count=4,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    low = jax.jit(f).lower(jnp.ones((2, 8, 8, 16)), jnp.ones((3, 3, 4, 32)))
    conv, = [r for r in costs.parse_hlo_ops(low.as_text())
             if r["op"] == "convolution"]
    assert conv["flops"] == 2 * (2 * 8 * 8 * 32) * 9 * 4


def test_parse_hlo_scan_counts_trip_count_times():
    """Ops inside a lax.scan body (lowered to stablehlo.while calling
    an outlined private function) must be charged trip_count x, not
    1x — the decode tick programs are scan-shaped."""
    import jax
    import jax.numpy as jnp

    def body(c, _):
        c = c @ c
        return c, jnp.sum(c)

    def f(x):
        return jax.lax.scan(body, x, None, length=5)

    text = jax.jit(f).lower(jnp.ones((4, 4), jnp.float32)).as_text()
    rows = costs.parse_hlo_ops(text)
    dots = [r for r in rows if r["op"] == "dot_general"]
    assert len(dots) == 1
    # one 4x4 @ 4x4 matmul (2*4*4*4 = 128 flops) x 5 trips
    assert dots[0]["flops"] == 5 * (2 * 4 * 4 * 4)
    assert dots[0]["count"] == 5
    # the while header itself must not be priced as an op
    assert not any(r["op"] == "while" for r in rows)


def test_parse_hlo_shared_type_binary_bytes():
    """Binary elementwise ops print in shared-type form; traffic must
    count BOTH operands plus the result (3x), and unary ops 2x."""
    text = ("%6 = stablehlo.add %4, %5 : tensor<16x64xf32>\n"
            "%7 = stablehlo.tanh %6 : tensor<16x64xf32>")
    rows = {r["op"]: r for r in costs.parse_hlo_ops(text)}
    assert rows["add"]["bytes"] == 3 * 4 * 16 * 64
    assert rows["tanh"]["bytes"] == 2 * 4 * 16 * 64


def test_cost_table_roofline_classes_and_shares():
    import jax
    import jax.numpy as jnp

    def f(a, b):
        return jnp.sum(a @ b)

    low = jax.jit(f).lower(jnp.ones((64, 64)), jnp.ones((64, 64)))
    table = costs.cost_table(low, peak_flops=1e12, peak_bytes_s=1e9)
    assert table["machine_balance"] == 1000.0
    rows = {r["op"]: r for r in table["rows"]}
    dot = rows["dot_general"]
    # intensity of a 64^3 matmul vs balance point 1000 -> memory-bound
    assert dot["class"] == "memory-bound"
    assert 0 < dot["pct_time"] <= 100
    assert abs(sum(r["pct_time"] for r in table["rows"]) - 100) < 1.0
    assert abs(sum(r["pct_flops"] for r in table["rows"]) - 100) < 1.0
    # XLA cross-check rides along when the program compiled
    assert table.get("xla_cost_analysis") is None or \
        table["xla_cost_analysis"]["flops"] > 0
    # and the text renderer works on the same table
    text = costs.format_table(table)
    assert "dot_general" in text and "memory-bound" in text


def test_cost_table_compute_bound_classification():
    text = ("%0 = stablehlo.dot_general %a, %b, contracting_dims = "
            "[1] x [0] : (tensor<1024x1024xbf16>, "
            "tensor<1024x1024xbf16>) -> tensor<1024x1024xbf16>")
    table = costs.cost_table(text=text, peak_flops=1e12,
                             peak_bytes_s=1e9)
    row = table["rows"][0]
    # 2*1024^3 flops over 3*2MB: intensity ~341 vs balance 1000
    assert row["class"] == "memory-bound"
    table2 = costs.cost_table(text=text, peak_flops=1e12,
                              peak_bytes_s=1e10)
    assert table2["rows"][0]["class"] == "compute-bound"


def test_cost_table_top_folds_tail():
    text = "\n".join(
        "%%%d = stablehlo.add %%a, %%b : tensor<%dxf32>" % (i, 8 + i)
        for i in range(10))
    table = costs.cost_table(text=text, top=3)
    assert len(table["rows"]) == 4
    assert table["rows"][-1]["op"].startswith("(other")
    assert sum(r["count"] for r in table["rows"]) == 10


def test_cost_table_serialises_as_json():
    """A cost table is plain data: its totals and rows survive a JSON
    round trip, so a tool can print or store one as it is."""
    import jax
    import jax.numpy as jnp
    low = jax.jit(lambda a, b: jnp.sum(a @ b)).lower(
        jnp.ones((8, 8)), jnp.ones((8, 8)))
    table = costs.cost_table(low, peak_flops=1e12, peak_bytes_s=1e9,
                             top=12)
    parsed = json.loads(json.dumps(table))
    assert parsed["machine_balance"] == table["machine_balance"]
    assert parsed["total_flops"] == table["total_flops"] > 0
    assert parsed["rows"][0]["flops"] > 0
    assert "class" in parsed["rows"][0]
