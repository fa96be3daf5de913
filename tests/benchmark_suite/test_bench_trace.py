"""The reduction from a profiler trace to numbers (benchmarks/trace.py) on
a small trace recorded on the chip, checked against a slow, separate
count; the average over devices on a hand-made timeline."""

import json
import os

import pytest

import bench_suite_util as util
from benchmarks import trace

DEV = "/device:TPU:0"


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(util.FIXTURES, "recorded_trace.json")) as f:
        return json.load(f)["events"]


def _covered(events, lo, hi):
    """Nanoseconds of [lo, hi) covered by any of *events*, the slow way:
    every elementary interval between two boundaries, one at a time."""
    cuts = sorted({lo, hi} | {min(max(t, lo), hi) for e in events
                              for t in (e["start_ns"],
                                        e["start_ns"] + e["dur_ns"])})
    return sum(b - a for a, b in zip(cuts, cuts[1:])
               if any(e["start_ns"] <= a and b <= e["start_ns"] + e["dur_ns"]
                      for e in events))


def test_busy_union_and_idle_share_of_the_recorded_trace(recorded):
    t = trace.Trace(recorded)
    spans = [e for e in recorded if e["name"].startswith("bench.")]
    ops = [e for e in recorded if e["plane"] == DEV]
    lo = min(e["start_ns"] for e in spans)
    hi = max(e["start_ns"] + e["dur_ns"] for e in spans)
    assert (t.lo, t.hi) == (lo, hi) and t.window_s == (hi - lo) * 1e-9
    busy = _covered(ops, lo, hi)
    assert t.busy_s == pytest.approx(busy * 1e-9, rel=1e-12)
    # the clip spans a block boundary: the device waits for the readback
    # to return and for the next step to be dispatched
    assert 0.25 < 1 - t.busy_s / t.window_s < 0.45
    assert busy <= sum(e["dur_ns"] for e in ops)


def test_gaps_are_attributed_to_the_host_span_in_their_middle(recorded):
    t = trace.Trace(recorded)
    gaps = dict(t.idle_gaps())
    assert sum(gaps.values()) == pytest.approx(t.window_s - t.busy_s)
    assert set(gaps) <= {"bench.fit_batch", "bench.loss_readback",
                         "bench.batch_fetch", "(none)"}
    # the long gap starts inside the readback and ends inside fit_batch
    assert gaps["bench.fit_batch"] > 1e-3 and \
        gaps["bench.loss_readback"] > 1e-3
    assert len(t.idle_gaps(n=1)) == 1


def test_op_families_survive_renumbering(recorded):
    assert trace.op_family("%copy-done.1375") == "copy-done"
    assert trace.op_family(
        "%all-gather-start.4 = (bf16[8]) all-gather-start(...)") == \
        "all-gather-start"
    assert trace.op_family("fusion") == "fusion"
    top = trace.Trace(recorded).top_ops(3)
    assert [name for name, _ in top][0] == "fusion"
    assert top == sorted(top, key=lambda kv: -kv[1])


def _ev(plane, name, start, dur, line=trace.OPS_LINE):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": start, "dur_ns": dur}


def test_busy_seconds_are_averaged_over_the_devices_used():
    events = [
        _ev("/host:CPU", "bench.fit_batch", 0, 1000, "python"),
        _ev(DEV, "%fusion.1", 0, 300),
        _ev(DEV, "%copy-start.2", 250, 200),        # overlaps: 0..450
        _ev(DEV, "%fusion.3", 800, 400),            # clipped to the window
        _ev(DEV, "%while.4", 0, 1000),              # a wrapper: not work
        _ev("/device:TPU:1", "%fusion.9", 0, 1000),
    ]
    t = trace.Trace(events)
    assert t.busy_s == pytest.approx(((450 + 200) + 1000) / 2 * 1e-9)
    assert dict(t.idle_gaps(device=0)) == {
        "bench.fit_batch": pytest.approx(350e-9)}
    assert dict(t.top_ops())["fusion"] == pytest.approx(
        (300 + 200 + 1000) / 2 * 1e-9)


@pytest.mark.parametrize("wrapper", [
    "%cond.52.clone = (bf16[24576,2048]) conditional(%p, %a, %b)",
    "%cond.7", "%conditional.3", "%while.4", "%call.1"])
def test_a_wrapper_s_time_is_its_children_s(wrapper):
    """A `lax.cond` (the routed layer's bounded branch) is on the ops
    line as `cond.N.clone` around the operations of the branch taken:
    counted beside them it would count their time twice."""
    events = [
        _ev("/host:CPU", "bench.fit_batch", 0, 2000, "python"),
        _ev(DEV, "%fusion.1", 0, 100),
        _ev(DEV, wrapper, 200, 1000),
        _ev(DEV, "%gmm.2 = bf16[] custom-call()", 210, 600),
        _ev(DEV, "%fusion.3", 850, 300),
    ]
    t = trace.Trace(events)
    assert t.busy_s == pytest.approx((100 + 600 + 300) * 1e-9)
    assert dict(t.top_ops()) == {
        "fusion": pytest.approx(400e-9), "gmm": pytest.approx(600e-9)}
    assert t.seconds_where(lambda name: True) == pytest.approx(1000e-9)
    # an operation that only starts like one is an operation
    t = trace.Trace(events + [_ev(DEV, "%condense_fusion.9", 1500, 50),
                              _ev(DEV, "%callback.2", 1600, 50)])
    assert {"condense_fusion", "callback"} <= set(dict(t.top_ops()))


def test_mosaic_time_is_the_custom_calls_not_what_reads_them():
    """Event names as the chip recorded them (PR 32, LFM2): the kernels
    say `custom_call_target="tpu_custom_call"`; a fusion and a copy that
    take a kernel's output as an operand name `%pallas_call.N` and are
    not kernels."""
    from benchmarks.layer_metrics import mosaic_time_share_pct

    class Outcome:
        trace = trace.Trace([
            _ev("/host:CPU", "bench.fit_batch", 0, 2000, "python"),
            _ev(DEV, "%gmm.17 = bf16[24576,1792]{1,0:T(8,128)(2,1)} "
                "custom-call(s32[]{:T(128)} %get-tuple-element.2120, "
                "bf16[24576,2048]{1,0} %broadcast_select_fusion.44), "
                'custom_call_target="tpu_custom_call"', 0, 300),
            _ev(DEV, "%branch_0_fun.3 = bf16[64,8192,64]{2,1,0} custom-call("
                'bf16[64,8192,64]{2,1,0} %bitcast.875), custom_call_target='
                '"tpu_custom_call", operand_layout_constraints={}', 300, 100),
            _ev(DEV, "%fusion.1917 = (bf16[2048,3]{0,1}) fusion(f32[2048,3]"
                "{0,1} %copy-done.563, f32[8,2048]{1,0} %pallas_call.23), "
                "kind=kLoop, calls=%fused_computation.2801", 400, 500),
            _ev(DEV, "%copy-start.67 = (bf16[32,8192,128]{2,1,0}) "
                "copy-start(bf16[32,8192,128]{2,1,0} %pallas_call.8)",
                900, 100),
        ])
    assert mosaic_time_share_pct.read(Outcome) == pytest.approx(
        100.0 * 400 / 1000)
    Outcome.trace = trace.Trace([
        _ev("/host:CPU", "bench.fit_batch", 0, 2000, "python"),
        _ev(DEV, "%fusion.1 = f32[] fusion(f32[] %pallas_call.2)", 0, 10)])
    assert mosaic_time_share_pct.read(Outcome) is None
    Outcome.trace = None
    assert mosaic_time_share_pct.read(Outcome) is None


def test_intervals():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 7)]) == [(0, 3), (5, 7)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace.total([(0, 3), (5, 7)]) == 5


def test_a_trace_without_device_work_or_spans_is_refused():
    span = _ev("/host:CPU", "bench.fit_batch", 0, 10, "python")
    with pytest.raises(ValueError, match="no operation ran"):
        trace.Trace([span])
    with pytest.raises(ValueError, match="host span"):
        trace.Trace([_ev(DEV, "%fusion.1", 0, 10)])
