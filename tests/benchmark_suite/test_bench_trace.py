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
