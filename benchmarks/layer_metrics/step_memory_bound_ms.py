"""Device time a step in the instructions whose HBM-bytes floor exceeds
their MXU floor (`profiler.cost_map` at the peaks of `peaks.json`; the
events' own times over the whole cycles of the step that the counts are
taken over: one denominator for the four cost readers).  Kernels
that state no FLOPs cannot be told either way: their time is printed
apart, not summed."""

from .. import program_costs

LAYER = "step program"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"


def read(outcome):
    p = program_costs.priced(outcome)
    if p is None:
        return None
    ms = program_costs.device_ms_by_bound(p)
    program_costs.program_spans.say_once(
        outcome, "costs-bound",
        "bench: device ms a step in instructions bound by HBM bytes %.3f, "
        "by the MXU %.3f, in kernels that state no FLOPs %.3f, in "
        "instructions that move nothing through HBM (the waits that end "
        "an async pair, work fed from the on-chip memory) %.3f" % (
            ms["bytes"], ms["mxu"], ms["kernel"], ms["nothing"]))
    return ms["bytes"]
