"""What a traced step's instructions take at the published peaks: the sum
over its device events of the larger of the instruction's MXU FLOPs over
the bf16 peak and its HBM bytes over the HBM bandwidth
(`profiler.cost_map`, `peaks.json`).  Printed with `step_device_ms` beside
it, and once a run the table the Speed queue is built from: the scopes
with the most milliseconds a step over their floor.  It may stand above the
measured time where XLA keeps arrays in the on-chip memory that the text
prices in HBM; a kernel that states no FLOPs has a bytes floor alone."""

from .. import program_costs
from . import step_device_ms

LAYER = "step program"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"


def read(outcome):
    p = program_costs.priced(outcome)
    if p is None:
        return None
    floor = 1e3 * p.sum(lambda r: max(p.floors(r)))
    program_costs.program_spans.say_once(
        outcome, "costs-floor",
        "bench: step floor %.3f ms (MXU alone %.3f, HBM alone %.3f) "
        "against %.3f ms of the same cycles' events (step_device_ms "
        "%.3f, the busy union over the host's steps)" % (
            floor, 1e3 * p.sum(lambda r: p.floors(r)[0]),
            1e3 * p.sum(lambda r: p.floors(r)[1]), p.device_ms(),
            step_device_ms.read(outcome)))
    program_costs.say_table(outcome, p)
    return floor
