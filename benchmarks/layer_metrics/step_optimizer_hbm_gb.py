"""HBM bytes a step that belong to the update: what `bytes_by_scope` of
`profiler.cost_map` credits to `mx.optimizer` and `mx.grad_clip`, in
whatever fusion they ride (a weight-gradient fusion that carries the
update divides its traffic between the backward and the update).  Printed
with the time those bytes take at the HBM's peak, the part of them inside
instructions named for another phase, and `step_optimizer_ms` beside
it."""

from .. import program_costs
from . import step_optimizer_ms

LAYER = "step program"
UNIT = "GB"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"


def _update_bytes(rec, riding_only=False):
    phase = program_costs.program_spans.phase
    if riding_only and phase(rec["op_name"]) == "optimizer":
        return 0.0
    return sum(b for op, b in rec["bytes_by_scope"].items()
               if phase(op) == "optimizer")


def read(outcome):
    p = program_costs.priced(outcome)
    if p is None:
        return None
    moved = p.sum(_update_bytes)
    riding = p.sum(lambda r: _update_bytes(r, riding_only=True))
    program_costs.program_spans.say_once(
        outcome, "costs-optimizer",
        "bench: the update moves %.3f GB a step through HBM, %.3f ms at "
        "the peak; %.3f GB (%.3f ms) of it inside instructions named for "
        "the forward or the backward; step_optimizer_ms %.3f" % (
            moved / 1e9, 1e3 * moved / p.bytes_per_s, riding / 1e9,
            1e3 * riding / p.bytes_per_s,
            step_optimizer_ms.read(outcome) or 0.0))
    return moved / 1e9
