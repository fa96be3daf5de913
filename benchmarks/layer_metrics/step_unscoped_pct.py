"""Share of the device's busy time under instructions that the step's scope
map does not hold, or holds under no `mx.` or `<op>:<node>` scope: how
much the forward/backward/optimizer split leaves out."""

from .. import program_spans

LAYER = "step program"
UNIT = "%"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"


def read(outcome):
    s = program_spans.device_seconds(
        outcome, lambda op: program_spans.phase(op) is None)
    return None if s is None else 100.0 * s / outcome.trace.busy_s
