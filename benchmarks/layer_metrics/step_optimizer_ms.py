"""Device time per step in the update (`mx.optimizer`, and `mx.grad_clip`
where the trainer clips): the traced blocks' device events, each given to a
phase by its instruction's op_name in the step's scope map."""

from .. import program_spans

LAYER = "step program"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"


def read(outcome):
    return program_spans.phase_ms_per_step(outcome, "optimizer")
