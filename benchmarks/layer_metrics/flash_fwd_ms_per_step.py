"""Device time per step in the Pallas flash-attention forward kernel
(scope `mx.flash.fwd`).  Nothing to read where the step calls none."""

from .. import program_spans

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"


def read(outcome):
    return program_spans.scope_ms_per_step(outcome, r"/mx\.flash\.fwd(/|$)")
