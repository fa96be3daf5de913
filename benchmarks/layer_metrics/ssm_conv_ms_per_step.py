"""Device time per step under scope `mx.ssm.conv`, forward and backward: in
the mamba layers the depthwise causal convolution over the x, B and C
channels, its bias and silu, the split into heads and groups, and the step
sizes' softplus (memory-bound work: its floor is one pass over the convolved
channels each way).  Nothing to read where the step holds no such scope."""

from . import ssm_ms_per_step

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"


def read(outcome):
    return ssm_ms_per_step.part_ms(outcome, "conv")
