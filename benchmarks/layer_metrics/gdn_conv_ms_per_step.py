"""Device time per step under scope `mx.gdn.conv`, forward and backward: in
the linear-attention layers the depthwise causal convolution over the
concatenated q, k, v channels, its silu, the per-head L2 norms of q and k,
the move to heads, and the two gates' activations (memory-bound work: its
floor is one pass over the projections each way).  Nothing to read where the
step holds no such scope."""

from . import gdn_ms_per_step

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"


def read(outcome):
    return gdn_ms_per_step.part_ms(outcome, "conv")
