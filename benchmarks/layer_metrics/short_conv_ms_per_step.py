"""Device time per step under the graph's `_contrib_GatedShortConv:*`
nodes, forward and backward: the input projection, the gates, the
depthwise causal convolution (scope `mx.shortconv`) and the output
projection.  Nothing to read where the step holds no such node."""

from .. import program_spans

LAYER = "step program"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"


def read(outcome):
    return program_spans.scope_ms_per_step(
        outcome, r"[/(]_contrib_GatedShortConv:")
