"""Device time per step under the graph's `BatchNorm:*` nodes, forward and
backward: the instructions whose op_name lies in such a node.  A fusion has
one op_name, its root's, so batch-norm arithmetic that XLA fuses into a
convolution's fusion is the convolution's here.  Nothing to read where the
step holds no such node (the transformer)."""

from .. import program_spans

LAYER = "step program"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"


def read(outcome):
    return program_spans.scope_ms_per_step(outcome, r"[/(]BatchNorm:")
