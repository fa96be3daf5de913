"""Device time per step in the Pallas flash-attention backward kernel
(scope `mx.flash.bwd`, kernel `mx_flash_bwd`: dq, dk and dv from scores
formed once a tile), wherever the step calls it: under a
`_contrib_DotProductAttention:*` node or inside a latent attention block.
Nothing to read where the step calls none."""

from .. import program_spans

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"


def read(outcome):
    return program_spans.scope_ms_per_step(outcome, r"/mx\.flash\.bwd(/|$)")
