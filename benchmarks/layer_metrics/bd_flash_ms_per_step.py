"""Device time per step in the two Pallas flash kernels (`mx_flash_fwd`
under scope `mx.flash.fwd`, `mx_flash_bwd` under `mx.flash.bwd`) where they
run under the block-diffusion mask, inside scope `mx.bd.attention`: the
loops visit the tiles that hold a visible pair and run a mask body on those
a boundary crosses.  Nothing to read where the step holds no such kernel."""

from .. import program_spans

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"

SCOPE = r"/mx\.bd\.attention/(.*/)?mx\.flash\.(fwd|bwd)(/|$)"


def read(outcome):
    return program_spans.scope_ms_per_step(outcome, SCOPE)
