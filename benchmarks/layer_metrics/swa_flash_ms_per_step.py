"""Device time per step in the two Pallas flash kernels (`mx_flash_fwd`
under scope `mx.flash.fwd`, `mx_flash_bwd` under `mx.flash.bwd`) where they
run through a causal window, inside scope `mx.swa.attention`: the loops
visit the tiles that hold a visible pair, bounded on both sides, and run a
mask body on those the window's edge or the diagonal crosses.  Nothing to
read where the step holds no such kernel."""

from .. import program_spans

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"

SCOPE = r"[/(]mx\.swa\.attention/(.*[/)])?mx\.flash\.(fwd|bwd)(/|$)"


def read(outcome):
    return program_spans.scope_ms_per_step(outcome, SCOPE)
