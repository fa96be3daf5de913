"""Device time per step under the scopes `mx.gqa.*`, forward and backward:
the gated full-attention layers of a net that mixes them with window layers
(`mx.gqa.project`, `mx.gqa.attention`, `mx.gqa.out`, cut as
`swa_ms_per_step` cuts `mx.swa.*`), so that the two kinds' shares of a step
are read side by side.  Prints the three parts beside the sum.  Nothing to
read where the step holds no such scope (an ungated `full_attention` layer
has none)."""

from .. import program_spans
from . import swa_ms_per_step

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"


def read(outcome):
    value = program_spans.scope_ms_per_step(
        outcome, swa_ms_per_step.scope("mx.gqa"))
    if value is None:
        return None
    swa_ms_per_step.say_parts(outcome, "mx.gqa", value)
    return value
