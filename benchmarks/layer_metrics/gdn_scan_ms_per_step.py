"""Device time per step under scope `mx.gdn.scan`, forward and backward: the
gated delta rule itself (`_contrib_GatedDeltaRule`) in the linear-attention
layers: the chunks' triangular systems, the scan over the chunks that
carries the state, and in the backward the systems again, the reverse walk
and the derivative of the systems.  What `gdn_scan_roofline_pct` measures
against the recurrence's floor.  Nothing to read where the step holds no
such scope."""

from . import gdn_ms_per_step

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"


def read(outcome):
    return gdn_ms_per_step.part_ms(outcome, "scan")
