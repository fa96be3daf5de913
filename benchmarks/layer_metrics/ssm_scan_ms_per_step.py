"""Device time per step under scope `mx.ssm.scan`, forward and backward: the
selective state-space recurrence itself (`_contrib_StateSpaceScan`) in the
mamba layers: the scan over the chunks that carries the state, each chunk's
decay matrix, scores and products, and in the backward each chunk again with
its derivative on the reverse walk.  What `ssm_scan_roofline_pct` measures
against the recurrence's floor.  Nothing to read where the step holds no such
scope."""

from . import ssm_ms_per_step

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"


def read(outcome):
    return ssm_ms_per_step.part_ms(outcome, "scan")
