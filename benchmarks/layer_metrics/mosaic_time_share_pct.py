"""Share of the device's busy time spent in Mosaic (Pallas) custom calls:
the flash-attention forward and backward kernels, and where the step holds
them the routed layers' grouped products (megablox `gmm`/`tgmm`) and the
gated short convolution's pair.  Nothing to read where the step holds no
such call (ResNet)."""

import re

LAYER = "kernels"
UNIT = "%"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"

# a Mosaic call is a custom-call whose target is tpu_custom_call; the
# event's name is the HLO instruction's text, which says so (the
# instruction itself is named after the kernel or the traced function,
# `branch_0_fun` under `platform_dependent`).  The target, and not the
# words `pallas` or `mosaic` anywhere in the text: the text names the
# operands too, and a fusion or a copy that reads `%pallas_call.23` is no
# kernel (PR 32: 0.245 s of such fusions in LFM2's 2.71 s window)
_MOSAIC = re.compile(r'custom_call_target="tpu_custom_call"')


def read(outcome):
    if outcome.trace is None:
        return None
    seconds = outcome.trace.seconds_where(_MOSAIC.search)
    if not seconds:
        return None
    return 100.0 * seconds / outcome.trace.busy_s
