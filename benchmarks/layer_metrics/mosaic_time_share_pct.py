"""Share of the device's busy time spent in Mosaic (Pallas) custom calls:
the flash-attention forward, dk/dv and dq kernels.  Nothing to read where
the step holds no such call (ResNet)."""

import re

LAYER = "kernels"
UNIT = "%"
MOVES = "train_samples_per_s"
SOURCE = "device_trace"

# a Mosaic call is a custom-call whose target is tpu_custom_call; the
# event's name is the HLO instruction's text, which says so (the
# instruction itself is named after the traced function, `branch_0_fun`
# under `platform_dependent`)
_MOSAIC = re.compile(r"tpu_custom_call|mosaic|pallas", re.I)


def read(outcome):
    if outcome.trace is None:
        return None
    seconds = outcome.trace.seconds_where(_MOSAIC.search)
    if not seconds:
        return None
    return 100.0 * seconds / outcome.trace.busy_s
