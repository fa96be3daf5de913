"""Device busy time per step: the union of the device's operation events
in the traced blocks, averaged over the chips, over the traced steps."""

LAYER = "step program"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"


def read(outcome):
    if outcome.trace is None:
        return None
    f = outcome.facts
    return 1e3 * outcome.trace.busy_s / (
        f["traced_blocks"] * f["steps_per_block"])
