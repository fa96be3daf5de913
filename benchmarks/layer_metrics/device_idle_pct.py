"""1 - busy / window of the traced blocks, averaged over the chips."""

LAYER = "device"
UNIT = "%"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"


def read(outcome):
    if outcome.trace is None:
        return None
    return 100.0 * (1.0 - outcome.trace.busy_s / outcome.trace.window_s)
