"""Host time per step inside `trainer.fit_batch`, call to return (span
`bench.fit_batch`), over the untraced blocks: what the trainer's Python
costs before the device is asked for anything."""

from . import host_feed_ms_per_step

LAYER = "trainers"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "program_span"


def read(outcome):
    return host_feed_ms_per_step.read(outcome, "bench.fit_batch")
