"""1 - whole-window rate / median block rate, over the blocks of the
traced run's window that the profiler does not cover: the share of the
window that stalls took.  `train_samples_per_s` carries them; this says
that they were stalls and not a slower step."""

LAYER = "the whole loop"
UNIT = "%"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "host_clock"


def read(outcome):
    reading = outcome.facts.get("reading")
    return None if reading is None else reading["deficit_pct"]
