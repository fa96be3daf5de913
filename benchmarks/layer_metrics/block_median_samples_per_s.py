"""The median of the per-block rates, over the blocks of the traced run's
window that the profiler does not cover: the pace of a usual block, which
one stalled block moves by nothing and a stall in every block does."""

LAYER = "the whole loop"
UNIT = "samples/s"
MOVES = "train_samples_per_s"
BETTER = "higher"
SOURCE = "host_clock"


def read(outcome):
    reading = outcome.facts.get("reading")
    return None if reading is None else reading["median_rate"]
