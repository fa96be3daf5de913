"""Time per block inside full (generation 2) collections of Python's heap
(span `mx.gc`, from a `gc.callbacks` entry), over the untraced blocks: a
stalled block's first suspect."""

from .. import program_spans

LAYER = "the whole loop"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "program_span"


def read(outcome):
    win = program_spans.untraced(outcome)
    if win is None:
        return None
    pauses = program_spans.named(outcome, ("mx.gc",), win[0], win[1])
    return None if pauses is None else \
        1e3 * program_spans.seconds(pauses) / win[3]
