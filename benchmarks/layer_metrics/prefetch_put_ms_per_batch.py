"""Host time per batch that the prefetcher's producer thread spends placing
a batch on the device (span `mx.prefetch.device_put`: the call, not the
transfer's end), over the untraced blocks."""

from .. import program_spans

LAYER = "input"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "program_span"


def read(outcome):
    win = program_spans.untraced(outcome)
    if win is None:
        return None
    puts = program_spans.named(outcome, ("mx.prefetch.device_put",),
                               win[0], win[1])
    if not puts:
        return None
    return 1e3 * sum(s.end - s.start for s in puts) / len(puts)
