"""Host time per step that the loop's thread waits in the prefetcher's ring
pop (span `mx.prefetch.wait`, the wait `input_wait_seconds` observes),
over the untraced blocks."""

from .. import program_spans

LAYER = "input"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "program_span"


def read(outcome):
    return program_spans.per_untraced_step_ms(outcome, "mx.prefetch.wait")
