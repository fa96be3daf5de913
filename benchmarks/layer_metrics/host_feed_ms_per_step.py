"""Host time per step that the loop waits in `next(iter)` on the
`DevicePrefetcher` (span `bench.batch_fetch`), over the untraced blocks."""

LAYER = "input"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "program_span"


def read(outcome, span="bench.batch_fetch"):
    f = outcome.facts
    if "untraced_span" not in f:
        return None
    steps = f["steps"] - f["traced_blocks"] * f["steps_per_block"]
    return 1e3 * outcome.spans.seconds(span, *f["untraced_span"]) / steps
