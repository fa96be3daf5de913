"""The trainer's own Python per step: the self time of span `mx.fit_batch`,
all of `fit_batch` but its one child, `mx.fit_batch.dispatch`, which holds
everything handed to the device (the batch's cast and placement, the key
split, lr and t, the jitted step) and so also every wait for a full device
queue.  Over the untraced blocks.  Prints the quartiles of the whole span
beside: the steps that found the queue empty say what a step costs the
host when nothing makes it wait."""

import statistics

from .. import program_spans

LAYER = "trainers"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "program_span"


def read(outcome):
    win = program_spans.untraced(outcome)
    if win is None:
        return None
    own = program_spans.self_seconds(outcome, "mx.fit_batch", win[0], win[1])
    if own is None:
        return None
    whole = [1e3 * (s.end - s.start) for s in program_spans.named(
        outcome, ("mx.fit_batch",), win[0], win[1])]
    if len(whole) > 1:
        program_spans.say_once(
            outcome, "fit-batch",
            "bench: mx.fit_batch ms a step, dispatch and waits included: "
            "least %.3f, quartiles %s, most %.3f" % (
                min(whole), " ".join(
                    "%.3f" % q for q in statistics.quantiles(whole, n=4)),
                max(whole)))
    return 1e3 * own / win[2]
