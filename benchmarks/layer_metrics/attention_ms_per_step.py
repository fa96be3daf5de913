"""Device time per step under the graph's `_contrib_DotProductAttention:*`
nodes, forward and backward: the two flash kernels and whatever the
wrappers around them cost (padding, slicing, the backward's delta pass,
copies XLA adds to feed them).  Prints the kernels' tile plan beside it,
as the `mx.flash.plan` spans carry it (one per traced call; the plan is
static per shape).  Nothing to read where the step holds no such node
(ResNet-50); no plan to print from a program without the span."""

import json

from .. import program_spans

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"


def read(outcome):
    value = program_spans.scope_ms_per_step(
        outcome, r"[/(]_contrib_DotProductAttention:")
    plans = program_spans.named(outcome, ("mx.flash.plan",)) or ()
    seen = {}
    for span in plans:
        if span.args:
            key = json.dumps(span.args, sort_keys=True)
            seen[key] = seen.get(key, 0) + 1
    for key, calls in seen.items():
        program_spans.say_once(
            outcome, "flash-plan:" + key,
            "bench: mx.flash.plan (%d traced calls) %s" % (calls, key))
    return value
