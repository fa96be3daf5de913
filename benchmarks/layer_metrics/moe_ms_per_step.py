"""Device time per step under the graph's `_contrib_RoutedExperts:*` nodes,
forward and backward: routing, the sort of token-expert pairs, the gather,
the grouped products, the weighted combine.  Prints the layer's plan
beside it, as the `mx.moe.plan` spans carry it (one per traced call).
Nothing to read where the step holds no such node."""

import json

from .. import program_spans

LAYER = "step program"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"


def read(outcome):
    value = program_spans.scope_ms_per_step(
        outcome, r"[/(]_contrib_RoutedExperts:")
    plans = program_spans.named(outcome, ("mx.moe.plan",)) or ()
    seen = {}
    for span in plans:
        if span.args:
            key = json.dumps(span.args, sort_keys=True)
            seen[key] = seen.get(key, 0) + 1
    for key, calls in seen.items():
        program_spans.say_once(
            outcome, "moe-plan:" + key,
            "bench: mx.moe.plan (%d traced calls) %s" % (calls, key))
    for phase in ("route", "dispatch", "experts", "combine"):
        ms = program_spans.scope_ms_per_step(
            outcome, r"/mx\.moe\.%s(/|$)" % phase)
        if ms is not None:
            program_spans.say_once(
                outcome, "moe-phase:" + phase,
                "bench: mx.moe.%s %.3f ms a step" % (phase, ms))
    return value
