"""Device time per step under the scopes `mx.ssm.*`, forward and backward:
the layers that carry a state by the selective state-space recurrence
(`mamba`).  `mx.ssm.project` holds the one product of the layer's input (the
output's gate, the convolved channels and the step sizes are its row blocks)
and its split; `mx.ssm.conv` the short causal convolution over the x, B and C
channels with its bias and silu, and the step sizes' softplus; `mx.ssm.scan`
the recurrence itself (`_contrib_StateSpaceScan`: the scan over the chunks, a
chunk's decay matrix, scores and three products a step, and the same again
with the reverse walk in the backward); `mx.ssm.out` the gated RMS norm (the
gate first) and the output product.  Prints the four parts beside the sum,
and the scan's plan as the `mx.ssm.plan` spans carry it (one per traced call:
heads, head width, state, groups, chunk, `path`, the bytes of state kept for
the backward).  Nothing to read where the step holds no such scope."""

import json

from .. import program_spans
from . import swa_ms_per_step

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"

PARTS = ("project", "conv", "scan", "out")


def scope(part="(%s)" % "|".join(PARTS)):
    return swa_ms_per_step.scope("mx.ssm", part)


def plans(outcome):
    """``[(plan, traced calls)]`` of the `mx.ssm.plan` spans; empty from a
    program without them."""
    seen = {}
    for span in program_spans.named(outcome, ("mx.ssm.plan",)) or ():
        if span.args:
            key = json.dumps(span.args, sort_keys=True)
            seen[key] = seen.get(key, 0) + 1
    return [(json.loads(key), calls) for key, calls in seen.items()]


def part_ms(outcome, part):
    return program_spans.scope_ms_per_step(outcome, scope(part))


def read(outcome):
    value = program_spans.scope_ms_per_step(outcome, scope())
    if value is None:
        return None
    for plan, calls in plans(outcome):
        key = json.dumps(plan, sort_keys=True)
        program_spans.say_once(
            outcome, "ssm-plan:" + key,
            "bench: mx.ssm.plan (%d traced calls) %s" % (calls, key))
    program_spans.say_once(
        outcome, "mx.ssm-parts",
        "bench: mx.ssm %.3f ms a step: %s" % (value, ", ".join(
            "%s %.3f" % (part, part_ms(outcome, part) or 0.0)
            for part in PARTS)))
    return value
