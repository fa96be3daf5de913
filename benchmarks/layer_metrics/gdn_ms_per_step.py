"""Device time per step under the scopes `mx.gdn.*`, forward and backward:
the layers that carry a state by the gated delta rule (`linear_attention`).
`mx.gdn.project` holds the six products of the layer's input (q, k and v in
one matrix, the output's gate, the decay's and the write strength's);
`mx.gdn.conv` the short causal convolution over the concatenated q, k, v
channels with its silu, the per-head L2 norms of q and k and the two gates'
activations; `mx.gdn.scan` the rule itself (`_contrib_GatedDeltaRule`: every
chunk's triangular system, the scan over the chunks, and the same again with
the reverse walk in the backward); `mx.gdn.out` the gated RMS norm of each
head's output and the output product.  Prints the four parts beside the sum,
and the rule's plan as the `mx.gdn.plan` spans carry it (one per traced
call: heads, key and value widths, chunk, chunks, `path`, the bytes of state
kept for the backward).  Nothing to read where the step holds no such
scope."""

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
    return swa_ms_per_step.scope("mx.gdn", part)


def plans(outcome):
    """``[(plan, traced calls)]`` of the `mx.gdn.plan` spans; empty from a
    program without them."""
    seen = {}
    for span in program_spans.named(outcome, ("mx.gdn.plan",)) or ():
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
            outcome, "gdn-plan:" + key,
            "bench: mx.gdn.plan (%d traced calls) %s" % (calls, key))
    program_spans.say_once(
        outcome, "mx.gdn-parts",
        "bench: mx.gdn %.3f ms a step: %s" % (value, ", ".join(
            "%s %.3f" % (part, part_ms(outcome, part) or 0.0)
            for part in PARTS)))
    return value
