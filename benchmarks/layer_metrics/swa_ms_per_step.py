"""Device time per step under the scopes `mx.swa.*`, forward and backward:
the layers whose attention looks through a causal window
(`sliding_attention`).  `mx.swa.project` holds the q, k, v and gate
products, the per-head norms, the rotary positions, the move to the
head-major layout and the key/value heads' repeat; `mx.swa.attention` the
graph's `_contrib_DotProductAttention:*` node (the two flash kernels and
whatever the wrappers around them cost: the backward's delta pass, copies
XLA adds to feed them); `mx.swa.out` the gate's multiply, the move back and
the output product.  Prints the three parts beside the sum, and the kernels'
tile plan as the `mx.flash.plan` spans carry it for a window (one per traced
call: `mask`, `window`, per kernel `tiles_visited`, `tiles_needed`,
`tiles_masked`, `tiles_ideal`).  Nothing to read where the step holds no
such scope."""

import json

from .. import program_spans

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"

PARTS = ("project", "attention", "out")


def scope(family, part="(%s)" % "|".join(PARTS)):
    """The regular expression of scope ``<family>.<part>`` in an op_name,
    forward (``.../jvp(mx.swa.out/...``) and backward alike."""
    return r"[/(]%s\.%s/" % (family.replace(".", r"\."), part)


def plans(outcome):
    """``[(plan, traced calls)]`` of the `mx.flash.plan` spans recorded
    under a window; empty from a program without them."""
    seen = {}
    for span in program_spans.named(outcome, ("mx.flash.plan",)) or ():
        if span.args and span.args.get("mask") == "window":
            key = json.dumps(span.args, sort_keys=True)
            seen[key] = seen.get(key, 0) + 1
    return [(json.loads(key), calls) for key, calls in seen.items()]


def say_parts(outcome, family, value):
    parts = [(part, program_spans.scope_ms_per_step(
        outcome, scope(family, part))) for part in PARTS]
    program_spans.say_once(
        outcome, family + "-parts",
        "bench: %s %.3f ms a step: %s" % (family, value, ", ".join(
            "%s %.3f" % (part, ms or 0.0) for part, ms in parts)))


def read(outcome):
    value = program_spans.scope_ms_per_step(outcome, scope("mx.swa"))
    if value is None:
        return None
    for plan, calls in plans(outcome):
        key = json.dumps(plan, sort_keys=True)
        program_spans.say_once(
            outcome, "swa-plan:" + key,
            "bench: mx.flash.plan (%d traced calls) %s" % (calls, key))
    say_parts(outcome, "mx.swa", value)
    return value
