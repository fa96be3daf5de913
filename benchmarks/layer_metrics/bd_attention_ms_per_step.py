"""Device time per step under scope `mx.bd.attention`, forward and
backward: the graph's `_contrib_DotProductAttention:*` nodes that run under
the block-diffusion mask (the two flash kernels and whatever the wrappers
around them cost: the backward's delta pass, copies XLA adds to feed them).
Prints beside it the kernels' tile plan, as the `mx.flash.plan` spans carry
it for that mask (one per traced call: `mask`, `block`, `half`, per kernel
`tiles_visited`, `tiles_masked`, `tiles_ideal`), and the projections under
`mx.bd.project` (the q, k and v products, the per-head norms, the rotary
positions, the move to the head-major layout, the key/value heads'
repeat), which lie outside the node.  Nothing to read where the step holds
no such scope."""

import json

from .. import program_spans

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"

SCOPE = r"/mx\.bd\.attention(/|$)"


def plans(outcome):
    """``[(plan, traced calls)]`` of the `mx.flash.plan` spans recorded
    under a block-diffusion mask; empty from a program without them."""
    seen = {}
    for span in program_spans.named(outcome, ("mx.flash.plan",)) or ():
        if span.args and span.args.get("mask") == "block_diffusion":
            key = json.dumps(span.args, sort_keys=True)
            seen[key] = seen.get(key, 0) + 1
    return [(json.loads(key), calls) for key, calls in seen.items()]


def read(outcome):
    value = program_spans.scope_ms_per_step(outcome, SCOPE)
    if value is None:
        return None
    for plan, calls in plans(outcome):
        key = json.dumps(plan, sort_keys=True)
        program_spans.say_once(
            outcome, "bd-plan:" + key,
            "bench: mx.flash.plan (%d traced calls) %s" % (calls, key))
    project = program_spans.scope_ms_per_step(outcome,
                                              r"[/(]mx\.bd\.project/")
    if project is not None:
        program_spans.say_once(
            outcome, "bd-project",
            "bench: block diffusion mx.bd.project %.3f ms a step beside "
            "mx.bd.attention %.3f" % (project, value))
    return value
