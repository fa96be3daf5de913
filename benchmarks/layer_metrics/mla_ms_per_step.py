"""Device time per step under the graph's `_contrib_LatentAttention:*`
nodes, forward and backward: the three input projections and the latent's
norm (`mx.mla.project`), rotary positions, the one rope key spread over
the heads, the concatenations and layout moves (`mx.mla.assemble`), the
two flash kernels at keys wider than values, the output projection
(`mx.mla.out`).  Prints the block's plan beside it, as the `mx.mla.plan`
spans carry it (one per traced call), and the phases.  Nothing to read
where the step holds no such node."""

import json

from .. import program_spans

LAYER = "step program"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"

PHASES = ("mx.mla.project", "mx.mla.assemble", "mx.flash.fwd",
          "mx.flash.bwd", "mx.mla.out")


def read(outcome):
    value = program_spans.scope_ms_per_step(
        outcome, r"[/(]_contrib_LatentAttention:")
    seen = {}
    for span in program_spans.named(outcome, ("mx.mla.plan",)) or ():
        if span.args:
            key = json.dumps(span.args, sort_keys=True)
            seen[key] = seen.get(key, 0) + 1
    for key, calls in seen.items():
        program_spans.say_once(
            outcome, "mla-plan:" + key,
            "bench: mx.mla.plan (%d traced calls) %s" % (calls, key))
    for phase in PHASES:
        ms = program_spans.scope_ms_per_step(
            outcome, r"_contrib_LatentAttention:.*/%s(/|$)"
            % phase.replace(".", r"\."))
        if ms is not None:
            program_spans.say_once(
                outcome, "mla-phase:" + phase,
                "bench: latent attention %s %.3f ms a step" % (phase, ms))
    return value
