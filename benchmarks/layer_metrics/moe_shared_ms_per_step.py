"""Device time per step under the scope `mx.moe.shared`, forward and
backward: the shared experts, one gated MLP that every token passes
through beside the routed experts (and that every chip of a layer's group
computes alike).  Nothing to read where the step holds none."""

from .. import program_spans

LAYER = "step program"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"


def read(outcome):
    return program_spans.scope_ms_per_step(outcome,
                                           r"/mx\.moe\.shared(/|$)")
