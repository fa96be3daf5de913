"""Device time per step under the scope `mx.moe.experts`: the routed
layers' grouped products and the gate between them, forward and backward
(the backward computes the hidden states again).  Nothing to read where
the step holds no routed layer."""

from .. import program_spans

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"


def read(outcome):
    return program_spans.scope_ms_per_step(outcome,
                                           r"/mx\.moe\.experts(/|$)")
