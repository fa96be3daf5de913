"""Device time per step under the scope `mx.mla.assemble`, forward and
backward: what the latent attention block spends between its projections
and the flash kernels on rotary positions, on spreading the one rope key
of a position over every head, on concatenating q and k at their full
width and on the moves between (batch, seq, heads) and (batch, heads,
seq): the cost of materialising k.  Nothing to read where the step holds
no latent attention."""

from .. import program_spans

LAYER = "step program"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"


def read(outcome):
    return program_spans.scope_ms_per_step(outcome,
                                           r"/mx\.mla\.assemble(/|$)")
