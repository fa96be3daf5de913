"""Device time per step under the scope `mx.dsa.align` inside the
`_contrib_SparseAttention:*` nodes: the alignment term that trains the
indexer, its value and its gradient in one pass (the heads' mean
probabilities formed again from q, k and the kept logsumexps, the indexer's
scores formed again, their difference carried back to the indexer's
queries, key and head weights).  Nothing to read where the step holds no
sparse attention."""

from . import dsa_ms_per_step

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"


def read(outcome):
    return dsa_ms_per_step.phase_ms(outcome, "mx.dsa.align")
