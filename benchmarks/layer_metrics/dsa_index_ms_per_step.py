"""Device time per step under the scopes `mx.dsa.index` and `mx.dsa.select`
inside the `_contrib_SparseAttention:*` nodes: the indexer's three
projections (forward, and backward from the alignment term's gradient), its
scores over every causal pair, the counting that finds each query's k-th
largest and the selection's bits: what choosing the keys costs before any
is attended to.  Nothing to read where the step holds no sparse
attention."""

from . import dsa_ms_per_step

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"


def read(outcome):
    return dsa_ms_per_step.phase_ms(outcome, "mx.dsa.index", "mx.dsa.select")
