"""Operations and bytes of learned sparse attention's own parts, from its
shapes: the only place these counts live.  Useful work only, and the
ALGORITHM's, whatever implements it: the attention core over the pairs a
query SELECTED (``min(t + 1, topk)`` keys for query ``t``), the indexer's
scores over the VISIBLE causal pairs (every one has to be scored before any
can be chosen).  Not counted: the pairs a masked kernel computes and throws
away, the scores a backward pass or the alignment term forms again, the
counting that finds the k-th largest.
"""

from __future__ import annotations


def visible_pairs(seq):
    """Causal query-key pairs of one sequence: ``sum_t (t + 1)``."""
    return seq * (seq + 1) // 2


def selected_pairs(seq, topk):
    """Pairs the selection keeps in one sequence: ``sum_t min(t + 1,
    topk)``."""
    k = min(int(topk), seq)
    return k * (k + 1) // 2 + (seq - k) * k


def core_flops(batch, heads, seq, topk, d, d_v, training=True):
    """FLOPs of the attention core over the selected pairs: the scores (*d*
    a pair) and their product with v (*d_v* a pair) for each of *heads*
    query heads, 2 a multiply-add; with *training* the backward's four
    contractions too: three times the forward."""
    forward = 2 * batch * heads * selected_pairs(seq, topk) * (d + d_v)
    return 3 * forward if training else forward


def core_bytes(batch, heads, kv_heads, seq, topk, d, d_v, itemsize=2,
               training=True):
    """Bytes the core has to move at the least: forward it reads q, each
    key/value head once and writes the output and a float32 logsumexp a
    row; the backward reads those and dO and the two float32 rows and
    writes dq, dk, dv.  (A form that gathers a query's keys moves far more:
    2 * topk * kv_heads * (d + d_v) * itemsize a query.)"""
    q_side = heads * seq * (d + d_v)                     # q, o
    k_side = kv_heads * seq * (d + d_v)                  # k, v
    forward = batch * ((q_side + k_side) * itemsize + 4 * heads * seq)
    if not training:
        return forward
    backward = batch * ((q_side + k_side) * itemsize + 8 * heads * seq
                        + (heads * seq * d + k_side) * itemsize)
    return forward + backward


def index_flops(batch, index_heads, width, seq, training=True):
    """FLOPs of the indexer's scores over the visible pairs: *index_heads*
    dots of *width* a pair, 2 a multiply-add; with *training* three times
    that (its backward exists through the alignment term)."""
    forward = 2 * batch * index_heads * width * visible_pairs(seq)
    return 3 * forward if training else forward


def index_bytes(batch, index_heads, width, seq, itemsize=2):
    """Bytes the forward scores and the selection have to move at the
    least: the indexer's queries, key and head weights in, one bit a pair
    out (both ways round) and a float32 logsumexp a row."""
    return batch * (seq * (index_heads + 1) * width * itemsize
                    + seq * index_heads * 4 + 2 * seq * seq // 8 + 4 * seq)
