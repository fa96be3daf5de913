"""Operations, bytes and tiles of causal attention through a sliding window,
from its shapes: the only place these counts live.  Over ``S`` positions
with a window of ``w`` keys, query ``t`` sees key ``s`` where

    t - w < s <= t

(``w`` keys, the query's own among them; an earlier query all it has).
Useful work only, and the ALGORITHM's, whatever implements it: the attention
core over the VISIBLE pairs.  Not counted: the pairs a kernel computes in a
tile that the window's edge or the diagonal crosses and throws away.  `visible` is the definition itself, in
numpy, and imports nothing of the program: the tests hold the program's mask
against it.
"""

from __future__ import annotations

import numpy as np


def visible(q_pos, k_pos, window):
    """Whether query position *q_pos* sees key position *k_pos* (numpy
    arrays that broadcast) through a window of *window* keys: the
    definition above."""
    return (k_pos <= q_pos) & (k_pos > q_pos - window)


def visible_pairs(seq, window):
    """Visible query-key pairs of one sequence a head: a row of ``w`` or
    more positions sees ``w``, row ``t < w`` sees ``t + 1``: ``w S - w (w -
    1) / 2`` (the causal triangle where ``w >= S``)."""
    w = min(window, seq)
    return w * seq - w * (w - 1) // 2


def causal_pairs(seq):
    """What a causal mask alone leaves: ``sum_t (t + 1)``."""
    return seq * (seq + 1) // 2


def core_flops(batch, heads, seq, window, d, d_v, training=True):
    """FLOPs of the flash attention core over the visible pairs, 2 a
    multiply-add: forward the scores (*d* a pair) and their product with v
    (*d_v* a pair) for each of *heads* query heads; with *training* the
    backward's five contractions too, the scores formed again from the kept
    logsumexp among them (the flash algorithm's own backward, 2.5 times its
    forward as arXiv:2205.14135 counts it: 14 FLOPs a pair and unit of
    width in all where ``d == d_v``)."""
    pairs = batch * heads * visible_pairs(seq, window)
    forward = 2 * pairs * (d + d_v)
    if not training:
        return forward
    # s again, dP = dO v^T and dv = p^T dO over d_v; dk, dq over d
    return forward + 2 * pairs * (3 * d + 2 * d_v)


def core_bytes(batch, heads, kv_heads, seq, d, d_v, itemsize=2,
               training=True):
    """Bytes the core has to move at the least: forward it reads q, each
    key/value head once and writes the output and a float32 logsumexp a
    row; the backward reads those and dO and the two float32 rows and
    writes dq, dk, dv."""
    q_side = heads * seq * (d + d_v)                     # q, o
    k_side = kv_heads * seq * (d + d_v)                  # k, v
    forward = batch * ((q_side + k_side) * itemsize + 4 * heads * seq)
    if not training:
        return forward
    backward = batch * ((q_side + k_side) * itemsize + 8 * heads * seq
                        + (heads * seq * d + k_side) * itemsize)
    return forward + backward


def tiles(seq, window, sub_q, sub_k):
    """``(needed, crossed)`` for one head: the score tiles of *sub_q*
    queries by *sub_k* keys that hold a visible pair, and those of them
    that hold a pair that is not visible too (the window's lower edge or
    the diagonal crosses them: a kernel has to run a mask body there and
    nowhere else).  A last tile may be short.  From the definition, a row
    of tiles at a time."""
    needed = crossed = 0
    for q0 in range(0, seq, sub_q):
        q_pos = np.arange(q0, min(q0 + sub_q, seq))
        for k0 in range(0, seq, sub_k):
            seen = visible(q_pos[:, None],
                           np.arange(k0, min(k0 + sub_k, seq))[None, :],
                           window)
            if seen.any():
                needed += 1
                crossed += not seen.all()
    return needed, crossed
