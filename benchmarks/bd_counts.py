"""Operations, bytes and tiles of attention under the block-diffusion mask,
from its shapes: the only place these counts live.  A sequence of ``L``
tokens in blocks of ``B`` is presented as ``2L`` positions, a clean copy
then a noised copy; with ``n(j) = j >= L`` and ``b(j) = (j mod L) // B``
query ``j`` sees key ``s`` where

    not n(s) and not n(j) and b(s) <= b(j)      (clean on clean)
    or not n(s) and n(j) and b(s) < b(j)        (noised on earlier clean)
    or n(s) and n(j) and b(s) == b(j)           (noised on its own block)

(BD3-LM, arXiv:2503.09573).  Useful work only, and the ALGORITHM's, whatever
implements it: the attention core over the VISIBLE pairs.  Not counted: the
pairs a kernel computes in a tile that a boundary crosses and throws away,
the scores a backward pass forms again.  `visible` is the definition itself,
in numpy, and imports nothing of the program: the tests hold the program's
mask against it.
"""

from __future__ import annotations

import numpy as np


def visible(q_pos, k_pos, half, block):
    """Whether query position *q_pos* sees key position *k_pos* (numpy
    arrays that broadcast) among ``2 * half`` positions in blocks of
    *block*: the definition above, term by term."""
    qn, kn = q_pos >= half, k_pos >= half
    qb, kb = (q_pos % half) // block, (k_pos % half) // block
    return (~kn & ~qn & (kb <= qb)) | (~kn & qn & (kb < qb)) \
        | (kn & qn & (kb == qb))


def visible_pairs(half, block):
    """Visible query-key pairs of one sequence a head: ``K(K+1)/2 B^2``
    clean on clean, ``K(K-1)/2 B^2`` noised on clean, ``K B^2`` noised on
    noised, with ``K = half / block`` blocks: ``half * (half + block)``."""
    return half * (half + block)


def causal_pairs(seq):
    """What a causal mask over *seq* positions leaves: ``sum_t (t + 1)``."""
    return seq * (seq + 1) // 2


def core_flops(batch, heads, half, block, d, d_v, training=True):
    """FLOPs of the attention core over the visible pairs: the scores (*d*
    a pair) and their product with v (*d_v* a pair) for each of *heads*
    query heads, 2 a multiply-add; with *training* the backward's four
    contractions too: three times the forward."""
    forward = 2 * batch * heads * visible_pairs(half, block) * (d + d_v)
    return 3 * forward if training else forward


def core_bytes(batch, heads, kv_heads, half, d, d_v, itemsize=2,
               training=True):
    """Bytes the core has to move at the least over the ``2 * half``
    positions: forward it reads q, each key/value head once and writes the
    output and a float32 logsumexp a row; the backward reads those and dO
    and the two float32 rows and writes dq, dk, dv."""
    seq = 2 * half
    q_side = heads * seq * (d + d_v)                     # q, o
    k_side = kv_heads * seq * (d + d_v)                  # k, v
    forward = batch * ((q_side + k_side) * itemsize + 4 * heads * seq)
    if not training:
        return forward
    backward = batch * ((q_side + k_side) * itemsize + 8 * heads * seq
                        + (heads * seq * d + k_side) * itemsize)
    return forward + backward


def tiles(half, block, sub_q, sub_k):
    """``(needed, crossed)`` for one head: the score tiles of *sub_q*
    queries by *sub_k* keys that hold a visible pair, and those of them
    that hold a pair that is not visible too (a boundary of the mask
    crosses them: a kernel has to run a mask body there and nowhere else).
    Each copy is cut into tiles of its own from its first position on, so
    no tile lies across the two copies; a last tile may be short.  From the
    definition, a row of tiles at a time."""
    starts_k = [h * half + k0 for h in (0, 1)
                for k0 in range(0, half, sub_k)]
    needed = crossed = 0
    for h in (0, 1):
        for q0 in range(0, half, sub_q):
            q_pos = h * half + np.arange(q0, min(q0 + sub_q, half))
            for k0 in starts_k:
                end = min(k0 % half + sub_k, half) + k0 // half * half
                seen = visible(q_pos[:, None], np.arange(k0, end)[None, :],
                               half, block)
                if seen.any():
                    needed += 1
                    crossed += not seen.all()
    return needed, crossed
