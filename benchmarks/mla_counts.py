"""Operations and bytes of the attention core at two head widths (queries
and keys *d* wide, values and the output *d_v* wide), from its shapes: the
only place these counts live.  Useful work only: the scores above a causal
diagonal, and the scores that the backward kernel forms again from q and
k, are not counted.
"""

from __future__ import annotations


def visible_scores(sq, sk, causal=True):
    """Query-key pairs a head computes: the rectangle, or with *causal*
    the part on and under the diagonal, the sequences' ends aligned."""
    if not causal:
        return sq * sk
    off = sk - sq
    lo = max(0, -off)                       # rows before it see no key
    return (sq - lo) * (lo + off + sq + off + 1) // 2 if sq > lo else 0


def flash_flops(batch_heads, sq, sk, d, d_v, causal=True, training=True):
    """FLOPs of the attention core: forward the scores (*d* a pair) and
    their product with v (*d_v* a pair), 2 a multiply-add; with *training*
    the backward's four contractions too (dv and dP over *d_v*, dq and dk
    over *d*): three times the forward."""
    forward = 2 * batch_heads * visible_scores(sq, sk, causal) * (d + d_v)
    return 3 * forward if training else forward


def flash_bytes(batch_heads, sq, sk, d, d_v, itemsize=2, training=True):
    """Bytes the core has to move at the least: forward reads q, k, v and
    writes the output and a float32 logsumexp a row; the backward reads q,
    k, v, dO and the two float32 rows (logsumexp, delta) and writes dq,
    dk, dv."""
    q_side, k_side = sq * (d + d_v), sk * (d + d_v)     # q, o | k, v
    forward = batch_heads * ((q_side + k_side) * itemsize + 4 * sq)
    if not training:
        return forward
    backward = batch_heads * ((q_side + k_side) * itemsize + 8 * sq
                              + (sq * d + k_side) * itemsize)
    return forward + backward
