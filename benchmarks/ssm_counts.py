"""Operations and bytes of the selective state-space recurrence (Mamba-2),
from its shapes: the only place these counts live.  Useful work only, and the
RECURRENCE's as it is stated, whatever implements it.  Per token and head,
with a state of ``P x N`` (the head's width by the state's size):

    S  <- exp(dt A) S              P N            (the decay)
    S  <- S + (dt x) B^T         2 P N            (the rank-one write)
    y  =  S C                    2 P N            (the read)

``5 P N`` FLOPs forward, and by the usual count twice that again for the
backward.  Not counted: what a chunkwise form adds to get there in parallel
(the chunk's scores ``C B^T``, the decay matrix, the products against the
chunk's starting state), the skip ``D x`` (P a token and head), the step
size's softplus.

The bytes the recurrence has to move at the least: forward it reads x, dt, B
and C and writes y; backward it reads the four again and the output's
gradient and writes the four gradients.  x and y ``P`` numbers a head and
token at *itemsize*, dt one float32 a head and token, B and C ``N`` numbers a
GROUP and token (all the heads of a group share them).  A state that stays on
chip moves nothing.  `recurrence` is the definition itself, in numpy float64,
and imports nothing of the program: the tests hold the op against it.
"""

from __future__ import annotations

import numpy as np


def token_flops(head_dim, state, training=True):
    """FLOPs a token and head: ``5 P N`` forward, three times that with the
    backward."""
    return (3 if training else 1) * 5 * head_dim * state


def scan_flops(batch, seq, heads, head_dim, state, training=True):
    return batch * seq * heads * token_flops(head_dim, state, training)


def token_bytes(heads, groups, head_dim, state, itemsize=2, training=True):
    """Bytes a token, all heads: forward x and y (``P`` a head) and B and C
    (``N`` a group) at *itemsize* and dt in float32; backward the four inputs
    again, the output's gradient, and the four gradients."""
    inputs = heads * (head_dim * itemsize + 4) + 2 * groups * state * itemsize
    forward = inputs + heads * head_dim * itemsize
    if not training:
        return forward
    return forward + inputs + heads * head_dim * itemsize + inputs


def scan_bytes(batch, seq, heads, groups, head_dim, state, itemsize=2,
               training=True):
    return batch * seq * token_bytes(heads, groups, head_dim, state,
                                     itemsize, training)


def state_kept_bytes(batch, seq, heads, head_dim, state, chunk):
    """What a chunkwise forward keeps for its backward: one float32 state a
    head at each chunk boundary.  A state kept at every token is *chunk*
    times that."""
    return 4 * batch * (seq // chunk) * heads * head_dim * state


def recurrence(x, dt, a, b, c, d, decay=True, skip=True):
    """The recurrence token by token in float64: x ``(B, S, H, P)``, dt ``(B,
    S, H)``, a, d ``(H,)``, b, c ``(B, S, G, N)`` -> ``(y (B, S, H, P),
    multiply-adds counted as they are done, the skip's apart)``.  Without
    *decay* the state is never decayed (``exp(dt a) = 1``); without *skip*
    ``d x`` is left out."""
    x, dt, a, b, c, d = (np.asarray(v, np.float64)
                         for v in (x, dt, a, b, c, d))
    batch, seq, heads, width = x.shape
    groups, size = b.shape[2:]
    out = np.zeros((batch, seq, heads, width))
    flops = 0
    for i in range(batch):
        for h in range(heads):
            g = h // (heads // groups)
            state = np.zeros((width, size))
            for t in range(seq):
                if decay:
                    state = np.exp(dt[i, t, h] * a[h]) * state
                flops += width * size
                state = state + np.outer(dt[i, t, h] * x[i, t, h],
                                         b[i, t, g])
                flops += 2 * width * size
                out[i, t, h] = state @ c[i, t, g]
                flops += 2 * width * size
                if skip:
                    out[i, t, h] += d[h] * x[i, t, h]
    return out, flops
