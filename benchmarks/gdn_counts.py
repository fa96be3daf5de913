"""Operations and bytes of the gated delta rule, from its shapes: the only
place these counts live.  Useful work only, and the RECURRENCE's as it is
stated, whatever implements it.  Per token and head, with a state of ``dk x
dv``:

    S  <- a S                      dk dv          (the decay)
    r  =  v - S^T k              2 dk dv          (what the state holds at k)
    S  <- S + (b k) r^T          2 dk dv          (the rank-one write)
    o  =  S^T q                  2 dk dv

``7 dk dv`` FLOPs forward, and by the usual count twice that again for the
backward.  Not counted: what a chunkwise form adds to get there in parallel
(the chunk's scores, the triangular solve, the products against the chunk's
starting state: some 200 K FLOPs a token and head at chunks of 64 and 96 x
192, more than the recurrence itself), the gates' activations, the norms.

The bytes the rule has to move at the least: forward it reads q, k, v, g, b
and writes o; backward it reads the five inputs again and the output's
gradient and writes the five gradients.  q, k, v, o and b in *itemsize*
bytes, g in float32.  A state that stays on chip moves nothing.
`recurrence` is the definition itself, in numpy float64, and imports nothing
of the program: the tests hold the op against it.
"""

from __future__ import annotations

import numpy as np


def token_flops(dk, dv, training=True):
    """FLOPs a token and head: ``7 dk dv`` forward, three times that with
    the backward."""
    return (3 if training else 1) * 7 * dk * dv


def rule_flops(batch, seq, heads, dk, dv, training=True):
    return batch * seq * heads * token_flops(dk, dv, training)


def token_bytes(dk, dv, itemsize=2, training=True):
    """Bytes a token and head: forward q, k (dk each), v, o (dv each) and b
    at *itemsize* and g in float32; backward the five inputs again, the
    output's gradient, and the five gradients."""
    inputs = (2 * dk + dv + 1) * itemsize + 4          # q, k, v, b; g
    forward = inputs + dv * itemsize
    if not training:
        return forward
    return forward + inputs + dv * itemsize + inputs


def rule_bytes(batch, seq, heads, dk, dv, itemsize=2, training=True):
    return batch * seq * heads * token_bytes(dk, dv, itemsize, training)


def state_kept_bytes(batch, seq, heads, dk, dv, chunk):
    """What a chunkwise forward keeps for its backward: one float32 state a
    head at each chunk boundary.  A state kept at every token is *chunk*
    times that."""
    return 4 * batch * (seq // chunk) * heads * dk * dv


def recurrence(q, k, v, g, b, erase=True):
    """The rule token by token in float64: q, k ``(B, S, H, dk)``, v ``(B,
    S, H, dv)``, g, b ``(B, S, H)`` -> ``(o (B, S, H, dv), multiply-adds
    counted as they are done)``.  Without *erase* the write is ``b k
    v^T``."""
    q, k, v, g, b = (np.asarray(x, np.float64) for x in (q, k, v, g, b))
    batch, seq, heads, dk = q.shape
    dv = v.shape[-1]
    out = np.zeros((batch, seq, heads, dv))
    flops = 0
    for i in range(batch):
        for h in range(heads):
            state = np.zeros((dk, dv))
            for t in range(seq):
                state = np.exp(g[i, t, h]) * state
                flops += dk * dv
                held = state.T @ k[i, t, h] if erase else 0.0
                flops += 2 * dk * dv
                state = state + np.outer(b[i, t, h] * k[i, t, h],
                                         v[i, t, h] - held)
                flops += 2 * dk * dv
                out[i, t, h] = state.T @ q[i, t, h]
                flops += 2 * dk * dv
    return out, flops
