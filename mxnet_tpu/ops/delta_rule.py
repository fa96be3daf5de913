"""The gated delta rule, a linear-attention layer's core, and the three small
operators a block builds around it.

A layer of this kind carries a matrix ``S`` (``dk x dv`` a head) along the
sequence instead of attending over it (arXiv:2412.06464, with the write
strength ``b`` in (0, 2) of arXiv:2411.12537).  Per head, ``S_{-1} = 0``:

    S_t = a_t S_{t-1} + b_t k_t (v_t - a_t S_{t-1}^T k_t)^T,   a_t = exp(g_t)
    o_t = S_t^T q_t

``_contrib_GatedDeltaRule`` computes it in the chunkwise-parallel form:
chunks of ``C`` tokens (64; a property of the algorithm, not of a model: the
result does not depend on it beyond float32 rounding).  With ``G`` the
cumulative sums of ``g`` inside a chunk, ``u_t = b_t (v_t - a_t S_{t-1}^T
k_t)`` and ``S_0`` the state the chunk starts from, the recurrence unrolls
to one unit-lower-triangular system a chunk and head (the WY/UT transform of
arXiv:2406.06484 section 3 with the decays of arXiv:2412.06464 section 3.3):

    (I + A) U = b * V - (b * exp(G) * K) S_0,
        A[t, j] = b_t exp(G_t - G_j) (k_t . k_j)  for j < t
    O   = (exp(G) * Q) S_0 + P U,   P[t, j] = exp(G_t - G_j) (q_t . k_j), j <= t
    S_C = exp(G_C) S_0 + (exp(G_C - G) * K)^T U

Every decay ratio is ``exp`` of a difference of cumulative sums (never a
quotient of two exponentials), and only of differences that are not positive.
That is the op's definition, and it is `jax.numpy` (`_forward`,
`_backward`): what does not depend on the state (``A``, the solve's two
right-hand sides, ``P`` and the decayed q and k) is computed for all chunks
at once; what does is one `lax.scan` over the ``S / C`` chunks, three
products a step.  The forward keeps its five inputs and the ``S / C``
chunk-boundary states (float32) and nothing per token of size ``dk x dv``;
the backward is the op's own (`jax.custom_vjp`): it rebuilds every chunk's
system, walks the chunks in reverse with the state's gradient as the carry,
and hands what that collects to the derivative of the all-chunks part.  No
array has two axes of the sequence, none is ``(S, H, dk, dv)``, and no loop
runs a token at a time.  The state, the cumulative sums, the solve and every
product here are float32 (q, k, v and b arrive in the block's dtype, g in
float32).

Where the program is lowered for the TPU on one device and the shape tiles
(`_gdn_plan`), the same runs as one Mosaic kernel each way, ``mx_gdn_fwd``
and ``mx_gdn_bwd``: a grid over (batch, head, chunk) with the chunks in turn,
the state (backward: its gradient) in VMEM for a head's whole walk, a chunk's
whole algebra on chip (the inverse of ``I + A`` by blocks,
`_unit_lower_inverse`, in place of `triangular_solve`; the derivative of the
chunk's system written out, `_chunk_backward`), and nothing between the five
inputs, the output and the kept chunk-start states in HBM.  Every product
with a float32 operand is float32 at `HIGHEST` there too; ``K K^T`` and ``Q
K^T``, whose operands are the bf16 inputs themselves, are exact at one pass.
`jax.lax.platform_dependent` chooses, as for `_gate` and `_head_norm_rotary`
in `ops/lm_blocks.py`; on every other platform, under a mesh of several
devices and at a shape that does not tile, the `jax.numpy` form runs.  Span
``mx.gdn.plan`` says which (``path`` ``kernel``, or ``xla`` and ``why``)
beside the sizes.

Around it `gluon.contrib.nn.GatedDeltaNet` uses ``_contrib_ShortConvHeads``
(one depthwise causal convolution of a few taps over the concatenated q, k, v
channels, silu, the per-head L2 norms of q and k, the move to heads),
``_contrib_DeltaRuleGates`` (the decay's logarithm and the write strength
from their two projections) and ``_contrib_GatedRMSNorm`` (the RMS norm of
each head's output times ``silu`` of a gate).  The first and the last keep
their inputs alone for the backward pass and compute their float32
intermediates again there.  The first is a Mosaic pair of its own where the
program is lowered for the TPU on one device and `_gdnconv_plan` gives tiles,
``mx_gdnconv_fwd`` and ``mx_gdnconv_bwd``, one pass over the channels each
way (span ``mx.gdnconv.plan`` says which path a call takes); the other two
are `jax.numpy` on every platform.

A state-space block (`gluon.contrib.nn.StateSpaceMixer`, `ops/state_space.py`)
shares two of them in another form: ``_contrib_ShortConvSilu`` is the same
taps and silu (`_taps_silu`) with a bias a channel and no heads to norm
(`jax.numpy` on every platform; span ``mx.ssmconv.plan``), and
``_contrib_GatedRMSNorm`` with ``gate_first`` puts the gate in front of the
norm, ``rms(x * silu(z))``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import profiler
from .lm_blocks import _halo_rows, _one_device, causal_taps
from .registry import register_op

#: tokens a chunk where the caller names none
DEFAULT_CHUNK = 64

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def _mm(spec, a, b):
    """A float32 contraction, summed as float32 on every platform."""
    return jnp.einsum(spec, a, b, precision=_HIGHEST,
                      preferred_element_type=_F32)


def _to_chunks(x, chunk):
    """``(B, S, H, ...)`` -> ``(N, B, H, C, ...)`` in float32, the chunk
    axis leading (what `lax.scan` walks)."""
    b, s, h = x.shape[:3]
    x = x.astype(_F32).reshape((b, s // chunk, chunk, h) + x.shape[3:])
    return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)


def _from_chunks(x):
    """``(N, B, H, C, ...)`` -> ``(B, S, H, ...)``."""
    n, b, h, c = x.shape[:4]
    return jnp.moveaxis(jnp.moveaxis(x, 0, 2), 1, 3).reshape(
        (b, n * c, h) + x.shape[4:])


def _chunk_systems(q, k, v, g, b):
    """What a chunk's arithmetic needs that does not depend on the state,
    for all chunks at once (inputs as `_to_chunks` hands them): ``Uv = (I +
    A)^-1 (b V)``, ``W = (I + A)^-1 (b exp(G) K)``, ``P``, ``exp(G) Q``,
    ``exp(G_C - G) K`` and ``exp(G_C)``."""
    chunk, dv = g.shape[-1], v.shape[-1]
    cum = jnp.cumsum(g, -1)
    last = cum[..., -1:]
    rows = jnp.arange(chunk)
    seen = rows[:, None] >= rows[None, :]
    # exp(G_t - G_j) for j <= t, zero above the diagonal: differences that
    # are positive are never exponentiated
    ratio = jnp.where(seen, jnp.exp(jnp.where(
        seen, cum[..., :, None] - cum[..., None, :], 0.0)), 0.0)
    grow = jnp.exp(cum)[..., None]
    a = b[..., :, None] * jnp.where(rows[:, None] > rows[None, :], ratio,
                                    0.0) * _mm("...td,...jd->...tj", k, k)
    rhs = jnp.concatenate([b[..., None] * v, b[..., None] * grow * k], -1)
    sol = jax.lax.linalg.triangular_solve(
        a + jnp.eye(chunk, dtype=_F32), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    p = ratio * _mm("...td,...jd->...tj", q, k)
    return (sol[..., :dv], sol[..., dv:], p, grow * q,
            jnp.exp(last - cum)[..., None] * k, jnp.exp(last)[..., None])


def _chunk_step(state, system):
    """One chunk from the state it starts at: ``(state after, output)``."""
    uv, w, p, qg, kd, glast = system
    u = uv - _mm("...td,...de->...te", w, state)
    out = _mm("...td,...de->...te", qg, state) \
        + _mm("...tj,...je->...te", p, u)
    return glast * state + _mm("...td,...te->...de", kd, u), out


def _chunk_step_backward(dstate, at):
    """`_chunk_step`'s derivative at one chunk, the gradient of the state
    after it coming in: the gradient of the state before it, and of the
    chunk's system."""
    (uv, w, p, qg, kd, glast), state, dout = at
    u = uv - _mm("...td,...de->...te", w, state)
    du = _mm("...tj,...te->...je", p, dout) \
        + _mm("...td,...de->...te", kd, dstate)
    before = _mm("...td,...te->...de", qg, dout) + glast * dstate \
        - _mm("...td,...te->...de", w, du)
    return before, (
        du, -_mm("...te,...de->...td", du, state),
        _mm("...te,...je->...tj", dout, u),
        _mm("...te,...de->...td", dout, state),
        _mm("...te,...de->...td", u, dstate),
        jnp.sum(state * dstate, (-2, -1), keepdims=True))


def _forward(q, k, v, g, b, chunk):
    systems = _chunk_systems(*(_to_chunks(x, chunk) for x in (q, k, v, g, b)))
    zero = jnp.zeros(systems[0].shape[1:3] + (k.shape[-1], v.shape[-1]),
                     _F32)

    def step(state, system):
        after, out = _chunk_step(state, system)
        return after, (state, out)

    _, (starts, out) = jax.lax.scan(step, zero, systems)
    return _from_chunks(out).astype(v.dtype), starts


def _again(kept, dout):
    """What a backward pass computes again, it computes from these: behind a
    barrier with the gradient that starts it, or XLA merges the second
    computation with the forward's and keeps the forward's intermediates
    alive until then (as `jax.checkpoint` guards its own)."""
    return jax.lax.optimization_barrier((kept, dout))


def _backward(q, k, v, g, b, starts, dout, chunk):
    (q, k, v, g, b, starts), dout = _again((q, k, v, g, b, starts), dout)
    systems, pull = jax.vjp(
        _chunk_systems, *(_to_chunks(x, chunk) for x in (q, k, v, g, b)))
    _, dsystems = jax.lax.scan(
        _chunk_step_backward, jnp.zeros_like(starts[0]),
        (systems, starts, _to_chunks(dout, chunk)), reverse=True)
    return tuple(_from_chunks(d).astype(x.dtype)
                 for d, x in zip(pull(dsystems), (q, k, v, g, b)))


# ---------------------------------------------------------------------------
# The same on the TPU: one Mosaic kernel each way.  The grid is (batch, head,
# chunk), the chunk axis last and sequential; a head's float32 state (forward)
# or the state's gradient (backward) lives in a VMEM scratch for its whole
# walk, and nothing of a chunk's algebra (the ratios, A, its inverse, P, the
# decayed q and k, U) goes to HBM.  What the forward keeps is what `_forward`
# keeps: the five inputs and the chunk-start states.
# ---------------------------------------------------------------------------

#: rows of the diagonal blocks of ``I + A`` that the in-chunk solve inverts by
#: substitution on the VPU before products on the MXU pair them
#: (`_unit_lower_inverse`); a chunk no longer than that is one block, and
#: substitution alone.  From `tools/gdn_sweep.py` on the v5e at (1, 3072, 30,
#: 96 | 192) bf16, device ms a call, forward + backward (PERF.md section 6,
#: PR 49): 3.404 + 4.904 at 8 rows, 2.934 + 4.385 at 16, 2.657 + 3.902 at 32,
#: **2.473 + 3.857 at 64** (`jax.numpy`: 6.164 + 13.551); at 4096 positions
#: the same order, 3.306 + 5.174 at 64.  A pairing is two dependent products
#: with nothing of the chunk to run beside them; the substitution's steps run
#: beside the products that do not wait for the inverse.  A grid step walks
#: one chunk of one head: 2, 5 and 15 heads a step (a loop inside the step)
#: read within 1% of one head's times, so the step's own cost is not what
#: the time is.  Not an option: the sweep sets it to compare
GDN_TILES = {"solve": 64}

#: what a kernel's blocks (each held twice), its scratch and a head's
#: float32 temporaries may take of the 16 MiB of VMEM that a Mosaic kernel is
#: given on the v5e
_GDN_VMEM = 12 << 20

_NN = ((1,), (0,))      # a @ b
_NT = ((1,), (1,))      # a @ b.T
_TN = ((0,), (0,))      # a.T @ b


def _dot(a, b, contract):
    """An MXU product summed in float32.  Both operands bf16 (the inputs as
    they arrived: ``K K^T``, ``Q K^T``): one pass, every product exact in
    float32.  Anything else is float32 at `HIGHEST`."""
    if not a.dtype == b.dtype == jnp.bfloat16:
        a, b = a.astype(_F32), b.astype(_F32)
    return jax.lax.dot_general(
        a, b, (contract, ((), ())), preferred_element_type=_F32,
        precision=None if a.dtype == jnp.bfloat16 else _HIGHEST)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _unit_lower_inverse(a, block):
    """``(I + a)^-1`` for a strictly lower-triangular ``(n, n)`` *a* of whole
    blocks of *block* rows.  The diagonal blocks by forward substitution
    (``X <- X - a[:, j] X[j, :]`` inside each, ``block - 1`` steps on the
    VPU, a step touching the sublane tiles from row ``j``'s down); then
    blocks are paired, ``[[L, 0], [M, N]]^-1 = [[L^-1, 0],
    [-N^-1 M L^-1, N^-1]]``, by two products a doubling.  Not the nilpotent
    series ``(I - a)(I + a^2)(I + a^4)...``: with entries near 2 its factors
    grow by orders of magnitude before they cancel.

    The steps keep the XLU busy (a column's lane broadcast each) and leave
    the MXU idle: products that do not wait for the inverse belong before it
    in the kernel's text, where Mosaic's scheduler runs them beside the
    steps (and no product may stand between, or the MXU's order holds the
    steps back)."""
    n = a.shape[0]
    x = []
    for at in range(0, n, block):
        column = a[at:at + block, at:at + block]
        # the block's rows of the identity; the sublane tiles (8 rows) above
        # row j's are final, the rest take the step as one array
        rest = (_iota((block, n), 0) + at == _iota((block, n), 1)).astype(
            _F32)
        for j in range(block - 1):
            if j and j % 8 == 0:
                x.append(rest[:8])
                rest = rest[8:]
            top = j - j % 8
            rest = rest - column[top:, j:j + 1] * rest[j - top:j - top + 1]
        x.append(rest)
    x = jnp.concatenate(x, 0)
    rows, cols = _iota((n, n), 0), _iota((n, n), 1)
    size = block
    while size < n:
        # M of every pair: the lower of its two blocks against the upper
        below = functools.reduce(jnp.logical_or, (
            (rows >= at + size) & (rows < at + 2 * size)
            & (cols >= at) & (cols < at + size)
            for at in range(0, n, 2 * size)))
        x = x - _dot(x, _dot(jnp.where(below, a, 0.0), x, _NN), _NN)
        size *= 2
    return x


def _chunk_algebra(q, k, v, g, b):
    """What both kernels compute of one chunk and head from its inputs alone
    (q, k ``(C, dk)``, v ``(C, dv)``, g and b as rows ``(1, C)``):
    `_chunk_systems`' quantities before the solve, by name."""
    c = q.shape[0]
    rows, cols = _iota((c, c), 0), _iota((c, c), 1)
    eye, seen, below = rows == cols, rows >= cols, rows > cols
    at = {"eye": eye, "seen": seen, "below": below}
    # the cumulative sums as a column and, the same numbers, as a row
    cum = jnp.sum(jnp.where(seen, g, 0.0), 1, keepdims=True)
    cum_row = jnp.sum(jnp.where(eye, cum, 0.0), 0, keepdims=True)
    last = cum[c - 1:]
    # exp of differences that are not positive, and of no other
    ratio = jnp.where(seen, jnp.exp(jnp.where(seen, cum - cum_row, 0.0)), 0.0)
    grow, fade = jnp.exp(cum), jnp.exp(last - cum)
    # b, a row as it arrives, as a column
    beta = jnp.sum(jnp.where(eye, b.astype(_F32), 0.0), 1, keepdims=True)
    below_ratio, kk = jnp.where(below, ratio, 0.0), _dot(k, k, _NT)
    qf, kf, vf = (x.astype(_F32) for x in (q, k, v))
    at.update(
        grow=grow, fade=fade, glast=jnp.exp(last), b=beta, ratio=ratio,
        below_ratio=below_ratio, kk=kk, a=beta * below_ratio * kk,
        p=ratio * _dot(q, k, _NT), q=qf, k=kf, v=vf,
        kb=beta * grow * kf, qg=grow * qf, kd=fade * kf)
    return at


def _chunk_solve(at, held, solve):
    """``(T, U)``: ``T = (I + A)^-1`` and ``U = T (b V - (b exp(G) K) S_0)``,
    *held* the product against the state."""
    t = _unit_lower_inverse(at["a"], solve)
    return t, _dot(t, at["b"] * at["v"] - held, _NN)


def _chunk_forward(q, k, v, g, b, state, solve):
    """``(the chunk's output, the state after it)``."""
    at, c = _chunk_algebra(q, k, v, g, b), q.shape[0]
    # (b exp(G) K) S_0 and (exp(G) Q) S_0 as one product of 2C rows, before
    # the solve, which it does not wait for
    held = _dot(jnp.concatenate([at["kb"], at["qg"]], 0), state, _NN)
    _, u = _chunk_solve(at, held[:c], solve)
    return (held[c:] + _dot(at["p"], u, _NN),
            at["glast"] * state + _dot(at["kd"], u, _TN))


def _chunk_backward(q, k, v, g, b, state, dout, dstate, solve):
    """One chunk's part of the backward walk, the derivative of
    `_chunk_forward` written out: from the chunk's inputs, the state it
    started from, its output's gradient and the gradient of the state after
    it, ``(dq, dk, dv, dg and db as rows, the gradient of the state before
    it)``."""
    at, c = _chunk_algebra(q, k, v, g, b), q.shape[0]
    eye, seen, below = at["eye"], at["seen"], at["below"]
    beta, grow, fade, glast = at["b"], at["grow"], at["fade"], at["glast"]
    qf, kf, vf, kb, qg, kd, p, a = (at[n] for n in (
        "q", "k", "v", "kb", "qg", "kd", "p", "a"))
    dout = dout.astype(_F32)
    # the four products that do not wait for the solve, before it
    held = _dot(kb, state, _NN)
    du = _dot(p, dout, _TN) + _dot(kd, dstate, _NN)
    dqg = _dot(dout, state, _NT)
    t, u = _chunk_solve(at, held, solve)
    dr = _dot(t, du, _TN)             # the transposed system: T^T is at hand
    both = jnp.concatenate([dout, dr], 0)
    scores = _dot(both, u, _NT)                         # (2C, C)
    dp = jnp.where(seen, scores[:c], 0.0)
    da = -jnp.where(below, scores[c:], 0.0)
    dkb = -_dot(dr, state, _NT)
    dkd = _dot(u, dstate, _NT)
    before = glast * dstate + _dot(jnp.concatenate([qg, -kb], 0), both, _TN)
    # through P = ratio * Q K^T and A = b * ratio * K K^T
    dscores = jnp.concatenate(
        [at["ratio"] * dp, beta * at["below_ratio"] * da], 0)   # (2C, C)
    right = _dot(dscores, kf, _NN)                      # (2C, dk)
    dq = grow * dqg + right[:c]
    dk = beta * grow * dkb + fade * dkd + right[c:] + _dot(
        dscores, jnp.concatenate([qf, kf], 0), _TN)
    dv = beta * dr
    # the decays: every ratio is exp of a difference of cumulative sums
    e = jnp.where(below, dp * p + da * a, 0.0)
    to_last = jnp.sum(dkd * kd, 1, keepdims=True)
    dcum = jnp.sum(e, 1, keepdims=True) \
        - jnp.sum(jnp.where(eye, jnp.sum(e, 0, keepdims=True), 0.0), 1,
                  keepdims=True) \
        + jnp.sum(dqg * qg + dkb * kb, 1, keepdims=True) - to_last
    dlast = jnp.sum(to_last, 0, keepdims=True) + glast * jnp.sum(
        jnp.sum(state * dstate, 1, keepdims=True), 0, keepdims=True)
    dcum = dcum + jnp.where(_iota((c, 1), 0) == c - 1, dlast, 0.0)
    # g's gradient is the reverse cumulative sum inside the chunk
    dg = jnp.sum(jnp.where(seen, dcum, 0.0), 0, keepdims=True)
    db = sum(jnp.sum(x, 1, keepdims=True) for x in (
        dr * vf, dkb * (grow * kf), da * (at["below_ratio"] * at["kk"])))
    return dq, dk, dv, dg, jnp.sum(jnp.where(eye, db, 0.0), 0,
                                   keepdims=True), before


def _gdn_fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, out_ref, starts_ref,
                    state, *, solve):
    """One chunk of one head: the state it starts the chunk from to the
    kept array, the chunk's output, the state on to the next chunk."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    start = state[...]
    starts_ref[0, 0, 0] = start
    out, state[...] = _chunk_forward(
        q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], g_ref[0, 0, 0],
        b_ref[0, 0, 0], start, solve)
    out_ref[0, 0] = out.astype(out_ref.dtype)


def _gdn_bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, starts_ref, dout_ref,
                    dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dstate, *, solve):
    """The same chunk on the way back (the grid walks the chunks from the
    last): the carry is the gradient of the state after the chunk."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    dq, dk, dv, dg, db, dstate[...] = _chunk_backward(
        q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], g_ref[0, 0, 0],
        b_ref[0, 0, 0], starts_ref[0, 0, 0], dout_ref[0, 0], dstate[...],
        solve)
    for ref, value in ((dq_ref, dq), (dk_ref, dk), (dv_ref, dv)):
        ref[0, 0] = value.astype(ref.dtype)
    dg_ref[0, 0, 0] = dg
    db_ref[0, 0, 0] = db.astype(db_ref.dtype)


def _head_major(x, chunk):
    """``(B, S, H, d)`` -> ``(B, H, S, d)`` as the kernels take q, k and v;
    ``(B, S, H)`` -> ``(B, H, N, 1, C)``, a chunk's gates one row."""
    x = jnp.swapaxes(x, 1, 2)
    if x.ndim == 4:
        return x
    return x.reshape(x.shape[:2] + (x.shape[2] // chunk, 1, chunk))


def _token_major(x):
    """`_head_major` undone."""
    if x.ndim == 5:
        x = x.reshape(x.shape[:2] + (-1,))
    return jnp.swapaxes(x, 1, 2)


def _gdn_specs(q, v, chunk, reverse):
    """The grid (batch, head, chunk: the chunks last and in turn) and the
    blocks: a chunk of one head's q or k, of its v or o, of a gate, and the
    head's state at one chunk boundary."""
    (batch, heads, seq, dk), dv = q.shape, v.shape[-1]
    n = seq // chunk
    at = (lambda c: n - 1 - c) if reverse else (lambda c: c)
    return (batch, heads, n), (
        pl.BlockSpec((1, 1, chunk, dk), lambda i, h, c: (i, h, at(c), 0)),
        pl.BlockSpec((1, 1, chunk, dv), lambda i, h, c: (i, h, at(c), 0)),
        pl.BlockSpec((1, 1, 1, 1, chunk),
                     lambda i, h, c: (i, h, at(c), 0, 0)),
        pl.BlockSpec((1, 1, 1, dk, dv), lambda i, h, c: (at(c), i, h, 0, 0)))


_GDN_STATIC = ("chunk", "solve", "interpret")
_GDN_WALK = ("parallel", "parallel", "arbitrary")


# jitted, so a step's linear layers share one trace and one Mosaic program of
# each kernel (as the flash wrappers)

@functools.partial(jax.jit, static_argnames=_GDN_STATIC)
def _gdn_fwd_pallas(q, k, v, g, b, chunk, solve, interpret=False):
    """`_forward` by ``mx_gdn_fwd``: ``(o, the chunk-start states)``."""
    with jax.named_scope("mx.gdn.rule"):
        q, k, v, g, b = (_head_major(x, chunk) for x in (q, k, v, g, b))
        (batch, heads, seq, dk), dv = q.shape, v.shape[-1]
        grid, (keys, values, gate, states) = _gdn_specs(q, v, chunk, False)
        out, starts = pl.pallas_call(
            functools.partial(_gdn_fwd_kernel, solve=solve),
            grid=grid, in_specs=[keys, keys, values, gate, gate],
            out_specs=[values, states],
            out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                       jax.ShapeDtypeStruct(
                           (seq // chunk, batch, heads, dk, dv), _F32)],
            scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=_GDN_WALK),
            interpret=interpret, name="mx_gdn_fwd",
        )(q, k, v, g, b)
        return _token_major(out), starts


@functools.partial(jax.jit, static_argnames=_GDN_STATIC)
def _gdn_bwd_pallas(q, k, v, g, b, starts, dout, chunk, solve,
                    interpret=False):
    """`_backward` by ``mx_gdn_bwd``: the five gradients."""
    with jax.named_scope("mx.gdn.rule"):
        q, k, v, g, b, dout = (_head_major(x, chunk)
                               for x in (q, k, v, g, b, dout))
        grid, (keys, values, gate, states) = _gdn_specs(q, v, chunk, True)
        grads = pl.pallas_call(
            functools.partial(_gdn_bwd_kernel, solve=solve),
            grid=grid,
            in_specs=[keys, keys, values, gate, gate, states, values],
            out_specs=[keys, keys, values, gate, gate],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                       for x in (q, k, v, g, b)],
            scratch_shapes=[pltpu.VMEM(starts.shape[-2:], _F32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=_GDN_WALK),
            interpret=interpret, name="mx_gdn_bwd",
        )(q, k, v, g, b, starts, dout)
        return tuple(_token_major(d) for d in grads)


def _padded(rows, cols, itemsize):
    """Bytes of a ``(rows, cols)`` array as the VMEM tiles it."""
    sub = 32 // itemsize
    return -(-rows // sub) * sub * -(-cols // 128) * 128 * itemsize


def _gdn_blocks(kernel, chunk, dk, dv, itemsize):
    """Bytes of VMEM one grid step of *kernel* takes: its blocks, each held
    twice (q and k, v and o or their gradients and the output's, the gates'
    rows, the head's state at a boundary), the carried state, and some
    forty float32 temporaries the size of a chunk's v."""
    keys, values, gates = {"fwd": (2, 2, 2), "bwd": (4, 3, 4)}[kernel]
    state = _padded(dk, dv, 4)
    blocks = keys * _padded(chunk, dk, itemsize) \
        + values * _padded(chunk, dv, itemsize) \
        + gates * _padded(1, chunk, 4) + state
    return 2 * blocks + state + 40 * _padded(chunk, max(chunk, dk, dv), 4)


def _gdn_plan(q, v, chunk):
    """``(tiles, None)`` where the kernels take this call, ``(None, why
    not)`` where it stays `_forward` and `_backward`.  From what the input
    shows alone: q and v ``(B, S, H, d)`` in 2 or 4 bytes, the chunk in
    whole blocks of the solve and whole sublane tiles, a grid step within
    `_GDN_VMEM`, one device."""
    solve = min(chunk, GDN_TILES["solve"])
    dk, dv = q.shape[-1], v.shape[-1]
    itemsize = jnp.dtype(v.dtype).itemsize
    if itemsize not in (2, 4) or q.dtype != v.dtype:
        return None, "q and v not of one dtype of 2 or 4 bytes"
    if chunk % solve or chunk % (32 // itemsize):
        return None, "a chunk of %d is not whole blocks of %d of the solve " \
            "and whole tiles of %d rows" % (chunk, solve, 32 // itemsize)
    if any(_gdn_blocks(kernel, chunk, dk, dv, itemsize) > _GDN_VMEM
           for kernel in ("fwd", "bwd")):
        return None, "a state of %d x %d with its blocks over the VMEM " \
            "budget" % (dk, dv)
    if not _one_device():
        # XLA does not partition a Mosaic kernel, and no cell spans chips
        return None, "a mesh of several devices"
    return {"solve": solve}, None


def _rule_forward(q, k, v, g, b, chunk):
    tiles, _ = _gdn_plan(q, v, chunk)
    if tiles is None:
        return _forward(q, k, v, g, b, chunk)
    return jax.lax.platform_dependent(
        q, k, v, g, b, default=functools.partial(_forward, chunk=chunk),
        tpu=functools.partial(_gdn_fwd_pallas, chunk=chunk, **tiles))


def _rule_backward(chunk, kept, dout):
    tiles, _ = _gdn_plan(kept[0], kept[2], chunk)
    if tiles is None:
        return _backward(*kept, dout, chunk)
    return jax.lax.platform_dependent(
        *kept, dout, default=functools.partial(_backward, chunk=chunk),
        tpu=functools.partial(_gdn_bwd_pallas, chunk=chunk, **tiles))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def gated_delta_rule(q, k, v, g, b, chunk=DEFAULT_CHUNK):
    """``o`` ``(B, S, H, dv)`` of the recurrence above from ``q, k (B, S, H,
    dk)`` (normalised by the caller), ``v (B, S, H, dv)`` and ``g, b (B, S,
    H)``, in chunks of *chunk* tokens: the kernels where `_gdn_plan` gives
    tiles and the program is lowered for the TPU, `_forward` and `_backward`
    everywhere else."""
    return _rule_forward(q, k, v, g, b, chunk)[0]


def _rule_fwd(q, k, v, g, b, chunk):
    out, starts = _rule_forward(q, k, v, g, b, chunk)
    return out, (q, k, v, g, b, starts)


gated_delta_rule.defvjp(_rule_fwd, _rule_backward)


def state_kept_bytes(batch, seq, heads, dk, dv, chunk=DEFAULT_CHUNK):
    """Bytes of state one call's forward keeps for its backward: a float32
    ``dk x dv`` a head at each of the ``seq / chunk`` chunk boundaries."""
    return 4 * batch * (seq // chunk) * heads * dk * dv


def _fold_state_kept(values):
    from ..observability import metrics
    metrics.gauge(
        "gdn_state_kept_bytes", "bytes of recurrent state the last step's "
        "gated delta rule calls kept for their backward, all layers").set(
            float(np.asarray(values, np.float64).sum()))


profiler.register_step_stat("gdn_state_kept_bytes", _fold_state_kept)


@register_op("_contrib_GatedDeltaRule", aliases=("GatedDeltaRule",))
def _gated_delta_rule_op(query, key, value, decay, beta,
                         chunk=DEFAULT_CHUNK):
    """The gated delta rule over ``query, key (B, S, H, dk)``, ``value (B,
    S, H, dv)``, ``decay`` (the decay's logarithm ``g <= 0``) and ``beta``
    (the write strength) ``(B, S, H)`` -> ``(B, S, H, dv)``: per head, with
    a float32 state ``S`` (``dk x dv``) that starts at zero,

        ``S_t = exp(g_t) S_{t-1} + beta_t k_t (v_t - exp(g_t) S_{t-1}^T
        k_t)^T``, ``o_t = S_t^T q_t``

    in the chunkwise-parallel form, *chunk* tokens a chunk (static; the
    sequence has to be whole chunks), with a backward of its own that keeps
    the inputs and the chunk-boundary states alone (`gated_delta_rule`:
    the kernels ``mx_gdn_fwd`` and ``mx_gdn_bwd`` on the TPU where
    `_gdn_plan` gives tiles, `jax.numpy` elsewhere).  q and k arrive
    normalised.  Span ``mx.gdn.plan`` says which path a call takes and, with
    step stat ``gdn_state_kept_bytes``, what it keeps."""
    chunk = int(chunk)
    batch, seq, heads, dk = query.shape
    dv = value.shape[-1]
    if seq % chunk:
        raise ValueError(
            "the gated delta rule runs in whole chunks: a sequence of %d "
            "tokens is not a multiple of the chunk of %d (pad the sequence, "
            "or name a chunk that divides it)" % (seq, chunk))
    if key.shape != query.shape or value.shape[:3] != query.shape[:3] \
            or decay.shape != query.shape[:3] or beta.shape != decay.shape:
        raise ValueError(
            "query and key (B, S, H, dk), value (B, S, H, dv), decay and "
            "beta (B, S, H): got %s, %s, %s, %s, %s" % (
                query.shape, key.shape, value.shape, decay.shape,
                beta.shape))
    kept = state_kept_bytes(batch, seq, heads, dk, dv, chunk)
    tiles, why = _gdn_plan(query, value, chunk)
    with profiler.scope(  # graftlint: disable=JG003
            "mx.gdn.plan", "gdn") as span:
        span.args = {
            "batch": batch, "tokens": seq, "heads": heads, "key_dim": dk,
            "value_dim": dv, "chunk": chunk, "chunks": seq // chunk,
            "dtype": jnp.dtype(value.dtype).name,
            # `kernel`: mx_gdn_fwd and mx_gdn_bwd where the program is
            # lowered for the TPU (the same `jax.numpy` as `xla` where it
            # is lowered for anything else); `xla`: the systems of all
            # chunks at once, then a scan over the chunks, and why
            "path": "xla" if tiles is None else "kernel", "why": why,
            "solve_block": tiles and tiles["solve"],
            "state_kept_bytes": kept,
            # what a state kept at every token would be
            "per_token_state_bytes": 4 * batch * seq * heads * dk * dv}
    # at trace time on purpose (as the routed op's counts)
    profiler.emit_step_stat(  # graftlint: disable=JG003
        "gdn_state_kept_bytes", jnp.float32(kept))
    return gated_delta_rule(query, key, value, decay, beta, chunk)


# ---------------------------------------------------------------------------
# Around the rule: the short convolution with its norms, the gates, the
# gated norm of the output.
# ---------------------------------------------------------------------------

def _unit(x, eps):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def _taps_silu(data, conv_weight, bias=None):
    """The depthwise causal taps over ``(B, S, channels)``, a *bias* a
    channel where one is given, silu: float32."""
    y = causal_taps(data, conv_weight)
    return jax.nn.silu(y if bias is None else y + bias.astype(_F32))


def _conv_heads_body(data, conv_weight, heads, dk, eps):
    batch, seq, width = data.shape
    y = _taps_silu(data, conv_weight)
    q, k, v = (part.reshape(batch, seq, heads, -1) for part in
               jnp.split(y, [heads * dk, 2 * heads * dk], -1))
    return tuple(part.astype(data.dtype) for part in (
        _unit(q, eps) * dk ** -0.5, _unit(k, eps), v))


def _conv_heads_body_backward(data, conv_weight, dout, heads, dk, eps):
    # the float32 intermediates are computed again from the two inputs
    kept, dout = _again((data, conv_weight), dout)
    return jax.vjp(functools.partial(_conv_heads_body, heads=heads, dk=dk,
                                     eps=eps), *kept)[1](dout)


# ---------------------------------------------------------------------------
# The same on the TPU: one Mosaic kernel each way, ``mx_gdnconv_fwd`` and
# ``mx_gdnconv_bwd``, one pass over the channels forward and one backward.
# A grid step holds a tile of rows of one block of channels; the channels
# come in groups of ``lcm(dk, 128)`` (whole heads AND whole lane tiles: 384
# at keys of 96), a block is a few groups, the blocks of q and k take the
# norms and the blocks of v do not.  Inside a step the rows go by in pieces
# that stay in registers from the load to the store (whole tiles an
# operation spill every intermediate, and the store slot bounds the kernel).
# A head of 96 straddles lane tiles, so a head's sum is a masked lane sum for
# each tile it has lanes in (`_head_sums`; the same sums as a product with a
# 0/1 matrix on the MXU were 1.4 times slower a call).  The kernels write q,
# k and v head-major, ``(batch, heads, seq, d)``, and read their gradients
# so: that is what the rule's kernels read and write, XLA folds the
# operator's token-major outputs' swap into the rule's own, and no copy moves
# a head of 96 to a lane tile's start (the flat outputs' reshape to heads was
# a split and two transposing copies a tensor each way).  Nothing float32
# goes to HBM either way, and the backward keeps the two inputs alone.
# ---------------------------------------------------------------------------

#: ``(rows of the sequence a grid step holds, rows of a piece inside it)`` of
#: each kernel, and the most channels of a block (cut to whole groups that
#: divide q and k's width and v's).  From `tools/gdnconv_sweep.py` on the v5e
#: at (1, 3072, 11520) bf16, 30 heads of 96 | 192, 4 taps, device ms a call
#: with the wrapper's moves from and to token-major, which fold away in a
#: step (docs/PERF_NOTES.md, PR 51): forward **0.466 at 1024 rows in pieces
#: of 128**, 0.560 in pieces of 64, 0.773 of 32, 0.446 of 256; 0.473 at 512
#: rows, 0.445 at 3072; 0.463 at 512 rows x 1152 channels; `jax.numpy` 1.891.
#: Backward **0.700 at 1024 rows in pieces of 64**, 0.805 of 32, 0.757 of
#: 128; 0.732 at 512 rows, 0.664 at 3072; 0.707 at 512 x 1152; `jax.numpy`
#: 5.332.  A head's sums as a product on the MXU (the piece's float32 numbers
#: in three bf16 parts against the 0/1 matrix of a group's heads) read 0.633
#: and 1.011 where the masked lane sums read 0.467 and 0.701 (PR 50's
#: builder's sweep), and left the module.  In the cell's step the kernels
#: alone read 0.361 and 0.555 ms a call.  Not options: the sweep sets them to
#: compare
GDNCONV_TILES = {"fwd": (1024, 128), "bwd": (1024, 64), "channels": 384}

#: what a kernel's blocks (each held twice) and a group's float32
#: temporaries may take of the 16 MiB of VMEM that a Mosaic kernel is given
#: on the v5e
_GDNCONV_VMEM = 14 << 20

#: the row of the taps' ``(8, channels)`` array that holds each lane's scale
#: (``dk ** -0.5`` on q's lanes, 1 on the others: the border between q and k
#: need not be a lane tile's)
_SCALE_ROW = 7


def _group(dk):
    """Channels of the least run that is whole heads of *dk* and whole lane
    tiles."""
    return int(np.lcm(dk, 128))


def _taps_and_scales(conv_weight, heads, dk):
    """The taps as the kernels read them, ``(8, channels)`` float32, row j
    the tap of ``x_(t-L+1+j)``, and in row `_SCALE_ROW` each lane's scale."""
    width, taps = conv_weight.shape
    scale = jnp.where(jnp.arange(width) < heads * dk, dk ** -0.5, 1.0)
    return jnp.zeros((8, width), _F32).at[:taps].set(
        conv_weight.astype(_F32).T).at[_SCALE_ROW].set(scale)


def _head_sums(a, dk):
    """``(rows, G)`` float32 -> at every lane the sum of *a* over the lane's
    head: a lane tile at a time, a masked lane sum for each head that has
    lanes in it (a head of 96 has them in two tiles), the heads' sums
    chosen back by lane."""
    lane, total, cut = _iota((1, 128), 1), {}, []
    for t in range(a.shape[1] // 128):
        tile = a[:, 128 * t:128 * (t + 1)]
        cut.append([(h, max(h * dk - 128 * t, 0),
                     min((h + 1) * dk - 128 * t, 128))
                    for h in range(128 * t // dk, (128 * t + 127) // dk + 1)])
        for h, lo, hi in cut[-1]:
            part = jnp.sum(tile if hi - lo == 128 else jnp.where(
                (lane >= lo) & (lane < hi), tile, 0.0), 1, keepdims=True)
            total[h] = total[h] + part if h in total else part
    out = []
    for heads in cut:
        chosen = total[heads[-1][0]]
        for h, _, hi in heads[-2::-1]:
            chosen = jnp.where(lane < hi, total[h], chosen)
        out.append(jnp.broadcast_to(chosen, (a.shape[0], 128)))
    return jnp.concatenate(out, 1)


def _sigmoid(x):
    """``1 / (1 + exp(-x))`` in float32 by the EUP's approximate reciprocal
    and two Newton steps of it, float32 from eight good bits on (a true
    division is a dozen VALU operations a number where this is six).
    ``-x`` is held under 80, so that a step never meets an infinity: ``x
    sigmoid(x)`` is under 1e-32 there either way."""
    d = 1.0 + jnp.exp(jnp.minimum(-x, 80.0))
    r = pl.reciprocal(d, approx=True)
    r = r * (2.0 - d * r)
    return r * (2.0 - d * r)


def _conv_silu(ext, w, lead):
    """From rows of the input in float32, *lead* rows of halo first, and the
    taps as rows *w*: the taps' shifted inputs ``u[j]`` = ``x_(t-L+1+j)`` of
    the rows after the halo, the sigmoid of their sum (in `causal_taps`'
    order) and its silu.  A shifted input is a sublane roll of all the rows
    and an aligned slice of it: the rows that wrap land in the halo's part
    and are cut off."""
    u = [pltpu.roll(ext, back, 0)[lead:] for back in range(len(w) - 1, 0, -1)]
    u.append(ext[lead:])
    conv = sum(x * tap for x, tap in zip(u, w))
    gate = _sigmoid(conv)
    return u, gate, conv * gate


def _rows_f32(ref, at, start=0, size=None):
    return ref[0, pl.ds(start, size or ref.shape[1]), at].astype(_F32)


def _by_kind(walk, qk_blocks):
    """*walk* over the block's groups, with the norms in q and k's blocks
    and without in v's."""
    normed = pl.program_id(0) < qk_blocks
    pl.when(normed)(functools.partial(walk, True))
    pl.when(jnp.logical_not(normed))(functools.partial(walk, False))


def _gdnconv_fwd_kernel(x_ref, x_before, w_ref, qk_ref, v_ref, *, group,
                        taps, dk, eps, qk_blocks, piece):
    """One ``(rows, channels)`` tile of the input to the same rows of its
    heads in q and k's head-major array (a block of q and k's channels) or
    in v's (a block of v's): a group of channels at a time, and in it
    *piece* rows at a time, which stay in registers from the load to the
    store; the rows a piece's taps look back at are the piece's before it,
    carried (the tile's first: the halo block's, zero before position 0 of
    every batch row, so packed rows never see each other)."""
    lead, rows = x_before.shape[1], x_ref.shape[1]
    first = pl.program_id(2) == 0

    def walk(normed):
        out_ref = qk_ref if normed else v_ref
        d = out_ref.shape[3]
        for lo in range(0, x_ref.shape[2], group):
            at = slice(lo, lo + group)
            w = [w_ref[j:j + 1, at] for j in range(taps)]

            def step(i, before):
                start = pl.multiple_of(i * piece, piece)
                own = _rows_f32(x_ref, at, start, piece)
                _, _, y = _conv_silu(jnp.concatenate([before, own], 0), w,
                                     lead)
                if normed:
                    y = y * jax.lax.rsqrt(_head_sums(y * y, dk) + eps) \
                        * w_ref[_SCALE_ROW:_SCALE_ROW + 1, at]
                y = y.astype(out_ref.dtype)
                for h in range(group // d):
                    out_ref[0, lo // d + h, pl.ds(start, piece)] = \
                        y[:, h * d:(h + 1) * d]
                return own[piece - lead:]

            jax.lax.fori_loop(0, rows // piece, step, jnp.where(
                first, 0.0, _rows_f32(x_before, at)))

    _by_kind(walk, qk_blocks)


def _heads_f32(ref, lo, group, start, size):
    """Channels *lo* .. *lo* + *group* of *size* rows of a head-major block
    ``(1, heads, rows, d)``, side by side in float32."""
    d = ref.shape[3]
    return jnp.concatenate(
        [ref[0, lo // d + h, pl.ds(start, size)].astype(_F32)
         for h in range(group // d)], 1)


def _gdnconv_bwd_kernel(x_ref, x_before, x_after, gqk_ref, gqk_after, gv_ref,
                        gv_after, w_ref, dx_ref, dw_ref, *, group, taps, dk,
                        eps, qk_blocks, piece):
    """The tile's part of the input's gradient, and of the taps' gradient
    added to *dw_ref*, which stays in VMEM over a channel block's whole
    walk of the rows.  The convolution, silu and the norms are computed
    again in float32, *piece* rows at a time from the tile's last to its
    first: the transpose of the taps looks ahead, ``dx_t = sum_j w_j
    dconv_(t+L-1-j)``, so a piece carries its first rows of ``dconv`` to the
    piece before it, and the walk starts from the halo block's after the
    tile (zero past the sequence's end)."""
    lead, rows = x_before.shape[1], x_ref.shape[1]
    first = pl.program_id(2) == 0
    last = pl.program_id(2) == pl.num_programs(2) - 1

    @pl.when((pl.program_id(1) == 0) & first)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def walk(normed):
        g_ref, g_after = (gqk_ref, gqk_after) if normed else (gv_ref,
                                                              gv_after)
        for lo in range(0, x_ref.shape[2], group):
            at = slice(lo, lo + group)
            w = [w_ref[j:j + 1, at] for j in range(taps)]

            def dconv_of(ext, dy):
                """``(u, dconv)`` of the rows of *ext* after its halo."""
                u, gate, y = _conv_silu(ext, w, lead)
                if normed:
                    dy = dy * w_ref[_SCALE_ROW:_SCALE_ROW + 1, at]
                    n = dy.shape[0]
                    both = _head_sums(jnp.concatenate([y * y, dy * y], 0),
                                      dk)
                    r = jax.lax.rsqrt(both[:n] + eps)
                    dy = r * (dy - y * (r * r) * both[n:])
                # silu's derivative
                return u, dy * (gate + y * (1.0 - gate))

            def step(i, carry):
                ahead, sums_w = carry
                i = rows // piece - 1 - i
                start = pl.multiple_of(i * piece, piece)
                before = jnp.where(
                    i == 0, jnp.where(first, 0.0, _rows_f32(x_before, at)),
                    _rows_f32(x_ref, at, pl.multiple_of(
                        jnp.maximum(start - lead, 0), lead), lead))
                u, dconv = dconv_of(
                    jnp.concatenate(
                        [before, _rows_f32(x_ref, at, start, piece)], 0),
                    _heads_f32(g_ref, lo, group, start, piece))
                ext = jnp.concatenate([dconv, ahead], 0)
                dx = dconv * w[taps - 1] + sum(
                    pltpu.roll(ext, piece + lead - k, 0)[:piece]
                    * w[taps - 1 - k] for k in range(1, taps))
                dx_ref[0, pl.ds(start, piece), at] = dx.astype(dx_ref.dtype)
                # the taps' gradient a sublane: summed over the sublanes once
                return dconv[:lead], tuple(
                    s + jnp.sum((dconv * x).reshape(piece // 8, 8, group), 0)
                    for s, x in zip(sums_w, u))

            _, after = dconv_of(
                jnp.concatenate([_rows_f32(x_ref, at, rows - lead, lead),
                                 _rows_f32(x_after, at)], 0),
                _heads_f32(g_after, lo, group, 0, lead))
            _, sums_w = jax.lax.fori_loop(
                0, rows // piece, step,
                (jnp.where(last, 0.0, after),
                 (jnp.zeros((8, group), _F32),) * taps))
            for j, s in enumerate(sums_w):
                dw_ref[j:j + 1, at] += jnp.sum(s, 0, keepdims=True)

    _by_kind(walk, qk_blocks)


def _gdnconv_call(kernel, data, conv_weight, heads, dk, eps, rows, channels,
                  piece):
    """What both `pallas_call`s share: the kernel with its static numbers,
    the grid (channel block, batch, tile of rows: a block's tiles in turn, so
    the taps' gradient stays where it is summed), the blocks and the taps'
    operand.  The blocks of the flat input: a tile, the halo block
    just before it and just after it (the sequence's ends clamp to a block
    that is there and the kernels zero it).  The blocks of the head-major
    arrays ``(batch, heads, seq, d)``, q and k's 2 H heads in one array and
    v's H in another: the tile's rows of the channel block's heads and the
    halo block after them; while the other array's blocks go by, an array's
    index is held where it was last written (q and k's) or will first be
    (v's), so that nothing is copied out that the kernel did not write.  A
    channel block's rows of the taps."""
    batch, seq, width = data.shape
    halo = _halo_rows(data.dtype)
    per, end = rows // halo, seq // halo - 1
    qk_blocks, dv = 2 * heads * dk // channels, width // heads - 2 * dk
    tiles = seq // rows

    def before(s):
        return jnp.maximum(s * per - 1, 0)

    def after(s):
        return jnp.minimum((s + 1) * per, end)

    def head_major(rows_of, where):
        def qk(c, i, s):
            on = c < qk_blocks
            return (jnp.where(on, i, batch - 1),
                    jnp.where(on, c, qk_blocks - 1),
                    where(jnp.where(on, s, tiles - 1)), 0)

        def v(c, i, s):
            on = c >= qk_blocks
            return (jnp.where(on, i, 0), jnp.where(on, c - qk_blocks, 0),
                    where(jnp.where(on, s, 0)), 0)

        return (pl.BlockSpec((1, channels // dk, rows_of, dk), qk),
                pl.BlockSpec((1, channels // dv, rows_of, dv), v))

    specs = dict(
        tile=pl.BlockSpec((1, rows, channels), lambda c, i, s: (i, s, c)),
        before=pl.BlockSpec((1, halo, channels),
                            lambda c, i, s: (i, before(s), c)),
        after=pl.BlockSpec((1, halo, channels),
                           lambda c, i, s: (i, after(s), c)),
        heads=head_major(rows, lambda s: s),
        heads_after=head_major(halo, after),
        taps=pl.BlockSpec((8, channels), lambda c, i, s: (0, c)))
    kernel = functools.partial(
        kernel, group=_group(dk), taps=conv_weight.shape[1], dk=dk, eps=eps,
        qk_blocks=qk_blocks, piece=piece)
    return kernel, (width // channels, batch, tiles), specs, \
        _taps_and_scales(conv_weight, heads, dk)


_GDNCONV_STATIC = ("heads", "dk", "eps", "rows", "channels", "piece",
                   "interpret")
# the head-major arrays are revisited across the channel axis
_GDNCONV_WALK = ("arbitrary",) * 3


# jitted, so a step's linear layers share one trace and one Mosaic program of
# each kernel (as the rule's wrappers)

@functools.partial(jax.jit, static_argnames=_GDNCONV_STATIC)
def _gdnconv_fwd_pallas(data, conv_weight, heads, dk, eps, rows, channels,
                        piece, interpret=False):
    """`_conv_heads_body` by ``mx_gdnconv_fwd``.  The kernel writes q, k
    and v head-major, ``(batch, heads, seq, d)``, as the rule's kernels read
    them; the outputs are those with the two axes swapped back, which XLA
    folds into the rule's own swap."""
    batch, seq, width = data.shape
    kernel, grid, specs, taps = _gdnconv_call(
        _gdnconv_fwd_kernel, data, conv_weight, heads, dk, eps, rows,
        channels, piece)
    qk, v = pl.pallas_call(
        kernel, grid=grid,
        in_specs=[specs["tile"], specs["before"], specs["taps"]],
        out_specs=list(specs["heads"]),
        out_shape=[
            jax.ShapeDtypeStruct((batch, 2 * heads, seq, dk), data.dtype),
            jax.ShapeDtypeStruct(
                (batch, heads, seq, width // heads - 2 * dk), data.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_GDNCONV_WALK),
        interpret=interpret, name="mx_gdnconv_fwd",
    )(data, data, taps)
    return tuple(jnp.swapaxes(x, 1, 2)
                 for x in (qk[:, :heads], qk[:, heads:], v))


@functools.partial(jax.jit, static_argnames=_GDNCONV_STATIC)
def _gdnconv_bwd_pallas(data, conv_weight, dout, heads, dk, eps, rows,
                        channels, piece, interpret=False):
    """`_conv_heads_body`'s derivative by ``mx_gdnconv_bwd``: the gradients
    of the input and of the taps from the three outputs' gradients, which
    the kernel reads head-major (as the rule's kernel wrote them)."""
    dq, dk_, dv = (jnp.swapaxes(d, 1, 2) for d in dout)
    dqk = jnp.concatenate([dq, dk_], 1)
    kernel, grid, specs, taps = _gdnconv_call(
        _gdnconv_bwd_kernel, data, conv_weight, heads, dk, eps, rows,
        channels, piece)
    (gqk, gv), (gqk_after, gv_after) = specs["heads"], specs["heads_after"]
    ddata, dw = pl.pallas_call(
        kernel, grid=grid,
        in_specs=[specs["tile"], specs["before"], specs["after"], gqk,
                  gqk_after, gv, gv_after, specs["taps"]],
        out_specs=[specs["tile"], specs["taps"]],
        out_shape=[jax.ShapeDtypeStruct(data.shape, data.dtype),
                   jax.ShapeDtypeStruct((8, data.shape[2]), _F32)],
        # the taps' gradient is summed over a channel block's tiles
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_GDNCONV_WALK),
        interpret=interpret, name="mx_gdnconv_bwd",
    )(data, data, data, dqk, dqk, dv, dv, taps)
    return ddata, dw[:conv_weight.shape[1]].T.astype(conv_weight.dtype)


def _gdnconv_blocks(kernel, rows, channels, group, piece, dk, dv, dtype):
    """Bytes of VMEM one grid step of *kernel* takes: its blocks, each held
    twice (the flat tile of the input, in the backward kernel of its
    gradient too; the same rows of the block's heads in q and k's array and
    in v's, as the lanes pad them; the halo blocks; the taps' 8 float32
    rows, with their gradient's in the backward kernel), and two dozen
    float32 temporaries of a piece of a group's columns."""
    flat, halos, taps = {"fwd": (1, 1, 1), "bwd": (2, 3, 2)}[kernel]
    item, halo = jnp.dtype(dtype).itemsize, _halo_rows(dtype)
    heads = sum(channels // d * _padded(r, d, item) for d in (dk, dv)
                for r in ((rows,) if kernel == "fwd" else (rows, halo)))
    blocks = channels * item * (flat * rows + halos * halo) + heads \
        + taps * 8 * channels * 4
    return 2 * blocks + 24 * _padded(piece + 2 * halo, group, 4)


def _gdnconv_plan(data, conv_weight, heads, dk):
    """``(tiles, None)`` where the kernels take this call, ``(None, why
    not)`` where it stays `_conv_heads_body`.  From what the input shows
    alone: ``(batch, seq, channels)`` in 2 or 4 bytes, q and k's width and
    v's in whole groups of ``lcm(dk, 128)`` channels and a block of them in
    whole heads of v, the taps within a halo block and the rows their
    gradient is summed in, the sequence in whole pieces of both kernels, a
    grid step within `_GDNCONV_VMEM`, one device."""
    tiles, (width, taps) = GDNCONV_TILES, conv_weight.shape
    keys, group = 2 * heads * dk, _group(dk)
    if data.ndim != 3 or jnp.dtype(data.dtype).itemsize not in (2, 4):
        return None, "not (batch, seq, channels) in a dtype of 2 or 4 bytes"
    if keys % group or (width - keys) % group:
        return None, "%d channels of q and k and %d of v are not whole " \
            "groups of %d (whole heads of %d and whole lane tiles)" % (
                keys, width - keys, group, dk)
    most = min(_SCALE_ROW, _halo_rows(data.dtype) + 1)
    if not 2 <= taps <= most:
        return None, "%d taps are not 2 to %d" % (taps, most)
    rows = {k: _fit_rows(data.shape[1], *tiles[k]) for k in ("fwd", "bwd")}
    if None in rows.values():
        return None, "a sequence of %d is not whole pieces of %d and %d " \
            "rows" % (data.shape[1], tiles["fwd"][1], tiles["bwd"][1])
    # the most whole groups within the tile that divide both widths
    both = int(np.gcd(keys, width - keys)) // group
    channels = group * max(n for n in range(1, both + 1) if both % n == 0
                           and (n == 1 or n * group <= tiles["channels"]))
    dv = (width - keys) // heads
    if channels % dv:
        return None, "v's heads of %d do not make whole blocks of %d " \
            "channels" % (dv, channels)
    if any(_gdnconv_blocks(k, r, channels, group, tiles[k][1], dk, dv,
                           data.dtype)
           > _GDNCONV_VMEM for k, r in rows.items()):
        return None, "groups of %d channels with their blocks over the " \
            "VMEM budget" % group
    if not _one_device():
        # XLA does not partition a Mosaic kernel, and no cell spans chips
        return None, "a mesh of several devices"
    return dict({k: dict(rows=r, piece=tiles[k][1]) for k, r in rows.items()},
                channels=channels), None


def _fit_rows(seq, most, piece):
    """The most rows up to *most* that are whole pieces and divide *seq*
    (None: no such number)."""
    return next((rows for rows in range(min(most, seq) // piece * piece, 0,
                                        -piece) if seq % rows == 0), None)


def _conv_heads_forward(data, conv_weight, heads, dk, eps):
    at = dict(heads=heads, dk=dk, eps=eps)
    tiles, _ = _gdnconv_plan(data, conv_weight, heads, dk)
    if tiles is None:
        return _conv_heads_body(data, conv_weight, **at)
    return jax.lax.platform_dependent(
        data, conv_weight,
        default=functools.partial(_conv_heads_body, **at),
        tpu=functools.partial(
            _gdnconv_fwd_pallas, **tiles["fwd"], channels=tiles["channels"],
            **at))


def _conv_heads_backward(heads, dk, eps, kept, dout):
    at = dict(heads=heads, dk=dk, eps=eps)
    tiles, _ = _gdnconv_plan(*kept, heads, dk)
    if tiles is None:
        return _conv_heads_body_backward(*kept, dout, **at)
    return jax.lax.platform_dependent(
        *kept, dout,
        default=functools.partial(_conv_heads_body_backward, **at),
        tpu=functools.partial(
            _gdnconv_bwd_pallas, **tiles["bwd"], channels=tiles["channels"],
            **at))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _conv_heads(data, conv_weight, heads, dk, eps):
    """q, k and v from their concatenated projections: the kernels where
    `_gdnconv_plan` gives tiles and the program is lowered for the TPU,
    `_conv_heads_body` (and JAX's derivative of it, from the two inputs
    again) everywhere else."""
    return _conv_heads_forward(data, conv_weight, heads, dk, eps)


_conv_heads.defvjp(
    lambda data, w, heads, dk, eps: (
        _conv_heads_forward(data, w, heads, dk, eps), (data, w)),
    _conv_heads_backward)


def _record_gdnconv_plan(data, conv_weight, tiles, why):
    """One `mx.gdnconv.plan` span each time the op is traced (as
    `mx.gdn.plan`: the plan is a fact of the compiled program)."""
    with profiler.scope(  # graftlint: disable=JG003
            "mx.gdnconv.plan", "gdn") as span:
        span.args = {
            "shape": list(data.shape), "dtype": jnp.dtype(data.dtype).name,
            "taps": conv_weight.shape[1],
            # `kernel`: mx_gdnconv_fwd and mx_gdnconv_bwd where the program
            # is lowered for the TPU (`_conv_heads_body` where it is
            # lowered for anything else); `xla`: the body, and why
            "path": "xla" if tiles is None else "kernel", "why": why,
            "seq_tile": tiles and {k: tiles[k]["rows"]
                                   for k in ("fwd", "bwd")},
            "piece_rows": tiles and {k: tiles[k]["piece"]
                                     for k in ("fwd", "bwd")},
            "channel_tile": tiles and tiles["channels"],
            "halo_rows": tiles and _halo_rows(data.dtype),
            # what either path keeps for the backward pass: the two inputs
            "residual_bytes": data.size * data.dtype.itemsize
            + conv_weight.size * conv_weight.dtype.itemsize}


@register_op("_contrib_ShortConvHeads", aliases=("ShortConvHeads",),
             num_outputs=3)
def _short_conv_heads(data, conv_weight, num_heads=1, key_dim=1, eps=1e-6):
    """A linear-attention block's q, k and v from their concatenated
    projections ``(B, S, H dk + H dk + H dv)``: one depthwise causal
    convolution over all the channels (*conv_weight* ``(channels, taps)``, no
    bias, zeros before position 0), silu, then by head ``q / sqrt(sum(q^2)
    + eps) / sqrt(dk)``, ``k / sqrt(sum(k^2) + eps)`` and ``v`` as it is:
    ``(B, S, H, dk)`` twice and ``(B, S, H, dv)``.  The convolution, the
    silu and the norms are float32 and rounded once; the backward pass keeps
    the two inputs and computes them again.

    Where the program is lowered for the TPU on one device, at widths that
    are whole groups of ``lcm(dk, 128)`` channels and a sequence in whole
    pieces (`GDNCONV_TILES`), it is the kernels ``mx_gdnconv_fwd`` and
    ``mx_gdnconv_bwd``, one pass over the channels each way
    (`mx.gdnconv.plan` says ``path: kernel``); on every other platform and
    at every other shape it is `_conv_heads_body`, the same arithmetic in
    `jax.numpy`, and the span says why."""
    heads, dk = int(num_heads), int(key_dim)
    if conv_weight.shape[0] != data.shape[-1] \
            or (data.shape[-1] - 2 * heads * dk) % heads \
            or data.shape[-1] <= 2 * heads * dk:
        raise ValueError(
            "%d channels are not %d heads of two %d-wide keys and a value "
            "under taps %s" % (data.shape[-1], heads, dk,
                               conv_weight.shape))
    _record_gdnconv_plan(data, conv_weight,
                         *_gdnconv_plan(data, conv_weight, heads, dk))
    return _conv_heads(data, conv_weight, heads, dk, float(eps))


def _conv_silu_body(data, conv_weight, bias):
    return _taps_silu(data, conv_weight, bias).astype(data.dtype)


@jax.custom_vjp
def _conv_silu_kept(data, conv_weight, bias):
    return _conv_silu_body(data, conv_weight, bias)


def _conv_silu_bwd(kept, dout):
    # the float32 intermediates are computed again from the three inputs
    kept, dout = _again(kept, dout)
    return jax.vjp(_conv_silu_body, *kept)[1](dout)


_conv_silu_kept.defvjp(
    lambda *kept: (_conv_silu_body(*kept), kept), _conv_silu_bwd)


@register_op("_contrib_ShortConvSilu", aliases=("ShortConvSilu",))
def _short_conv_silu(data, conv_weight, bias):
    """`_contrib_ShortConvHeads`' convolution with a bias and no heads to
    norm, a state-space block's: ``silu(taps(data) + bias)`` over ``(B, S,
    channels)``, *conv_weight* ``(channels, taps)`` depthwise and causal
    (zeros before position 0), *bias* ``(channels,)``.  Float32, rounded
    once; the backward pass keeps the three inputs and computes it again.
    `jax.numpy` on every platform: the kernels ``mx_gdnconv_*`` norm q and k
    by head and carry no bias, and span ``mx.ssmconv.plan`` says so."""
    if conv_weight.shape[0] != data.shape[-1] \
            or bias.shape != data.shape[-1:]:
        raise ValueError("%d channels under taps %s and a bias %s" % (
            data.shape[-1], conv_weight.shape, bias.shape))
    with profiler.scope(  # graftlint: disable=JG003
            "mx.ssmconv.plan", "ssm") as span:
        span.args = {
            "shape": list(data.shape), "dtype": jnp.dtype(data.dtype).name,
            "taps": conv_weight.shape[1], "path": "xla",
            "why": "taps, a bias and silu with no heads to norm: the kernels "
                   "mx_gdnconv_fwd and mx_gdnconv_bwd norm q and k by head "
                   "and carry no bias",
            # what is kept for the backward pass: the three inputs
            "residual_bytes": sum(x.size * x.dtype.itemsize
                                  for x in (data, conv_weight, bias))}
    return _conv_silu_kept(data, conv_weight, bias)


@register_op("_contrib_DeltaRuleGates", aliases=("DeltaRuleGates",),
             num_outputs=2)
def _delta_rule_gates(decay, beta, a_log, dt_bias, allow_neg_eigval=False):
    """The gated delta rule's two gates from their projections ``(B, S,
    H)``: the decay's logarithm ``g = -exp(a_log) softplus(decay +
    dt_bias)`` in float32 (*a_log*, *dt_bias* one number a head) and the
    write strength ``sigmoid(beta)``, doubled with *allow_neg_eigval* (then
    in (0, 2): ``I - b k k^T`` has an eigenvalue in (-1, 1),
    arXiv:2411.12537), in beta's dtype."""
    g = -jnp.exp(a_log.astype(_F32)) * jax.nn.softplus(
        decay.astype(_F32) + dt_bias.astype(_F32))
    b = jax.nn.sigmoid(beta.astype(_F32))
    return g, ((2.0 * b) if allow_neg_eigval else b).astype(beta.dtype)


def _gated_norm_body(data, gate, gamma, eps, gate_first=False):
    batch, seq, heads, dv = data.shape
    x = data.astype(_F32)
    if gate_first:
        x = x * jax.nn.silu(gate.astype(_F32).reshape(data.shape))
        gamma = gamma.reshape(-1, dv)       # a scale a head, or one for all
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * gamma.astype(_F32)
    if not gate_first:
        z = gate.astype(_F32).reshape(batch, seq, heads, dv)
        y = y * jax.nn.silu(z)
    return y.reshape(batch, seq, heads * dv).astype(data.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gated_norm(data, gate, gamma, eps, gate_first):
    return _gated_norm_body(data, gate, gamma, eps, gate_first)


def _gated_norm_bwd(eps, gate_first, kept, dout):
    kept, dout = _again(kept, dout)
    return jax.vjp(functools.partial(_gated_norm_body, eps=eps,
                                     gate_first=gate_first), *kept)[1](dout)


_gated_norm.defvjp(
    lambda data, gate, gamma, eps, gate_first: (
        _gated_norm_body(data, gate, gamma, eps, gate_first),
        (data, gate, gamma)),
    _gated_norm_bwd)


@register_op("_contrib_GatedRMSNorm", aliases=("GatedRMSNorm",))
def _gated_rms_norm(data, gate, gamma, eps=1e-6, gate_first=False):
    """``rms(o, gamma) * silu(z)`` for ``o (B, S, H, dv)`` and ``z (B, S, H
    dv)``: the RMS norm over each head's ``dv`` numbers by one *gamma*
    ``(dv,)`` for all heads, gated, as ``(B, S, H dv)``; float32, rounded
    once; the backward pass keeps the three inputs.  With *gate_first* the
    gate stands in front of the norm, ``rms(o * silu(z), gamma)`` (a
    state-space block's: its "heads" are the groups the norm runs over, and
    *gamma* may be ``(H dv,)``, a scale a channel)."""
    return _gated_norm(data, gate, gamma, float(eps), bool(gate_first))
