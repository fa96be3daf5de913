"""The selective state-space recurrence of Mamba-2 (arXiv:2405.21060), a
state-space layer's core, and the small operator that makes its step sizes.

A layer of this kind carries a matrix ``S`` (``P x N`` a head: the head's
width by the state's size) along the sequence instead of attending over it.
Per head ``h`` of group ``g``, ``S_{-1} = 0``:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,   y_t = S_t C_t + D x_t

with a scalar decay a head and token (``dt_t > 0`` the step size, ``A < 0``),
the input and output maps ``B_t`` and ``C_t`` (``N`` numbers each) shared by
the heads of a group, and a skip ``D`` a head.  It differs from the gated
delta rule (`ops/delta_rule.py`) in kind: the write does not look at what the
state holds, so there is no triangular system to solve.

``_contrib_StateSpaceScan`` computes it in the chunkwise (SSD) form: chunks of
``Q`` tokens (256 where the caller names none; a property of the algorithm:
the result does not depend on it beyond float32 rounding).  With ``G`` the
cumulative sums of ``dt A`` inside a chunk and ``S_0`` the state the chunk
starts from,

    Y   = (L * (C B^T)) (dt * X) + exp(G) * (C S_0^T) + D X,
        L[t, j] = exp(G_t - G_j) for j <= t, 0 above
    S_Q = exp(G_Q) S_0 + (exp(G_Q - G) * dt * X)^T B

Every decay ratio is ``exp`` of a difference of cumulative sums that is not
positive.  It is `jax.numpy`: one `lax.scan` over the ``S / Q`` chunks, each
step a chunk's whole algebra (`_chunk`), so nothing of size ``Q x Q`` a head
exists for more than one chunk at a time.  The forward keeps its six inputs
and the ``S / Q`` chunk-start states (float32) and nothing per token of size
``P x N``; the backward is the op's own (`jax.custom_vjp`): behind
`delta_rule._again`'s barrier it walks the chunks in reverse with the state's
gradient as the carry and rebuilds each chunk from the kept inputs and its
start.  No array has two axes of the sequence and no loop runs a token at a
time.  The state, the cumulative sums, the decay matrix and every product
here are float32 (x, B and C arrive in the block's dtype, dt and A in
float32).  Span ``mx.ssm.plan`` says the sizes, the ``path`` (``xla``: there
is no kernel yet) and what a call keeps.

``_contrib_StateSpaceGates`` makes ``dt = softplus(dt + dt_bias)`` and ``A =
-exp(A_log)`` in float32, the arithmetic of ``_contrib_DeltaRuleGates`` with
the two factors handed over apart (the recurrence scales its input by ``dt``
as well).  Around them `gluon.contrib.nn.StateSpaceMixer` uses
``_contrib_ShortConvSilu`` and ``_contrib_GatedRMSNorm`` of
`ops/delta_rule.py`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import profiler
from .delta_rule import _F32, _again, _from_chunks, _mm, _to_chunks
from .registry import register_op

#: tokens a chunk where the caller names none (the published
#: ``mamba_chunk_size``)
DEFAULT_CHUNK = 256


def _chunk(x, dt, bm, cm, a, d, state):
    """One chunk from the state it starts at: ``(output, state after)``.
    ``x (B, G, R, Q, P)``, ``dt (B, G, R, Q)``, ``bm, cm (B, G, Q, N)``, ``a,
    d (G, R)``, ``state (B, G, R, P, N)``: G groups of R heads."""
    q = dt.shape[-1]
    cum = jnp.cumsum(dt * a[..., None], -1)
    last = cum[..., -1:]
    rows = jnp.arange(q)
    seen = rows[:, None] >= rows[None, :]
    # exp(G_t - G_j) for j <= t, zero above the diagonal: differences that
    # are positive are never exponentiated
    ratio = jnp.where(seen, jnp.exp(jnp.where(
        seen, cum[..., :, None] - cum[..., None, :], 0.0)), 0.0)
    scores = _mm("bgtn,bgjn->bgtj", cm, bm)
    xdt = dt[..., None] * x
    out = _mm("bgrtj,bgrjp->bgrtp", ratio * scores[:, :, None], xdt) \
        + jnp.exp(cum)[..., None] * _mm("bgtn,bgrpn->bgrtp", cm, state) \
        + d[..., None, None] * x
    after = jnp.exp(last)[..., None] * state + _mm(
        "bgrjp,bgjn->bgrpn", jnp.exp(last - cum)[..., None] * xdt, bm)
    return out, after


def _grouped(x, groups):
    """Chunked ``(N, B, H, ...)`` with the heads as ``(G, R)``."""
    return x.reshape(x.shape[:2] + (groups, x.shape[2] // groups)
                     + x.shape[3:])


def _ungrouped(x):
    return x.reshape(x.shape[:2] + (x.shape[2] * x.shape[3],) + x.shape[4:])


def _chunked(x, dt, a, bm, cm, d, chunk):
    """The six inputs as `_chunk` takes them, the chunk axis leading."""
    groups = bm.shape[2]
    return ((_grouped(_to_chunks(x, chunk), groups),
             _grouped(_to_chunks(dt, chunk), groups),
             _to_chunks(bm, chunk), _to_chunks(cm, chunk)),
            (a.astype(_F32).reshape(groups, -1),
             d.astype(_F32).reshape(groups, -1)))


def _forward(x, dt, a, bm, cm, d, chunk):
    chunks, heads = _chunked(x, dt, a, bm, cm, d, chunk)
    zero = jnp.zeros(chunks[0].shape[1:4] + (x.shape[-1], bm.shape[-1]), _F32)

    def step(state, at):
        out, after = _chunk(*at, *heads, state)
        return after, (state, out)

    _, (starts, out) = jax.lax.scan(step, zero, chunks)
    return _from_chunks(_ungrouped(out)).astype(x.dtype), starts


def _backward(chunk, kept, dout):
    (x, dt, a, bm, cm, d, starts), dout = _again(kept, dout)
    chunks, heads = _chunked(x, dt, a, bm, cm, d, chunk)
    dout = _grouped(_to_chunks(dout, chunk), bm.shape[2])

    def step(dstate, at):
        # the chunk again from its start, and its derivative
        *ins, start, dy = at
        _, pull = jax.vjp(_chunk, *ins, *heads, start)
        *dins, da, dd, before = pull((dy, dstate))
        return before, (tuple(dins), da, dd)

    _, ((dx, ddt, dbm, dcm), da, dd) = jax.lax.scan(
        step, jnp.zeros_like(starts[0]), chunks + (starts, dout),
        reverse=True)
    return (_from_chunks(_ungrouped(dx)).astype(x.dtype),
            _from_chunks(_ungrouped(ddt)).astype(dt.dtype),
            da.sum(0).reshape(a.shape).astype(a.dtype),
            _from_chunks(dbm).astype(bm.dtype),
            _from_chunks(dcm).astype(cm.dtype),
            dd.sum(0).reshape(d.shape).astype(d.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def state_space_scan(x, dt, a, bm, cm, d, chunk=DEFAULT_CHUNK):
    """``y (B, S, H, P)`` of the recurrence above from ``x (B, S, H, P)``,
    ``dt (B, S, H)``, ``a, d (H,)`` and ``bm, cm (B, S, G, N)``, in chunks of
    *chunk* tokens."""
    return _forward(x, dt, a, bm, cm, d, chunk)[0]


def _scan_fwd(x, dt, a, bm, cm, d, chunk):
    out, starts = _forward(x, dt, a, bm, cm, d, chunk)
    return out, (x, dt, a, bm, cm, d, starts)


state_space_scan.defvjp(_scan_fwd, _backward)


def state_kept_bytes(batch, seq, heads, head_dim, state, chunk=DEFAULT_CHUNK):
    """Bytes of state one call's forward keeps for its backward: a float32
    ``P x N`` a head at each of the ``seq / chunk`` chunk boundaries."""
    return 4 * batch * (seq // chunk) * heads * head_dim * state


def _fold_state_kept(values):
    from ..observability import metrics
    metrics.gauge(
        "ssm_state_kept_bytes", "bytes of recurrent state the last step's "
        "state-space scans kept for their backward, all layers").set(
            float(np.asarray(values, np.float64).sum()))


profiler.register_step_stat("ssm_state_kept_bytes", _fold_state_kept)


@register_op("_contrib_StateSpaceScan", aliases=("StateSpaceScan",))
def _state_space_scan_op(data, dt, a, b, c, d, chunk=DEFAULT_CHUNK):
    """The selective state-space recurrence over ``data (B, S, H, P)``, the
    step sizes ``dt (B, S, H)`` (positive: after their softplus), ``a (H,)``
    (negative), the input and output maps ``b, c (B, S, G, N)`` of G groups
    of ``H / G`` heads, and the skip ``d (H,)`` -> ``(B, S, H, P)``: per head,
    with a float32 state ``S`` (``P x N``) that starts at zero,

        ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) b_t``, ``y_t = S_t c_t +
        d x_t``

    in the chunkwise (SSD) form, *chunk* tokens a chunk (static; the sequence
    has to be whole chunks), with a backward of its own that keeps the
    inputs and the chunk-boundary states alone (`state_space_scan`;
    `jax.numpy` on every platform).  Span ``mx.ssm.plan`` says the sizes and,
    with step stat ``ssm_state_kept_bytes``, what a call keeps."""
    chunk = int(chunk)
    batch, seq, heads, width = data.shape
    groups, state = b.shape[2:] if b.ndim == 4 else (0, 0)
    if dt.shape != data.shape[:3] or b.shape != c.shape or b.ndim != 4 \
            or b.shape[:2] != data.shape[:2] or heads % max(groups, 1) \
            or a.shape != (heads,) or d.shape != (heads,):
        raise ValueError(
            "data (B, S, H, P), dt (B, S, H), a and d (H,), b and c (B, S, "
            "G, N) with G dividing H: got %s, %s, %s, %s, %s, %s" % (
                data.shape, dt.shape, a.shape, b.shape, c.shape, d.shape))
    if seq % chunk:
        raise ValueError(
            "the state-space scan runs in whole chunks: a sequence of %d "
            "tokens is not a multiple of the chunk of %d (pad the sequence, "
            "or name a chunk that divides it)" % (seq, chunk))
    kept = state_kept_bytes(batch, seq, heads, width, state, chunk)
    with profiler.scope(  # graftlint: disable=JG003
            "mx.ssm.plan", "ssm") as span:
        span.args = {
            "batch": batch, "seq": seq, "heads": heads, "head_dim": width,
            "state": state, "groups": groups, "chunk": chunk,
            "chunks": seq // chunk, "dtype": jnp.dtype(data.dtype).name,
            # `xla`: a scan over the chunks, a chunk's algebra a step
            "path": "xla", "why": "no kernel computes this recurrence yet",
            "state_kept_bytes": kept,
            # what a state kept at every token would be
            "per_token_state_bytes": 4 * batch * seq * heads * width * state}
    # at trace time on purpose (as the routed op's counts)
    profiler.emit_step_stat(  # graftlint: disable=JG003
        "ssm_state_kept_bytes", jnp.float32(kept))
    return state_space_scan(data, dt.astype(_F32), a.astype(_F32), b, c, d,
                            chunk)


@register_op("_contrib_StateSpaceGates", aliases=("StateSpaceGates",),
             num_outputs=2)
def _state_space_gates(dt, a_log, dt_bias):
    """A state-space layer's step sizes and decay rates from the projection
    ``dt (B, S, H)``: ``softplus(dt + dt_bias)`` and ``-exp(a_log)`` (*a_log*,
    *dt_bias* one number a head), both float32."""
    return (jax.nn.softplus(dt.astype(_F32) + dt_bias.astype(_F32)),
            -jnp.exp(a_log.astype(_F32)))
