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
What does not depend on the state (``A``, the solve's two right-hand sides,
``P`` and the decayed q and k) is computed for all chunks at once; what does
is one `lax.scan` over the ``S / C`` chunks, three products a step.  The
forward keeps its five inputs and the ``S / C`` chunk-boundary states
(float32) and nothing per token of size ``dk x dv``; the backward is the op's
own (`jax.custom_vjp`): it rebuilds every chunk's system, walks the chunks in
reverse with the state's gradient as the carry, and hands what that collects
to the derivative of the all-chunks part.  No array has two axes of the
sequence, none is ``(S, H, dk, dv)``, and no loop runs a token at a time.
The state, the cumulative sums, the solve and every product here are float32
(q, k, v and b arrive in the block's dtype, g in float32).

Around it `gluon.contrib.nn.GatedDeltaNet` uses ``_contrib_ShortConvHeads``
(one depthwise causal convolution of a few taps over the concatenated q, k, v
channels, silu, the per-head L2 norms of q and k, the move to heads),
``_contrib_DeltaRuleGates`` (the decay's logarithm and the write strength
from their two projections) and ``_contrib_GatedRMSNorm`` (the RMS norm of
each head's output times ``silu`` of a gate).  The first and the last keep
their inputs alone for the backward pass and compute their float32
intermediates again there.  All of it is `jax.numpy` on every platform;
span ``mx.gdn.plan`` says so (``path`` ``xla``) beside the sizes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import profiler
from .lm_blocks import causal_taps
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def gated_delta_rule(q, k, v, g, b, chunk=DEFAULT_CHUNK):
    """``o`` ``(B, S, H, dv)`` of the recurrence above from ``q, k (B, S, H,
    dk)`` (normalised by the caller), ``v (B, S, H, dv)`` and ``g, b (B, S,
    H)``, in chunks of *chunk* tokens."""
    return _forward(q, k, v, g, b, chunk)[0]


def _rule_fwd(q, k, v, g, b, chunk):
    out, starts = _forward(q, k, v, g, b, chunk)
    return out, (q, k, v, g, b, starts)


def _again(kept, dout):
    """What a backward pass computes again, it computes from these: behind a
    barrier with the gradient that starts it, or XLA merges the second
    computation with the forward's and keeps the forward's intermediates
    alive until then (as `jax.checkpoint` guards its own)."""
    return jax.lax.optimization_barrier((kept, dout))


def _rule_bwd(chunk, kept, dout):
    (q, k, v, g, b, starts), dout = _again(kept, dout)
    systems, pull = jax.vjp(
        _chunk_systems, *(_to_chunks(x, chunk) for x in (q, k, v, g, b)))
    _, dsystems = jax.lax.scan(
        _chunk_step_backward, jnp.zeros_like(starts[0]),
        (systems, starts, _to_chunks(dout, chunk)), reverse=True)
    return tuple(_from_chunks(d).astype(x.dtype)
                 for d, x in zip(pull(dsystems), (q, k, v, g, b)))


gated_delta_rule.defvjp(_rule_fwd, _rule_bwd)


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
    the inputs and the chunk-boundary states alone (`gated_delta_rule`).
    q and k arrive normalised.  Span ``mx.gdn.plan`` and step stat
    ``gdn_state_kept_bytes`` say what a call keeps."""
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
    with profiler.scope(  # graftlint: disable=JG003
            "mx.gdn.plan", "gdn") as span:
        span.args = {
            "batch": batch, "tokens": seq, "heads": heads, "key_dim": dk,
            "value_dim": dv, "chunk": chunk, "chunks": seq // chunk,
            "dtype": jnp.dtype(value.dtype).name,
            # every platform runs the same jax.numpy: the systems of all
            # chunks at once, then a scan over the chunks
            "path": "xla",
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


def _conv_heads_body(data, conv_weight, heads, dk, eps):
    batch, seq, width = data.shape
    y = jax.nn.silu(causal_taps(data, conv_weight))
    q, k, v = (part.reshape(batch, seq, heads, -1) for part in
               jnp.split(y, [heads * dk, 2 * heads * dk], -1))
    return tuple(part.astype(data.dtype) for part in (
        _unit(q, eps) * dk ** -0.5, _unit(k, eps), v))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _conv_heads(data, conv_weight, heads, dk, eps):
    return _conv_heads_body(data, conv_weight, heads, dk, eps)


def _conv_heads_bwd(heads, dk, eps, kept, dout):
    # the float32 intermediates are computed again from the two inputs
    kept, dout = _again(kept, dout)
    return jax.vjp(functools.partial(_conv_heads_body, heads=heads, dk=dk,
                                     eps=eps), *kept)[1](dout)


_conv_heads.defvjp(
    lambda data, w, heads, dk, eps: (_conv_heads_body(data, w, heads, dk,
                                                       eps), (data, w)),
    _conv_heads_bwd)


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
    the two inputs and computes them again."""
    heads, dk = int(num_heads), int(key_dim)
    if conv_weight.shape[0] != data.shape[-1] \
            or (data.shape[-1] - 2 * heads * dk) % heads \
            or data.shape[-1] <= 2 * heads * dk:
        raise ValueError(
            "%d channels are not %d heads of two %d-wide keys and a value "
            "under taps %s" % (data.shape[-1], heads, dk,
                               conv_weight.shape))
    return _conv_heads(data, conv_weight, heads, dk, float(eps))


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


def _gated_norm_body(data, gate, gamma, eps):
    batch, seq, heads, dv = data.shape
    x = data.astype(_F32)
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * gamma.astype(_F32)
    z = gate.astype(_F32).reshape(batch, seq, heads, dv)
    return (y * jax.nn.silu(z)).reshape(batch, seq, heads * dv).astype(
        data.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gated_norm(data, gate, gamma, eps):
    return _gated_norm_body(data, gate, gamma, eps)


def _gated_norm_bwd(eps, kept, dout):
    kept, dout = _again(kept, dout)
    return jax.vjp(functools.partial(_gated_norm_body, eps=eps),
                   *kept)[1](dout)


_gated_norm.defvjp(
    lambda data, gate, gamma, eps: (_gated_norm_body(data, gate, gamma, eps),
                                    (data, gate, gamma)),
    _gated_norm_bwd)


@register_op("_contrib_GatedRMSNorm", aliases=("GatedRMSNorm",))
def _gated_rms_norm(data, gate, gamma, eps=1e-6):
    """``rms(o, gamma) * silu(z)`` for ``o (B, S, H, dv)`` and ``z (B, S, H
    dv)``: the RMS norm over each head's ``dv`` numbers by one *gamma*
    ``(dv,)`` for all heads, gated, as ``(B, S, H dv)``; float32, rounded
    once; the backward pass keeps the three inputs."""
    return _gated_norm(data, gate, gamma, float(eps))
