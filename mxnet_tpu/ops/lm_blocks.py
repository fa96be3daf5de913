"""The parts of today's decoder blocks, as operators: RMS norm, rotary
positions, the gated (SwiGLU) feed-forward, the gated short causal
convolution, the latent attention block (low-rank keys and values, one
rotary key for all heads, keys wider than values), and a dropless top-k
routed expert layer that is told which experts it holds.

The reference framework predates all of them (SURVEY section 5.7); they
are TPU extensions beside ``_contrib_DotProductAttention``.  Each is a
graph node, so its device time lies under ``<op>:<node>`` in the step's
scope map; the routed layer's phases and the convolution carry scopes of
their own (docs/observability.md "Spans and device scopes").

Which path runs where.  The routed layer's grouped products are the
installed JAX's megablox kernels where the program is lowered for the TPU
and XLA's ragged dot on every other platform (`_product`); its combine, the
sum of each token's pairs, is one pass over the pair buffer's rows in token
order (`_combine_rows`: a row gather, then the Mosaic kernel
``mx_moe_combine``) where the program is lowered for the TPU and
`_combine_plan` gives tiles (one device, 2-byte rows of whole 128-lane
tiles, and a bounded buffer that `_sum_pairs` would gather `COMBINE_RATIO`
times over), and `_sum_pairs`, a row gather a choice, everywhere else.  The gated short
convolution's middle (`_gate`: the gates and the depthwise causal taps
between its two projections) is a pair of Mosaic kernels of this module,
``mx_shortconv_fwd`` and ``mx_shortconv_bwd``, where the program is lowered
for the TPU and the shape tiles (`_shortconv_plan`: one device, ``d`` a
multiple of 128, the sequence in whole tiles), and `_gate_body`, the
same arithmetic in `jax.numpy`, on every other platform and at every other
shape.  The per-head norm of q and k, their rotary positions and their
move to the head-major layout (`_norm_turn_by_head`: the sparse attention
block's, and the operator ``_contrib_HeadNormRotary`` that gluon.contrib.nn
``GroupedQueryAttention`` puts behind its q and k products) are
`_head_norm_rotary`, the Mosaic pair ``mx_headrope_fwd`` and
``mx_headrope_bwd``, on the same terms (`_headrope_plan`: one device, heads
of whole 128-lane tiles, the sequence in whole tiles; a part of a head
turned at given frequencies by two rolls and a third table), and `_rotary`
or `_rotary_given` over `_rms_norm` at every other shape.  All are decided
by what the code sees in its input and by the platform it is lowered for:
no argument, environment variable or switch.
The latent attention block's core is `ops/attention.py` `flash_attention`
at two head widths (the Mosaic kernels on the TPU, the chunked scan
elsewhere); its projections and its assembly of q and k are XLA's.

Grouped-query attention has no operator of its own: behind
``_contrib_HeadNormRotary`` the key/value heads are repeated to the query
heads (``repeat``) in front of ``_contrib_DotProductAttention``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import profiler
from ._precision import matmul_precision
from .attention import flash_attention
from .nn import _fully_connected
from .registry import register_op

__all__ = ["routed_expert_counts"]


def _dot(x, w):
    """``x`` (..., k) against ``w`` (n, k) as `FullyConnected` computes
    it: summed in float32, in x's dtype."""
    return _fully_connected(x, w, no_bias=True, flatten=False)


@register_op("_contrib_RMSNorm", aliases=("RMSNorm",))
def _rms_norm(data, gamma, eps=1e-5):
    """``x / sqrt(mean(x^2) + eps) * gamma`` over the last axis, the
    statistics in float32."""
    x = data.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return (y * gamma.astype(jnp.float32)).astype(data.dtype)


def rope_frequencies(parameters, head_dim):
    """A layer kind's rotary settings, shaped as one entry of a published
    ``rope_parameters`` mapping (``rope_type`` ``default`` or ``yarn``,
    ``rope_theta``, ``partial_rotary_factor``, and for YaRN ``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    ``attention_factor``), as `_contrib_HeadNormRotary`'s attributes:
    ``theta`` alone where one theta turns the whole head (today's op), else
    ``rotary_dim``, the ``inv_freq`` of its ``rotary_dim / 2`` pairs and the
    ``table_scale`` that multiplies cos and sin.

    YaRN (arXiv:2309.00071, as the public code applies it) over ``dim =
    rotary_dim``: ``e_i = theta^(-2i/dim)``, ``n_i = e_i / factor``, ``c(r)
    = dim ln(original / (2 pi r)) / (2 ln theta)``, ``low = max(floor(
    c(beta_fast)), 0)``, ``high = min(ceil(c(beta_slow)), dim - 1)``,
    ``ramp_i = clip((i - low) / (high - low), 0, 1)``, ``inv_freq_i = n_i
    ramp_i + e_i (1 - ramp_i)``; the scale is ``attention_factor`` (``0.1
    ln(factor) + 1`` where not given).  In float64."""
    kind = parameters.get("rope_type", "default")
    theta = float(parameters.get("rope_theta", 10000.0))
    dim = int(head_dim * float(parameters.get("partial_rotary_factor", 1)))
    if dim < 2 or dim % 2 or dim > head_dim:
        raise ValueError("partial_rotary_factor %r leaves %d of a head's %d"
                         % (parameters.get("partial_rotary_factor"), dim,
                            head_dim))
    e = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if kind == "default":
        if dim == head_dim:
            return {"theta": theta}
        inv, scale = e, 1.0
    elif kind == "yarn":
        factor = float(parameters["factor"])
        original = float(parameters["original_max_position_embeddings"])

        def turns(r):
            return dim * math.log(original / (2 * math.pi * r)) \
                / (2 * math.log(theta))

        low = max(math.floor(turns(float(parameters.get("beta_fast", 32)))),
                  0)
        high = min(math.ceil(turns(float(parameters.get("beta_slow", 1)))),
                   dim - 1)
        ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                       / max(high - low, 1e-3), 0.0, 1.0)
        inv = e / factor * ramp + e * (1.0 - ramp)
        scale = float(parameters.get("attention_factor")
                      or 0.1 * math.log(factor) + 1.0)
    else:
        raise ValueError("rope_type %r is not built (default and yarn are)"
                         % (kind,))
    return {"theta": theta, "rotary_dim": dim,
            "inv_freq": tuple(float(f) for f in inv),
            "table_scale": scale}


def _given_cos_sin(seq, d, rotary_dim, inv_freq, scale):
    """``(cos, sin)`` ``(seq, rotary_dim)`` in float64 for positions ``0 ..
    seq - 1`` at the given frequencies, one a pair of the first *rotary_dim*
    of a *d*-wide head, each laid twice side by side and times *scale*."""
    if len(inv_freq) != rotary_dim // 2 or rotary_dim > d:
        raise ValueError("%d frequencies do not turn %d of a %d-wide head"
                         % (len(inv_freq), rotary_dim, d))
    ang = np.arange(seq, dtype=np.float64)[:, None] \
        * np.asarray(inv_freq, np.float64)[None, :]
    return (np.concatenate([np.cos(ang)] * 2, -1) * scale,
            np.concatenate([np.sin(ang)] * 2, -1) * scale)


def _rotary_given(data, rotary_dim, inv_freq, scale):
    """Rotary positions ``0 .. seq - 1`` on the first *rotary_dim* of each
    head of ``(batch, heads, seq, d)`` at the given frequencies, one a
    pair, the pairs split by halves of the rotated part (``(x[i], x[i +
    rotary_dim / 2])``); cos and sin times *scale*; the other dims pass
    through untouched.  Angles in float64 rounded once, the turn in
    float32, as `_rotary`."""
    half = rotary_dim // 2
    cos, sin = (jnp.asarray(t, jnp.float32) for t in _given_cos_sin(
        data.shape[-2], data.shape[-1], rotary_dim, inv_freq, scale))
    x = data.astype(jnp.float32)
    turned, kept = x[..., :rotary_dim], x[..., rotary_dim:]
    rot = jnp.concatenate([-turned[..., half:], turned[..., :half]], -1)
    return jnp.concatenate([turned * cos + rot * sin, kept],
                           -1).astype(data.dtype)


def _rotary(data, theta=10000.0, interleaved=False, positions=None,
            mrope_section=()):
    """Rotary positions on ``(batch, heads, seq, d)``: positions ``0 ..
    seq - 1``, frequencies ``theta ** (-2i / d)``, the angles in float32.
    Frequency ``i`` turns the pair ``(x[i], x[i + d/2])`` (rotate-half), or
    with *interleaved* the pair ``(x[2i], x[2i + 1])``.  The interleaved
    pairs are swapped by a product with a fixed signed permutation (exact:
    every sum has one term), not by strided slices (a scatter backward).

    With *positions* ``(axes, batch, seq)`` the positions are an operand
    and have several axes (a text, height and width axis): the ``d / 2``
    frequencies are dealt to the axes in chunks of *mrope_section* (``[16,
    24, 24]``: the first 16 frequencies turn by axis 0's position, the next
    24 by axis 1's, the last 24 by axis 2's), rotate-half.  Plain text has
    all its axes equal to ``0 .. seq - 1``, which is what no *positions*
    means: the one-axis arithmetic, to the letter."""
    s, d = data.shape[-2], data.shape[-1]
    half = d // 2
    sections = tuple(int(n) for n in mrope_section) or (half,)
    if sum(sections) != half:
        raise ValueError("mrope_section %r has to cover the %d frequencies "
                         "of a %d-wide head" % (sections, half, d))
    inv = 1.0 / (float(theta) ** (np.arange(half, dtype=np.float64) / half))
    x = data.astype(jnp.float32)
    if positions is not None:
        if interleaved:
            raise ValueError("positions as an operand turn rotate-half "
                             "pairs; the interleaved form is not built")
        if positions.shape[0] != len(sections):
            raise ValueError("positions have %d axes, mrope_section %d"
                             % (positions.shape[0], len(sections)))
        pos = positions.astype(jnp.float32)
        # (batch, seq, half): each frequency beside its own axis's position
        ang = jnp.concatenate(
            [jnp.broadcast_to(pos[a][..., None], pos.shape[1:] + (n,))
             for a, n in enumerate(sections)], -1) \
            * jnp.asarray(inv, jnp.float32)
        cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
        sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
        rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
        return (x * cos + rot * sin).astype(data.dtype)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    if interleaved:
        cos = jnp.asarray(np.repeat(np.cos(ang), 2, -1), jnp.float32)
        sin = jnp.asarray(np.repeat(np.sin(ang), 2, -1), jnp.float32)
        swap = np.zeros((d, d), np.float32)
        even = 2 * np.arange(half)
        swap[even + 1, even], swap[even, even + 1] = -1.0, 1.0
        rot = jnp.einsum(
            "...d,de->...e", data, jnp.asarray(swap, data.dtype),
            precision=matmul_precision(data.dtype, data.dtype),
            preferred_element_type=jnp.float32)
    else:
        cos = jnp.asarray(np.concatenate([np.cos(ang)] * 2, -1), jnp.float32)
        sin = jnp.asarray(np.concatenate([np.sin(ang)] * 2, -1), jnp.float32)
        rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return (x * cos + rot * sin).astype(data.dtype)


@register_op("_contrib_RotaryEmbedding", aliases=("RotaryEmbedding",),
             input_names=("data", "positions"))
def _rotary_embedding(data, *positions, theta=10000.0, interleaved=False,
                      mrope_section=(), use_positions=False):
    """`_rotary` as an operator: *positions* is an input with
    ``use_positions``, and is not one without."""
    return _rotary(data, theta, interleaved,
                   positions[0] if positions else None, mrope_section)


def _with_positions(*names):
    def active(params):
        return names + (("positions",) if params.get("use_positions")
                        else ())
    return active


def _silu_mul(h, g):
    """``silu(h) * g`` computed in float32, in h's dtype."""
    h32 = h.astype(jnp.float32)
    return (h32 * jax.nn.sigmoid(h32) * g.astype(jnp.float32)
            ).astype(h.dtype)


@register_op("_contrib_GatedMLP", aliases=("GatedMLP",))
def _gated_mlp(data, w1, w3, w2):
    """``W2(silu(W1 x) * W3 x)``; weights as ``FullyConnected`` holds
    them: w1, w3 ``(hidden, in)``, w2 ``(in, hidden)``."""
    return _dot(_silu_mul(_dot(data, w1), _dot(data, w3)), w2)


@register_op("_contrib_SharedExperts", aliases=("SharedExperts",))
def _shared_experts(data, w1, w3, w2):
    """The shared experts beside a routed layer: one gated MLP (as
    ``_contrib_GatedMLP``) that every token passes through and every chip
    of the layer's group computes alike, under device scope
    ``mx.moe.shared``."""
    with jax.named_scope("mx.moe.shared"):
        return _gated_mlp(data, w1, w3, w2)


# ---------------------------------------------------------------------------
# The latent attention block (multi-head latent attention, expanded form).
# ---------------------------------------------------------------------------

def _record_mla_plan(data, heads, nope, rope, v_dim, rank):
    """One `mx.mla.plan` span each time the op is traced (as
    `mx.flash.plan`: the plan is a fact of the compiled program)."""
    batch, seq = data.shape[0], data.shape[1]
    per_width = batch * heads * seq * jnp.dtype(data.dtype).itemsize
    with profiler.scope(  # graftlint: disable=JG003
            "mx.mla.plan", "mla") as span:
        span.args = {
            "heads": heads, "qk_nope_head_dim": nope,
            "qk_rope_head_dim": rope, "v_head_dim": v_dim,
            "kv_lora_rank": rank, "batch": batch, "seq": seq,
            "dtype": jnp.dtype(data.dtype).name,
            # what the straightforward assembly writes before the kernels
            # read it: q and k at heads x (nope + rope), the one rope key
            # of a position spread over every head
            "assembled_q_bytes": per_width * (nope + rope),
            "assembled_k_bytes": per_width * (nope + rope),
            "rope_key_copies": heads}


@register_op("_contrib_LatentAttention", aliases=("LatentAttention",))
def _latent_attention(data, q_weight, kv_a_weight, kv_norm_gamma,
                      kv_b_weight, out_weight, num_heads=1,
                      qk_nope_head_dim=128, qk_rope_head_dim=64,
                      v_head_dim=128, rope_theta=10000.0,
                      rope_interleave=True, eps=1e-6):
    """Causal latent attention on ``(batch, seq, d)``, the expanded form a
    model is trained in, with H = *num_heads* heads and no biases:

    ``q = x Wq`` -> (H, nope + rope), split ``q_nope | q_rope``;
    ``x Wkv_a`` -> ``c | k_rope`` (the latent of width ``kv_lora_rank``,
    and ONE rotary key of width rope for all heads);
    ``rms(c, gamma) Wkv_b`` -> (H, nope + v), split ``k_nope | v``;
    rotary positions on ``q_rope`` and ``k_rope`` (pairs ``(x[2i],
    x[2i+1])`` with *rope_interleave*, rotate-half without);
    ``q_h = [q_nope_h | q_rope_h]``, ``k_h = [k_nope_h | k_rope]``, scores
    ``q_h . k_h / sqrt(nope + rope)``, causal softmax, times ``v_h`` (v
    wide), the heads joined, ``Wo``.

    Weights as ``FullyConnected`` holds them: q_weight ``(H (nope + rope),
    d)``, kv_a_weight ``(rank + rope, d)``, kv_norm_gamma ``(rank,)``,
    kv_b_weight ``(H (nope + v), rank)``, out_weight ``(d, H v)``.  The
    core is `flash_attention` with keys nope + rope wide and values v
    wide: nothing is padded from the one to the other.  k is materialised
    at H x (nope + rope) in front of it (`mx.mla.assemble`); the absorbed
    form, which never builds it, is a decode matter and is not here.
    """
    heads, nope, rope, v_dim = (int(num_heads), int(qk_nope_head_dim),
                                int(qk_rope_head_dim), int(v_head_dim))
    batch, seq, _ = data.shape
    rank = kv_norm_gamma.shape[0]
    theta, interleaved = float(rope_theta), bool(rope_interleave)
    _record_mla_plan(data, heads, nope, rope, v_dim, rank)

    def by_head(y, width):
        # (B, S, H * width) -> (B, H, S, width)
        return y.reshape(batch, seq, heads, width).transpose(0, 2, 1, 3)

    with jax.named_scope("mx.mla"):
        with jax.named_scope("mx.mla.project"):
            q = _dot(data, q_weight)
            ckv = _dot(data, kv_a_weight)
            kv = _dot(_rms_norm(ckv[..., :rank], kv_norm_gamma, eps),
                      kv_b_weight)
        with jax.named_scope("mx.mla.assemble"):
            q, kv = by_head(q, nope + rope), by_head(kv, nope + v_dim)
            q = jnp.concatenate(
                [q[..., :nope], _rotary(q[..., nope:], theta, interleaved)],
                -1)
            k_rope = _rotary(ckv[..., rank:][:, None], theta, interleaved)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_rope, (batch, heads, seq, rope))], -1)
            v = kv[..., nope:]
        att = flash_attention(q, k, v, causal=True,
                              sm_scale=(nope + rope) ** -0.5)
        with jax.named_scope("mx.mla.assemble"):
            att = att.transpose(0, 2, 1, 3).reshape(batch, seq,
                                                    heads * v_dim)
        with jax.named_scope("mx.mla.out"):
            return _dot(att, out_weight)


# ---------------------------------------------------------------------------
# Learned sparse attention: grouped-query attention over the keys an indexer
# chose, the indexer trained by an alignment term.
# ---------------------------------------------------------------------------

def _record_dsa_plan(data, heads, kv_heads, head_dim, index_heads,
                     index_width, topk):
    """One `mx.dsa.plan` span each time the op is traced (as
    `mx.flash.plan`, which the attention's own call records beside it)."""
    from . import sparse_attention
    batch, seq = data.shape[0], data.shape[1]
    with profiler.scope(  # graftlint: disable=JG003
            "mx.dsa.plan", "dsa") as span:
        span.args = dict(
            sparse_attention.select_plan(seq, index_heads, index_width,
                                         data.dtype),
            **sparse_attention.align_plan(seq, index_heads, index_width,
                                          heads, kv_heads, head_dim,
                                          data.dtype),
            batch=batch, tokens=seq, topk=min(topk, seq), heads=heads,
            kv_heads=kv_heads, head_dim=head_dim, index_heads=index_heads,
            index_head_dim=index_width, dtype=jnp.dtype(data.dtype).name,
            # every causal tile is visited under the selection's bits: no
            # tile is skipped and no key is gathered
            form="masked flash: a selection operand, one bit a pair",
            selection_bytes=2 * batch * -(-seq // 32) * seq * 4)


def _fold_dsa_counts(rows):
    rows = np.asarray(rows).reshape(-1, 2)
    profiler.bump_counter("dsa_selected_keys_total", int(rows[:, 0].sum()))
    profiler.bump_counter("dsa_visible_keys_total", int(rows[:, 1].sum()))


def _fold_dsa_alignment(values):
    from ..observability import metrics
    metrics.gauge(
        "dsa_alignment_loss", "the indexer's alignment term, the mean over "
        "the last step's sparse attention layers").set(
            float(np.mean(np.asarray(values, np.float64))))


@register_op("_contrib_SparseAttention", aliases=("SparseAttention",),
             num_outputs=2,
             input_names=("data", "q_weight", "k_weight", "v_weight",
                          "out_weight", "q_gamma", "k_gamma",
                          "index_q_weight", "index_k_weight",
                          "index_w_weight", "positions"))
def _sparse_attention(data, q_weight, k_weight, v_weight, out_weight,
                      q_gamma, k_gamma, index_q_weight, index_k_weight,
                      index_w_weight, *positions, num_heads=1,
                      num_kv_heads=1, index_heads=1, topk=2048,
                      rope_theta=10000.0, mrope_section=(), eps=1e-6,
                      use_positions=False):
    """Causal grouped-query attention on ``(batch, seq, d)`` over the keys a
    learned indexer chose (DeepSeek-V3.2-Exp's sparse attention), H =
    *num_heads* query heads over *num_kv_heads* key/value heads, J =
    *index_heads* indexer heads over one indexer key, no biases:

    ``q = rope(rms_head(x Wq))``, ``k = rope(rms_head(x Wk))``, ``v = x Wv``
    (RMS norm over each head, then rotary positions: `_rotary`, over
    *positions* ``(axes, batch, seq)`` dealt by *mrope_section* with
    ``use_positions``, text positions without; where `_headrope_plan` gives
    tiles, `_head_norm_rotary`: the same in one pass each way, rounded
    once, and `mx.headrope.plan` says ``path: kernel``);
    on ``xd = stop_gradient(x)``: ``qI = xd WqI`` (J x di), ``kI = xd WkI``
    (di), ``w = (xd Ww) * J^-1/2 * di^-1/2``; ``I[t, s] = sum_j w[t, j] *
    relu(qI[t, j] . kI[s])``;
    query ``t`` sees the causal keys whose ``I[t, s]`` is at least the
    *topk*-th largest of its row (ties with it included; all of them where
    there are no more than *topk*): a choice, which carries no gradient;
    softmax attention over those keys, the heads joined, ``Wo``.

    The indexer is trained by the alignment term ``L = mean_t KL(p[t, .] ||
    softmax_{S_t} I[t, .])``, ``p`` the heads' mean probabilities held
    fixed.  ``L`` is the op's SECOND output, shape ``(1,)`` in float32, for
    the net to hand to the objective (`gluon.model_zoo.decoder`
    `AlignedLoss`), so its gradient takes the loss's own scale: it is the
    ONLY path to WqI, WkI and Ww, and it reaches nothing else; the first
    output reaches every other weight and none of those three.  ``L`` also
    leaves as gauge ``dsa_alignment_loss``, the chosen and the causal pairs
    as counters ``dsa_selected_keys_total`` and ``dsa_visible_keys_total``
    (from the bits the kernels were handed, so ties show).

    Weights as ``FullyConnected`` holds them.  The selection is
    `ops/sparse_attention.py` `index_select` (bits, one a pair; no ``S x
    S`` array is written), the attention `ops/attention.py`
    `selected_attention` (the flash kernels with the selection as an
    operand, on the TPU), the term `alignment_term`."""
    from . import sparse_attention
    from .attention import selected_attention
    heads, kv_heads, j = int(num_heads), int(num_kv_heads), int(index_heads)
    batch, seq, _ = data.shape
    head_dim = q_gamma.shape[0]
    width = index_k_weight.shape[0]
    pos = positions[0] if positions else None
    _record_dsa_plan(data, heads, kv_heads, head_dim, j, width, int(topk))

    tables = []

    def by_head(y, n, gamma=None):
        # (B, S, n * hd) -> (B, n, S, hd), normed over hd and turned
        if gamma is None:
            return y.reshape(batch, seq, n, head_dim).transpose(0, 2, 1, 3)
        # one pair of tables an op: q and k turn by the same angles
        return _norm_turn_by_head(y, n, gamma, pos, mrope_section,
                                  rope_theta, eps, tables)

    with jax.named_scope("mx.dsa"):
        with jax.named_scope("mx.dsa.project"):
            q = by_head(_dot(data, q_weight), heads, q_gamma)
            k = by_head(_dot(data, k_weight), kv_heads, k_gamma)
            v = by_head(_dot(data, v_weight), kv_heads)
            group = heads // kv_heads
            k_all = jnp.repeat(k, group, axis=1) if group > 1 else k
            v_all = jnp.repeat(v, group, axis=1) if group > 1 else v
        with jax.named_scope("mx.dsa.index"):
            xd = jax.lax.stop_gradient(data)
            qi = _dot(xd, index_q_weight)
            ki = _dot(xd, index_k_weight)
            w = _dot(xd, index_w_weight).astype(jnp.float32) \
                * (j ** -0.5 * width ** -0.5)
        sel_q, sel_k, lse_i = sparse_attention.index_select(qi, ki, w,
                                                            int(topk))
        scale = head_dim ** -0.5
        att, lse = selected_attention(q, k_all, v_all, sel_q, sel_k, scale)
        term = sparse_attention.alignment_term(qi, ki, w, q, k, lse, lse_i,
                                               sel_q, scale)
        with jax.named_scope("mx.dsa.out"):
            out = _dot(att.transpose(0, 2, 1, 3).reshape(
                batch, seq, heads * head_dim), out_weight)
        # at trace time on purpose (as the routed op's counts)
        profiler.emit_step_stat(  # graftlint: disable=JG003
            "dsa_key_counts", jnp.stack([
                jnp.sum(jax.lax.population_count(sel_q), dtype=jnp.int32),
                jnp.int32(batch * seq * (seq + 1) // 2)]))
        profiler.emit_step_stat(  # graftlint: disable=JG003
            "dsa_alignment_loss", term)
        return out, term.reshape(1)


profiler.register_step_stat("dsa_key_counts", _fold_dsa_counts)
profiler.register_step_stat("dsa_alignment_loss", _fold_dsa_alignment)


# ---------------------------------------------------------------------------
# Training by diffusion over blocks (BD3-LM, arXiv:2503.09573): the net's
# input is a clean copy of each sequence then a noised copy, attention runs
# under `ops/attention.py` `BlockDiffusion`; here the positions both copies
# share and the weighted cross-entropy over the noised copy.
# ---------------------------------------------------------------------------

@register_op("_contrib_BlockDiffusionPositions",
             aliases=("BlockDiffusionPositions",))
def _block_diffusion_positions(data):
    """Rotary positions of ``(batch, 2L)`` token ids that are a clean copy
    then a noised copy of ``L`` tokens: ``(1, batch, 2L)`` int32, position
    ``j mod L`` (both copies of token ``i`` stand at ``i``), as
    `_contrib_RotaryEmbedding` takes them with ``use_positions``."""
    batch, seq = data.shape[0], data.shape[1]
    return jnp.broadcast_to(
        jnp.arange(seq, dtype=jnp.int32) % (seq // 2), (1, batch, seq))


def _fold_bd_counts(rows):
    rows = np.asarray(rows).reshape(-1, 2)
    profiler.bump_counter("bd_masked_positions_total", int(rows[:, 0].sum()))
    profiler.bump_counter("bd_positions_total", int(rows[:, 1].sum()))


def _fold_bd_loss(values):
    from ..observability import metrics
    metrics.gauge(
        "bd_loss", "the block-diffusion objective of the last step, the "
        "mean over its rows").set(
            float(np.mean(np.asarray(values, np.float64))))


def _fold_bd_pairs(values):
    profiler.bump_counter("bd_visible_pairs_total",
                          int(np.asarray(values, np.int64).sum()))


@register_op("_contrib_BlockDiffusionLoss", aliases=("BlockDiffusionLoss",))
def _block_diffusion_loss(data, label):
    """The masked-diffusion objective of one step, a value a row: *data*
    ``(batch, L, vocab)`` logits of the noised copy, *label* ``(batch, 2,
    L)`` float32 holding the clean ids ``x`` and the weights ``w`` (``1 /
    t`` of its block where the position was masked, else 0):

        ``loss = (1 / L) * sum_i w_i * -log softmax(logits_i)[x_i]``

    with the logsumexp, the sum and the division in float32.  No shift:
    the token predicted is the one at the masked position itself.  The
    positions that carry loss and all positions leave as counters
    ``bd_masked_positions_total`` and ``bd_positions_total``, the value as
    gauge ``bd_loss``."""
    x = data.astype(jnp.float32)
    ids = label[:, 0].astype(jnp.int32)
    w = label[:, 1].astype(jnp.float32)
    # the target's logit by a select: a gather's gradient is a scatter
    hit = jax.lax.broadcasted_iota(jnp.int32, x.shape, 2) == ids[..., None]
    nll = jax.nn.logsumexp(x, -1) - jnp.sum(jnp.where(hit, x, 0.0), -1)
    rows = jnp.sum(w * nll, -1) / x.shape[1]
    # at trace time on purpose (as the routed op's counts)
    profiler.emit_step_stat(  # graftlint: disable=JG003
        "bd_position_counts", jnp.stack([
            jnp.sum(w > 0, dtype=jnp.int32), jnp.int32(w.size)]))
    profiler.emit_step_stat(  # graftlint: disable=JG003
        "bd_loss", jnp.mean(rows))
    return rows


profiler.register_step_stat("bd_position_counts", _fold_bd_counts)
profiler.register_step_stat("bd_loss", _fold_bd_loss)
profiler.register_step_stat("bd_visible_pairs", _fold_bd_pairs)


def _fold_swa_pairs(values):
    profiler.bump_counter("swa_visible_pairs_total",
                          int(np.asarray(values, np.int64).sum()))


profiler.register_step_stat("swa_visible_pairs", _fold_swa_pairs)


# ---------------------------------------------------------------------------
# The gated short convolution.  Its middle, everything between the input
# projection and the output projection, is one function `_gate`: on the TPU
# a pair of Mosaic kernels that pass over ``bcx`` once each way; everywhere
# else, and at shapes the kernels do not tile, `_gate_body`.
# ---------------------------------------------------------------------------

#: rows of the sequence a grid step of each kernel holds, and the most
#: channels it works on at a time inside the step (a block is whole rows of
#: ``bcx``, so that ``db``, ``dc`` and ``dx`` leave as one array).  From
#: `tools/shortconv_sweep.py` on the v5e at (2, 8192, 3 x 2048) bf16 with 3
#: taps, device ms a call (PERF.md section 6, PR 29): forward 0.398 at 256
#: rows (82% of 819 GB/s), 0.412 at 128, 0.443 at 64, 512 over the VMEM;
#: backward 0.753 at 128 rows (76%), 0.802 at 64, 0.910 at 32, 256 over the
#: VMEM; chunks of 256 to 2048 channels within 2% of each other.  Not
#: options: the sweep sets them to compare
SHORTCONV_TILES = {"fwd": 256, "bwd": 128, "channels": 512}

#: what a kernel's blocks, each held twice, may take of the 16 MiB of VMEM
#: that a Mosaic kernel is given on the v5e; the rest is for one channel
#: chunk's float32 temporaries
_SHORTCONV_VMEM = 10 << 20


def causal_taps(u, conv_weight):
    """The depthwise causal convolution ``c_t = sum_j w_j u_(t-L+1+j)`` over
    ``(..., seq, d)`` in float32, ``u`` zero before the sequence of every
    batch row; *conv_weight* is ``(d, L)``.  The gated short convolution's
    and the linear-attention block's (`ops/delta_rule.py`)."""
    taps, seq = conv_weight.shape[1], u.shape[-2]
    u = u.astype(jnp.float32)
    w = conv_weight.astype(jnp.float32)
    padded = jnp.pad(u, [(0, 0)] * (u.ndim - 2) + [(taps - 1, 0), (0, 0)])
    return sum(padded[..., j:j + seq, :] * w[:, j] for j in range(taps))


def _gate_body(bcx, conv_weight):
    """``C * conv(B * X)`` in `jax.numpy`, the middle's definition: ``u``
    and the taps in float32 (a product of two bf16 numbers is exact there),
    the result rounded to the input's dtype once."""
    d = conv_weight.shape[0]
    b, c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    u = b.astype(jnp.float32) * x.astype(jnp.float32)
    conv = causal_taps(u, conv_weight)
    return (c.astype(jnp.float32) * conv).astype(bcx.dtype)


def _halo_rows(dtype):
    """Rows of the block that brings a tile its neighbours: one sublane
    tile of *dtype* (8 rows of 4 bytes, 16 of 2)."""
    return 32 // jnp.dtype(dtype).itemsize


def _shortconv_blocks(kernel, rows, d, dtype):
    """Bytes of one grid step's blocks of *kernel*, each held twice: the
    tile's columns of ``d`` (``bcx`` and ``gated``; in the backward kernel
    ``dgated`` and ``dbcx`` too), its halo blocks, and the taps' 8 float32
    rows (with their gradient's in the backward kernel)."""
    wide, halos, taps = {"fwd": (4, 2, 1), "bwd": (7, 4, 2)}[kernel]
    return 2 * (d * jnp.dtype(dtype).itemsize
                * (wide * rows + halos * _halo_rows(dtype)) + taps * 8 * d * 4)


def _shortconv_plan(bcx, conv_weight):
    """The tiles `_gate` runs this input at, or None where it runs
    `_gate_body` and lets JAX differentiate it.  The kernels take ``(batch,
    seq, 3d)`` on one device: ``d`` a multiple of 128 (the channel chunk is
    cut to divide it), the sequence in whole tiles of both kernels, the
    taps within a halo block and the 8 rows their gradient is summed in,
    blocks within `_SHORTCONV_VMEM`."""
    tiles, (d, taps) = SHORTCONV_TILES, conv_weight.shape
    if bcx.ndim != 3 or jnp.dtype(bcx.dtype).itemsize not in (2, 4) \
            or d % 128 or not 2 <= taps <= 8 \
            or any(bcx.shape[1] % tiles[k] for k in ("fwd", "bwd")):
        return None
    if any(_shortconv_blocks(k, tiles[k], d, bcx.dtype) > _SHORTCONV_VMEM
           for k in ("fwd", "bwd")):
        return None
    if not _one_device():
        # XLA does not partition a Mosaic kernel, and no cell spans chips
        return None
    return dict(tiles, channels=_fit(d, tiles["channels"]))


def _record_shortconv_plan(bcx, conv_weight, plan):
    """One `mx.shortconv.plan` span each time the op is traced (as
    `mx.flash.plan`: the plan is a fact of the compiled program)."""
    kept = bcx.size * bcx.dtype.itemsize \
        + conv_weight.size * conv_weight.dtype.itemsize
    with profiler.scope(  # graftlint: disable=JG003
            "mx.shortconv.plan", "shortconv") as span:
        span.args = {
            "shape": list(bcx.shape), "dtype": jnp.dtype(bcx.dtype).name,
            "taps": conv_weight.shape[1],
            "path": "xla" if plan is None else "kernel",
            "seq_tile": plan and {k: plan[k] for k in ("fwd", "bwd")},
            "channel_tile": plan and plan["channels"],
            "halo_rows": plan and _halo_rows(bcx.dtype),
            # what `_gate` keeps for the backward pass: bcx and the taps;
            # on the other path JAX keeps what its derivative of the body
            # asks for
            "residual_bytes": plan and kept}


def _taps_t(conv_weight):
    """The taps as the kernels read them: ``(8, d)`` float32, row j the
    tap of ``u_(t-L+1+j)``."""
    d, taps = conv_weight.shape
    return jnp.zeros((8, d), jnp.float32).at[:taps].set(
        conv_weight.astype(jnp.float32).T)


def _f32(ref, cols):
    return ref[0, :, cols].astype(jnp.float32)


def _chunk(bcx_ref, b_before, x_before, w_ref, lo, d, taps, channels):
    """Channels *lo* .. *lo* + *channels* of a tile, in float32: ``b``,
    ``c``, ``x``; the taps ``w[j]`` as rows; ``u[j]`` = ``u_(t-L+1+j)``, the
    tile's ``u = b * x`` as tap j sees it; and ``conv``, summed in
    `_gate_body`'s order.  ``u`` is zero before the sequence: the rows
    before the first tile of every batch row are zeroed, so packed rows
    never see each other.  A shifted ``u`` is a sublane roll of the halo
    rows and the tile's together and an aligned slice of it: the rows that
    wrap land in the halo's part and are cut off."""
    at = slice(lo, lo + channels)
    b, c, x = (_f32(bcx_ref, slice(k * d + lo, k * d + lo + channels))
               for k in range(3))
    before = jnp.where(pl.program_id(1) > 0,
                       _f32(b_before, at) * _f32(x_before, at), 0.0)
    halo, own = before.shape[0], b * x
    ext = jnp.concatenate([before, own], 0)
    u = [pltpu.roll(ext, back, 0)[halo:] for back in range(taps - 1, 0, -1)]
    u.append(own)
    w = [w_ref[j:j + 1, at] for j in range(taps)]
    return b, c, x, w, u, sum(u[j] * w[j] for j in range(taps))


def _shortconv_fwd_kernel(bcx_ref, b_before, x_before, w_ref, out_ref, *,
                          d, taps, channels):
    """One ``(rows, 3d)`` tile of ``bcx`` to ``(rows, d)`` of ``gated``, a
    channel chunk at a time."""
    for lo in range(0, d, channels):
        _, c, _, _, _, conv = _chunk(bcx_ref, b_before, x_before, w_ref, lo,
                                     d, taps, channels)
        out_ref[0, :, lo:lo + channels] = (c * conv).astype(out_ref.dtype)


def _shortconv_bwd_kernel(bcx_ref, b_before, x_before, c_after, dg_ref,
                          dg_after, w_ref, dbcx_ref, dw_ref, *, d, taps,
                          channels):
    """The tile's ``db``, ``dc``, ``dx`` into the three column blocks of
    ``dbcx``, and its part of the taps' gradient added to *dw_ref*, which
    stays in VMEM over the whole grid.  ``u`` and ``conv`` are computed
    again in float32.  ``du_t = sum_j w_j * dconv_(t+L-1-j)`` looks ahead:
    the rows after the tile come with it, zero past the sequence's end."""
    tile, rows = pl.program_id(1), dg_ref.shape[1]

    @pl.when((pl.program_id(0) == 0) & (tile == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    for lo in range(0, d, channels):
        at = slice(lo, lo + channels)
        b, c, x, w, u, conv = _chunk(bcx_ref, b_before, x_before, w_ref, lo,
                                     d, taps, channels)
        dg = _f32(dg_ref, at)
        dconv = dg * c
        for j in range(taps):
            dw_ref[j:j + 1, at] += jnp.sum(dconv * u[j], 0, keepdims=True)
        after = jnp.where(tile < pl.num_programs(1) - 1,
                          _f32(dg_after, at) * _f32(c_after, at), 0.0)
        ext = jnp.concatenate([dconv, after], 0)
        du = dconv * w[taps - 1] + sum(
            pltpu.roll(ext, ext.shape[0] - ahead, 0)[:rows]
            * w[taps - 1 - ahead] for ahead in range(1, taps))
        for k, grad in enumerate((du * x, dg * conv, du * b)):
            dbcx_ref[0, :, slice(k * d + lo, k * d + lo + channels)] = \
                grad.astype(dbcx_ref.dtype)


def _shortconv_specs(bcx, rows):
    """The index maps both kernels share: a tile, and the halo blocks just
    before and just after it in column block *col* of ``bcx``'s three (the
    sequence's ends clamp to a block that is there; the kernels zero it)."""
    halo = _halo_rows(bcx.dtype)
    per, last = rows // halo, bcx.shape[1] // halo - 1

    def before(col):
        return lambda i, s: (i, jnp.maximum(s * per - 1, 0), col)

    def after(col):
        return lambda i, s: (i, jnp.minimum((s + 1) * per, last), col)

    return halo, (lambda i, s: (i, s, 0)), before, after


_SHORTCONV_STATIC = ("rows", "channels", "interpret")


# jitted, so a step's four layers of one shape share one trace and one
# Mosaic program of each kernel (as the flash wrappers since PR 25)

@functools.partial(jax.jit, static_argnames=_SHORTCONV_STATIC)
def _shortconv_fwd_pallas(bcx, conv_weight, rows, channels, interpret=False):
    (batch, seq, _), (d, taps) = bcx.shape, conv_weight.shape
    halo, tile, before, _ = _shortconv_specs(bcx, rows)
    with jax.named_scope("mx.shortconv.gate"):
        return pl.pallas_call(
            functools.partial(_shortconv_fwd_kernel, d=d, taps=taps,
                              channels=channels),
            grid=(batch, seq // rows),
            in_specs=[pl.BlockSpec((1, rows, 3 * d), tile),
                      pl.BlockSpec((1, halo, d), before(0)),
                      pl.BlockSpec((1, halo, d), before(2)),
                      pl.BlockSpec((8, d), lambda i, s: (0, 0))],
            out_specs=pl.BlockSpec((1, rows, d), tile),
            out_shape=jax.ShapeDtypeStruct((batch, seq, d), bcx.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret, name="mx_shortconv_fwd",
        )(bcx, bcx, bcx, _taps_t(conv_weight))


@functools.partial(jax.jit, static_argnames=_SHORTCONV_STATIC)
def _shortconv_bwd_pallas(bcx, conv_weight, dgated, rows, channels,
                          interpret=False):
    (batch, seq, _), (d, taps) = bcx.shape, conv_weight.shape
    halo, tile, before, after = _shortconv_specs(bcx, rows)
    with jax.named_scope("mx.shortconv.gate"):
        dbcx, dw = pl.pallas_call(
            functools.partial(_shortconv_bwd_kernel, d=d, taps=taps,
                              channels=channels),
            grid=(batch, seq // rows),
            in_specs=[pl.BlockSpec((1, rows, 3 * d), tile),
                      pl.BlockSpec((1, halo, d), before(0)),
                      pl.BlockSpec((1, halo, d), before(2)),
                      pl.BlockSpec((1, halo, d), after(1)),
                      pl.BlockSpec((1, rows, d), tile),
                      pl.BlockSpec((1, halo, d), after(0)),
                      pl.BlockSpec((8, d), lambda i, s: (0, 0))],
            out_specs=[pl.BlockSpec((1, rows, 3 * d), tile),
                       pl.BlockSpec((8, d), lambda i, s: (0, 0))],
            out_shape=[jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
                       jax.ShapeDtypeStruct((8, d), jnp.float32)],
            # the taps' gradient is summed over both axes of the grid
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret, name="mx_shortconv_bwd",
        )(bcx, bcx, bcx, bcx, dgated, dgated, _taps_t(conv_weight))
        return dbcx, dw[:taps].T.astype(conv_weight.dtype)


def _body_backward(bcx, conv_weight, dgated):
    return jax.vjp(_gate_body, bcx, conv_weight)[1](dgated)


def _gate_forward(bcx, conv_weight):
    tiles = _shortconv_plan(bcx, conv_weight)
    return jax.lax.platform_dependent(
        bcx, conv_weight, default=_gate_body,
        tpu=functools.partial(_shortconv_fwd_pallas, rows=tiles["fwd"],
                              channels=tiles["channels"]))


def _gate_backward(kept, dgated):
    tiles = _shortconv_plan(*kept)
    return jax.lax.platform_dependent(
        *kept, dgated, default=_body_backward,
        tpu=functools.partial(_shortconv_bwd_pallas, rows=tiles["bwd"],
                              channels=tiles["channels"]))


@jax.custom_vjp
def _gate_tiled(bcx, conv_weight):
    """`_gate` at a shape the kernels tile: they run where the program is
    lowered for the TPU, `_gate_body` (and JAX's derivative of it, from
    ``bcx`` again) where it is lowered for anything else."""
    return _gate_forward(bcx, conv_weight)


_gate_tiled.defvjp(lambda *a: (_gate_forward(*a), a), _gate_backward)


def _gate(bcx, conv_weight):
    """``gated = C * conv(B * X)`` from ``bcx = [B, C, X]`` ``(..., seq,
    3d)`` and the taps ``(d, L)``.  One pass over ``bcx`` forward and one
    backward where `_shortconv_plan` gives tiles, keeping nothing for the
    backward pass but its two arguments; `_gate_body` where it gives
    none."""
    plan = _shortconv_plan(bcx, conv_weight)
    _record_shortconv_plan(bcx, conv_weight, plan)
    if plan is None:
        return _gate_body(bcx, conv_weight)
    return _gate_tiled(bcx, conv_weight)


@register_op("_contrib_GatedShortConv", aliases=("GatedShortConv",))
def _gated_short_conv(data, in_weight, conv_weight, out_weight):
    """The gated short convolution operator on ``(batch, seq, d)``:
    ``[B, C, X] = split3(x W_in)``, ``u = B * X``, ``c_t = sum_j w_j *
    u_(t-L+1+j)`` (depthwise, causal, ``u`` zero before the sequence of
    every batch row; conv_weight is ``(d, L)``), ``y = (C * c) W_out``.
    Weights as ``FullyConnected`` holds them: in_weight ``(3d, d)``,
    out_weight ``(d, d)``.

    The two projections are XLA's; what lies between them is `_gate`.  On
    the TPU, at ``d`` a multiple of 128 and a sequence in whole tiles
    (`SHORTCONV_TILES`) on one device, it is the kernels
    ``mx_shortconv_fwd`` and ``mx_shortconv_bwd`` (`mx.shortconv.plan` says
    ``path: kernel``), which read ``bcx`` once each way and keep no float32
    array for the backward pass; on every other platform and at every other
    shape it is `_gate_body`, the same arithmetic in `jax.numpy`."""
    with jax.named_scope("mx.shortconv"):
        return _dot(_gate(_dot(data, in_weight), conv_weight), out_weight)


# ---------------------------------------------------------------------------
# A projection's output to normed, turned heads: the RMS norm over each
# head, rotate-half positions and the move from ``(batch, seq, heads x d)``
# to ``(batch, heads, seq, d)`` in one pass over the data each way.  On the
# TPU a pair of Mosaic kernels; everywhere else `_headrope_body`.
# ---------------------------------------------------------------------------

#: rows of the sequence a grid step of each kernel holds, and the most heads
#: it takes at a time (a block of the flat projection is ``heads`` column
#: blocks of ``d``; the head-major array's block is their transpose, which
#: the two index maps make and no copy).  From `tools/headrope_sweep.py` on
#: the v5e at (1, 16384, 32 x 128) bf16, device ms a call (PERF.md section
#: 6, PR 34; 268 MB move forward, 403 backward): whole rows win, forward
#: 0.435 at 256 rows x 32 heads (75% of 819 GB/s), 0.452 at 512 x 8, 0.463
#: at 1024 x 4, 0.486 at 256 x 16, 0.502 at 256 x 8, 0.539 at 2048 x 1,
#: 0.802 at 512 x 1; backward 0.655 at 128 x 32, 0.662 at 256 x 32 (74%),
#: 0.678 at 1024 x 4, 0.696 at 512 x 8, 0.736 at 256 x 8, 0.947 at 512 x
#: 1; 512 x 32 is over the VMEM.  At k's 4 heads 256 rows take 0.088 and
#: 0.112 where 1024 take 0.076 and 0.099: 0.1 ms a step, left.  Not
#: options: the sweep sets them to compare
HEADROPE_TILES = {"fwd": 256, "bwd": 256, "heads": 32}

#: what a kernel's blocks and temporaries (`_headrope_blocks`) may take of
#: the 16 MiB of VMEM that a Mosaic kernel is given on the v5e: the backward
#: kernel's at 256 rows x 32 heads of 128 in bf16 count 13.75
_HEADROPE_VMEM = 15 << 20


def _rotary_tables(seq, d, theta, positions, sections, given=None):
    """``(cos, sin)`` ``(1 or batch, seq, d)`` in float32 from `_rotary`'s
    own arithmetic for rotate-half pairs (float64 angles of ``0 .. seq - 1``
    rounded once; with *positions* ``(axes, batch, seq)`` the operand's, in
    float32, each frequency beside its own axis's position), the sign of
    ``rot = [-x2, x1]`` folded into the sine's first half: ``x * cos +
    roll(x, d / 2) * sin`` is `_rotary`'s ``x * cos + rot * sin`` bit for
    bit.

    With *given* ``(rotary_dim, inv_freq, scale)`` the tables are
    `_rotary_given`'s (`_given_cos_sin`), which turns the first
    ``rotary_dim`` lanes by pairs ``h = rotary_dim / 2`` apart: ``cos`` is 1
    on the lanes that pass through, and where ``rotary_dim < d`` the sine
    comes as two tables, one a direction: ``sin_up`` (``+sin`` on lanes ``h
    .. rotary_dim - 1``, beside ``roll(x, h)``, which brings ``x[j - h]``)
    and ``sin_down`` (``-sin`` on lanes ``0 .. h - 1``, beside ``roll(x, d -
    h)``, which brings ``x[j + h]``), 0 elsewhere.  A turned lane has one
    sine term that is not 0 and a kept lane none: ``x * cos + roll(x, h) *
    sin_up + roll(x, d - h) * sin_down`` is `_rotary_given`, lane for lane.
    Where ``rotary_dim == d`` the two rolls are one and so are their
    tables: the pair above."""
    if given:
        rotary_dim = given[0]
        h, kept = rotary_dim // 2, np.zeros((seq, d - rotary_dim))
        cos, sin = _given_cos_sin(seq, d, *given)
        none = np.zeros((seq, h))
        sines = ([-sin[:, :h], sin[:, h:]],) if rotary_dim == d else (
            [none, sin[:, h:], kept], [-sin[:, :h], none, kept])
        return tuple(jnp.asarray(np.concatenate(t, -1)[None], jnp.float32)
                     for t in ([cos, kept + 1.0], *sines))
    half = d // 2
    sections = tuple(int(n) for n in sections) or (half,)
    inv = 1.0 / (float(theta) ** (np.arange(half, dtype=np.float64) / half))
    if positions is None:
        ang = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
        cos, sin = np.cos(ang), np.sin(ang)
        return (jnp.asarray(np.concatenate([cos, cos], -1)[None], jnp.float32),
                jnp.asarray(np.concatenate([-sin, sin], -1)[None], jnp.float32))
    pos = positions.astype(jnp.float32)
    ang = jnp.concatenate(
        [jnp.broadcast_to(pos[a][..., None], pos.shape[1:] + (n,))
         for a, n in enumerate(sections)], -1) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return (jnp.concatenate([cos, cos], -1),
            jnp.concatenate([-sin, sin], -1))


def _headrope_rolls(d, rotary_dim):
    """The lane distances that bring a pair's other half beside it, one a
    sine table of `_rotary_tables`: a whole head's pairs lie ``d / 2`` apart
    either way round, a part's ``rotary_dim / 2`` up and the rest of ``d``
    down."""
    h = (rotary_dim or d) // 2
    return (h,) if 2 * h == d else (h, d - h)


def _headrope_body(y, gamma, tables, heads, eps, rotary_dim=None):
    """``_rotary(_rms_norm(y by head, gamma).transpose(0, 2, 1, 3))`` from
    the tables (`_rotary_given` over the norm from a part's three), to the
    bit: the norm rounded to the input's dtype, then the rotation rounded
    again."""
    batch, seq, _ = y.shape
    x = _rms_norm(y.reshape(batch, seq, heads, -1), gamma, eps).transpose(
        0, 2, 1, 3).astype(jnp.float32)
    cos, *sines = tables
    rolled = [jnp.roll(x, r, -1)
              for r in _headrope_rolls(x.shape[-1], rotary_dim)]
    out = x * cos[:, None]
    for brought, sin in zip(rolled, sines):
        out = out + brought * sin[:, None]
    return out.astype(y.dtype)


def _one_device():
    """Whether XLA would have nothing to partition here: no mesh of several
    devices around the op, or every axis of it manual (a `shard_map`)."""
    from ..parallel.mesh import current_mesh
    mesh = current_mesh()
    return mesh is None or mesh.size == 1 or set(
        jax.sharding.get_abstract_mesh().manual_axes) == set(mesh.axis_names)


def _headrope_blocks(kernel, rows, heads, d, dtype, tables=2):
    """Bytes of VMEM one grid step of *kernel* takes: its blocks, each held
    twice (the flat and the head-major one, in the backward kernel ``dy``
    too, and the rows of the two or three *tables*), and a head's float32
    temporaries (ten of them live in the backward kernel)."""
    wide = {"fwd": 2, "bwd": 3}[kernel]
    return rows * d * (2 * (wide * heads * jnp.dtype(dtype).itemsize
                            + tables * 4) + 10 * 4)


def _headrope_plan(y, heads, positions=None, sections=(), rotary_dim=None):
    """``(tiles, None)`` where `_head_norm_rotary` takes this projection,
    ``(None, why not)`` where the caller keeps `_rotary` over `_rms_norm`
    (which also says what is wrong with positions or sections it cannot
    turn by).  The kernels take ``(batch, seq, heads x d)`` on one device:
    ``d`` whole 128-lane tiles, the sequence in whole tiles of both
    kernels, a grid step within `_HEADROPE_VMEM` (its tables one more where
    *rotary_dim* is a part of ``d``)."""
    tiles = HEADROPE_TILES
    d = y.shape[-1] // heads
    tables = 1 + len(_headrope_rolls(d, rotary_dim))
    n = len(sections) or 1
    if y.ndim != 3 or jnp.dtype(y.dtype).itemsize not in (2, 4):
        return None, "not (batch, seq, width) in 2 or 4 bytes"
    if d % 128:
        return None, "a head of %d is not whole 128-lane tiles" % d
    if any(y.shape[1] % tiles[k] for k in ("fwd", "bwd")):
        return None, "a sequence of %d is not whole tiles of %d and %d" % (
            y.shape[1], tiles["fwd"], tiles["bwd"])
    if sum(int(s) for s in sections or (d // 2,)) != d // 2 or (
            positions is not None and positions.shape != (n,) + y.shape[:2]):
        return None, "positions or sections `_rotary` refuses"
    # the most heads a grid step can take: a divisor of them, within the
    # sweep's cap and, with both kernels' blocks, within the VMEM budget
    at_once = next((h for h in range(min(heads, tiles["heads"]), 0, -1)
                    if heads % h == 0 and all(
                        _headrope_blocks(k, tiles[k], h, d, y.dtype, tables)
                        <= _HEADROPE_VMEM for k in ("fwd", "bwd"))), None)
    if at_once is None:
        return None, "blocks over the VMEM budget"
    at = dict(tiles, heads=at_once)
    if not _one_device():
        # XLA does not partition a Mosaic kernel, and no cell spans chips
        return None, "a mesh of several devices"
    return at, None


def _record_headrope_plan(y, heads, plan, why, tables, rotary_dim):
    """One `mx.headrope.plan` span each time a projection is handed over
    (as `mx.flash.plan`: the plan is a fact of the compiled program)."""
    with profiler.scope(  # graftlint: disable=JG003
            "mx.headrope.plan", "headrope") as span:
        span.args = {
            "shape": list(y.shape), "dtype": jnp.dtype(y.dtype).name,
            "heads": heads, "head_dim": y.shape[-1] // heads,
            # the lanes of a head that turn: all of them but at given
            # frequencies over a part
            "rotary_dim": rotary_dim,
            "path": "xla" if plan is None else "kernel", "why": why,
            "seq_tile": plan and {k: plan[k] for k in ("fwd", "bwd")},
            "head_tile": plan and plan["heads"],
            # cos and one sine a roll: two, or three where a part turns
            "tables": plan and len(tables),
            "table_bytes": plan and sum(t.size * t.dtype.itemsize
                                        for t in tables),
            # what `_head_norm_rotary` keeps for the backward pass: the
            # projection as the product wrote it, and the norm's scale
            "residual_bytes": plan and y.size * y.dtype.itemsize
            + y.shape[-1] // heads * 4}


def _headrope_fwd_kernel(y_ref, g_ref, *refs, d, eps, rolls):
    """A ``(rows, heads x d)`` block of the projection to ``(heads, rows,
    d)`` of the head-major array, a head at a time: all of it in float32,
    rounded once.  *refs*: cos, a sine table a roll, the block out."""
    *tables, out_ref = refs
    gamma, cos, *sines = [g_ref[...]] + [t[0] for t in tables]
    for h in range(out_ref.shape[1]):
        x = y_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)
        n = x * jax.lax.rsqrt(
            jnp.sum(x * x, -1, keepdims=True) * (1.0 / d) + eps) * gamma
        out = n * cos
        for r, sin in zip(rolls, sines):
            out = out + pltpu.roll(n, r, 1) * sin
        out_ref[0, h] = out.astype(out_ref.dtype)


def _headrope_bwd_kernel(y_ref, g_ref, *refs, d, eps, rolls):
    """The block's ``dy`` into the flat layout and its part of the scale's
    gradient.  The rotation is linear in the normed head, so the cotangent
    turns back by the transposed rolls (``d`` less each distance: a roll by
    ``d / 2`` is its own); the head's ``rsqrt`` is computed again from the
    kept projection.  *refs*: cos, a sine table a roll, ``dout``, then the
    two blocks out."""
    *tables, dout_ref, dy_ref, dg_ref = refs
    gamma, cos, *sines = [g_ref[...]] + [t[0] for t in tables]
    dgamma = jnp.zeros_like(gamma)
    for h in range(dout_ref.shape[1]):
        x = y_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)
        dout = dout_ref[0, h].astype(jnp.float32)
        r = jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) * (1.0 / d) + eps)
        xh = x * r
        dn = dout * cos
        for distance, sin in zip(rolls, sines):
            dn = dn + pltpu.roll(dout * sin, d - distance, 1)
        dgamma += jnp.sum(dn * xh, 0, keepdims=True)
        dxh = dn * gamma
        dy_ref[0, :, h * d:(h + 1) * d] = (r * (dxh - xh * (
            jnp.sum(dxh * xh, -1, keepdims=True) * (1.0 / d)))).astype(
                dy_ref.dtype)
    dg_ref[0] = dgamma


def _headrope_specs(y, tables, heads, rows, at_once):
    """The grid (batch, sequence tile, group of heads, the heads innermost
    so that a tile's rows of the tables are fetched once) and the block of
    each operand: flat, head-major, the scale, each table."""
    batch, seq, width = y.shape
    d = width // heads
    per_row = tables[0].shape[0] > 1
    return (batch, seq // rows, heads // at_once), d, (
        pl.BlockSpec((1, rows, at_once * d), lambda b, s, h: (b, s, h)),
        pl.BlockSpec((1, at_once, rows, d), lambda b, s, h: (b, h, s, 0)),
        pl.BlockSpec((1, d), lambda b, s, h: (0, 0)),
        [pl.BlockSpec((1, rows, d),
                      lambda b, s, h: (b if per_row else 0, s, 0))
         for _ in tables])


_HEADROPE_STATIC = ("heads", "eps", "rows", "at_once", "rotary_dim",
                    "interpret")


# jitted, so q's and k's calls of a step's four layers share two traces
# and two Mosaic programs of each kernel

@functools.partial(jax.jit, static_argnames=_HEADROPE_STATIC)
def _headrope_fwd_pallas(y, gamma, tables, heads, eps, rows, at_once,
                         rotary_dim=None, interpret=False):
    grid, d, (flat, by_head, scale, table) = _headrope_specs(
        y, tables, heads, rows, at_once)
    with jax.named_scope("mx.headrope"):
        return pl.pallas_call(
            functools.partial(_headrope_fwd_kernel, d=d, eps=eps,
                              rolls=_headrope_rolls(d, rotary_dim)),
            grid=grid, in_specs=[flat, scale, *table],
            out_specs=by_head,
            out_shape=jax.ShapeDtypeStruct(
                (y.shape[0], heads, y.shape[1], d), y.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",) * 3),
            interpret=interpret, name="mx_headrope_fwd",
        )(y, gamma.astype(jnp.float32).reshape(1, d), *tables)


@functools.partial(jax.jit, static_argnames=_HEADROPE_STATIC)
def _headrope_bwd_pallas(y, gamma, tables, dout, heads, eps, rows, at_once,
                         rotary_dim=None, interpret=False):
    grid, d, (flat, by_head, scale, table) = _headrope_specs(
        y, tables, heads, rows, at_once)
    with jax.named_scope("mx.headrope"):
        dy, dgamma = pl.pallas_call(
            functools.partial(_headrope_bwd_kernel, d=d, eps=eps,
                              rolls=_headrope_rolls(d, rotary_dim)),
            grid=grid, in_specs=[flat, scale, *table, by_head],
            out_specs=[flat, pl.BlockSpec(
                (1, 1, d), lambda b, s, h: (
                    (b * grid[1] + s) * grid[2] + h, 0, 0))],
            out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                       jax.ShapeDtypeStruct((math.prod(grid), 1, d),
                                            jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",) * 3),
            interpret=interpret, name="mx_headrope_bwd",
        )(y, gamma.astype(jnp.float32).reshape(1, d), *tables, dout)
        return dy, jnp.sum(dgamma, (0, 1)).astype(gamma.dtype)


def _headrope_body_backward(y, gamma, tables, dout, heads, eps,
                            rotary_dim=None):
    return jax.vjp(functools.partial(_headrope_body, heads=heads, eps=eps,
                                     rotary_dim=rotary_dim),
                   y, gamma, tables)[1](dout)[:2]


def _headrope_forward(y, gamma, tables, heads, eps, rotary_dim):
    tiles, _ = _headrope_plan(y, heads, rotary_dim=rotary_dim)
    return jax.lax.platform_dependent(
        y, gamma, tables,
        default=functools.partial(_headrope_body, heads=heads, eps=eps,
                                  rotary_dim=rotary_dim),
        tpu=functools.partial(_headrope_fwd_pallas, heads=heads, eps=eps,
                              rows=tiles["fwd"], at_once=tiles["heads"],
                              rotary_dim=rotary_dim))


def _headrope_backward(heads, eps, rotary_dim, kept, dout):
    y, gamma, tables = kept
    tiles, _ = _headrope_plan(y, heads, rotary_dim=rotary_dim)
    dy, dgamma = jax.lax.platform_dependent(
        y, gamma, tables, dout,
        default=functools.partial(_headrope_body_backward, heads=heads,
                                  eps=eps, rotary_dim=rotary_dim),
        tpu=functools.partial(_headrope_bwd_pallas, heads=heads, eps=eps,
                              rows=tiles["bwd"], at_once=tiles["heads"],
                              rotary_dim=rotary_dim))
    # the tables are positions, which are data: nothing flows back to them
    return dy, dgamma, tuple(jnp.zeros_like(t) for t in tables)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _head_norm_rotary(y, gamma, tables, heads, eps, rotary_dim=None):
    """A projection's output ``(batch, seq, heads x d)`` as the product
    wrote it to ``(batch, heads, seq, d)``, each head normed (RMS over
    ``d``, scaled by *gamma*) and turned by the *tables* of `_rotary_tables`
    (two, or the three that turn *rotary_dim* of ``d``), at a shape
    `_headrope_plan` gives tiles for.  Where the program is lowered for the
    TPU the kernels ``mx_headrope_fwd`` and ``mx_headrope_bwd`` read every
    element once and write it once each way: nothing in float32 leaves the
    chip, the result is rounded once, and the projection alone is kept for
    the backward pass.  Lowered for anything else it is `_headrope_body`
    and JAX's derivative of it (from ``y`` again)."""
    return _headrope_forward(y, gamma, tables, heads, eps, rotary_dim)


_head_norm_rotary.defvjp(
    lambda y, gamma, tables, heads, eps, rotary_dim: (
        _headrope_forward(y, gamma, tables, heads, eps, rotary_dim),
        (y, gamma, tables)),
    _headrope_backward)


def _norm_turn_by_head(y, heads, gamma, positions, sections, theta, eps,
                       tables=None, given=None):
    """A projection's output ``(batch, seq, heads x d)`` to ``(batch,
    heads, seq, d)``, each head normed by *gamma* and turned by *positions*
    (``(axes, batch, seq)`` dealt by *sections*; a text's where None): where
    `_headrope_plan` gives tiles `_head_norm_rotary`, at every other input
    `_rotary` over `_rms_norm`; `mx.headrope.plan` says which, and why.  A
    caller that turns several projections by the same angles (an op's q and
    k) hands each call the same list *tables*: the first that takes the
    kernels builds them into it.  *given* ``(rotary_dim, inv_freq, scale)``
    turns a part of each head at given frequencies: the same kernels by two
    rolls and a third table where the part is less than the head, and
    `_rotary_given` over `_rms_norm` where the plan refuses."""
    batch, seq, _ = y.shape
    d = y.shape[-1] // heads
    tables = [] if tables is None else tables
    if given and positions is not None:
        raise ValueError("given frequencies turn by a text's positions; "
                         "positions as an operand are not built")
    rotary_dim = given[0] if given else d
    plan, why = _headrope_plan(y, heads, positions, sections, rotary_dim)
    if plan and not tables:
        tables.extend(jax.lax.stop_gradient(t) for t in _rotary_tables(
            seq, d, theta, positions, sections, given))
    if given and not plan:
        why = "rotary over %d of a head's %d at given frequencies: %s" % (
            rotary_dim, d, why)
    _record_headrope_plan(y, heads, plan, why, tables, rotary_dim)
    if plan:
        return _head_norm_rotary(y, gamma, tuple(tables), heads, float(eps),
                                 rotary_dim)
    normed = _rms_norm(y.reshape(batch, seq, heads, d), gamma,
                       eps).transpose(0, 2, 1, 3)
    if given:
        return _rotary_given(normed, *given)
    return _rotary(normed, float(theta), False, positions, sections)


def _norm_by_width_or_unturned(y, heads, gamma, norm_over, rotary, positions,
                               theta, eps, given):
    """`_contrib_HeadNormRotary`'s two departures, in XLA: an RMS norm over
    each head (*gamma* ``(d,)``) or over the whole width (*gamma* ``(heads x
    d,)``), the move to ``(batch, heads, seq, d)``, and the rotary positions
    where the layer has them."""
    batch, seq, width = y.shape
    d = width // heads
    why = " and ".join(
        ["one norm over the whole width of %d, not a head's %d" % (width, d)]
        * (norm_over == "width") + ["no rotary positions: nothing to turn"]
        * (not rotary))
    _record_headrope_plan(y, heads, None, why, (), d if rotary else 0)
    if norm_over == "width":
        normed = _rms_norm(y, gamma, eps).reshape(batch, seq, heads, d)
    else:
        normed = _rms_norm(y.reshape(batch, seq, heads, d), gamma, eps)
    normed = normed.transpose(0, 2, 1, 3)
    if not rotary:
        return normed
    if given:
        return _rotary_given(normed, *given)
    return _rotary(normed, float(theta), False, positions, ())


@register_op("_contrib_HeadNormRotary", aliases=("HeadNormRotary",),
             input_names=("data", "gamma", "positions"))
def _head_norm_rotary_op(data, gamma, *positions, num_heads=1,
                         theta=10000.0, eps=1e-5, use_positions=False,
                         rotary_dim=0, inv_freq=(), table_scale=1.0,
                         norm_over="head", rotary=True):
    """A q or k projection's output ``(batch, seq, num_heads x d)`` to the
    attention's ``(batch, num_heads, seq, d)``: ``_contrib_RMSNorm`` over
    each head by *gamma* ``(d,)``, then ``_contrib_RotaryEmbedding``
    (rotate-half; *positions* ``(1, batch, seq)`` is an input with
    ``use_positions``, and the positions are ``0 .. seq - 1`` without), as
    `_norm_turn_by_head` computes them.  With *inv_freq* the rotary
    positions turn the first *rotary_dim* of each head alone (all of it
    where 0), at those frequencies and not *theta*'s, cos and sin times
    *table_scale* (`rope_frequencies` makes the three from a published
    ``rope_parameters`` entry); without it this is the op it was.

    Two departures, neither of which the kernels serve (`mx.headrope.plan`
    says ``path`` ``xla`` and why): *norm_over* ``"width"`` norms the whole
    ``num_heads x d`` of a token at once by a *gamma* that wide, and
    *rotary* false leaves the heads unturned (no positions at all)."""
    given = None
    if inv_freq:
        d = data.shape[-1] // int(num_heads)
        given = (int(rotary_dim) or d, tuple(float(f) for f in inv_freq),
                 float(table_scale))
    positions = positions[0] if positions else None
    if norm_over == "width" or not rotary:
        return _norm_by_width_or_unturned(
            data, int(num_heads), gamma, norm_over, bool(rotary), positions,
            theta, eps, given)
    return _norm_turn_by_head(data, int(num_heads), gamma, positions, (),
                              theta, eps, given=given)


# ---------------------------------------------------------------------------
# The routed expert layer.
# ---------------------------------------------------------------------------

#: the grouped products on the TPU: "megablox" (the installed JAX's Pallas
#: kernels) or "ragged" (XLA's ragged dot, which every other platform
#: takes), and the kernels' tiles (m, k, n); from `tools/moe_sweep.py` on
#: the v5e at 65536 rows of which 19 k are routed, 2048 x 1792 (PERF.md
#: section 6, PR 26).  Not options: the sweep sets them to compare
GROUPED_PATH = "megablox"
GROUPED_TILES = (512, 1024, 1024)

#: rows of the pair buffer over the pairs that uniform routing would land
#: on the held experts.  The benchmark's cell lands 1.17 to 1.20 times
#: those in every routed layer (PERF.md section 6, PR 26 and PR 27); a
#: quarter is to spare, and a step with more pairs than rows runs at the
#: worst case and drops nothing.  Not an option either
BUFFER_FACTOR = 1.5


def _route(x, router_weight, bias, top_k, norm_topk_prob, scaling,
           scoring_func="sigmoid"):
    """Scores over all the router's experts, the top-k, and each chosen
    expert's weight, all in float32: ``(chosen (N, k) int32, weights (N,
    k))``.  ``sigmoid``: the top-k of score + bias, a chosen score over the
    chosen scores' sum + 1e-6, times *scaling*.  ``softmax``: a softmax over
    all the experts, its top-k, a chosen gate over the chosen gates' sum;
    no bias and no scaling."""
    logits = jnp.dot(
        x.astype(jnp.float32), router_weight.astype(jnp.float32).T,
        precision=jax.lax.Precision.HIGHEST)
    if scoring_func == "softmax":
        if any(bias) or scaling != 1.0:
            raise ValueError("a softmax router takes no expert_bias and no "
                             "routed_scaling_factor")
        weights, chosen = jax.lax.top_k(jax.nn.softmax(logits, -1), top_k)
        if norm_topk_prob:
            weights = weights / jnp.sum(weights, -1, keepdims=True)
        return chosen.astype(jnp.int32), weights
    if scoring_func != "sigmoid":
        raise ValueError("scoring_func %r is not sigmoid or softmax"
                         % (scoring_func,))
    scores = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(scores + jnp.asarray(bias, jnp.float32), top_k)
    weights = jnp.take_along_axis(scores, chosen, axis=1)
    if norm_topk_prob:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-6)
    return chosen.astype(jnp.int32), weights * scaling


def routed_expert_counts(chosen, num_router_experts, first_expert, held,
                         buffer_rows):
    """What the counters are folded from, as one int32 row: the tokens
    assigned to each of the router's experts, then the pairs that landed
    on a held expert, the tokens with no held expert among their choices,
    the rows the pair buffer ran at (*buffer_rows* where the pairs fit
    them, every choice of every token where not) and whether it was the
    worst case for want of rows (`_at_buffer` decides on the same count).
    """
    load = jnp.sum(chosen.reshape(-1, 1) == jnp.arange(num_router_experts),
                   axis=0, dtype=jnp.int32)
    local = (chosen >= first_expert) & (chosen < first_expert + held)
    pairs = jnp.sum(local, dtype=jnp.int32)
    overflowed = pairs > buffer_rows
    return jnp.concatenate([load, jnp.stack([
        pairs, jnp.sum(~jnp.any(local, axis=1), dtype=jnp.int32),
        jnp.where(overflowed, chosen.size, buffer_rows),
        overflowed]).astype(jnp.int32)])


def _fold_expert_counts(rows):
    """One step's rows (one per routed layer) into the counters; runs on
    the host, where `profiler.fold_step_stats` is called."""
    rows = np.asarray(rows).reshape(-1, rows.shape[-1])
    load = rows[:, :-4].astype(np.float64)
    pairs, without, ran_at, overflowed = rows[:, -4:].sum(axis=0)
    profiler.bump_counter("moe_stat_steps_total")
    profiler.bump_counter("moe_stat_layers_total", len(rows))
    profiler.bump_counter("moe_assignments_total", int(load.sum()))
    profiler.bump_counter("moe_local_assignments_total", int(pairs))
    profiler.bump_counter("moe_tokens_without_local_expert_total",
                          int(without))
    profiler.bump_counter("moe_buffer_rows_total", int(ran_at))
    profiler.bump_counter("moe_worst_case_buffer_layers_total",
                          int(overflowed))
    profiler.bump_counter(
        "moe_expert_load_max_over_mean_sum",
        float((load.max(axis=1) / np.maximum(load.mean(axis=1), 1e-30)
               ).sum()))


def _ragged(lhs, rhs, sizes, transpose_rhs=False):
    """Rows of *lhs* in groups of *sizes* against ``rhs[group]``."""
    if transpose_rhs:
        rhs = rhs.swapaxes(1, 2)
    return jax.lax.ragged_dot(
        lhs, rhs, sizes, precision=matmul_precision(lhs.dtype, rhs.dtype),
        preferred_element_type=jnp.float32).astype(lhs.dtype)


def _ragged_t(lhs, grad, sizes, dtype):
    """``lhs[group].T @ grad[group]`` for each group: ``(groups, k, n)``."""
    dims = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
    return jax.lax.ragged_dot_general(
        lhs, grad, sizes, dims,
        precision=matmul_precision(lhs.dtype, grad.dtype),
        preferred_element_type=jnp.float32).astype(dtype)


def _megablox_backend():
    # the package's own `gmm` attribute is its custom-vjp function and
    # hides the module of the two kernels
    import importlib
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _fit(size, cap):
    """The largest multiple of 128 up to *cap* that divides *size*; the
    whole of a *size* that has none (the tests' small shapes)."""
    for tile in range(min(cap, size) // 128 * 128, 0, -128):
        if size % tile == 0:
            return tile
    return size


def _tiles(caps, m, k, n):
    """The tiles *caps* (`GROUPED_TILES`) cut to the problem: every tile
    divides its dimension (1792 = 7 x 256 takes 896 under a cap of
    1024)."""
    return tuple(_fit(size, cap) for size, cap in zip((m, k, n), caps))


def _megablox(lhs, rhs, sizes, caps, transpose_rhs=False):
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    return _megablox_backend().gmm(
        lhs, rhs, sizes, lhs.dtype, _tiles(caps, m, k, n),
        transpose_rhs=transpose_rhs)


def _megablox_t(lhs, grad, sizes, caps, dtype):
    m, k = lhs.shape
    return _megablox_backend().tgmm(
        lhs.swapaxes(0, 1), grad, sizes, dtype,
        _tiles(caps, m, k, grad.shape[1]))


# *grouped* below is ``(path, tiles)``: `GROUPED_PATH` and `GROUPED_TILES`
# as `_at_buffer` read them, or "ragged" where it says so

def _product(grouped, lhs, rhs, sizes, transpose_rhs=False):
    """Rows of *lhs* in groups of *sizes* against ``rhs[group]`` (against
    its transpose with *transpose_rhs*).  The kernels exist for the TPU
    alone; every other platform takes XLA's ragged dot."""
    path, caps = grouped
    if path == "ragged":
        return _ragged(lhs, rhs, sizes, transpose_rhs)
    return jax.lax.platform_dependent(
        lhs, rhs, sizes,
        tpu=functools.partial(_megablox, caps=caps,
                              transpose_rhs=transpose_rhs),
        default=functools.partial(_ragged, transpose_rhs=transpose_rhs))


def _product_t(grouped, lhs, grad, sizes, dtype):
    """``lhs[group].T @ grad[group]`` for each group."""
    path, caps = grouped
    if path == "ragged":
        return _ragged_t(lhs, grad, sizes, dtype)
    return jax.lax.platform_dependent(
        lhs, grad, sizes,
        tpu=functools.partial(_megablox_t, caps=caps, dtype=dtype),
        default=functools.partial(_ragged_t, dtype=dtype))


def _expert_hidden(grouped, xs, w1, w3, sizes):
    h = _product(grouped, xs, w1, sizes)
    g = _product(grouped, xs, w3, sizes)
    return h, g, _silu_mul(h, g)


# Rows of the pair buffer beyond the pairs that exist belong to no group.
# The grouped products neither read nor write them (the kernels leave
# them uninitialised, XLA's ragged dot zeroes them), so nothing below may
# use such a row as a number.  `_sum_pairs` gathers a row a choice and
# selects it by whether the pair exists.  `_combine_rows` multiplies: a
# token's row of its 0/1 (or weight) matrix times a chunk of rows, and 0
# times what is not a number is not 0, so the kernel first selects every
# row of a chunk past the last existing pair to 0, and only then takes
# the product.

def _sum_pairs(rows, place, exists, weights=None):
    """For each token, the sum over its pairs that exist of ``rows[place
    of the pair]`` (times the pair's weight), in float32.  One row gather
    a choice: a ``(tokens, k, d)`` array would be laid out with its k
    padded to a whole sublane tile.  The body every platform but the TPU
    takes, the worst-case buffer's, and what `_combine_rows` is held to."""
    total = 0.0
    for k in range(place.shape[1]):
        row = jnp.where(exists[:, k, None],
                        jnp.take(rows, place[:, k], axis=0), 0
                        ).astype(jnp.float32)
        total = total + (row if weights is None
                         else row * weights[:, k, None])
    return total


def _places(inverse, sizes, top_k):
    """Where each token's pairs landed ``(tokens, k)``, and which of them
    exist here (landed among the held experts' rows)."""
    place = inverse.reshape(-1, top_k)
    exists = place < jnp.sum(sizes)
    return jnp.where(exists, place, 0), exists


#: the combine's pass over the pairs that exist (`_combine_rows`): (tokens
#: a tile, buffer rows a chunk), and the least ``tokens x top_k /
#: buffer_rows`` (the rows `_sum_pairs` gathers over the rows that can
#: hold a pair) from which the pass is taken; from `tools/moe_sweep.py
#: --combine` on the v5e (docs/PERF_NOTES.md, PR 46): a layer's two
#: directions take 2.28 ms where the gathers take 3.61 at 5.33 (16384
#: tokens x 8 over 24576 rows), 0.49 for 1.42 at 8192 x 6 over 9216, 0.13
#: for 1.49 at 20, and 2.26 for 2.05 at 2.67 (16384 x 4 over the same
#: 24576 rows, which the gathers read from on-chip memory).  Not options:
#: the sweep sets them to compare
COMBINE_TILES = (128, 128)
COMBINE_RATIO = 4.0

#: a token-ordered row's code is its token times this, plus its choice
_COMBINE_CHOICES = 16


def _combine_plan(tokens, top_k, rows, d, dtype):
    """``(tiles, None)`` where the combine takes `_combine_rows` at a pair
    buffer of *rows* rows, ``(None, why not)`` where it stays `_sum_pairs`:
    the kernel takes 2-byte rows of whole 128-lane tiles on one device,
    the tokens and the buffer in whole tiles, and pays where `_sum_pairs`
    gathers `COMBINE_RATIO` rows or more for each row that can hold a
    pair (at the worst case's buffer that is 1: every pair can exist)."""
    tile, chunk = COMBINE_TILES
    if tokens * top_k < COMBINE_RATIO * rows:
        return None, "%d x %d pairs gathered for %d rows is %.2f, under " \
            "%g: the gather a choice reads no more" % (
                tokens, top_k, rows, tokens * top_k / rows, COMBINE_RATIO)
    if jnp.dtype(dtype).itemsize != 2 or d % 128:
        return None, "not 2-byte rows of whole 128-lane tiles"
    if top_k > _COMBINE_CHOICES:
        return None, "%d experts a token, over the %d a row's code holds" % (
            top_k, _COMBINE_CHOICES)
    if tokens % tile or rows % chunk:
        return None, "%d tokens and %d rows are not whole tiles of %d " \
            "and %d" % (tokens, rows, tile, chunk)
    if not _one_device():
        # XLA does not partition a Mosaic kernel, and no cell spans chips
        return None, "a mesh of several devices"
    return COMBINE_TILES, None


def _token_order(order, inverse, sizes, top_k, rows, tiles):
    """The pair buffer's *rows* rows in token order, and the work list of
    `_combine_rows` over them, as int32 arrays: integer work on ``tokens x
    top_k`` elements, once a layer for both directions.

    A pair's id is ``token * top_k + choice``, so the pairs that exist,
    sorted by id, are sorted by token: ``perm`` is the buffer row of the
    j-th of them (a permutation of the buffer's rows: those that hold no
    pair come last) and ``code`` says whose it is, ``token *
    _COMBINE_CHOICES + choice``, a chunk a row of it; a row with no pair
    has the token after the last, which no tile holds.  A tile of tokens
    owns a contiguous run of the existing pairs; it reads the whole chunks
    that cover its run, one work item a chunk and one for a tile with no
    pair: ``(tile, chunk, flags)`` of each item, tiles in order (flags: 1
    the tile's first item, 2 a chunk to add, 4 its last), the items past
    the last tile's doing nothing.  At most a chunk a tile is read twice,
    so tiles + chunks items always do.  Last, the buffer rows those chunks
    cover (`moe_combine_rows_read_total`)."""
    tile, chunk = tiles
    pairs = inverse.shape[0]
    n_tiles, n_chunks = pairs // top_k // tile, rows // chunk
    total = jnp.sum(sizes).astype(jnp.int32)
    at = jnp.arange(rows, dtype=jnp.int32)
    pair, perm = jax.lax.sort(
        (jnp.where(at < total, order[:rows], pairs), at), num_keys=1)
    code = (pair // top_k * _COMBINE_CHOICES + pair % top_k).reshape(
        n_chunks, 1, chunk)
    held = jnp.sum((inverse < total).reshape(n_tiles, -1), axis=1,
                   dtype=jnp.int32)
    end = jnp.cumsum(held)
    first = (end - held) // chunk
    count = jnp.where(held > 0, -(-end // chunk) - first, 0)
    items = jnp.maximum(count, 1)
    before = jnp.cumsum(items) - items
    k = jnp.arange(n_tiles + n_chunks, dtype=jnp.int32)
    of = jnp.sum(before[None, :] <= k[:, None], axis=1, dtype=jnp.int32) - 1
    nth = k - before[of]
    live = nth < count[of]
    flags = (nth == 0) + 2 * live + 4 * (nth == items[of] - 1)
    # an item that adds nothing stays on the chunk before it: no fetch
    reads = jax.lax.cummax(jnp.where(live, first[of] + nth, 0))
    return (perm, code, of, reads, flags.astype(jnp.int32),
            total[None]), chunk * jnp.sum(count)


def _combine_kernel(tile_ref, chunk_ref, flag_ref, total_ref, code_ref,
                    *refs):
    """One work item of `_token_order`: a chunk of the token-ordered rows
    into its tile's float32 accumulator, as (token of the tile x row of
    the chunk) times the chunk on the MXU.  The matrix holds 1 where the
    row is that token's, or with *weights* (the tile's ``(tokens, k)``)
    the weight of the token's choice that the row is, as three bfloat16
    pieces in three passes: a piece times a bfloat16 row is exact in
    float32, so the weight is applied and the sum held in float32.  Rows
    past the last existing pair are selected to 0 first (they are not
    numbers).  *refs*: the weights (forward), the chunk, the tile out, the
    accumulator."""
    *weights, rows_ref, out_ref, acc_ref = refs
    k = pl.program_id(0)
    flags = flag_ref[k]
    tile, chunk = out_ref.shape[0], rows_ref.shape[0]

    @pl.when(flags & 1 != 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def add(past):
        """The chunk into the accumulator, its last *past* rows (those
        beyond the last existing pair) selected to 0 first."""
        code = code_ref[0]
        mine = code // _COMBINE_CHOICES - tile_ref[k] * tile == \
            jax.lax.broadcasted_iota(jnp.int32, (tile, chunk), 0)
        rows = rows_ref[...]
        if past is not None:
            row = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
            rows = jnp.where(row < chunk - past, rows, jnp.zeros_like(rows))
        if not weights:
            acc_ref[...] += jnp.dot(mine.astype(rows.dtype), rows,
                                    preferred_element_type=jnp.float32)
            return
        choice, rest = code % _COMBINE_CHOICES, 0.0
        for c in range(weights[0].shape[1]):
            rest = rest + jnp.where(mine & (choice == c),
                                    weights[0][:, c:c + 1], 0.0)
        pieces = []
        for _ in range(3):
            pieces.append(rest.astype(rows.dtype))
            rest = rest - pieces[-1].astype(jnp.float32)
        # one on top of the other: the MXU loads a block of the chunk
        # once for the three
        part = jnp.dot(jnp.concatenate(pieces, axis=0), rows,
                       preferred_element_type=jnp.float32)
        acc_ref[...] += part[:tile] + part[tile:2 * tile] + part[2 * tile:]

    # rows of this chunk past the last existing pair (one chunk has any)
    past = (chunk_ref[k] + 1) * chunk - total_ref[0]

    @pl.when((flags & 2 != 0) & (past <= 0))
    def _():
        add(None)

    @pl.when((flags & 2 != 0) & (past > 0))
    def _():
        add(past)

    @pl.when(flags & 4 != 0)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _combine_rows(rows, run, *weights, tokens, tiles, interpret=False):
    """`_sum_pairs` over the pairs that exist, each read once: the buffer's
    *rows* gathered into token order (one row gather; *run* is
    `_token_order`'s), then the Mosaic kernel ``mx_moe_combine`` over the
    work list, each tile of tokens written once, in the rows' dtype.  With
    *weights* ``(tokens, top_k)`` in float32 a pair's row times its weight
    (the forward pass); without, the plain sum (the backward pass)."""
    perm, code, of, reads, flags, total = run
    (tile, chunk), d = tiles, rows.shape[1]
    by_tile = [pl.BlockSpec((tile, w.shape[1]), lambda k, of, *_: (of[k], 0))
               for w in weights]
    return pl.pallas_call(
        _combine_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(of.shape[0],),
            in_specs=[pl.BlockSpec((1, 1, chunk), lambda k, of, reads, *_: (
                reads[k], 0, 0))] + by_tile + [pl.BlockSpec(
                    (chunk, d), lambda k, of, reads, *_: (reads[k], 0))],
            out_specs=pl.BlockSpec(
                (tile, d), lambda k, of, *_: (of[k], 0)),
            scratch_shapes=[pltpu.VMEM((tile, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((tokens, d), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="mx_moe_combine",
    )(of, reads, flags, total, code, *weights,
      # a permutation: every index is a row of the buffer, once
      rows.at[perm].get(mode="promise_in_bounds", unique_indices=True))


def _combine(combine, rows, top_k, inverse, sizes, run, *weights):
    """For each token the sum over its pairs that exist of their *rows*
    (times the pair's weight with *weights*), held in float32 and rounded
    once to the rows' dtype: `_combine_rows` at the tiles *combine* where
    the program is lowered for the TPU, `_sum_pairs` on every other
    platform, and where `_combine_plan` gave no tiles."""
    def gathers(rows, *weights):
        return _sum_pairs(rows, *_places(inverse, sizes, top_k),
                          *weights).astype(rows.dtype)

    if combine is None:
        return gathers(rows, *weights)
    return jax.lax.platform_dependent(
        rows, run, *weights,
        tpu=functools.partial(_combine_rows, tiles=combine,
                              tokens=inverse.shape[0] // top_k),
        default=lambda rows, run, *weights: gathers(rows, *weights))


def _buffer_rows(tokens, top_k, held, router_experts):
    """Rows of the pair buffer: `BUFFER_FACTOR` times the pairs uniform
    routing lands on the held experts, in whole row tiles, and never more
    than the worst case (every choice of every token held here), which it
    is where all the router's experts are held or the shape is smaller
    than a tile."""
    worst, tile = tokens * top_k, GROUPED_TILES[0]
    expected = math.ceil(BUFFER_FACTOR * worst * held / router_experts)
    return min(worst, -(-expected // tile) * tile)


def _at_buffer(rows, combine, body, order, sizes, *rest):
    """``body(n, grouped, combine, order, sizes, *rest)`` at a pair buffer
    of ``n`` = *rows* rows where the pairs that exist fit them, and at the
    worst case's where they do not: the same pairs in the same groups from
    row 0 either way, so nothing is dropped.  Where *rows* is the worst
    case there is one path and no `cond`.  Called from inside the custom
    VJP's two sides, so JAX differentiates neither branch and each keeps
    its temporaries to itself.

    The worst-case branch of a `cond` takes XLA's ragged dot on every
    platform: with the kernels in both branches a step holds twice the
    Mosaic programs, and tracing, lowering and loading the second set
    cost a set-up 3 s of 31 where no step of the benchmark ever runs
    them (PERF.md section 6, PR 27).  A step that overflows takes half
    as long again in this layer and sums in XLA's order, not the
    kernels'.  For the same reason, and because at the worst case every
    pair can exist, that branch combines by `_sum_pairs` (*combine*, the
    tiles of `_combine_plan` at *rows*, is the bounded branch's alone)."""
    worst, grouped = order.shape[0], (GROUPED_PATH, GROUPED_TILES)
    if rows == worst:
        return body(worst, grouped, combine, order, sizes, *rest)
    return jax.lax.cond(
        jnp.sum(sizes) <= rows,
        functools.partial(body, rows, grouped, combine),
        functools.partial(body, worst, ("ragged", None), None), order,
        sizes, *rest)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _experts(rows, combine, x, weights, order, inverse, sizes, run, w1, w3,
             w2):
    """The held experts' part of the result for tokens *x* ``(N, d)``
    with pair weights *weights* ``(N, k)``: *order* lists the pairs
    (token * k + choice) sorted by held expert, those on no held expert
    last; *inverse* is where each pair landed; *sizes* the pairs of each
    held expert; *rows* the pair buffer's rows (`_buffer_rows`); *combine*
    the tiles of `_combine_plan` with *run* from `_token_order`, or None
    and ()."""
    return _experts_fwd(rows, combine, x, weights, order, inverse, sizes,
                        run, w1, w3, w2)[0]


# The two bodies are jitted, so a step's routed layers of one shape share
# a trace of each.  On the chip that left set-up where it was and the step
# 3.7% faster: XLA places the same operations otherwise (PERF.md section
# 6, PR 27)

@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _forward_at(n, grouped, combine, order, sizes, x, weights, inverse, run,
                w1, w3, w2):
    top_k = weights.shape[1]
    with jax.named_scope("mx.moe.dispatch"):
        xs = jnp.take(x, order[:n] // top_k, axis=0)
    with jax.named_scope("mx.moe.experts"):
        a = _expert_hidden(grouped, xs, w1, w3, sizes)[2]
        y = _product(grouped, a, w2, sizes)
    with jax.named_scope("mx.moe.combine"):
        return _combine(combine, y, top_k, inverse, sizes, run, weights)


def _experts_fwd(rows, combine, x, weights, order, inverse, sizes, run, w1,
                 w3, w2):
    out = _at_buffer(rows, combine, _forward_at, order, sizes, x, weights,
                     inverse, run, w1, w3, w2)
    return out, (x, weights, order, inverse, sizes, run, w1, w3, w2)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _backward_at(n, grouped, combine, order, sizes, x, weights, inverse,
                 run, w1, w3, w2, dout):
    top_k = weights.shape[1]
    order = order[:n]
    w_sorted = jnp.take(weights.reshape(-1), order)[:, None]
    with jax.named_scope("mx.moe.dispatch"):
        token = order // top_k
        xs = jnp.take(x, token, axis=0)
        dos = jnp.take(dout, token, axis=0)
    with jax.named_scope("mx.moe.experts"):
        h, g, a = _expert_hidden(grouped, xs, w1, w3, sizes)
        # d out / d (a W2) without the pair's weight: the weight's own
        # gradient is its dot with a, the hidden state's is it weighted
        gu = _product(grouped, dos, w2, sizes, True).astype(jnp.float32)
        dw_sorted = jnp.sum(gu * a.astype(jnp.float32), -1, keepdims=True)
        dy = (dos.astype(jnp.float32) * w_sorted).astype(dos.dtype)
        dw2 = _product_t(grouped, a, dy, sizes, w2.dtype)
        da = gu * w_sorted
        h32, g32 = h.astype(jnp.float32), g.astype(jnp.float32)
        sig = jax.nn.sigmoid(h32)
        dh = (da * g32 * sig * (1 + h32 * (1 - sig))).astype(x.dtype)
        dg = (da * h32 * sig).astype(x.dtype)
        dw1 = _product_t(grouped, xs, dh, sizes, w1.dtype)
        dw3 = _product_t(grouped, xs, dg, sizes, w3.dtype)
        by_w1 = _product(grouped, dh, w1, sizes, True)
        by_w3 = _product(grouped, dg, w3, sizes, True)
    with jax.named_scope("mx.moe.combine"):
        # the two products' sum, rounded once: formed here, a row once,
        # whichever way the rows then reach their tokens
        dxs = (by_w1.astype(jnp.float32) + by_w3.astype(jnp.float32)
               ).astype(x.dtype)
        dx = _combine(combine, dxs, top_k, inverse, sizes, run)
        place, exists = _places(inverse, sizes, top_k)
        dweights = jnp.where(exists, jnp.take(dw_sorted[:, 0], place), 0)
    return dx, dweights.astype(weights.dtype), dw1, dw3, dw2


def _experts_bwd(rows, combine, res, dout):
    """The experts' hidden states are computed again and not kept: they
    are the layer's largest arrays."""
    x, weights, order, inverse, sizes, run, w1, w3, w2 = res
    dx, dweights, dw1, dw3, dw2 = _at_buffer(
        rows, combine, _backward_at, order, sizes, x, weights, inverse, run,
        w1, w3, w2, dout)
    return dx, dweights, None, None, None, None, dw1, dw3, dw2


_experts.defvjp(_experts_fwd, _experts_bwd)


def _sort_pairs(chosen, first_expert, held):
    """Token-expert pairs by held expert, those on no held expert last:
    ``(order, inverse, sizes)``.  A counting sort over the held + 1
    keys: a pair's place is its key's start plus the pairs of that key
    before it, so neither a sort nor a scatter is needed for *inverse*,
    and *order* is one stable sort."""
    key = chosen.reshape(-1) - first_expert
    key = jnp.where((key >= 0) & (key < held), key, held)
    onehot = (key[:, None] == jnp.arange(held + 1)).astype(jnp.int32)
    counts = jnp.sum(onehot, axis=0)
    starts = jnp.cumsum(counts) - counts
    before = jnp.cumsum(onehot, axis=0) - onehot
    inverse = jnp.sum((before + starts) * onehot, axis=1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    return order, inverse, counts[:held]


def _record_moe_plan(tokens, router_experts, top_k, held, first_expert,
                     rows, dtype, hidden, expert_hidden, combine, why):
    """One `mx.moe.plan` span each time the op is traced (as
    `mx.flash.plan`: the plan is a fact of the compiled program).
    *combine* and *why* are `_combine_plan`'s."""
    with profiler.scope(  # graftlint: disable=JG003
            "mx.moe.plan", "moe") as span:
        span.args = {
            "router_experts": router_experts, "experts_per_token": top_k,
            "experts_held": held, "first_expert": first_expert,
            "tokens": tokens, "pair_bound": tokens * top_k,
            "buffer_rows": rows,
            "bound": "worst case: every choice of every token held here"
            if rows == tokens * top_k else
            "%g x the pairs of uniform routing (%d x %d x %d / %d), in row "
            "tiles of %d; a step with more pairs runs at pair_bound, "
            "through the ragged dot" % (
                BUFFER_FACTOR, tokens, top_k, held, router_experts,
                GROUPED_TILES[0]),
            "dtype": jnp.dtype(dtype).name, "path": GROUPED_PATH,
            "tiles": None if GROUPED_PATH != "megablox" else {
                "up": _tiles(GROUPED_TILES, rows, hidden, expert_hidden),
                "down": _tiles(GROUPED_TILES, rows, expert_hidden, hidden)},
            # how a token's pairs come back to it at the bounded buffer
            # (the worst case's branch gathers a choice at a time)
            "combine": {
                "path": "xla" if combine is None else "kernel", "why": why,
                "token_tile": combine and combine[0],
                "chunk_rows": combine and combine[1]}}


@register_op("_contrib_RoutedExperts", aliases=("RoutedExperts",))
def _routed_experts(data, router_weight, w1, w3, w2, expert_bias=(),
                    num_experts_per_tok=1, first_expert=0,
                    norm_topk_prob=True, routed_scaling_factor=1.0,
                    scoring_func="sigmoid"):
    """Dropless top-k routed experts, one chip's share.

    data ``(..., d)``; router_weight ``(E, d)`` over ALL the layer's
    experts; w1, w3 ``(held, d, hidden)`` and w2 ``(held, hidden, d)``
    are the experts ``first_expert .. first_expert + held - 1`` that
    live here.  Routing is over all E: ``s = sigmoid(x W_r)``, the
    chosen are the top-k of ``s + expert_bias`` (a fixed attribute, not
    trained), a chosen expert's weight is ``s_e / (sum of the chosen s +
    1e-6)`` (``norm_topk_prob``) times ``routed_scaling_factor``, all in
    float32; with *scoring_func* ``softmax``, ``g = softmax(x W_r)``, the
    chosen are the top-k of ``g`` and a weight is ``g_e / (sum of the
    chosen g)``, with no bias and no scaling.  The result is the held
    experts' part, ``sum over e chosen and held of w_e * W2_e(silu(W1_e x)
    * W3_e x)``: what the absent
    experts would add is left out.  No capacity and no dropped token:
    token-expert pairs are sorted by expert into a buffer of
    `_buffer_rows` rows, the three products run over the groups that
    exist, and each token's pairs come back to it summed (`_combine`):
    where `_combine_plan` gives tiles and the program is lowered for the
    TPU, the pairs that exist are read once each, the buffer's rows
    gathered into token order (a pair's id is token x k + choice, so the
    existing pairs sorted by id are sorted by token: `_token_order`) and
    each tile of tokens summing its contiguous run of them on the MXU
    (`_combine_rows`); everywhere else each token gathers a row a choice
    and selects the ones that exist (`_sum_pairs`).  A buffer row past the
    last pair is not a number, so it is selected away before either sum.
    A step with more pairs than rows takes the worst case's buffer (every
    choice of every token held here) through the same code, with the
    ragged dot and the gather a choice (`_at_buffer`).  The counts of
    `routed_expert_counts` leave through `profiler.emit_step_stat` under
    ``moe_expert_counts``, the buffer rows the combine read under
    ``moe_combine_rows``.
    """
    lead, d = data.shape[:-1], data.shape[-1]
    x = data.reshape(-1, d)
    held, router_experts = w1.shape[0], router_weight.shape[0]
    top_k, first = int(num_experts_per_tok), int(first_expert)
    bias = tuple(expert_bias) or (0.0,) * router_experts
    rows = _buffer_rows(x.shape[0], top_k, held, router_experts)
    combine, why = _combine_plan(x.shape[0], top_k, rows, d, data.dtype)
    _record_moe_plan(x.shape[0], router_experts, top_k, held, first, rows,
                     data.dtype, d, w1.shape[2], combine, why)
    with jax.named_scope("mx.moe.route"):
        chosen, weights = _route(x, router_weight, bias, top_k,
                                 bool(norm_topk_prob),
                                 float(routed_scaling_factor),
                                 str(scoring_func))
        # at trace time on purpose: it hands the traced counts to the
        # program that is being traced, which returns them every step
        profiler.emit_step_stat(  # graftlint: disable=JG003
            "moe_expert_counts",
            routed_expert_counts(chosen, router_experts, first, held, rows))
        order, inverse, sizes = _sort_pairs(chosen, first, held)
    run, read = (), chosen.size
    if combine:
        with jax.named_scope("mx.moe.combine"):
            run, read = _token_order(order, inverse, sizes, top_k, rows,
                                     combine)
            # a step with more pairs than rows gathers a choice at a time
            read = jnp.where(jnp.sum(sizes) <= rows, read, chosen.size)
    profiler.emit_step_stat(  # graftlint: disable=JG003
        "moe_combine_rows", jnp.asarray(read, jnp.int32))
    out = _experts(rows, combine, x, weights, order, inverse, sizes, run, w1,
                   w3, w2)
    return out.reshape(*lead, d)


def _fold_combine_rows(values):
    """One step's values (one per routed layer): the buffer rows the
    combine read, to set beside `moe_local_assignments_total`."""
    profiler.bump_counter("moe_combine_rows_read_total",
                          int(np.asarray(values, np.int64).sum()))


profiler.register_step_stat("moe_expert_counts", _fold_expert_counts)
profiler.register_step_stat("moe_combine_rows", _fold_combine_rows)


from .registry import get_op as _get_op  # noqa: E402

_get_op("_contrib_RotaryEmbedding").active_inputs = _with_positions("data")
_get_op("_contrib_HeadNormRotary").active_inputs = _with_positions(
    "data", "gamma")
_get_op("_contrib_SparseAttention").active_inputs = _with_positions(
    "data", "q_weight", "k_weight", "v_weight", "out_weight", "q_gamma",
    "k_gamma", "index_q_weight", "index_k_weight", "index_w_weight")
