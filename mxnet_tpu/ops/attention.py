"""Scaled-dot-product attention: the long-context stance of this framework.

The reference predates Transformers — its only artifact is
``_contrib_div_sqrt_dim`` (reference: src/operator/contrib/transformer.cc:33)
and sequence scaling comes from bucketing + the fused RNN op (SURVEY §5.7).
On TPU the idiomatic equivalent is one attention op with a flash (blockwise,
online-softmax) kernel, plus a sequence-parallel ring variant over the ICI
mesh (``mxnet_tpu.parallel.sequence``).  This module provides:

- ``_chunked_attention``: lax.scan blockwise attention with online softmax —
  O(S * chunk) activation memory, differentiable through the scan, runs on
  every backend (the non-TPU dispatch target).
- ``flash_attention``: Pallas TPU kernels — MXU-tiled forward with online
  softmax in f32 scratch (saving the per-row logsumexp), and a custom VJP
  running the standard flash backward as two Pallas kernels
  (``_flash_bwd_dkdv_kernel`` / ``_flash_bwd_dq_kernel``) that recompute
  p from the saved logsumexp and accumulate blockwise.
- ``_contrib_DotProductAttention`` / ``_contrib_div_sqrt_dim`` registered
  operators, so the op is reachable from mx.nd / mx.sym like any other.

Layout is (batch, heads, seq, head_dim) throughout.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ._precision import matmul_precision
from .registry import register_op

__all__ = ["flash_attention", "attention_reference"]

_NEG_INF = -1e30
# Per-row softmax state (running max, denominator, logsumexp, delta)
# rides lane-replicated as (rows, _LANES): Mosaic tiles the last two
# dims of every block (8, 128), so a (1, blk_q) block over a (bh, sq)
# array does not lower and 1-D VMEM scratch has no native layout.
_LANES = 128
# Default q and k block length.  On v5e 512 and 1024 both compile and
# land the same distance from the f32 reference; 2048 asks for 25.8 MiB
# of the 16 MiB scoped VMEM and does not compile (CHANGES.md PR 21).
# Untuned: no timing chose it.
_BLK = 1024


def attention_reference(q, k, v, causal=False, sm_scale=None):
    """O(S^2)-memory einsum attention — the numeric oracle for tests.

    Degenerate-row convention (shared by all paths in this module): a
    causal query row that can see NO keys (seq_q > seq_k under the
    aligned-ends convention) outputs zeros and contributes zero
    gradient — softmax over an empty visible set is undefined, and both
    the uniform-average and NaN alternatives leak masked content or
    poison training."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   precision=matmul_precision(q.dtype, k.dtype)) \
        * sm_scale
    if causal:
        qlen, klen = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((qlen, klen), bool), klen - qlen)
        s = jnp.where(mask, s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        p = p * mask.any(-1)[:, None]  # zero fully-masked rows
    else:
        p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
                      precision=matmul_precision(q.dtype, v.dtype)
                      ).astype(q.dtype)


# ---------------------------------------------------------------------------
# Chunked (blockwise) attention: scan over K/V chunks with online softmax.
# ---------------------------------------------------------------------------

def _online_softmax_update(o, m, l, s, vb):
    """One blockwise online-softmax accumulation step over masked scores
    *s* against value block *vb*; shared by the chunked scan here and the
    ring-attention scan (parallel/sequence.py) so the two paths cannot
    drift numerically.

    p is cast to vb's storage dtype for the MXU dot (full bf16 rate;
    f32 inputs are untouched) while the o/m/l state stays f32 via
    preferred_element_type — the same convention as the Pallas kernel."""
    m_new = jnp.maximum(m, s.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l = l * alpha + p.sum(axis=-1)
    o = o * alpha[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(vb.dtype), vb,
        precision=matmul_precision(vb.dtype, vb.dtype),
        preferred_element_type=jnp.float32)
    return o, m_new, l


def _finalize_softmax(o, m, l):
    """Final division of an online-softmax accumulation, applying the
    degenerate-row convention: rows whose running max *m* never rose
    above the _NEG_INF sentinel saw no visible key and output zeros
    (with zero gradient — l_safe keeps the untaken 0/0 branch out of
    the vjp, where 0 * nan would poison it).  Shared by the chunked and
    ring paths; the flash kernel encodes the same rule in-kernel."""
    degenerate = m <= _NEG_INF * 0.5
    l_safe = jnp.where(degenerate, 1.0, l)
    return jnp.where(degenerate[..., None], 0.0, o / l_safe[..., None])

def _chunked_attention(q, k, v, causal=False, sm_scale=None, chunk=512):
    """Blockwise attention with online softmax over K chunks.

    Memory is O(S_q * chunk) instead of O(S_q * S_k); the scan body is
    rematerialized on backward (jax.checkpoint), which is exactly the
    flash-attention recompute strategy expressed at the XLA level.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    b, h, sq, d = q.shape
    sk = k.shape[2]
    chunk = min(chunk, sk)
    nchunk = -(-sk // chunk)
    pad = nchunk * chunk - sk
    if pad:
        kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    else:
        kp, vp = k, v
    kc = kp.reshape(b, h, nchunk, chunk, d).transpose(2, 0, 1, 3, 4)
    vc = vp.reshape(b, h, nchunk, chunk, d).transpose(2, 0, 1, 3, 4)
    q_pos = jnp.arange(sq) + (sk - sq)  # align ends for causal cross-length

    @jax.checkpoint
    def body(carry, xs):
        o, m, l = carry
        ci, kb, vb = xs
        # storage-dtype operands, f32 accumulation: bf16 runs at the
        # full MXU rate (a pre-cast to f32 would halve it)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kb,
                       precision=matmul_precision(q.dtype, kb.dtype),
                       preferred_element_type=jnp.float32) * sm_scale
        k_pos = ci * chunk + jnp.arange(chunk)
        valid = k_pos < sk
        if causal:
            valid = valid[None, :] & (k_pos[None, :] <= q_pos[:, None])
            s = jnp.where(valid[None, None], s, _NEG_INF)
        else:
            s = jnp.where(valid[None, None, None, :], s, _NEG_INF)
        o, m, l = _online_softmax_update(o, m, l, s, vb)
        return (o, m, l), None

    o0 = jnp.zeros((b, h, sq, d), jnp.float32)
    m0 = jnp.full((b, h, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    (o, m, l), _ = jax.lax.scan(
        body, (o0, m0, l0), (jnp.arange(nchunk), kc, vc))
    return _finalize_softmax(o, m, l).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas flash forward kernel.
# ---------------------------------------------------------------------------

def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *maybe_lse_and_scratch,
                      sm_scale, causal, blk_q, blk_k, seq_q, seq_k):
    if len(maybe_lse_and_scratch) == 4:
        lse_ref, acc_ref, m_ref, l_ref = maybe_lse_and_scratch
    else:  # inference path: no logsumexp output allocated
        lse_ref = None
        acc_ref, m_ref, l_ref = maybe_lse_and_scratch
    ik = pl.program_id(2)
    nk = pl.num_programs(2)
    iq = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _compute():
        # operands stay in their storage dtype: a bf16 x bf16 MXU dot
        # with f32 accumulation (preferred_element_type) runs at the
        # full bf16 MXU rate — pre-casting to f32 would halve it
        q = q_ref[0]                               # (blk_q, d)
        k = k_ref[0]                               # (blk_k, d)
        v = v_ref[0]
        s = _mxu_dot(q, k, ((1,), (1,))) * sm_scale

        k_pos = ik * blk_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < seq_k
        if causal:
            # sequence ends aligned (decode-style cross-length causal),
            # same convention as attention_reference/_chunked_attention
            q_pos = (iq * blk_q + (seq_k - seq_q)
                     + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
            mask = mask & (k_pos <= q_pos)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]                        # (blk_q, _LANES)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _lanes_to(m_new, blk_k))
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        # p in v's dtype for the second MXU dot (flash convention: the
        # f32 online-softmax state carries the precision; p's entries
        # are probabilities in [0,1] where bf16 relative error is ~2^-8)
        acc_ref[...] = (acc_ref[...] * _lanes_to(alpha, acc_ref.shape[1])
                        + _mxu_dot(p.astype(v.dtype), v, ((1,), (0,))))

    if causal:
        # skip K blocks entirely above the diagonal: their tiles are fully
        # masked and would pay two MXU dots for nothing (~2x on sq == sk)
        visible = ik * blk_k <= iq * blk_q + blk_q - 1 + (seq_k - seq_q)
        pl.when(visible)(_compute)
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _finish():
        # rows whose running max never rose above the sentinel saw no
        # visible key (causal with seq_q > seq_k): emit zeros, and a
        # +1e30 lse so the backward's recomputed p = exp(s - lse)
        # underflows to 0 for them — zero output, zero gradient, same
        # convention as attention_reference/_chunked_attention
        m = m_ref[...]
        degenerate = m <= _NEG_INF * 0.5
        l_safe = jnp.where(degenerate, 1.0, l_ref[...])
        # widen the f32 state, then compare: Mosaic does not tile or
        # reshape i1 vectors
        dp = acc_ref.shape[1]
        o_ref[0] = jnp.where(_lanes_to(m, dp) <= _NEG_INF * 0.5, 0.0,
                             acc_ref[...] / _lanes_to(l_safe, dp)
                             ).astype(o_ref.dtype)
        if lse_ref is not None:
            # logsumexp residual for the flash backward
            lse_ref[0] = jnp.where(degenerate, -_NEG_INF,
                                   m + jnp.log(l_safe))


def _mxu_dot(a, b, contract):
    """In-kernel MXU dot, f32 accumulation, at the framework's precision
    policy: bf16 operands take the full-rate path, f32 operands HIGHEST —
    Mosaic's default would multiply them as bf16 (the f32 op then
    disagrees with the CPU at 1e-2; consistency sweep, PR 21)."""
    return jax.lax.dot_general(
        a, b, (contract, ((), ())),
        precision=matmul_precision(a.dtype, b.dtype),
        preferred_element_type=jnp.float32)


def _lanes_to(x, n):
    """Widen lane-replicated row state (rows, _LANES) to (rows, n)."""
    reps, rem = divmod(n, _LANES)
    if rem:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], n))
    return jnp.tile(x, (1, reps))


def _pad_bh(x, s_pad, d_pad):
    b, h, s, d = x.shape
    xp = jnp.pad(x, ((0, 0), (0, 0), (0, s_pad), (0, d_pad)))
    return xp.reshape(b * h, s + s_pad, d + d_pad)


def _flash_fwd_pallas(q, k, v, causal, sm_scale, blk_q=_BLK, blk_k=_BLK,
                      interpret=False, with_lse=False):
    """Flash forward: grid (B*H, nq, nk); f32 accumulators in VMEM
    scratch.  ``with_lse`` also returns the per-row logsumexp residual
    (the flash backward's recompute anchor)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    blk_q = min(blk_q, sq)
    blk_k = min(blk_k, sk)
    # pad seq dims to block multiples, head dim to the 128-lane tile
    d_pad = -d % 128
    sq_pad = -sq % blk_q
    sk_pad = -sk % blk_k
    qp = _pad_bh(q, sq_pad, d_pad)
    kp = _pad_bh(k, sk_pad, d_pad)
    vp = _pad_bh(v, sk_pad, d_pad)
    bh = b * h
    dp = d + d_pad
    nq = (sq + sq_pad) // blk_q
    nk = (sk + sk_pad) // blk_k

    kernel = functools.partial(
        _flash_fwd_kernel, sm_scale=sm_scale, causal=causal,
        blk_q=blk_q, blk_k=blk_k, seq_q=sq, seq_k=sk)
    out_specs = [pl.BlockSpec((1, blk_q, dp),
                              lambda bh_, iq, ik: (bh_, iq, 0))]
    out_shape = [jax.ShapeDtypeStruct((bh, sq + sq_pad, dp), q.dtype)]
    if with_lse:  # training: also emit the logsumexp residual
        out_specs.append(pl.BlockSpec((1, blk_q, _LANES),
                                      lambda bh_, iq, ik: (bh_, iq, 0)))
        out_shape.append(jax.ShapeDtypeStruct((bh, sq + sq_pad, _LANES),
                                              jnp.float32))
    with jax.named_scope("mx.flash.fwd"):
        res = pl.pallas_call(
            kernel,
            grid=(bh, nq, nk),
            in_specs=[
                pl.BlockSpec((1, blk_q, dp),
                             lambda bh_, iq, ik: (bh_, iq, 0)),
                pl.BlockSpec((1, blk_k, dp),
                             lambda bh_, iq, ik: (bh_, ik, 0)),
                pl.BlockSpec((1, blk_k, dp),
                             lambda bh_, iq, ik: (bh_, ik, 0)),
            ],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((blk_q, dp), jnp.float32),
                pltpu.VMEM((blk_q, _LANES), jnp.float32),
                pltpu.VMEM((blk_q, _LANES), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="mx_flash_fwd",
        )(qp, kp, vp)
    out = res[0].reshape(b, h, sq + sq_pad, dp)[:, :, :sq, :d]
    if with_lse:
        return out, res[1][..., 0]  # lse stays padded (bh, sqp) for the bwd
    return out


# ---------------------------------------------------------------------------
# Pallas flash backward kernels (standard flash-attention backward:
# recompute p from the saved logsumexp, accumulate dq / dk / dv blockwise;
# delta_i = rowsum(dO_i * O_i) precomputed at the XLA level).
# ---------------------------------------------------------------------------

def _bwd_p_block(q_ref, k_ref, lse_ref, iq, ik, *, sm_scale, causal,
                 blk_q, blk_k, seq_q, seq_k):
    """Recomputed softmax block p = exp(q k^T * scale - lse).

    The dot keeps the storage dtype (bf16 runs at full MXU rate) and
    accumulates f32 via preferred_element_type."""
    q = q_ref[0]
    k = k_ref[0]
    s = _mxu_dot(q, k, ((1,), (1,))) * sm_scale
    k_pos = ik * blk_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = k_pos < seq_k
    if causal:
        q_pos = (iq * blk_q + (seq_k - seq_q)
                 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
        mask = mask & (k_pos <= q_pos)
    s = jnp.where(mask, s, _NEG_INF)
    return jnp.exp(s - _lanes_to(lse_ref[0], blk_k))


def _flash_bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                           delta_ref, dk_ref, dv_ref,
                           dk_acc, dv_acc, *, sm_scale, causal,
                           blk_q, blk_k, seq_q, seq_k):
    ik = pl.program_id(1)
    iq = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _compute():
        p = _bwd_p_block(q_ref, k_ref, lse_ref, iq, ik,
                         sm_scale=sm_scale, causal=causal, blk_q=blk_q,
                         blk_k=blk_k, seq_q=seq_q, seq_k=seq_k)
        do = do_ref[0]
        v = v_ref[0]
        q = q_ref[0]
        # dv += p^T dO — p cast to the storage dtype for a full-rate
        # MXU dot; accumulators stay f32
        dv_acc[...] += _mxu_dot(p.astype(do.dtype), do, ((0,), (0,)))
        # ds = p * (dO v^T - delta) * scale;  dk += ds^T q
        dp = _mxu_dot(do, v, ((1,), (1,)))
        ds = p * (dp - _lanes_to(delta_ref[0], blk_k)) * sm_scale
        dk_acc[...] += _mxu_dot(ds.astype(q.dtype), q, ((0,), (0,)))

    if causal:
        visible = ik * blk_k <= iq * blk_q + blk_q - 1 + (seq_k - seq_q)
        pl.when(visible)(_compute)
    else:
        _compute()

    @pl.when(iq == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                         delta_ref, dq_ref, dq_acc, *, sm_scale, causal,
                         blk_q, blk_k, seq_q, seq_k):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _compute():
        p = _bwd_p_block(q_ref, k_ref, lse_ref, iq, ik,
                         sm_scale=sm_scale, causal=causal, blk_q=blk_q,
                         blk_k=blk_k, seq_q=seq_q, seq_k=seq_k)
        do = do_ref[0]
        v = v_ref[0]
        k = k_ref[0]
        dp = _mxu_dot(do, v, ((1,), (1,)))
        ds = p * (dp - _lanes_to(delta_ref[0], blk_k)) * sm_scale
        dq_acc[...] += _mxu_dot(ds.astype(k.dtype), k, ((1,), (0,)))

    if causal:
        visible = ik * blk_k <= iq * blk_q + blk_q - 1 + (seq_k - seq_q)
        pl.when(visible)(_compute)
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _flash_bwd_pallas(q, k, v, out, lse, dout, causal, sm_scale,
                      blk_q=_BLK, blk_k=_BLK, interpret=False):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    blk_q = min(blk_q, sq)
    blk_k = min(blk_k, sk)
    d_pad = -d % 128
    sq_pad = -sq % blk_q
    sk_pad = -sk % blk_k
    qp = _pad_bh(q, sq_pad, d_pad)
    kp = _pad_bh(k, sk_pad, d_pad)
    vp = _pad_bh(v, sk_pad, d_pad)
    dop = _pad_bh(dout, sq_pad, d_pad)
    outp = _pad_bh(out, sq_pad, d_pad)
    bh, dp = b * h, d + d_pad
    nq = (sq + sq_pad) // blk_q
    nk = (sk + sk_pad) // blk_k
    # delta_i = rowsum(dO_i * O_i) — zero on padded rows since dO is 0
    delta = jnp.sum(dop.astype(jnp.float32) * outp.astype(jnp.float32),
                    axis=-1)
    # the per-row residuals enter lane-replicated (see _LANES); the
    # widened copies live only for this call, the saved lse is (bh, sqp)
    lse = jnp.broadcast_to(lse[..., None], lse.shape + (_LANES,))
    delta = jnp.broadcast_to(delta[..., None], delta.shape + (_LANES,))

    common = dict(sm_scale=sm_scale, causal=causal, blk_q=blk_q,
                  blk_k=blk_k, seq_q=sq, seq_k=sk)
    q_spec_q = pl.BlockSpec((1, blk_q, dp), lambda bh_, a, b_: (bh_, a, 0))
    q_spec_k = pl.BlockSpec((1, blk_q, dp), lambda bh_, a, b_: (bh_, b_, 0))
    k_spec_q = pl.BlockSpec((1, blk_k, dp), lambda bh_, a, b_: (bh_, b_, 0))
    k_spec_k = pl.BlockSpec((1, blk_k, dp), lambda bh_, a, b_: (bh_, a, 0))
    r_spec_q = pl.BlockSpec((1, blk_q, _LANES),
                            lambda bh_, a, b_: (bh_, a, 0))
    r_spec_k = pl.BlockSpec((1, blk_q, _LANES),
                            lambda bh_, a, b_: (bh_, b_, 0))

    # dk/dv: grid (bh, nk, nq) — k-block resident, q streamed
    with jax.named_scope("mx.flash.dkdv"):
        dk, dv = pl.pallas_call(
            functools.partial(_flash_bwd_dkdv_kernel, **common),
            grid=(bh, nk, nq),
            in_specs=[q_spec_k, k_spec_k, k_spec_k, q_spec_k, r_spec_k,
                      r_spec_k],
            out_specs=[k_spec_k, k_spec_k],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sk + sk_pad, dp), k.dtype),
                jax.ShapeDtypeStruct((bh, sk + sk_pad, dp), v.dtype)],
            scratch_shapes=[pltpu.VMEM((blk_k, dp), jnp.float32),
                            pltpu.VMEM((blk_k, dp), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="mx_flash_dkdv",
        )(qp, kp, vp, dop, lse, delta)

    # dq: grid (bh, nq, nk) — q-block resident, k streamed
    with jax.named_scope("mx.flash.dq"):
        dq = pl.pallas_call(
            functools.partial(_flash_bwd_dq_kernel, **common),
            grid=(bh, nq, nk),
            in_specs=[q_spec_q, k_spec_q, k_spec_q, q_spec_q, r_spec_q,
                      r_spec_q],
            out_specs=q_spec_q,
            out_shape=jax.ShapeDtypeStruct((bh, sq + sq_pad, dp), q.dtype),
            scratch_shapes=[pltpu.VMEM((blk_q, dp), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="mx_flash_dq",
        )(qp, kp, vp, dop, lse, delta)

    dq = dq.reshape(b, h, sq + sq_pad, dp)[:, :, :sq, :d]
    dk = dk.reshape(b, h, sk + sk_pad, dp)[:, :, :sk, :d]
    dv = dv.reshape(b, h, sk + sk_pad, dp)[:, :, :sk, :d]
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, sm_scale, interpret):
    return _flash_fwd_pallas(q, k, v, causal, sm_scale, interpret=interpret)


def _flash_vjp_fwd(q, k, v, causal, sm_scale, interpret):
    out, lse = _flash_fwd_pallas(q, k, v, causal, sm_scale,
                                 interpret=interpret, with_lse=True)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, sm_scale, interpret, res, g):
    q, k, v, out, lse = res
    return _flash_bwd_pallas(q, k, v, out, lse, g, causal, sm_scale,
                             interpret=interpret)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, causal=False, sm_scale=None, interpret=False,
                    chunk=512):
    """Blockwise (flash) attention, (B, H, S, D) layout.

    Pallas MXU kernel on TPU; chunked-scan XLA path elsewhere (*chunk*
    is its block length).  Both have O(S * block) activation memory;
    grads flow through either.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret:
        dt = jnp.result_type(q.dtype, k.dtype, v.dtype)
        return _flash(q.astype(dt), k.astype(dt), v.astype(dt),
                      causal, float(sm_scale), True).astype(q.dtype)

    def _tpu(q, k, v):
        # the kernels' MXU dots need one operand dtype (f32 q against a
        # bf16 KV cache would raise); promote once here so the uniform
        # bf16 fast path is untouched.  platform_dependent traces BOTH
        # branches on every platform, so the promotion stays inside
        dt = jnp.result_type(q.dtype, k.dtype, v.dtype)

        def local(q, k, v):
            return _flash(q.astype(dt), k.astype(dt), v.astype(dt),
                          causal, float(sm_scale), False).astype(q.dtype)

        from ..parallel.mesh import current_mesh
        mesh = current_mesh()
        manual = jax.sharding.get_abstract_mesh().manual_axes
        if mesh is None or mesh.size == 1 or \
                set(manual) == set(mesh.axis_names):
            # one device, or already per shard (a pipeline stage)
            return local(q, k, v)
        # XLA does not partition a Mosaic kernel ("wrap the call in a
        # shard_map"): run it per shard.  Attention is independent over
        # batch and heads, so dp splits the batch and tp the heads; any
        # other mesh axis computes replicated.
        spec = P("dp" if "dp" in mesh.shape else None,
                 "tp" if "tp" in mesh.shape else None)
        return jax.shard_map(local, mesh=mesh, in_specs=(spec,) * 3,
                             out_specs=spec, check_vma=False)(q, k, v)

    def _other(q, k, v):
        return _chunked_attention(q, k, v, causal, sm_scale,
                                  int(chunk)).astype(q.dtype)

    # decided at LOWERING time per platform, not by which devices this
    # process happens to see: only the target platform's branch is
    # lowered, so the Mosaic kernels are the program on a TPU and never
    # reach a CPU compile
    return jax.lax.platform_dependent(q, k, v, tpu=_tpu, default=_other)


# ---------------------------------------------------------------------------
# Operator registrations.
# ---------------------------------------------------------------------------

@register_op("_contrib_DotProductAttention",
             input_names=("query", "key", "value"))
def _dot_product_attention(query, key, value, causal=False, sm_scale=None,
                           chunk=512):
    """Fused scaled-dot-product attention (TPU-native; no reference
    counterpart — the reference predates Transformers, SURVEY §5.7)."""
    return flash_attention(query, key, value, causal=bool(causal),
                           sm_scale=sm_scale, chunk=chunk)
