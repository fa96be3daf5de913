"""Attention stack tests: chunked/flash attention vs the einsum oracle,
ring attention on the virtual 8-device mesh (SURVEY §5.7 TPU stance)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)                    # `benchmarks`' counts

import mxnet_tpu as mx
from mxnet_tpu.ops.attention import (attention_reference, _chunked_attention,
                                     _flash_fwd_pallas, flash_attention)
from mxnet_tpu.parallel import make_mesh, sequence_parallel_attention

# a square sub-tile of edge t visits S^2/2 + S*t/2 scores of the causal
# triangle's S^2/2: 1.125 at t = 256 and S = 2048
_TRIANGLE_SLACK = 1.15


def _rand_qkv(b=2, h=3, sq=64, sk=64, d=16, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, h, sq, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, h, sk, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, h, sk, d).astype(np.float32))
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_chunked_matches_reference(causal):
    q, k, v = _rand_qkv()
    ref = attention_reference(q, k, v, causal=causal)
    out = _chunked_attention(q, k, v, causal=causal, chunk=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_chunked_grads_match_reference(causal):
    q, k, v = _rand_qkv(b=1, h=2, sq=32, sk=32, d=8)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)

    def loss_chk(q, k, v):
        return jnp.sum(
            _chunked_attention(q, k, v, causal=causal, chunk=8) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_chk = jax.grad(loss_chk, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_chk):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-4, atol=2e-4)


def test_chunked_cross_length_causal():
    # decode-style: fewer queries than keys, causal ends aligned
    q, k, v = _rand_qkv(sq=8, sk=64)
    ref = attention_reference(q, k, v, causal=True)
    out = _chunked_attention(q, k, v, causal=True, chunk=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_flash_forward_interpret(causal):
    # interpret=True runs the TPU kernel logic on CPU
    q, k, v = _rand_qkv(b=1, h=2, sq=48, sk=48, d=16)
    ref = attention_reference(q, k, v, causal=causal)
    out = _flash_fwd_pallas(q, k, v, causal, 1.0 / np.sqrt(16),
                            blk_q=16, blk_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_pallas_flash_cross_length_causal_interpret():
    q, k, v = _rand_qkv(b=1, h=1, sq=8, sk=64, d=16)
    ref = attention_reference(q, k, v, causal=True)
    out = _flash_fwd_pallas(q, k, v, True, 1.0 / np.sqrt(16),
                            blk_q=8, blk_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_pallas_flash_fully_masked_rows_finite():
    # causal with seq_q > seq_k: early q rows see NO keys (aligned-ends
    # convention puts their positions before key 0).  Every k-block
    # fails the visibility test for those q-blocks; regression: the
    # final division emitted NaN (0/0).  Convention: such rows output
    # zeros with zero gradient, identically in every path.
    q, k, v = _rand_qkv(b=1, h=1, sq=16, sk=4, d=16)
    out = _flash_fwd_pallas(q, k, v, True, 1.0 / np.sqrt(16),
                            blk_q=4, blk_k=4, interpret=True)
    assert bool(jnp.isfinite(out).all())
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(out)[:, :, :12], 0.0)
    chk = _chunked_attention(q, k, v, causal=True, chunk=4)
    np.testing.assert_allclose(np.asarray(chk), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_fully_masked_rows_grads_match():
    # gradients through degenerate rows are ZERO and the flash custom
    # vjp agrees with autodiff through the reference on every input
    q, k, v = _rand_qkv(b=1, h=1, sq=16, sk=4, d=16)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


def test_flash_bf16_matches_f32_oracle():
    """bf16 storage with f32 online-softmax state and f32 MXU
    accumulation (preferred_element_type): fwd and grads must track the
    f32 oracle within bf16 tolerance."""
    q, k, v = _rand_qkv(b=1, h=2, sq=32, sk=32, d=16)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    ref = attention_reference(q, k, v, causal=True)
    out = flash_attention(qb, kb, vb, causal=True, interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=5e-2, atol=5e-2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=True)
                       .astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True)
                       .astype(jnp.float32) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(qb, kb, vb)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b), rtol=1e-1, atol=1e-1)


def test_ring_attention_cross_length_causal():
    mesh = make_mesh({"sp": 8})
    q, k, v = _rand_qkv(b=1, h=2, sq=32, sk=64, d=8)
    ref = attention_reference(q, k, v, causal=True)
    out = sequence_parallel_attention(q, k, v, mesh, axis="sp", causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_and_chunked_bf16_track_oracle():
    mesh = make_mesh({"sp": 8})
    q, k, v = _rand_qkv(b=1, h=2, sq=32, sk=32, d=8)
    ref = attention_reference(q, k, v, causal=True)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    ring = sequence_parallel_attention(qb, kb, vb, mesh, axis="sp",
                                       causal=True)
    np.testing.assert_allclose(np.asarray(ring, np.float32),
                               np.asarray(ref), rtol=5e-2, atol=5e-2)
    chk = _chunked_attention(qb, kb, vb, causal=True, chunk=8)
    np.testing.assert_allclose(np.asarray(chk, np.float32),
                               np.asarray(ref), rtol=5e-2, atol=5e-2)


def test_flash_attention_grad_interpret():
    q, k, v = _rand_qkv(b=1, h=1, sq=32, sk=32, d=8)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v) ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    mesh = make_mesh({"sp": 8})
    q, k, v = _rand_qkv(b=2, h=2, sq=64, sk=64, d=16)
    ref = attention_reference(q, k, v, causal=causal)
    out = sequence_parallel_attention(q, k, v, mesh, axis="sp",
                                      causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_grads_match_full():
    mesh = make_mesh({"sp": 8})
    q, k, v = _rand_qkv(b=1, h=2, sq=32, sk=32, d=8)

    def loss_ring(q, k, v):
        return jnp.sum(
            sequence_parallel_attention(q, k, v, mesh, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_ring):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-4, atol=2e-4)


def test_ndarray_op_and_div_sqrt_dim():
    q, k, v = _rand_qkv(b=1, h=1, sq=16, sk=16, d=4)
    out = mx.nd.contrib.DotProductAttention(
        mx.nd.array(np.asarray(q)), mx.nd.array(np.asarray(k)),
        mx.nd.array(np.asarray(v)))
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(out.asnumpy(), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    x = mx.nd.array(np.ones((2, 16), np.float32))
    y = mx.nd.contrib.div_sqrt_dim(x)
    np.testing.assert_allclose(y.asnumpy(), np.ones((2, 16)) / 4.0,
                               rtol=1e-6)


def test_symbolic_attention_with_grad():
    import mxnet_tpu.symbol as sym
    q = sym.var("q")
    k = sym.var("k")
    v = sym.var("v")
    out = sym.contrib.DotProductAttention(q, k, v)
    qn, kn, vn = _rand_qkv(b=1, h=1, sq=16, sk=16, d=4)
    ex = out.bind(mx.cpu(), {"q": mx.nd.array(np.asarray(qn)),
                             "k": mx.nd.array(np.asarray(kn)),
                             "v": mx.nd.array(np.asarray(vn))},
                  args_grad={"q": mx.nd.zeros(qn.shape),
                             "k": mx.nd.zeros(kn.shape),
                             "v": mx.nd.zeros(vn.shape)})
    y = ex.forward(is_train=True)[0]
    ref = attention_reference(qn, kn, vn)
    np.testing.assert_allclose(y.asnumpy(), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    ex.backward(mx.nd.ones(y.shape))
    g_ref = jax.grad(
        lambda a, b, c: jnp.sum(attention_reference(a, b, c)),
        argnums=(0, 1, 2))(qn, kn, vn)
    np.testing.assert_allclose(ex.grad_dict["q"].asnumpy(),
                               np.asarray(g_ref[0]), rtol=2e-4, atol=2e-4)


def _check_flash_against_reference(q, k, v, causal, tol_out, tol_grad,
                                    **tiles):
    """Both wrappers in interpret mode against the oracle's output and
    its three gradients."""
    from mxnet_tpu.ops.attention import _flash_bwd_pallas
    d = q.shape[-1]
    scale = 1.0 / np.sqrt(d)
    out, lse = _flash_fwd_pallas(q, k, v, causal, scale, interpret=True,
                                 with_lse=True, **tiles)
    assert out.shape == q.shape and out.dtype == q.dtype
    assert lse.shape == (q.shape[0] * q.shape[1], 1, q.shape[2])
    g = jnp.asarray(np.random.RandomState(9).randn(
        *out.shape).astype(np.float32)).astype(q.dtype)
    grads = _flash_bwd_pallas(q, k, v, out, lse, g, causal, scale,
                              interpret=True, **tiles)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    ref, vjp = jax.vjp(
        lambda a, b, c: attention_reference(a, b, c, causal=causal,
                                            sm_scale=scale), *f32)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=tol_out, atol=tol_out)
    for got, want, x in zip(grads, vjp(g.astype(jnp.float32)), (q, k, v)):
        assert got.shape == x.shape and got.dtype == x.dtype
        assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want), rtol=tol_grad,
                                   atol=tol_grad)


# (seq_q, seq_k, tiles): the sub-tile smaller than, equal to and larger
# than the sequence; rectangular sub-tiles; several resident blocks on
# either side (the grid's clamped index maps); a sequence that is no
# multiple of the tile; cross-length, ends aligned, with seq_q > seq_k
# leaving rows that see no key
_GEOMETRIES = [
    (48, 48, dict(blk_q=16, blk_k=16)),
    (32, 32, dict(blk_q=32, blk_k=32)),
    (24, 24, dict(blk_q=64, blk_k=64)),
    (64, 64, dict(blk_q=16, blk_k=32)),
    (64, 64, dict(blk_q=32, blk_k=8)),
    (64, 64, dict(blk_q=16, blk_k=16, res_q=32, res_k=32)),
    (64, 64, dict(blk_q=8, blk_k=16, res_q=16, res_k=16)),
    (40, 56, dict(blk_q=16, blk_k=16)),
    (40, 56, dict(blk_q=16, blk_k=16, res_q=16, res_k=32)),
    (24, 72, dict(blk_q=24, blk_k=24)),
    (24, 72, dict(blk_q=8, blk_k=24, res_q=8, res_k=24)),
    (48, 16, dict(blk_q=16, blk_k=16)),
    (44, 20, dict(blk_q=8, blk_k=8, res_q=16, res_k=8)),
    # loops too long to unroll whole: static bounds (ten tiles a side) and
    # traced ones (two query blocks over sixteen key tiles), both run
    # four tiles an iteration and the rest one by one
    (160, 160, dict(blk_q=16, blk_k=16)),
    (128, 128, dict(blk_q=8, blk_k=8, res_q=64, res_k=128)),
]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,sk,tiles", _GEOMETRIES)
def test_pallas_flash_backward_interpret(causal, sq, sk, tiles):
    q, k, v = _rand_qkv(b=1, h=2, sq=sq, sk=sk, d=16)
    _check_flash_against_reference(q, k, v, causal, 2e-5, 2e-4, **tiles)


# dq accumulates across grid steps: several K/V blocks a head, so that
# each adds its share to the rows of query blocks it has seen before
_SEVERAL_KV_BLOCKS = [
    (64, 64, dict(blk_q=16, blk_k=16, res_q=32, res_k=16)),
    (64, 64, dict(blk_q=16, blk_k=8, res_q=64, res_k=16)),
    (40, 56, dict(blk_q=8, blk_k=8, res_q=8, res_k=8)),         # sq < sk
    (44, 100, dict(blk_q=16, blk_k=16, res_q=16, res_k=32)),    # padded
    (48, 16, dict(blk_q=16, blk_k=8, res_q=16, res_k=8)),       # sq > sk
]


@pytest.mark.parametrize("dq_accumulator", ["vmem", "hbm"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,sk,tiles", _SEVERAL_KV_BLOCKS)
def test_flash_backward_dq_in_either_place_interpret(causal, sq, sk, tiles,
                                                     dq_accumulator,
                                                     dq_accumulates_in):
    """dq's accumulator over the head's whole sequence in VMEM, and the
    fallback of a sequence too long for that, f32 partials a K/V block
    that XLA sums (the plan's choice at a `_VMEM_DQ` this size would
    pass): the same three gradients either way."""
    from mxnet_tpu.ops.attention import _flash_plan
    assert _flash_plan(sq, sk, 16, jnp.float32, **tiles).dq_accumulator \
        == "vmem"                               # at the module's constant
    dq_accumulates_in(dq_accumulator)
    plan = _flash_plan(sq, sk, 16, jnp.float32, **tiles)
    assert plan.sk_bwd // plan.bwd.res_k > 1
    assert plan.dq_accumulator == dq_accumulator
    q, k, v = _rand_qkv(b=1, h=2, sq=sq, sk=sk, d=16)
    _check_flash_against_reference(q, k, v, causal, 2e-5, 2e-4, **tiles)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,sk", [(2048, 2048), (300, 2048), (1000, 1000)])
def test_flash_backward_whole_head_unrolled_d64_interpret(causal, sq, sk):
    """The plan's own tiles at the LM cell's width: one grid step a head,
    every loop unrolled; `sq == sk`, `sq < sk`, and a padded length."""
    from mxnet_tpu.ops.attention import _flash_plan, _unrolls_whole
    plan = _flash_plan(sq, sk, 64, jnp.bfloat16)
    assert _unrolls_whole(plan.bwd, plan.sq_bwd, plan.sk_bwd)
    q, k, v = (x.astype(jnp.bfloat16)
               for x in _rand_qkv(b=1, h=1, sq=sq, sk=sk, d=64))
    _check_flash_against_reference(q, k, v, causal, 5e-2, 1.5e-1)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("d", [64, 80, 128])
def test_pallas_flash_head_dims_and_dtypes_interpret(d, dtype, causal):
    """d 64 crosses HBM unpadded, d 80 is padded to 128 lanes, d 128 is
    the tile; sm_scale is a power of two at d 64 only (folded into an
    operand there, on the score tile elsewhere)."""
    from mxnet_tpu.ops.attention import _flash_plan
    assert _flash_plan(40, 56, d, dtype).d_block == {64: 64, 80: 128,
                                                     128: 128}[d]
    q, k, v = (x.astype(dtype)
               for x in _rand_qkv(b=1, h=1, sq=40, sk=56, d=d))
    tol = (2e-5, 2e-4) if dtype == "float32" else (5e-2, 1.5e-1)
    _check_flash_against_reference(q, k, v, causal, *tol, blk_q=16,
                                   blk_k=16, res_k=32)


def test_pallas_flash_default_plan_short_sequence_interpret():
    """The plan's own tiles where the sequence is shorter than one."""
    q, k, v = _rand_qkv(b=1, h=1, sq=24, sk=40, d=64)
    _check_flash_against_reference(q, k, v, True, 2e-5, 2e-4)


def _padded_positions(n, n_p, parts):
    """``(position, real)`` of each of the *n_p* padded indices of *n*
    positions in *parts* equal parts, each padded on its own (a padded one
    takes its part's last position)."""
    per_p, per = n_p // parts, n // parts
    part, local = np.divmod(np.arange(n_p), per_p)
    return part * per + np.minimum(local, per - 1), local < per


def _tiles_by_definition(mask, g):
    """``(any, all, pairs)`` from the description's own `visible`, a row of
    sub-tiles at a time: which sub-tiles hold a visible pair, in which every
    real query sees every key (and every key is real), and the visible
    pairs.  A padded query row is computed like a real one (its dO is
    zero): it never makes a tile masked, the padded key columns do."""
    t = g.t
    q_pos, q_real = _padded_positions(g.seq_q, g.pad_q, mask.parts)
    k_pos, k_real = _padded_positions(g.seq_k, g.pad_k, mask.parts)
    shape = (g.pad_q // t.sub_q, g.pad_k // t.sub_k)
    any_, all_, pairs = np.zeros(shape, bool), np.zeros(shape, bool), 0
    for band in range(shape[0]):
        rows = slice(band * t.sub_q, (band + 1) * t.sub_q)
        seen = k_real & np.broadcast_to(mask.visible(
            q_pos[rows, None] + g.off, k_pos[None, :]), (t.sub_q, g.pad_k))
        seen = seen.reshape(t.sub_q, shape[1], t.sub_k)
        real = q_real[rows, None, None]
        any_[band] = (seen & real).any(axis=(0, 2))
        all_[band] = (seen | ~real).all(axis=(0, 2))
        pairs += int((seen & real).sum())
    return any_, all_, pairs


def _tiles_by_the_loops(mask, kernel, g):
    """``(visits, bodies)`` a sub-tile: how often the kernel's loops run it
    and how often under a mask body, from the description's runs at every
    grid step."""
    t = g.t
    nqs, nks = t.res_q // t.sub_q, t.res_k // t.sub_k
    visits = np.zeros((g.pad_q // t.sub_q, g.pad_k // t.sub_k), int)
    bodies = np.zeros_like(visits)
    for q0 in range(0, g.pad_q, t.res_q):
        for k0 in range(0, g.pad_k, t.res_k):
            iq, ik = q0 // t.sub_q, k0 // t.sub_k
            for j in range(nqs if kernel == "fwd" else nks):
                if kernel == "fwd":
                    runs = mask.k_runs(g, q0 + j * t.sub_q, k0, nks)
                    at = [(iq + j, slice(ik + lo, ik + max(lo, hi)), body)
                          for lo, hi, body in runs]
                else:
                    runs = mask.q_runs(g, k0 + j * t.sub_k, q0, nqs)
                    at = [(slice(iq + lo, iq + max(lo, hi)), ik + j, body)
                          for lo, hi, body in runs]
                for rows, cols, body in at:
                    visits[rows, cols] += 1
                    bodies[rows, cols] += body is not None
    return visits, bodies


def _bd(half, block, d, dtype, **tiles):
    from benchmarks import bd_counts
    from mxnet_tpu.ops.attention import BlockDiffusion
    # a mask body wherever a boundary crosses, and on few tiles more (the
    # loops' bounds are whole blocks of the mask, not rows)
    return (BlockDiffusion(block, half), 2 * half, 2 * half, d, dtype, tiles,
            lambda t: bd_counts.tiles(half, block, t.sub_q, t.sub_k), True)


def _swa(seq, window, d, dtype):
    from benchmarks import swa_counts
    from mxnet_tpu.ops.attention import Window
    return (Window(window), seq, seq, d, dtype, {},
            lambda t: swa_counts.tiles(seq, window, t.sub_q, t.sub_k), False)


def _description_cases():
    """``(description, sq, sk, d, dtype, tile overrides, the benchmark's own
    count of (needed, crossed) tiles or None, whether a wholly visible tile
    may run a body)``"""
    from mxnet_tpu.ops.attention import Causal, Full
    cases = {}
    for sq, sk, tiles in _GEOMETRIES + [
            (2048, 2048, {}), (1000, 1000, {}), (300, 2048, {}),
            (2048, 300, {}),
            (4096, 4096, dict(blk_q=512, blk_k=256, res_q=1024,
                              res_k=2048))]:
        for mask in (Full(), Causal()):
            name = "%s-%dx%d-%s" % (type(mask).__name__, sq, sk, "-".join(
                "%s%d" % kv for kv in sorted(tiles.items())))
            cases[name] = (mask, sq, sk, 64, jnp.bfloat16, tiles, None, False)
    for half, block, d, dtype in [
            (8192, 4, 128, jnp.bfloat16), (8192, 32, 128, jnp.bfloat16),
            (6144, 4, 128, jnp.bfloat16), (2048, 4, 64, jnp.bfloat16),
            (1000, 8, 64, jnp.float32), (6144, 6, 128, jnp.bfloat16)]:
        cases["BlockDiffusion-%d-%d" % (half, block)] = _bd(
            half, block, d, dtype)
    for seq, window, d, dtype in [
            (8192, 512, 128, jnp.bfloat16), (6144, 512, 128, jnp.bfloat16),
            (4096, 512, 128, jnp.bfloat16), (8192, 1024, 128, jnp.bfloat16),
            (8192, 2000, 64, jnp.bfloat16), (2048, 100, 64, jnp.bfloat16),
            (1000, 77, 64, jnp.float32), (4096, 4095, 128, jnp.bfloat16)]:
        cases["Window-%d-%d" % (seq, window)] = _swa(seq, window, d, dtype)
    return cases


_DESCRIPTIONS = _description_cases()


@pytest.mark.parametrize("case", sorted(_DESCRIPTIONS))
def test_the_loops_visit_what_the_description_calls_visible(case):
    """Both kernels' loops against the description's own `visible`: every
    sub-tile that holds a visible pair is run once and no other is run (a
    tile all of whose real rows are hidden costs nothing); a tile run
    without a mask body is wholly visible, and a tile the mask or the key
    padding crosses runs one (where the description's runs are whole blocks
    of its own, a few more do); the pair count is the definition's sum;
    `_tile_counts` is the loops' count; and the benchmark's own count of
    the tiles (`bd_counts`, `swa_counts`), where it has one, agrees."""
    from mxnet_tpu.ops.attention import (_KERNELS, _Frame, _flash_plan,
                                         _tile_counts)
    mask, sq, sk, d, dtype, tiles, yardstick, slack = _DESCRIPTIONS[case]
    assert mask.checked(mask.causal, sq, sk) == mask
    plan = _flash_plan(sq, sk, d, dtype, halves=mask.parts, **tiles)
    for kernel in _KERNELS:
        t = getattr(plan, kernel)
        sq_p, sk_p = (plan.sq_fwd, plan.sk_fwd) if kernel == "fwd" \
            else (plan.sq_bwd, plan.sk_bwd)
        assert sq_p % t.res_q == 0 and t.res_q % t.sub_q == 0
        assert sk_p % t.res_k == 0 and t.res_k % t.sub_k == 0
        g = _Frame(t, sq, sk, sq_p, sk_p, False)
        any_, all_, pairs = _tiles_by_definition(mask, g)
        visits, bodies = _tiles_by_the_loops(mask, kernel, g)
        assert (visits == any_).all(), kernel
        assert all_[(visits > 0) & (bodies == 0)].all(), kernel
        crossed = int((any_ & ~all_).sum())
        if slack:
            assert crossed <= bodies.sum() <= 1.5 * crossed + 2, kernel
        else:
            assert ((bodies > 0) == (any_ & ~all_)).all(), kernel
        assert pairs == mask.pairs(sq, sk)
        got = _tile_counts(kernel, plan, sq, sk, mask.causal, mask)
        assert got["tiles_visited"] == visits.sum(), kernel
        assert got["tiles_masked"] == bodies.sum(), kernel
        assert got.get("tiles_needed", visits.sum()) == visits.sum(), kernel
        assert got["tiles_ideal"] == pytest.approx(
            pairs / (t.sub_q * t.sub_k), abs=1e-3)
        if yardstick:
            needed, edge = yardstick(t)
            assert (needed, edge) == (visits.sum(), crossed), kernel


@pytest.mark.parametrize("sq,sk,d,dtype", [
    (2048, 2048, 64, "bfloat16"),       # the benchmark's LM cell
    (4096, 4096, 128, "bfloat16"),
    (8192, 8192, 128, "float32"),
    (128, 16384, 128, "bfloat16"),
])
def test_flash_plan_stays_inside_vmem_and_near_the_triangle(sq, sk, d,
                                                            dtype):
    from mxnet_tpu.ops.attention import (_KERNELS, _flash_plan, _plan_args,
                                         _tile_counts)
    plan = _flash_plan(sq, sk, d, dtype)
    rec = _plan_args(plan, sq, sk, d, dtype, True)
    assert rec["d_block"] == d
    from mxnet_tpu.ops import attention as A
    # the forward inside Mosaic's default 16 MiB; the backward's blocks
    # and tile inside the same budget, its dq side inside `_VMEM_DQ`, and
    # what its call asks for no more than those and Mosaic's share
    assert rec["fwd"]["vmem_bytes"] <= A._VMEM_BUDGET < 16 << 20
    bwd, itemsize = rec["bwd"], jnp.dtype(dtype).itemsize
    dq = A._dq_bytes("vmem", plan.sq_bwd, plan.d_block, itemsize)
    assert bwd["dq_accumulator"] == "vmem" and dq <= A._VMEM_DQ
    assert bwd["vmem_bytes"] - dq <= A._VMEM_BUDGET
    assert bwd["vmem_bytes"] + A._VMEM_MOSAIC <= bwd["vmem_limit_bytes"] \
        <= A._VMEM_BUDGET + A._VMEM_DQ + A._VMEM_MOSAIC == 64 << 20
    for kernel in _KERNELS:
        if sq == sk:
            assert rec[kernel]["tiles_visited"] <= \
                _TRIANGLE_SLACK * rec[kernel]["tiles_ideal"], kernel
            # the diagonal's tiles alone are masked: one for each
            # sub-tile along the tile's shorter edge
            assert rec[kernel]["tiles_masked"] == \
                sq // min(getattr(plan, kernel)[2:]), kernel
        # the plan is a function of what the call sees, nothing else
        assert _tile_counts(kernel, _flash_plan(sq, sk, d, dtype), sq, sk,
                            True) == _tile_counts(kernel, plan, sq, sk,
                                                  True)


def test_equal_descriptions_share_one_traced_kernel():
    """A description hashes by value: every layer of a model that gives an
    equal one (a fresh object each) finds the wrappers' trace cache, so a
    kernel body is traced once a shape and not once a layer (`setup_s`)."""
    from mxnet_tpu.ops import attention as A
    q, _, _ = _rand_qkv(b=1, h=2, sq=128, sk=128, d=16)
    cases = ((True, lambda: A.Window(40)), (True, lambda: None),
             (False, lambda: A.BlockDiffusion(4, 64)))
    before = A._flash_fwd_pallas._cache_size()
    for _ in range(3):
        for causal, mask in cases:
            flash_attention(q, q, q, causal=causal, interpret=True,
                            mask=mask())
    assert A._flash_fwd_pallas._cache_size() - before == len(cases)
    assert A.Window(40) == A.Window(40) != A.Window(41)
    assert A.Full() != A.Causal() and len({A.Causal(), A.Causal()}) == 1


def test_flash_plan_is_recorded_once_per_traced_call():
    """`mx.flash.plan`: one span where the call is traced, none where
    the compiled program runs."""
    from mxnet_tpu import profiler

    @jax.jit
    def f(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True)

    q, k, v = _rand_qkv(b=1, h=1, sq=32, sk=32, d=64)
    import time
    t0 = time.perf_counter()
    f(q, k, v).block_until_ready()
    first = [s for s in profiler.spans(since=t0)
             if s.name == "mx.flash.plan"]
    assert len(first) == 1
    args = first[0].args
    assert (args["sq"], args["sk"], args["d"], args["causal"]) == \
        (32, 32, 64, True)
    for kernel in ("fwd", "bwd"):
        assert set(args[kernel]) >= {"tiles_visited", "tiles_masked",
                                     "tiles_ideal", "resident", "sub_tile",
                                     "vmem_bytes"}
    assert not {"dkdv", "dq"} & set(args)
    assert args["bwd"]["dq_accumulator"] == "vmem"
    assert args["bwd"]["vmem_limit_bytes"] >= args["bwd"]["vmem_bytes"]
    assert args["d_block"] == 64
    f(q, k, v).block_until_ready()
    assert len([s for s in profiler.spans(since=t0)
                if s.name == "mx.flash.plan"]) == 1


def test_traced_training_step_holds_exactly_the_two_flash_scopes():
    import re
    aval = jax.ShapeDtypeStruct((2, 4, 2048, 64), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
        aval, aval, aval).lower(lowering_platforms=("tpu",)).as_text(
            debug_info=True)
    assert set(re.findall(r"mx\.flash\.(\w+)", text)) == {"fwd", "bwd"}
    assert set(re.findall(r"mx_flash_\w+", text)) == \
        {"mx_flash_fwd", "mx_flash_bwd"}
    assert text.count("tpu_custom_call") == 2
    # no head-dim padding and no lane-replicated statistics around them
    assert "x128xbf16" not in text and "x2048x128xf32" not in text


@pytest.mark.parametrize("s,d,d_v,sha", [
    (2048, 64, 64,
     "c90fe3b230b0e563445ae24e8e0b1cb912c1359ba1d4c947bb5f6fa50e8d19b0"),
    (8192, 64, 64,
     "3f340183c39ead4ebe8801fe8e998d129fde443d75ca807321f6cf62b5792909"),
    (8192, 192, 128,
     "32fe50cfa89e3417db19a0f7682f539741c0f60d15b8fa25d3d081a85b14a789"),
])
def test_the_forward_kernel_s_jaxpr_is_the_one_before_the_fused_backward(
        s, d, d_v, sha, tmp_path):
    """The forward call at the three LM cells' shapes, as text (source
    locations cut), is commit 4c772db's to the letter: what changes the
    backward leaves `mx_flash_fwd` the program it was.  The hashes are
    that commit's (PR 31's evidence for its unchanged forward); a change
    to the forward that an issue asks for, or a JAX that prints jaxprs
    another way, re-pins them: on a mismatch the text is written out to
    be set beside `git show 4c772db:mxnet_tpu/ops/attention.py`'s."""
    import hashlib
    import re
    q = jax.ShapeDtypeStruct((1, 2, s, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 2, s, d_v), jnp.bfloat16)
    text = str(jax.make_jaxpr(lambda q, k, v: _flash_fwd_pallas(
        q, k, v, True, d ** -0.5, with_lse=True))(q, q, v))
    assert "mx_flash_fwd" in text
    text = re.sub(r" at \S+:\d+", "", text)
    got = hashlib.sha256(text.encode()).hexdigest()
    if got != sha:
        path = tmp_path / ("mx_flash_fwd_%d_%d_%d.jaxpr.txt" % (s, d, d_v))
        path.write_text(text)
        pytest.fail(
            "the forward kernel's jaxpr at (%d, %d / %d) is no longer commit "
            "4c772db's: sha256 %s, %d lines, written to %s (jax %s)"
            % (s, d, d_v, got, text.count("\n"), path, jax.__version__))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_lowers_for_tpu_without_a_chip(causal):
    """The public op, lowered for the TPU platform from this CPU host:
    the Pallas path is what a TPU program gets, and every block shape
    passes the Mosaic lowering's tiling rule (a ``(1, blk_q)`` block over
    a ``(bh, sq)`` array used to be refused here for the gradient)."""
    aval = jax.ShapeDtypeStruct((2, 4, 2048, 64), jnp.bfloat16)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=causal)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    def calls(f):
        text = jax.jit(f).trace(aval, aval, aval).lower(
            lowering_platforms=("tpu",)).as_text()
        # the chunked scan is the other platforms' branch only
        assert "stablehlo.while" not in text
        return text.count("tpu_custom_call")

    assert calls(fwd) == 1
    assert calls(jax.grad(loss, argnums=(0, 1, 2))) == 2   # fwd, bwd


def test_flash_under_a_mesh_runs_per_shard():
    """XLA does not partition a Mosaic kernel: traced under a trainer's
    mesh the TPU branch shard_maps over it (dp splits the batch), so a
    sharded step still lowers — with one kernel call, on local shapes."""
    from mxnet_tpu.parallel.mesh import use_mesh
    mesh = make_mesh({"dp": 2}, jax.devices()[:2])
    aval = jax.ShapeDtypeStruct((4, 2, 256, 64), jnp.bfloat16)

    def fwd(q, k, v):
        with use_mesh(mesh):
            return flash_attention(q, k, v, causal=True)

    text = jax.jit(fwd).trace(aval, aval, aval).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 1
    assert "sdy.manual_computation" in text or "shard_map" in text

    # already per shard (a PipelineTrainer stage traces like this): the
    # kernel is called as is, not shard_mapped a second time
    from jax.sharding import PartitionSpec as P

    def staged(q, k, v):
        return jax.shard_map(fwd, mesh=mesh, in_specs=(P("dp"),) * 3,
                             out_specs=P("dp"), check_vma=False)(q, k, v)

    text = jax.jit(staged).trace(aval, aval, aval).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 1
