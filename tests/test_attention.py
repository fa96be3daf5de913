"""Attention stack tests: chunked/flash attention vs the einsum oracle,
ring attention on the virtual 8-device mesh (SURVEY §5.7 TPU stance)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops.attention import (attention_reference, _chunked_attention,
                                     _flash_fwd_pallas, flash_attention)
from mxnet_tpu.parallel import make_mesh, sequence_parallel_attention


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


@pytest.mark.parametrize("causal,sq,sk,d,blk", [
    (False, 48, 48, 16, 16),
    (True, 48, 48, 16, 16),
    (True, 24, 72, 8, 24),    # cross-length causal, uneven blocks
    (False, 40, 56, 24, 16),  # seq not divisible by block, d not 128
])
def test_pallas_flash_backward_interpret(causal, sq, sk, d, blk):
    from mxnet_tpu.ops.attention import _flash_fwd_pallas, _flash_bwd_pallas
    q, k, v = _rand_qkv(b=1, h=2, sq=sq, sk=sk, d=d)
    scale = 1.0 / np.sqrt(d)
    out, lse = _flash_fwd_pallas(q, k, v, causal, scale, blk_q=blk,
                                 blk_k=blk, interpret=True, with_lse=True)
    g = jnp.asarray(np.random.RandomState(9).randn(
        *out.shape).astype(np.float32))
    dq, dk, dv = _flash_bwd_pallas(q, k, v, out, lse, g, causal, scale,
                                   blk_q=blk, blk_k=blk, interpret=True)
    ref, vjp = jax.vjp(
        lambda a, b, c: attention_reference(a, b, c, causal=causal,
                                            sm_scale=scale), q, k, v)
    rq, rk, rv = vjp(g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rq),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rk),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rv),
                               rtol=2e-4, atol=2e-4)


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
    assert calls(jax.grad(loss, argnums=(0, 1, 2))) == 3   # fwd, dkdv, dq


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
