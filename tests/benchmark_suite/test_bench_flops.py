"""The FLOP shape functions (benchmarks/flops.py) against XLA's own count
of the plain references at tiny sizes, and against known totals."""

import jax
import jax.numpy as jnp
import pytest

import bench_suite_util  # noqa: F401
from benchmarks import flops
from benchmarks.reference import common, resnet, transformer_lm


def _xla_flops(fn, *args):
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return cost["flops"]


LM = {"vocab_size": 512, "hidden_size": 128, "ffn_dim": 512,
      "num_attention_heads": 4, "num_hidden_layers": 2,
      "max_position_embeddings": 256}


def test_lm_forward_matches_xla_with_attention_priced_in_full():
    p = jax.eval_shape(lambda: common.init_params(
        transformer_lm.param_table(LM), 0))
    tokens = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    xla = _xla_flops(lambda p, t: transformer_lm.logits(p, LM, t), p, tokens)
    # the plain reference computes every score, so XLA counts attention in
    # full; contractions are nearly all of it (norms, softmax: a few %)
    full = flops.transformer_lm_forward_flops(512, 128, 512, 2, 256,
                                              causal=False)
    assert full <= xla <= 1.08 * full


def test_causal_attention_is_priced_as_half():
    args = (512, 128, 512, 2, 256)
    full = flops.transformer_lm_forward_flops(*args, causal=False)
    half = flops.transformer_lm_forward_flops(*args, causal=True)
    attention = 2 * 2 * 2 * 256 * 256 * 128
    assert full - half == attention // 2
    assert flops.transformer_lm_train_flops(*args) == 3 * half


def test_opt_1_3b_per_token():
    per_seq = flops.transformer_lm_train_flops(50272, 2048, 8192, 24, 2048)
    matmul_params = 24 * (4 * 2048 ** 2 + 2 * 2048 * 8192) + 50272 * 2048
    assert per_seq / 2048 == pytest.approx(
        6 * matmul_params + 3 * 24 * 2 * 2048 * 2048)


RESNET = {"image_size": 224, "stem_channels": 64, "units": [3, 4, 6, 3],
          "stage_channels": [256, 512, 1024, 2048], "classes": 10}


def test_resnet_forward_matches_xla():
    p = jax.eval_shape(lambda: common.init_params(
        resnet.param_table(RESNET), 0))
    images = jax.ShapeDtypeStruct((1, 3, 224, 224), jnp.float32)
    xla = _xla_flops(lambda p, x: resnet.logits(p, RESNET, x), p, images)
    ours = flops.resnet_forward_flops(RESNET)
    # XLA leaves out the taps that fall on padding and adds batch norm's
    # elementwise work: a few % either way at 224
    assert 0.95 * ours <= xla <= 1.08 * ours


def test_resnet50_is_3_86_gmacs_at_224():
    cfg = dict(RESNET, classes=1000)
    assert flops.resnet_forward_flops(cfg) / 2e9 == pytest.approx(3.86,
                                                                  abs=0.01)
