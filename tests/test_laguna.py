"""Window and full attention mixed by layer: the causal window in
`ops/attention.py` (the `jax.numpy` body, the two flash kernels interpreted,
their plan), `_contrib_HeadNormRotary` over a part of a head at given
frequencies, `GroupedQueryAttention` with a window, an output gate and
rotary settings by layer kind, the decoder kind `sliding_attention` with
head counts a layer, against the window's definition
(`benchmarks/swa_counts.py`) and the plain float32 reference
`benchmarks/reference/laguna.py`, at a small size on the CPU with seeded
weights: float32 on both sides, so only the order of the arithmetic
differs."""

import hashlib
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import compare, swa_counts  # noqa: E402
from benchmarks.models import common as models_common  # noqa: E402
from benchmarks.models import laguna as family  # noqa: E402
from benchmarks.reference import common as ref_common  # noqa: E402
from benchmarks.reference import laguna as reference  # noqa: E402
from mxnet_tpu import profiler  # noqa: E402
from mxnet_tpu.ops import attention, lm_blocks  # noqa: E402
from mxnet_tpu.ops.registry import get_op  # noqa: E402

SEED = 2 ** 31 + 7
YARN = {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5}


def config(**changes):
    """The cell's shapes, small: two kinds of layer with different head
    counts, a window shorter than the sequence, half a head turned by YaRN's
    frequencies in the full layers, 16 router outputs with 4 held."""
    cfg = {"family": "laguna", "hidden_size": 64, "intermediate_size": 128,
           "moe_intermediate_size": 32,
           "shared_expert_intermediate_size": 32, "num_experts_per_tok": 3,
           "router_experts": 16, "num_experts": 4, "first_expert": 4,
           "num_attention_heads": 4,
           "num_attention_heads_per_layer": [4, 6, 6, 4],
           "num_key_value_heads": 2, "head_dim": 16,
           "layer_types": ["full_attention", "sliding_attention",
                           "sliding_attention", "full_attention"],
           "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"],
           "gating_types": ["per_head"] * 4, "gating": "per-head",
           "sliding_window": 20, "rms_norm_eps": 1e-6,
           "norm_topk_prob": True, "moe_routed_scaling_factor": 2.5,
           "rope_parameters": {
               "full_attention": dict(
                   YARN, factor=8, original_max_position_embeddings=16,
                   beta_fast=4, attention_factor=1.2),
               "sliding_attention": {"rope_type": "default",
                                     "rope_theta": 10000,
                                     "partial_rotary_factor": 1}},
           "tie_word_embeddings": False, "num_hidden_layers": 4,
           "vocab_size": 96, "initializer_range": 0.02,
           "embedding_initializer_range": 1.0,
           "train": {"optimizer": "sgd", "lr": 0.01, "momentum": 0.9,
                     "wd": 0.0, "multi_precision": False,
                     "sequence_length": 48, "per_chip_batch": 2}}
    cfg.update(changes)
    return cfg


def cut(layers, **changes):
    """`config` at its first *layers* layers."""
    cfg = config(num_hidden_layers=layers, **changes)
    for key in ("num_attention_heads_per_layer", "layer_types",
                "mlp_layer_types", "gating_types"):
        cfg[key] = cfg[key][:layers]
    return cfg


def seeded(cfg, seed=SEED):
    """``(net, loss, names, reference parameters)`` from one seed."""
    table = reference.param_table(cfg)
    net, loss = family.build(cfg)
    names = models_common.seeded_net(
        net, table, ref_common.init_params(table, seed))
    return net, loss, names, ref_common.init_params(table, seed)


def highest(fn, *args):
    with jax.default_matmul_precision("highest"):
        return jax.jit(fn)(*args)


def rand(i, *shape, scale=1.0):
    return scale * jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(44), i), shape, jnp.float32)


def dense(q, k, v, window, scale):
    """The masked softmax written out, the mask from
    `swa_counts.visible`."""
    sq, sk = q.shape[2], k.shape[2]
    seen = jnp.asarray(swa_counts.visible(
        np.arange(sq)[:, None] + (sk - sq), np.arange(sk)[None, :], window))
    att = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") * scale
    p = jax.nn.softmax(jnp.where(seen, att, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")


# -- the window ----------------------------------------------------------------
@pytest.mark.parametrize("seq,window", [(64, 20), (48, 1), (48, 48),
                                        (50, 7), (8192, 512)])
def test_the_window_is_its_definition_and_leaves_what_the_count_says(
        seq, window):
    pos = np.arange(seq)
    want = swa_counts.visible(pos[:, None], pos[None, :], window)
    got = np.asarray(attention.Window(window).visible(
        jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]))
    assert (got == want).all()
    # a row of `window` or more positions sees `window` keys, its own the
    # last; an earlier row all it has
    assert want.sum(-1).tolist() == [min(t + 1, window) for t in range(seq)]
    assert want.sum() == swa_counts.visible_pairs(seq, window) \
        == attention.Window(window).pairs(seq, seq)
    assert reference.sees(pos[:, None], pos[None, :], window).sum() \
        == want.sum()


def test_the_cell_s_window_shows_an_eighth_of_the_triangle():
    assert swa_counts.visible_pairs(8192, 512) == 4063488
    assert swa_counts.causal_pairs(8192) == 33558528
    assert 4063488 / 33558528 == pytest.approx(0.121, abs=5e-4)
    assert swa_counts.visible_pairs(4096, 512) == 1966336
    # a window of the whole sequence is the causal mask
    assert swa_counts.visible_pairs(512, 512) == swa_counts.causal_pairs(512)
    assert swa_counts.visible_pairs(100, 4096) == swa_counts.causal_pairs(100)


def test_a_window_that_is_not_one_is_refused():
    q = rand(0, 1, 2, 32, 16)
    with pytest.raises(ValueError, match="causal=True"):
        attention.flash_attention(q, q, q, mask=attention.Window(8))
    with pytest.raises(ValueError, match="at least one key"):
        attention.flash_attention(q, q, q, causal=True,
                                  mask=attention.Window(0))
    # a window that leaves every causal key visible is no mask at all
    assert attention._described(True, attention.Window(32), 32, 32) \
        == attention._described(True, None, 32, 32) == attention.Causal()
    assert attention._described(True, attention.Window(31), 32, 32) \
        == attention.Window(31)


#: ``(seq_q, seq_k, d, window, tiles)``: a window that is and is not whole
#: sub-tiles, narrower and wider than a tile's two edges together (whole
#: tiles between the edges then), a padded sequence, a shorter query
#: sequence (ends aligned), resident blocks of several sub-tiles
KERNEL_CASES = {
    "whole-sub-tiles": (512, 512, 64, 128, dict(blk_q=128, blk_k=128)),
    "not-whole": (512, 512, 64, 100, dict(blk_q=128, blk_k=128)),
    "whole-tiles-inside": (512, 512, 64, 300, dict(
        blk_q=64, blk_k=128, res_q=128, res_k=256)),
    "padded": (500, 500, 64, 77, dict(
        blk_q=128, blk_k=128, res_q=256, res_k=256)),
    "fewer-queries": (384, 512, 64, 130, dict(
        blk_q=128, blk_k=128, res_q=128, res_k=256)),
    "one-key": (256, 256, 32, 1, dict(blk_q=128, blk_k=128)),
    "the-plan-s-own": (1024, 1024, 128, 512, {}),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernels_and_body_are_the_dense_masked_softmax(case):
    """The two flash kernels interpreted and the chunked `jax.numpy` body
    against the mask's definition: the output and all three gradients."""
    sq, sk, d, window, tiles = KERNEL_CASES[case]
    q, k, v, g = (rand(10 + i, 1, 2, s, d)
                  for i, s in enumerate((sq, sk, sk, sq)))
    mask, scale = attention.Window(window), d ** -0.5
    want, vjp = jax.vjp(lambda q, k, v: dense(q, k, v, window, scale),
                        q, k, v)
    out, lse = attention._flash_fwd_pallas(
        q, k, v, True, scale, interpret=True, with_lse=True, mask=mask,
        **tiles)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=3e-6)
    got = attention._flash_bwd_pallas(q, k, v, out, lse, g, True, scale,
                                      interpret=True, mask=mask, **tiles)
    for name, a, b in zip("qkv", got, vjp(g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   err_msg="d" + name)
    body, body_vjp = jax.vjp(lambda q, k, v: attention._chunked_attention(
        q, k, v, True, scale, 128, mask), q, k, v)
    np.testing.assert_allclose(np.asarray(body), np.asarray(want), atol=3e-6)
    for a, b in zip(body_vjp(g), vjp(g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    np.testing.assert_allclose(np.asarray(attention.attention_reference(
        q, k, v, True, scale, mask)), np.asarray(want), atol=3e-6)


def test_a_window_of_the_whole_sequence_is_the_causal_kernel():
    """``w >= S``: the description is dropped before any kernel is built,
    so the call IS the causal one, to the bit and in its jaxpr."""
    q, k, v = (rand(20 + i, 1, 2, 256, 32) for i in range(3))
    for window in (256, 300):
        got = attention.flash_attention(q, k, v, causal=True, interpret=True,
                                        mask=attention.Window(window))
        want = attention.flash_attention(q, k, v, causal=True, interpret=True)
        assert (np.asarray(got) == np.asarray(want)).all()
    op = get_op("_contrib_DotProductAttention").fn
    np.testing.assert_allclose(
        np.asarray(op(q, k, v, causal=True, window=300)),
        np.asarray(op(q, k, v, causal=True)), atol=1e-6)


def test_the_public_op_looks_through_the_window_and_counts_its_pairs():
    q, k, v = (rand(30 + i, 2, 2, 96, 16) for i in range(3))
    op = get_op("_contrib_DotProductAttention").fn
    with profiler.collect_step_stats() as stats:
        got = op(q, k, v, causal=True, sm_scale=0.25, window=20)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(dense(q, k, v, 20, 0.25)),
                               atol=3e-6)
    assert [float(x) for x in stats["swa_visible_pairs"]] == [
        2.0 * swa_counts.visible_pairs(96, 20)]
    before = profiler.counter_value("swa_visible_pairs_total")
    profiler.fold_step_stats({n: np.stack([np.asarray(x) for x in v])
                              for n, v in stats.items()})
    assert profiler.counter_value("swa_visible_pairs_total") - before \
        == 2 * swa_counts.visible_pairs(96, 20)


# -- the plan -------------------------------------------------------------------
# (the loops against the window's definition at eight shapes, the cell's among
# them, and against `swa_counts.tiles`: cases `Window-*` of
# tests/test_attention.py's one test over descriptions)
def test_the_plan_at_8192_through_512_is_62_tiles_of_272():
    """At `_SUB_LOOPED` (256 queries by 512 keys) and 8192 positions: the
    first two rows of tiles see one tile, the other thirty two, every one
    under a mask body (the window's edge or the diagonal crosses it): 8.13 M
    pairs computed for 4.06 M visible; a causal kernel visits 272."""
    plan = attention._flash_plan(8192, 8192, 128, jnp.bfloat16)
    args = attention._plan_args(plan, 8192, 8192, 128, jnp.bfloat16, True,
                                None, attention.Window(512))
    assert (args["mask"], args["window"], args["causal"]) == (
        "window", 512, True)
    for kernel in ("fwd", "bwd"):
        assert args[kernel]["sub_tile"] == [256, 512]
        assert args[kernel]["tiles_visited"] == 62
        assert args[kernel]["tiles_needed"] == 62
        assert args[kernel]["tiles_masked"] == 62
        assert args[kernel]["tiles_ideal"] == 31.002
    assert swa_counts.tiles(8192, 512, 256, 512) == (62, 62)
    assert 62 * 256 * 512 == 8126464
    # two thirds full at 256 x 256: the next step's yardstick
    assert swa_counts.tiles(8192, 512, 256, 256) == (93, 62)
    causal = attention._plan_args(plan, 8192, 8192, 128, jnp.bfloat16, True)
    assert causal["fwd"]["tiles_visited"] == causal["bwd"][
        "tiles_visited"] == 272
    assert "mask" not in causal and "tiles_needed" not in causal["fwd"]


def test_the_plan_span_carries_the_window():
    q = rand(40, 1, 2, 128, 16)
    since = max([s.id for s in profiler.spans()] or [0])
    attention.flash_attention(q, q, q, causal=True, interpret=True,
                              mask=attention.Window(40))
    args = [s for s in profiler.spans()
            if s.name == "mx.flash.plan" and s.id > since][-1].args
    assert (args["mask"], args["window"], args["causal"]) == (
        "window", 40, True)
    for kernel in ("fwd", "bwd"):
        assert args[kernel]["tiles_visited"] == args[kernel]["tiles_needed"]


#: the parent's kernels at the six language cells' shapes, as text
#: (`jaxpr_sha`), forward then backward: causal (OPT, LFM2, Kanana), with a
#: selection operand (Keye), under the block-diffusion mask (SDAR), all five
#: commit d96fd39's, and through a window (Laguna), commit c1cc16f's
PARENT_KERNELS = {
    "opt-1.3b_train_1chip": (
        "c90fe3b230b0e563445ae24e8e0b1cb912c1359ba1d4c947bb5f6fa50e8d19b0",
        "7b1a57f9e47f01055a3f621edc3d25d77492ce4522fe182c144cdd280195bd6a"),
    "lfm2-8b-a1b_train_ep4share": (
        "3f340183c39ead4ebe8801fe8e998d129fde443d75ca807321f6cf62b5792909",
        "bdbb555b4c54de4d3a35ae0911502d8c9dbfdd26fe458dc879595836f50fe4e2"),
    "kanana-2-30b-a3b_train_ep8share": (
        "32fe50cfa89e3417db19a0f7682f539741c0f60d15b8fa25d3d081a85b14a789",
        "ea6c3eb0c6dbace358e57fa9bf8b380cc93996ff763dd492b0bd8de9e38c8d37"),
    "keye-vl-2.0-30b-a3b_train_ep8share": (
        "224296eb4223e4f3be806cc58e1ef3fb4896b82ff65818268f731552257ad200",
        "d83fed3c838e667129452e2a973f66c5c6be3878718473640cc3e48df92e6fec"),
    "sdar-30b-a3b-chat_train_ep8share": (
        "3e1fe5d3cf180766c1f59efde4a8cb60631cc621b615c4c8a8d16bfb188dc0e2",
        "089ec1634b8d48e9a02d3127156436715c85f7cdc1707b2f90f7c547eac9aa5e"),
    "laguna-s-2.1_train_ep32share": (
        "86abead6355761858da04e2575e36b9c0123d0a3223c2765b7b002b143e25346",
        "3e6ba351529d227cfe1370eb803efe6330eef81bfc0fddeca79b45660136614d"),
}
SHAPES = {"opt-1.3b_train_1chip": (2048, 64, 64),
          "lfm2-8b-a1b_train_ep4share": (8192, 64, 64),
          "kanana-2-30b-a3b_train_ep8share": (8192, 192, 128),
          "keye-vl-2.0-30b-a3b_train_ep8share": (16384, 128, 128),
          "sdar-30b-a3b-chat_train_ep8share": (16384, 128, 128),
          "laguna-s-2.1_train_ep32share": (4096, 128, 128)}


def jaxpr_sha(fn, *avals):
    text = re.sub(r" at \S+:\d+", "", str(jax.make_jaxpr(fn)(*avals)))
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
@pytest.mark.parametrize("cell", sorted(PARENT_KERNELS))
def test_the_language_cells_kernels_trace_as_the_parent_s(cell, kernel):
    """A change to how a mask is described reorders Python, not the program:
    the causal, selected, block-diffusion and window calls at the six
    language cells' shapes are the parent's to the letter (a JAX that prints
    jaxprs another way re-pins them)."""
    s, d, d_v = SHAPES[cell]
    q = jax.ShapeDtypeStruct((1, 2, s, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 2, s, d_v), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((2, 1, s), jnp.float32)
    extra, avals = {}, ()
    causal = True
    if cell.startswith("keye"):
        avals = (jax.ShapeDtypeStruct((1, s // 32, s), jnp.int32),)
    elif cell.startswith("sdar"):
        causal, extra = False, {"mask": attention.BlockDiffusion(4, s // 2)}
    elif cell.startswith("laguna"):
        extra = {"mask": attention.Window(512)}

    def sel(rest):
        return dict(extra, sel=rest[0]) if rest else extra

    if kernel == "fwd":
        got = jaxpr_sha(lambda q, k, v, *rest: attention._flash_fwd_pallas(
            q, k, v, causal, d ** -0.5, with_lse=True, **sel(rest)),
            q, q, v, *avals)
    else:
        got = jaxpr_sha(
            lambda q, k, v, o, l, do, *rest: attention._flash_bwd_pallas(
                q, k, v, o, l, do, causal, d ** -0.5, **sel(rest)),
            q, q, v, v, lse, v, *avals)
    assert got == PARENT_KERNELS[cell][kernel == "bwd"]


# -- rotary positions over a part of a head -------------------------------------
def test_the_yarn_table_is_the_equations_in_float64():
    """`rope_frequencies` against a transcription of ISSUE 44's equations in
    numpy float64, at the published settings: 64 of a head's 128 dims,
    theta 500000, factor 128 from 8192 positions."""
    got = lm_blocks.rope_frequencies(YARN, 128)
    assert got["rotary_dim"] == 64 and got["theta"] == 500000.0
    assert got["table_scale"] == 1.4852030263919618
    assert got["table_scale"] == pytest.approx(0.1 * math.log(128) + 1)
    i = np.arange(32, dtype=np.float64)
    e = 500000.0 ** (-2.0 * i / 64)
    n = e / 128.0

    def c(r):
        return 64 * np.log(8192 / (2 * np.pi * r)) / (2 * np.log(500000.0))

    low, high = max(np.floor(c(32)), 0), min(np.ceil(c(1)), 63)
    assert (low, high) == (9, 18)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = n * ramp + e * (1 - ramp)
    np.testing.assert_allclose(np.asarray(got["inv_freq"]), want, rtol=1e-14)
    # the fast pairs keep their frequency, the slow ones turn 128 times
    # slower
    assert got["inv_freq"][0] == 1.0 and got["inv_freq"][9] == e[9]
    assert got["inv_freq"][18] == pytest.approx(e[18] / 128)
    # ... and the reference's own transcription agrees
    r, inv, scale = reference.inv_frequencies(YARN, 128)
    assert (r, scale) == (64, got["table_scale"])
    np.testing.assert_allclose(inv, want, rtol=1e-14)
    # one theta over the whole head is today's op: no table is given
    assert lm_blocks.rope_frequencies(
        {"rope_type": "default", "rope_theta": 10000,
         "partial_rotary_factor": 1}, 128) == {"theta": 10000.0}
    with pytest.raises(ValueError, match="rope_type"):
        lm_blocks.rope_frequencies({"rope_type": "linear"}, 128)


def test_given_frequencies_of_one_theta_over_the_whole_head_are_rotary():
    """``rotary_dim == d``, one theta's frequencies and scale 1: `_rotary`,
    bit for bit (under `jax.disable_jit`: XLA's CPU backend fuses another
    program otherwise than this one)."""
    x = rand(50, 2, 3, 24, 16).astype(jnp.bfloat16)
    inv = tuple(1.0 / (1e6 ** (np.arange(8, dtype=np.float64) / 8)))
    with jax.disable_jit():
        got = lm_blocks._rotary_given(x, 16, inv, 1.0)
        want = lm_blocks._rotary(x, 1e6)
    assert (np.asarray(got, np.float32) == np.asarray(want,
                                                      np.float32)).all()


def test_the_head_norm_rotary_op_at_today_s_arguments_is_the_parent_s():
    """Without `inv_freq` the op is the op it was: commit d96fd39's jaxpr."""
    y = jax.ShapeDtypeStruct((1, 512, 4 * 128), jnp.bfloat16)
    g = jax.ShapeDtypeStruct((128,), jnp.float32)
    assert jaxpr_sha(lambda y, g: lm_blocks._head_norm_rotary_op(
        y, g, num_heads=4, theta=1e6, eps=1e-6), y, g) == \
        "f1f7a9343c6ba124c9bd1b503e5522ee1b0f190f5bcf0d6e98982a71ab462f20"


def test_half_a_head_turns_and_half_passes_through():
    y = rand(51, 2, 24, 3 * 16)
    gamma = 1.0 + 0.1 * rand(52, 16)
    turn = config()["rope_parameters"]["full_attention"]
    given = lm_blocks.rope_frequencies(turn, 16)
    assert given["rotary_dim"] == 8 and len(given["inv_freq"]) == 4
    since = max([s.id for s in profiler.spans()] or [0])
    got = get_op("_contrib_HeadNormRotary").fn(
        y, gamma, num_heads=3, eps=1e-6, **given)
    normed = reference.rms(y.reshape(2, 24, 3, 16), gamma, 1e-6).transpose(
        0, 2, 1, 3)
    want = reference.rope(normed, turn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    # the last eight dims of every head are the normed input, untouched
    assert (np.asarray(got)[..., 8:] == np.asarray(normed)[..., 8:]).all()
    # the pair (i, i + 4) turns by pos * inv_freq_i, scaled
    pos, f = 5, given["inv_freq"][1] * 5
    a, b = np.asarray(normed)[0, 0, pos, 1], np.asarray(normed)[0, 0, pos, 5]
    assert np.asarray(got)[0, 0, pos, 1] == pytest.approx(
        1.2 * (a * math.cos(f) - b * math.sin(f)), abs=1e-6)
    span = [s for s in profiler.spans()
            if s.name == "mx.headrope.plan" and s.id > since][-1]
    assert span.args["path"] == "xla"
    assert "8 of a head's 16" in span.args["why"]
    with pytest.raises(ValueError, match="frequencies do not turn"):
        get_op("_contrib_HeadNormRotary").fn(
            y, gamma, num_heads=3, rotary_dim=8, inv_freq=(1.0, 0.5))


# -- the attention block and the decoder ----------------------------------------
def test_a_decoder_layer_of_the_kind_needs_its_window():
    from mxnet_tpu.gluon.model_zoo.decoder import (OPERATOR_KINDS,
                                                    get_decoder_lm)
    assert OPERATOR_KINDS[5] == "sliding_attention"
    shape = dict(vocab=32, dim=64, num_dense_layers=1, dense_hidden=64,
                 expert_hidden=32, num_experts=4, num_experts_per_tok=1,
                 kv_heads=2, head_dim=16)
    with pytest.raises(ValueError, match="sliding_window"):
        get_decoder_lm(layer_types=["sliding_attention"], heads=4, **shape)
    with pytest.raises(ValueError, match="through a causal window"):
        get_decoder_lm(layer_types=["local_attention"], heads=4, **shape)
    with pytest.raises(ValueError, match="3 head counts for 2 layers"):
        get_decoder_lm(layer_types=["full_attention"] * 2, heads=[4, 4, 4],
                       **shape)


def test_the_block_takes_its_settings_by_layer_kind():
    net, _ = family.build(config())
    ops = [layer.operator for layer in net.layers]
    assert [o._heads for o in ops] == [4, 6, 6, 4]
    assert [o._mask for o in ops] == [
        {"causal": True}, {"causal": True, "window": 20},
        {"causal": True, "window": 20}, {"causal": True}]
    # the sliding layers turn the whole head by one theta (today's op, and
    # so the kernel pair's on the chip); the full ones half of it by a table
    assert ops[1]._rotary == {"theta": 10000.0}
    assert ops[0]._rotary["rotary_dim"] == 8
    assert ops[0]._rotary["table_scale"] == 1.2
    assert [o.gate_weight.shape for o in ops] == [
        (4, 64), (6, 64), (6, 64), (4, 64)]
    assert [o._group["__scope__"] for o in ops] == [
        "mx.gqa.project", "mx.swa.project", "mx.swa.project",
        "mx.gqa.project"]
    assert ops[1]._after == {"attention": {"__scope__": "mx.swa.attention"},
                             "out": {"__scope__": "mx.swa.out"}}


def test_the_compiled_layers_lie_under_their_scopes():
    """The lowered step names the three groups of each kind of layer, and a
    plan span a kind says which path the per-head norm and rotary take."""
    import mxnet_tpu as mx
    cfg = cut(2)
    net, loss, _, _ = seeded(cfg)
    (x, y), = family.batches(cfg, SEED, 1, 2)
    trainer = models_common.make_trainer(net, loss, cfg["train"],
                                         jax.devices()[:1])
    since = max([s.id for s in profiler.spans()] or [0])
    trainer.fit_batch(x, y)
    names = set(profiler.scope_map("parallel_step").values())
    for scope in ("mx.gqa.project", "mx.gqa.attention", "mx.gqa.out",
                  "mx.swa.project", "mx.swa.attention", "mx.swa.out"):
        assert any(re.search(r"[/(]%s/" % re.escape(scope), n)
                   for n in names if n), scope
    plans = [s.args for s in profiler.spans()
             if s.name == "mx.headrope.plan" and s.id > since]
    whys = {p["why"] for p in plans}
    assert any("8 of a head's 16 at given frequencies" in w for w in whys)
    # (a head of 16 is not whole lane tiles: the CPU preset's sliding layers
    # keep the body too; the cell's heads of 128 take the kernels, below)
    assert any("not whole 128-lane tiles" in w for w in whys)
    del mx


# -- the whole model ----------------------------------------------------------
KINDS = {"a-full-layer": cut(1), "a-window-layer-on-top": cut(2),
         "all": config(),
         "window-of-one-tile": config(sliding_window=16),
         "window-past-the-sequence": config(sliding_window=64),
         "first-share": config(first_expert=0)}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_logits_loss_and_every_gradient_agree_with_the_reference(kind):
    """Through `ParallelTrainer.fit_batch`: the loss a step reports is the
    mean next-token cross-entropy, and every leaf's gradient is the
    reference's.  Tolerances: float32 on both sides, summed in another
    order (2e-4 of a leaf's largest entry, as the other families')."""
    import mxnet_tpu as mx
    cfg = KINDS[kind]
    net, loss, names, params = seeded(cfg)
    (x, y), = family.batches(cfg, SEED, 1, 2)
    got = net(mx.nd.array(x, dtype="int32")).asnumpy()
    assert got.shape == (2, 48, 96)
    want = highest(lambda p: reference.logits(p, cfg, x), params)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-4, atol=2e-6)

    trainer = models_common.make_trainer(net, loss, cfg["train"],
                                         jax.devices()[:1])
    got_loss = float(trainer.fit_batch(x, y))
    value, grads = highest(jax.value_and_grad(
        lambda p: reference.loss_sum(p, cfg, x, y)), params)
    assert got_loss == pytest.approx(float(value) / 2, rel=1e-5)
    assert set(names) == set(grads)
    lr = cfg["train"]["lr"]
    for ref_name, prog_name in names.items():
        g = -np.asarray(trainer._opt_state[prog_name][0]) / lr
        w = np.asarray(grads[ref_name]) / 2
        scale = max(np.abs(w).max(), 1e-12)
        assert np.abs(g - w).max() <= 2e-4 * scale, ref_name
        assert np.abs(w).max() > 0, ref_name


def test_heads_of_128_through_the_head_rope_pair_agree_with_the_reference(
        interpreted_headrope):
    """A full layer and a window layer at the published head width, where
    the plan gives tiles: the full layer's q and k (4 and 2 heads, half a
    head at YaRN's frequencies) go through the pair by three tables, the
    window layer's (6 and 2, the whole head by one theta) by two, and the
    logits, the loss and every leaf's gradient are the reference's to the
    tolerance the body holds."""
    import mxnet_tpu as mx
    cfg = cut(2, head_dim=128)
    # whole tiles of the interpreted kernels' 32 rows
    cfg["train"] = dict(cfg["train"], sequence_length=64)
    net, loss, names, params = seeded(cfg)
    (x, y), = family.batches(cfg, SEED, 1, 2)
    since = max([s.id for s in profiler.spans()] or [0])
    got = net(mx.nd.array(x, dtype="int32")).asnumpy()
    want = highest(lambda p: reference.logits(p, cfg, x), params)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-4, atol=2e-6)
    trainer = models_common.make_trainer(net, loss, cfg["train"],
                                         jax.devices()[:1])
    got_loss = float(trainer.fit_batch(x, y))
    value, grads = highest(jax.value_and_grad(
        lambda p: reference.loss_sum(p, cfg, x, y)), params)
    assert got_loss == pytest.approx(float(value) / 2, rel=1e-5)
    lr = cfg["train"]["lr"]
    for ref_name, prog_name in names.items():
        g = -np.asarray(trainer._opt_state[prog_name][0]) / lr
        w = np.asarray(grads[ref_name]) / 2
        assert np.abs(g - w).max() <= 2e-4 * max(np.abs(w).max(), 1e-12), \
            ref_name
    plans = [s.args for s in profiler.spans()
             if s.name == "mx.headrope.plan" and s.id > since]
    assert {(p["heads"], p["path"], p["rotary_dim"], p["tables"])
            for p in plans} == {(4, "kernel", 64, 3), (2, "kernel", 64, 3),
                                (6, "kernel", 128, 2), (2, "kernel", 128, 2)}


def test_three_trainer_steps_follow_the_reference():
    cfg = config()
    train = cfg["train"]
    table = reference.param_table(cfg)
    net, loss, names, params = seeded(cfg)
    batches = family.batches(cfg, SEED, 3, 2)
    trainer = models_common.make_trainer(net, loss, train, jax.devices()[:1])
    to_ref = {prog: ref for ref, prog in names.items()}
    got = {"losses": []}
    for i, (x, y) in enumerate(batches):
        got["losses"].append(float(trainer.fit_batch(x, y)))
        if i == 0:
            mom = {n: trainer._opt_state[n][0] for n in trainer.param_names}
            got["first_update_norms"] = ref_common.leaf_norms(mom)
            first = {to_ref[n]: np.asarray(a) for n, a in mom.items()}
    dist = ref_common.distance_from_init(
        table, SEED, {to_ref[n]: trainer._params[n]
                      for n in trainer.param_names})
    got["total_update_norms"] = {names[r]: v for r, v in dist.items()}
    with jax.default_matmul_precision("highest"):
        ref = ref_common.follow_steps(
            lambda p, x, y: reference.loss_sum(p, cfg, x, y), params,
            batches, {"lr": train["lr"], "momentum": train["momentum"],
                      "wd": train["wd"]},
            lambda p: ref_common.distance_from_init(table, SEED, p),
            rows_per_block=1, first_update=first)
    for name, (value, detail) in compare.training_numbers(
            got, ref, names).items():
        assert value <= 1e-4, (name, value, detail)


def test_the_window_s_control_moves_the_reference():
    """With every causal key visible in the sliding layers the reference is
    another model: the gradient of a sliding layer's own leaves moves by
    tenths (the loss of a model at its seeded weights hardly does: it is
    the logarithm of the vocabulary either way)."""
    cfg = config()
    _, _, _, params = seeded(cfg)
    (x, y), = family.batches(cfg, SEED, 1, 2)

    def grads(cfg, sight):
        return highest(jax.grad(lambda p: reference.loss_sum(
            p, cfg, x, y, sight=sight)), params)

    own, other = grads(cfg, "window"), grads(cfg, "causal")
    for leaf in ("l1.wv", "l2.wq", "l1.wg"):
        gap = float(jnp.linalg.norm(own[leaf] - other[leaf])
                    / jnp.linalg.norm(own[leaf]))
        assert gap > 0.1, (leaf, gap)
    # ... and is the same model where the window covers the sequence
    wide = config(sliding_window=48)
    a, b = grads(wide, "window"), grads(wide, "causal")
    assert all((np.asarray(a[n]) == np.asarray(b[n])).all() for n in a)
    with pytest.raises(ValueError, match="sight"):
        reference.attention(params, 1, cfg, jnp.zeros((1, 48, 64)),
                            sight="block_diagonal")


def test_the_counters_say_what_a_step_saw():
    """`swa_visible_pairs_total` from the window's geometry a sliding layer,
    the routed layers' counts as the reference's own forward pass has
    them."""
    cfg = config()
    net, loss, _, params = seeded(cfg)
    (x, y), = family.batches(cfg, SEED, 1, 2)
    trainer = models_common.make_trainer(net, loss, cfg["train"],
                                         jax.devices()[:1])
    names = ("swa_visible_pairs_total", "moe_stat_layers_total",
             "moe_assignments_total", "moe_local_assignments_total")
    before = [profiler.counter_value(n) for n in names]
    trainer.fit_batch(x, y)
    trainer.flush_step_stats()
    pairs, layers, assigned, local = (
        profiler.counter_value(n) - b for n, b in zip(names, before))
    assert pairs == 2 * 2 * swa_counts.visible_pairs(48, 20)
    assert layers == 3 and assigned == 3 * 2 * 48 * 3
    counts = np.asarray(highest(
        lambda p: reference.expert_counts(p, cfg, x), params))
    assert counts.shape == (3, 16) and counts.sum() == assigned
    assert counts[:, 4:8].sum() == local


def test_the_shares_parts_of_a_routed_layer_add_up_to_the_uncut_one():
    """The routed parts that all four shares of the 16 experts give, with
    the shared expert (which every chip computes alike) counted once, are
    what the uncut layer gives: the cut leaves out what the absent experts
    would add and nothing else."""
    cfg = config(num_experts=16, first_expert=0)
    table = reference.param_table(cfg)
    p = ref_common.init_params(table, SEED)
    x = rand(60, 2, 48, 64)
    whole = highest(lambda p: reference.feed_forward(p, 1, cfg, x), p)
    parts = 0.0
    for share in range(4):
        held = {n: (v[4 * share:4 * share + 4] if ".expert_w" in n else v)
                for n, v in p.items()}
        parts = parts + highest(lambda q: reference.routed(
            q, "l1.", cfg, x, first=4 * share, held=4), held)
    parts = parts + highest(lambda p: reference.shared(p, "l1.", cfg, x), p)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=1e-5, atol=1e-7)
    # ... and the program's share is the reference's share
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.contrib.nn import RoutedExperts
    block = RoutedExperts(64, 32, 16, 3, experts_held=4, first_expert=8,
                          routed_scaling_factor=2.5)
    block.initialize()
    block(mx.nd.array(np.asarray(x)))
    for prm, name in zip(block.collect_params().values(),
                         ("router", "expert_w1", "expert_w3", "expert_w2")):
        v = p["l1." + name]
        prm.set_data(mx.nd.array(np.asarray(
            v[8:12] if name != "router" else v)))
    got = block(mx.nd.array(np.asarray(x))).asnumpy()
    held = {n: (v[8:12] if ".expert_w" in n else v) for n, v in p.items()}
    want = highest(lambda q: reference.routed(q, "l1.", cfg, x, first=8,
                                              held=4), held)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-4, atol=2e-6)


# -- compiled for the described chip --------------------------------------------
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """A chipless compile cannot be read back from the persistent cache:
    off around these tests, so that they stay silent."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compiled_layer(one_chip, heads, rope, **kind):
    """``(optimized text, spans)`` of one of the cell's attention layers,
    forward and backward, at 4096 positions, *heads* and 8 heads of 128 in
    bf16, compiled for a v5e."""
    import mxnet_tpu as mx
    from mxnet_tpu import executor
    from mxnet_tpu.gluon.contrib.nn import GroupedQueryAttention
    seq, dim = 4096, 3072
    block = GroupedQueryAttention(dim, heads, 8, 128, epsilon=1e-6,
                                  gate=True, rope=rope, **kind)
    graph = executor._build_eval(block(mx.sym.var("x")), True)
    bf = jnp.bfloat16
    avals = {p.name: jax.ShapeDtypeStruct(p.shape, bf, sharding=one_chip)
             for p in block.collect_params().values()}
    avals["x"] = jax.ShapeDtypeStruct((1, seq, dim), bf, sharding=one_chip)
    since = max([s.id for s in profiler.spans()] or [0])

    def step(args, dout):
        def objective(args):
            out, = graph(args, {}, None)[0]
            return jnp.sum(out.astype(jnp.float32) * dout)
        return jax.grad(objective)(args)

    compiled = jax.jit(step).lower(avals, jax.ShapeDtypeStruct(
        (1, seq, dim), jnp.float32, sharding=one_chip)).compile()
    return compiled.as_text(), [s for s in profiler.spans() if s.id > since]


def test_a_sliding_layer_compiles_for_the_described_chip_with_no_square_array(
        one_chip, no_cache):
    """The cell's sliding layer, forward and backward, at 4096 positions, 72
    and 8 heads of 128 in bf16 through 512 keys, and its full layer at 48
    and 8: both kernels compile for a v5e, the q and k passes take the
    head-rope pair (the full layer's over half a head: two rolls and three
    tables), and no array of the compiled program has two axes of the
    sequence."""
    seq = 4096
    text, spans = compiled_layer(
        one_chip, 72, {"rope_type": "default", "rope_theta": 10000,
                       "partial_rotary_factor": 1}, window=512)
    full_text, full_spans = compiled_layer(one_chip, 48, YARN)
    for t in (text, full_text):
        assert t.count('custom_call_target="tpu_custom_call"') >= 6
        for name in ("mx_flash_fwd", "mx_flash_bwd", "mx_headrope_fwd",
                     "mx_headrope_bwd"):
            assert name in t, name
        assert not re.search(r"\[(\d+,)*%d,(\d+,)*%d[,\]]" % (seq, seq), t)
    plans = [s.args for s in spans if s.name == "mx.flash.plan"]
    assert plans and all(p["mask"] == "window" and p["window"] == 512
                         for p in plans)
    assert plans[0]["fwd"]["tiles_visited"] == plans[0]["fwd"][
        "tiles_needed"] == swa_counts.tiles(seq, 512, 256, 512)[0] == 30
    ropes = [s.args for s in spans + full_spans
             if s.name == "mx.headrope.plan"]
    assert {(p["heads"], p["path"]) for p in ropes} == {
        (72, "kernel"), (8, "kernel"), (48, "kernel")}
    assert {(p["heads"], p["rotary_dim"], p["tables"], p["head_tile"])
            for p in ropes} == {(72, 128, 2, 24), (8, 128, 2, 8),
                                (48, 64, 3, 24), (8, 64, 3, 8)}
    # three float32 tables over the sequence where two were
    assert {p["table_bytes"] for p in ropes} == {2 * seq * 128 * 4,
                                                 3 * seq * 128 * 4}
