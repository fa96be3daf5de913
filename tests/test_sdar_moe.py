"""Training by diffusion over blocks: the block-diffusion mask in
`ops/attention.py` (the `jax.numpy` body, the two flash kernels interpreted,
their plan), `GroupedQueryAttention` with positions as an input, the decoder
kind `block_diffusion_attention` with its loss, against the mask's
definition (`benchmarks/bd_counts.py`) and the plain float32 reference
`benchmarks/reference/sdar_moe.py`, at a small size on the CPU with seeded
weights: float32 on both sides, so only the order of the arithmetic
differs."""

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

from benchmarks import bd_counts, compare  # noqa: E402
from benchmarks.models import common as models_common  # noqa: E402
from benchmarks.models import sdar_moe as family  # noqa: E402
from benchmarks.reference import common as ref_common  # noqa: E402
from benchmarks.reference import sdar_moe as reference  # noqa: E402
from mxnet_tpu import profiler  # noqa: E402
from mxnet_tpu.ops import attention  # noqa: E402
from mxnet_tpu.ops.registry import get_op  # noqa: E402

SEED = 2 ** 31 + 7


def config(**changes):
    cfg = {"family": "sdar_moe", "hidden_size": 64, "intermediate_size": 128,
           "moe_intermediate_size": 32, "num_experts_per_tok": 2,
           "router_experts": 16, "num_experts": 4, "first_expert": 4,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 16, "rope_theta": 1000000, "rope_scaling": None,
           "rms_norm_eps": 1e-6, "norm_topk_prob": True,
           "tie_word_embeddings": False, "num_hidden_layers": 4,
           "vocab_size": 96, "initializer_range": 0.02,
           "embedding_initializer_range": 1.0,
           "train": {"optimizer": "sgd", "lr": 0.01, "momentum": 0.9,
                     "wd": 0.0, "multi_precision": False,
                     "sequence_length": 48, "diffusion_block": 4,
                     "t_min": 0.001, "mask_token_id": 95,
                     "per_chip_batch": 2}}
    cfg.update(changes)
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
        jax.random.fold_in(jax.random.PRNGKey(39), i), shape, jnp.float32)


# -- the mask -----------------------------------------------------------------
SHAPES = [(64, 4), (60, 6), (48, 3), (40, 40), (40, 1), (96, 32), (70, 5)]


@pytest.mark.parametrize("half,block", SHAPES)
def test_the_mask_is_its_definition_and_leaves_half_times_half_plus_block(
        half, block):
    """The program's mask, the reference's and the counts' own are one
    function of the positions, and the visible pairs a head are ``L^2 + L
    B``: a quarter of the square where ``B`` is small beside ``L``."""
    pos = np.arange(2 * half)
    want = bd_counts.visible(pos[:, None], pos[None, :], half, block)
    got = attention.BlockDiffusion(block, half).visible(
        jnp.asarray(pos)[:, None], jnp.asarray(pos)[None, :])
    assert np.array_equal(np.asarray(got), want)
    assert np.array_equal(np.asarray(reference.sees(
        jnp.asarray(pos)[:, None], jnp.asarray(pos)[None, :], half, block)),
        want)
    assert want.sum() == bd_counts.visible_pairs(half, block) \
        == half * (half + block)
    assert want.any(axis=1).all()               # every query sees a key
    # clean queries never see a noised key; a noised query sees its own
    # clean token's block nowhere in the clean copy
    assert not want[:half, half:].any()
    own = np.arange(half) // block
    assert not want[half:, :half][own[:, None] == own[None, :]].any()
    # the controls' masks are others (with one block there is no earlier
    # clean block to take away)
    for sight in ("causal", "block_diagonal")[:1 + (half > block)]:
        other = np.asarray(reference.sees(pos[:, None], pos[None, :], half,
                                          block, sight))
        assert (other != want).any(), sight
    assert not np.asarray(reference.sees(
        pos[:, None], pos[None, :], half, block, "block_diagonal"))[
            half:, :half].any()


def test_the_cell_s_mask_shows_a_quarter_of_the_square():
    assert bd_counts.visible_pairs(8192, 4) == 67141632
    assert 4 * 8192 * 8192 == 268435456
    assert bd_counts.causal_pairs(16384) == 134225920
    assert bd_counts.visible_pairs(8192, 4) / 268435456 \
        == pytest.approx(0.2501, abs=1e-4)


def test_a_mask_that_does_not_describe_the_sequence_is_refused():
    q = rand(1, 1, 2, 32, 8)
    bd = attention.BlockDiffusion
    with pytest.raises(ValueError, match="does not describe"):
        attention.flash_attention(q, q, q, mask=bd(4, 12))
    with pytest.raises(ValueError, match="does not describe"):
        attention.flash_attention(q, q, q, mask=bd(3, 16))
    with pytest.raises(ValueError, match="not causal"):
        attention.flash_attention(q, q, q, causal=True, mask=bd(4, 16))
    with pytest.raises(ValueError, match="is not built"):
        get_op("_contrib_DotProductAttention").fn(q, q, q, mask="sliding")


# -- the kernels and the body against a dense masked softmax -------------------
def dense(q, k, v, mask, scale):
    pos = np.arange(q.shape[2])
    seen = bd_counts.visible(pos[:, None], pos[None, :], mask.half,
                             mask.block)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


#: half, block, sub-tile (q, k), resident (q, k): lengths that are and are
#: not whole tiles, blocks that do and do not divide a sub-tile, a block as
#: long as the sequence and one of a single token, the plan's own tiles
KERNEL_CASES = {
    "whole-tiles": (64, 4, (16, 32), (32, 64)),
    "ragged": (60, 4, (16, 32), (32, 64)),
    "block-6-of-16": (60, 6, (16, 32), (32, 32)),
    "block-3": (48, 3, (16, 16), (16, 32)),
    "one-block": (40, 40, (16, 32), (32, 64)),
    "blocks-of-one": (40, 1, (8, 16), (16, 16)),
    "own-plan": (200, 8, None, None),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernels_and_body_are_the_dense_masked_softmax(case):
    """Values and all three gradients of the two flash kernels (interpret
    mode) and of the `jax.numpy` body against a dense softmax under the
    mask's definition."""
    half, block, sub, res = KERNEL_CASES[case]
    mask = attention.BlockDiffusion(block, half)
    scale = 16 ** -0.5
    q, k, v, g = (rand(i, 1, 2, 2 * half, 16) for i in range(4))
    want, vjp = jax.vjp(lambda q, k, v: dense(q, k, v, mask, scale), q, k, v)
    wants = (want,) + vjp(g)
    tiles = {}
    if sub:
        tiles = dict(blk_q=sub[0], blk_k=sub[1], res_q=res[0], res_k=res[1])
    out, lse = attention._flash_fwd_pallas(
        q, k, v, False, scale, interpret=True, with_lse=True, mask=mask,
        **tiles)
    got = (out,) + attention._flash_bwd_pallas(
        q, k, v, out, lse, g, False, scale, interpret=True, mask=mask,
        **tiles)
    body, vjp = jax.vjp(lambda q, k, v: attention._chunked_attention(
        q, k, v, False, scale, 32, mask), q, k, v)
    for name, a, b, c in zip("o dq dk dv".split(), got, (body,) + vjp(g),
                             wants):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=3e-6,
                                   err_msg="kernel " + name)
        np.testing.assert_allclose(np.asarray(b), np.asarray(c), atol=3e-6,
                                   err_msg="body " + name)
    # ... and the oracle of the other tests takes the same description
    np.testing.assert_allclose(
        np.asarray(attention.attention_reference(q, k, v, sm_scale=scale,
                                                 mask=mask)),
        np.asarray(want), atol=3e-6)


def test_the_public_op_runs_the_kernels_under_the_mask():
    """`flash_attention(..., interpret=True)` and the graph's op: the same
    numbers, gradients through the custom rule."""
    mask = attention.BlockDiffusion(4, 64)
    q, k, v = (rand(10 + i, 2, 2, 128, 16) for i in range(3))
    want = dense(q, k, v, mask, 0.25)

    def loss(fn):
        return jax.grad(lambda q: jnp.sum(fn(q) ** 2))(q)

    np.testing.assert_allclose(np.asarray(attention.flash_attention(
        q, k, v, interpret=True, mask=mask)), np.asarray(want), atol=3e-6)
    op = get_op("_contrib_DotProductAttention").fn
    np.testing.assert_allclose(np.asarray(op(
        q, k, v, sm_scale=0.25, mask="block_diffusion", mask_block=4)),
        np.asarray(want), atol=3e-6)
    np.testing.assert_allclose(
        np.asarray(loss(lambda q: attention.flash_attention(
            q, k, v, interpret=True, mask=mask))),
        np.asarray(loss(lambda q: dense(q, k, v, mask, 0.25))), atol=2e-5)


# -- the plan -----------------------------------------------------------------
# (the loops against the mask's definition at six shapes, the cell's among
# them, and against `bd_counts.tiles`: cases `BlockDiffusion-*` of
# tests/test_attention.py's one test over descriptions)
def test_the_cell_s_plan_is_576_tiles_of_1056():
    """At `_SUB_LOOPED` (256 queries by 512 keys) and ``L`` 8192: 272
    (clean on clean) + 272 (noised on clean) + 32 (noised on its own
    blocks), 96 of them under a mask body; a causal kernel over the same
    16384 positions visits 1056."""
    plan = attention._flash_plan(16384, 16384, 128, jnp.bfloat16, halves=2)
    args = attention._plan_args(plan, 16384, 16384, 128, jnp.bfloat16, False,
                                None, attention.BlockDiffusion(4, 8192))
    assert (args["mask"], args["block"], args["half"]) == (
        "block_diffusion", 4, 8192)
    for kernel in ("fwd", "bwd"):
        assert args[kernel]["sub_tile"] == [256, 512]
        assert args[kernel]["tiles_visited"] == 576
        assert args[kernel]["tiles_masked"] == 96
        assert args[kernel]["tiles_ideal"] == 512.25
    assert bd_counts.tiles(8192, 4, 256, 512) == (576, 96)
    assert bd_counts.tiles(8192, 4, 512, 512) == (288, 48)
    causal = attention._plan_args(
        attention._flash_plan(16384, 16384, 128, jnp.bfloat16), 16384, 16384,
        128, jnp.bfloat16, True)
    assert causal["fwd"]["tiles_visited"] == 1056
    assert "mask" not in causal


#: commit 3394d86's plans of the four language cells (`_plan_args`, causal):
#: tiles visited, tiles masked, sub-tile, resident blocks, forward then
#: backward
PARENT_PLANS = {
    "opt-1.3b_train_1chip": ((2048, 64, 64), (
        (36, 8, [256, 256], [2048, 2048]),
        (36, 8, [256, 256], [2048, 2048]))),
    "lfm2-8b-a1b_train_ep4share": ((8192, 64, 64), (
        (272, 32, [256, 512], [1024, 4096]),
        (272, 32, [256, 512], [4096, 1024]))),
    "kanana-2-30b-a3b_train_ep8share": ((8192, 192, 128), (
        (272, 32, [256, 512], [1024, 2048]),
        (272, 32, [256, 512], [2048, 1024]))),
    "keye-vl-2.0-30b-a3b_train_ep8share": ((16384, 128, 128), (
        (1056, 64, [256, 512], [1024, 4096]),
        (1056, 64, [256, 512], [4096, 1024]))),
}


@pytest.mark.parametrize("cell", sorted(PARENT_PLANS))
def test_the_language_cells_causal_plans_are_the_parent_s(cell):
    (s, d, d_v), want = PARENT_PLANS[cell]
    plan = attention._flash_plan(s, s, d, jnp.bfloat16, d_v=d_v)
    args = attention._plan_args(plan, s, s, d, jnp.bfloat16, True, d_v)
    got = tuple((args[k]["tiles_visited"], args[k]["tiles_masked"],
                 args[k]["sub_tile"], args[k]["resident"])
                for k in ("fwd", "bwd"))
    assert got == want
    assert "mask" not in args and args["causal"] is True


def test_the_selected_plan_masks_every_tile_it_visits_as_before():
    """Keye's kernels take a selection operand: the span says every visited
    tile is a masked one, as commit 3394d86's did."""
    q = rand(20, 1, 2, 256, 16)
    since = profiler.spans()[-1].id if profiler.spans() else -1
    attention._record_plan(q, q, q, True, selected=True)
    args = [s for s in profiler.spans()
            if s.name == "mx.flash.plan" and s.id > since][-1].args
    assert args["selection"] == "bits" and "mask" not in args
    for kernel in ("fwd", "bwd"):
        assert args[kernel]["tiles_masked"] == args[kernel]["tiles_visited"]


def test_the_plan_span_carries_the_mask():
    q = rand(21, 1, 2, 128, 16)
    since = profiler.spans()[-1].id if profiler.spans() else -1
    attention.flash_attention(q, q, q, interpret=True,
                              mask=attention.BlockDiffusion(4, 64))
    args = [s for s in profiler.spans()
            if s.name == "mx.flash.plan" and s.id > since][-1].args
    assert (args["mask"], args["block"], args["half"]) == (
        "block_diffusion", 4, 64)
    assert args["causal"] is False
    assert args["fwd"]["tiles_visited"] >= args["fwd"]["tiles_masked"] > 0


# -- the operators -------------------------------------------------------------
def test_positions_of_two_copies():
    pos = get_op("_contrib_BlockDiffusionPositions").fn(
        jnp.zeros((3, 10), jnp.int32))
    assert pos.shape == (1, 3, 10) and pos.dtype == jnp.int32
    assert np.array_equal(np.asarray(pos[0, 1]), [0, 1, 2, 3, 4] * 2)


def test_the_loss_is_the_weighted_cross_entropy_of_the_masked_positions():
    """``(1 / L) sum_i w_i * -log softmax(logits_i)[x_i]`` a row, its
    gradient only where a weight is, and the counters."""
    from mxnet_tpu.observability import metrics
    logits = rand(30, 2, 6, 11)
    ids = np.array([[1, 5, 10, 0, 3, 3], [2, 2, 9, 7, 4, 0]], np.float32)
    w = np.array([[0, 2.5, 0, 1.0, 0, 0], [4.0, 0, 0, 0, 0, 1.25]],
                 np.float32)
    label = jnp.asarray(np.stack([ids, w], 1))
    op = get_op("_contrib_BlockDiffusionLoss").fn
    logp = np.asarray(jax.nn.log_softmax(logits, -1))
    want = [sum(-w[r, i] * logp[r, i, int(ids[r, i])] for i in range(6)) / 6
            for r in range(2)]
    np.testing.assert_allclose(np.asarray(op(logits, label)), want,
                               rtol=1e-6)
    grad = np.asarray(jax.grad(lambda x: jnp.sum(op(x, label)))(logits))
    assert (np.abs(grad).sum(-1) > 0).tolist() == (w > 0).tolist()
    before = [profiler.counter_value(n) for n in (
        "bd_masked_positions_total", "bd_positions_total")]
    with profiler.collect_step_stats() as stats:
        op(logits, label)
    profiler.fold_step_stats({k: np.stack(v) for k, v in stats.items()})
    assert profiler.counter_value("bd_masked_positions_total") \
        - before[0] == 4
    assert profiler.counter_value("bd_positions_total") - before[1] == 12
    assert metrics.snapshot()["bd_loss"]["value"] == pytest.approx(
        float(np.mean(want)), rel=1e-6)


def test_grouped_query_attention_without_a_mask_is_what_it_was():
    """LFM2's `full_attention` layers go through the same block: without
    `diffusion_block` its graph has the nodes it had (no named group, a
    causal attention node, rotary positions counted from 0), and with it
    the projections carry the group's scope and the attention the mask."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.contrib.nn import GroupedQueryAttention

    def nodes(**kwargs):
        block = GroupedQueryAttention(64, 4, 2, 16, 1e6, 1e-6, **kwargs)
        args = [mx.sym.var("x")] + (
            [mx.sym.var("pos")] if kwargs else [])
        return [n for n in block(*args)._topo() if n.op is not None]

    plain, masked = nodes(), nodes(diffusion_block=4)
    assert [n.op.name for n in plain] == [n.op.name for n in masked]
    assert not any(n.attrs.get("__scope__") for n in plain)
    att, = [n for n in plain if n.op.name == "_contrib_DotProductAttention"]
    assert att.params["causal"] is True and "mask" not in att.params
    assert not any(n.params.get("use_positions") for n in plain)
    att, = [n for n in masked
            if n.op.name == "_contrib_DotProductAttention"]
    assert att.params["mask"] == "block_diffusion"
    assert att.params["mask_block"] == 4 and "causal" not in att.params
    assert sum(bool(n.params.get("use_positions")) for n in masked) == 2
    scoped = [n.op.name for n in masked
              if n.attrs.get("__scope__") == "mx.bd.project"]
    assert scoped.count("FullyConnected") == 3
    assert "_contrib_DotProductAttention" not in scoped


LAYERS = {
    # the cell's: 2 x 8192 positions as an input, 32 heads over 4 of 128
    "sdar": (dict(units=2048, num_heads=32, num_kv_heads=4, head_dim=128,
                  diffusion_block=4), 16384, True),
    # LFM2's `full_attention`: 8192 positions counted, 32 heads over 8 of 64
    "lfm2": (dict(units=2048, num_heads=32, num_kv_heads=8, head_dim=64),
             8192, False),
}


@pytest.mark.parametrize("layer", sorted(LAYERS))
def test_the_layer_lowered_for_the_tpu_holds_the_pair_where_the_plan_gives_it(
        layer):
    """One `GroupedQueryAttention` layer's forward and backward as the
    executor evaluates its graph, in bf16, lowered for the TPU from this
    CPU host.  At the cell's shape q's and k's `_contrib_HeadNormRotary`
    nodes are the kernels `mx_headrope_fwd` and `mx_headrope_bwd`, one each
    a node, under the group's scope `mx.bd.project` and the pair's own
    `mx.headrope` inside it, and `mx.headrope.plan` says `kernel` for the
    32 and the 4 heads.  At LFM2's 64-wide heads the plan says `xla` and
    why, and the program holds neither kernel."""
    import mxnet_tpu as mx
    from mxnet_tpu import executor
    from mxnet_tpu.gluon.contrib.nn import GroupedQueryAttention
    kwargs, seq, with_positions = LAYERS[layer]
    block = GroupedQueryAttention(rope_theta=1e6, epsilon=1e-6, **kwargs)
    inputs = [mx.sym.var("x")] + (
        [mx.sym.var("pos")] if with_positions else [])
    graph = executor._build_eval(block(*inputs), True)
    bf = jnp.bfloat16
    avals = {p.name: jax.ShapeDtypeStruct(p.shape, bf)
             for p in block.collect_params().values()}
    avals["x"] = jax.ShapeDtypeStruct((1, seq, kwargs["units"]), bf)

    def step(args, positions, dout):
        def objective(args):
            out, = graph(dict(args, **positions), {}, None)[0]
            return jnp.sum(out.astype(jnp.float32) * dout)
        return jax.grad(objective)(args)

    positions = {"pos": jax.ShapeDtypeStruct((1, 1, seq), jnp.int32)} \
        if with_positions else {}
    since = max([s.id for s in profiler.spans()] or [0])
    text = jax.jit(step).trace(
        avals, positions, jax.ShapeDtypeStruct(avals["x"].shape, jnp.float32)
    ).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    plans = [s.args for s in profiler.spans()
             if s.name == "mx.headrope.plan" and s.id > since]
    heads = [kwargs["num_heads"], kwargs["num_kv_heads"]]
    assert [p["heads"] for p in plans] == heads
    assert all(p["shape"] == [1, seq, p["heads"] * kwargs["head_dim"]]
               and p["dtype"] == "bfloat16" for p in plans)
    for kernel in ("mx_flash_fwd", "mx_flash_bwd"):
        assert kernel in text, kernel
    if layer == "lfm2":
        assert all(p["path"] == "xla" and p["why"].startswith(
            "a head of 64 is not whole 128-lane tiles") for p in plans)
        assert "mx_headrope" not in text and "mx.headrope" not in text
        return
    assert all(p["path"] == "kernel" and p["why"] is None for p in plans)
    assert [p["head_tile"] for p in plans] == heads
    # one pair of tables a node, from the positions operand: cos and sin
    # over 16384 positions of 128 in float32
    assert all(p["table_bytes"] == 2 * seq * 128 * 4 for p in plans)
    for way in ("fwd", "bwd"):
        # the kernel under the pair's scope, called from q's node and from
        # k's under the group's (the compiled program joins the two names)
        assert '"mx.headrope/mx_headrope_%s/pallas_call"' % way in text
        nodes = set(re.findall(
            r'mx\.bd\.project/_contrib_HeadNormRotary:(\w+)\)+/cond/'
            r'branch_0_fun/jit\(_headrope_%s_pallas\)"' % way, text))
        assert len(nodes) == 2, (way, nodes)


def test_a_decoder_layer_of_the_kind_needs_its_block_length():
    from mxnet_tpu.gluon.model_zoo.decoder import (OPERATOR_KINDS,
                                                   get_decoder_lm)
    assert OPERATOR_KINDS[4] == "block_diffusion_attention"
    with pytest.raises(ValueError, match="diffusion_block"):
        get_decoder_lm(vocab=32, dim=64,
                       layer_types=["block_diffusion_attention"],
                       num_dense_layers=1, dense_hidden=64, expert_hidden=32,
                       num_experts=4, num_experts_per_tok=1, heads=4,
                       kv_heads=2, head_dim=16)


# -- the whole model ----------------------------------------------------------
KINDS = {"one-layer": dict(num_hidden_layers=1), "all": {},
         "tied-head": dict(tie_word_embeddings=True, num_hidden_layers=2),
         # the cell's own: the per-head norms' scales start above one
         "sharp-attention": dict(qk_norm_initializer=1.573,
                                 num_hidden_layers=2),
         # ... and the MASK row starts at the matrices' scale
         "small-mask-row": dict(mask_embedding_initializer_range=0.02,
                                num_hidden_layers=2),
         "block-3": dict(num_hidden_layers=2, train=dict(
             config()["train"], diffusion_block=3))}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_logits_loss_and_every_gradient_agree_with_the_reference(kind):
    """Through `ParallelTrainer.fit_batch`: the logits are the noised
    half's, the loss a step reports is the weighted cross-entropy, and every
    leaf's gradient is the reference's.  Tolerances: float32 on both sides,
    summed in another order (2e-4 of a leaf's largest entry, as the other
    families')."""
    import mxnet_tpu as mx
    cfg = config(**KINDS[kind])
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
        # (a last layer's experts may get none: only masked positions
        # carry loss, they enter as one vector, and their experts may all
        # be held elsewhere)
        assert np.abs(w).max() > 0 or "expert" in ref_name \
            or ref_name.endswith((".router", ".ffn_norm")), ref_name


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


def test_the_mask_row_starts_at_a_scale_of_its_own():
    """`mask_embedding_initializer_range` is the ``MASK`` row's standard
    deviation and no other row's: the other rows are the draws they are
    without the key, the ``MASK`` row the same draw at the other scale, and
    the harness's distance from the seeded value is made from the same
    table."""
    cfg = config(mask_embedding_initializer_range=0.02, num_hidden_layers=1)
    table = reference.param_table(cfg)
    plain = reference.param_table(config(num_hidden_layers=1))
    assert (plain["embed"][1][1] == 1.0).all()
    got = np.asarray(ref_common.init_params(table, SEED)["embed"])
    want = np.asarray(ref_common.init_params(plain, SEED)["embed"])
    np.testing.assert_array_equal(got[:95], want[:95])
    np.testing.assert_allclose(got[95], 0.02 * want[95], rtol=1e-6)
    dist = ref_common.distance_from_init(table, SEED, {"embed": got})
    assert dist["embed"] <= 1e-5


def test_a_control_s_mask_moves_the_reference():
    """Either control's mask in the reference moves its loss and its
    gradients: what `benchmarks/control_mask.py` rests on."""
    cfg = config(num_hidden_layers=2)
    params = ref_common.init_params(reference.param_table(cfg), SEED)
    (x, y), = family.batches(cfg, SEED, 1, 2)
    own = highest(jax.grad(lambda p: reference.loss_sum(p, cfg, x, y)),
                  params)
    for sight in ("causal", "block_diagonal"):
        other = highest(jax.grad(lambda p: reference.loss_sum(
            p, cfg, x, y, sight=sight)), params)
        gap = ref_common.relative_difference(
            {n: np.asarray(v) for n, v in other.items()}, own)
        assert gap > 0.01, (sight, gap)
    with pytest.raises(ValueError, match="is not one of"):
        reference.sees(0, 0, 4, 2, "windowed")


def test_the_batch_carries_its_noise():
    """``x`` is the clean ids then the noised ids, ``y`` the clean ids and
    the weights: a masked position holds the ``MASK`` id and the weight ``1
    / t`` of its block, every other its own id and no weight; the clean ids
    never hold the ``MASK`` id; the same seed gives the same batch."""
    cfg = config(vocab_size=11, train=dict(
        config()["train"], sequence_length=4096, mask_token_id=7))
    (x, y), (x2, _) = family.batches(cfg, 5, 2, 3)
    assert x.shape == (3, 8192) and x.dtype == np.int32
    assert y.shape == (3, 2, 4096) and y.dtype == np.float32
    clean, noisy, ids, w = x[:, :4096], x[:, 4096:], y[:, 0], y[:, 1]
    assert np.array_equal(clean, ids.astype(np.int32))
    assert 7 not in clean and set(np.unique(clean)) == set(range(11)) - {7}
    masked = w > 0
    assert np.array_equal(noisy[masked], np.full(masked.sum(), 7))
    assert np.array_equal(noisy[~masked], clean[~masked])
    # one level a block: the weights of a block's masked positions agree,
    # and lie between 1 and 1 / t_min
    blocks = w.reshape(3, -1, 4)
    top = blocks.max(-1, keepdims=True)
    assert np.all((blocks == 0) | (blocks == top))
    assert 1.0 <= w[masked].min() and w[masked].max() <= 1000.0
    # E[w] = 1 a position: about half the positions masked at a mean level
    # of one half
    assert masked.mean() == pytest.approx(0.5, abs=0.03)
    assert w.mean() == pytest.approx(1.0, abs=0.1)
    again, = family.batches(cfg, 5, 1, 3)
    assert np.array_equal(again[0], x) and np.array_equal(again[1], y)
    assert not np.array_equal(x2, x)
    assert family.sample_shapes(cfg, 3) == (((3, 8192), np.int32),
                                            ((3, 2, 4096), np.float32))


def test_the_counters_say_what_a_step_saw():
    """`bd_visible_pairs_total` from the mask's geometry a layer,
    `bd_positions_total` / `bd_masked_positions_total` from the batch's
    weights, the routed counters' layer-steps beside them, `bd_loss` the
    step's loss."""
    from mxnet_tpu.observability import metrics
    cfg = config(num_hidden_layers=2)
    net, loss, _, _ = seeded(cfg)
    (x, y), = family.batches(cfg, SEED, 1, 2)
    names = ("bd_visible_pairs_total", "bd_positions_total",
             "bd_masked_positions_total", "moe_stat_layers_total")
    before = [profiler.counter_value(n) for n in names]
    trainer = models_common.make_trainer(net, loss, cfg["train"],
                                         jax.devices()[:1])
    got = float(trainer.fit_batch(x, y))
    trainer.flush_step_stats()
    pairs, positions, masked, layers = (
        profiler.counter_value(n) - b for n, b in zip(names, before))
    assert layers == 2
    assert pairs == layers * 2 * bd_counts.visible_pairs(48, 4)
    assert positions == 2 * 48
    assert masked == int((y[:, 1] > 0).sum())
    assert metrics.snapshot()["bd_loss"]["value"] == pytest.approx(
        got, rel=1e-3)


def test_the_routed_counters_equal_the_reference_s_counts():
    """The softmax router's choices over the ``2L`` positions as the routed
    op counts them against `reference.expert_counts`."""
    cfg = config(num_hidden_layers=2)
    net, loss, _, params = seeded(cfg)
    (x, y), = family.batches(cfg, SEED, 1, 2)
    want = np.asarray(highest(
        lambda p: reference.expert_counts(p, cfg, x), params))
    assert want.shape == (2, 16) and (want.sum(1) == 2 * 96 * 2).all()
    names = ("moe_stat_layers_total", "moe_assignments_total",
             "moe_local_assignments_total")
    before = [profiler.counter_value(n) for n in names]
    trainer = models_common.make_trainer(net, loss, cfg["train"],
                                         jax.devices()[:1])
    trainer.fit_batch(x, y)
    trainer.flush_step_stats()
    layers, pairs, held = (
        profiler.counter_value(n) - b for n, b in zip(names, before))
    # experts 4 to 7 are held here
    assert (layers, pairs, held) == (2, want.sum(), want[:, 4:8].sum())


# -- the share ----------------------------------------------------------------
def test_the_eight_shares_parts_of_a_routed_layer_add_up_to_the_uncut_one():
    """The guide's share test: each of eight chips holds 2 of 16 experts
    and routes over all 16 with the softmax router; their parts add up to
    the reference's uncut layer."""
    cfg = config(num_experts=16, first_expert=0)
    x = rand(100, 2, 96, 64)
    router = rand(101, 16, 64, scale=0.3)
    w1, w3 = rand(102, 16, 64, 32, scale=0.2), rand(103, 16, 64, 32, scale=0.2)
    w2 = rand(104, 16, 32, 64, scale=0.2)
    p = {"l0.router": router, "l0.expert_w1": w1, "l0.expert_w3": w3,
         "l0.expert_w2": w2}
    whole = highest(lambda p, x: reference.routed(p, "l0.", cfg, x), p, x)
    op = get_op("_contrib_RoutedExperts").fn
    total = 0.0
    for share in range(8):
        at = slice(2 * share, 2 * share + 2)
        part = highest(lambda p, x, first=2 * share: reference.routed(
            {"l0.router": p["l0.router"],
             "l0.expert_w1": p["l0.expert_w1"][at],
             "l0.expert_w3": p["l0.expert_w3"][at],
             "l0.expert_w2": p["l0.expert_w2"][at]},
            "l0.", cfg, x, first=first, held=2), p, x)
        with jax.default_matmul_precision("highest"):
            mine = op(x, router, w1[at], w3[at], w2[at],
                      num_experts_per_tok=2, first_expert=2 * share,
                      scoring_func="softmax")
        # the program's share is the reference's share
        np.testing.assert_allclose(np.asarray(mine), np.asarray(part),
                                   rtol=2e-4, atol=2e-6)
        total = total + mine
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=2e-4, atol=2e-6)


# ---------------------------------------------------------------------------
# Compiled for a described v5e, without a chip (`benchmarks/rehearse.py`).
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture(scope="module")
def compiled_attention(v5e):
    """The attention node's forward and backward at the cell's size (2 x
    8192 positions in blocks of 4, 32 heads of 128, the key/value heads
    already repeated), compiled once for the described chip with the
    persistent cache off (such a compile is written to it and cannot be
    read back).  The node's TPU branch is `_flash`; the op itself chooses by
    the platform it is lowered for, which a described chip is not."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(v5e.devices[0])
    x = jax.ShapeDtypeStruct((1, 32, 16384, 128), jnp.bfloat16, sharding=one)
    mask = attention.BlockDiffusion(4, 8192)

    def step(q, k, v, dout):
        def objective(q, k, v):
            out = attention._flash(q, k, v, False, 128 ** -0.5, False, mask)
            return jnp.sum(out.astype(jnp.float32) * dout)
        return jax.grad(objective, argnums=(0, 1, 2))(q, k, v)

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return jax.jit(step).lower(x, x, x, x).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()


def test_the_attention_compiles_for_the_described_chip_with_no_square_array(
        compiled_attention):
    """Mosaic and XLA:TPU take the two kernels under the mask with what the
    plan asks for, and no array in the compiled program has two axes of
    16384: no scores, no probabilities, no mask, no bit a pair."""
    text = compiled_attention.as_text()
    for kernel in ("mx_flash_fwd", "mx_flash_bwd"):
        assert kernel in text, kernel
    assert not re.search(r"\[[0-9,]*16384,[0-9,]*16384", text)
    assert "s32[1,512,16384]" not in text       # no selection operand
    # q, k, v, dO in and three gradients out are 0.94 GB; the temporaries
    # (the output, the two float32 rows, the delta pass) stay under a
    # seventh of ONE head's 16384 x 16384 float32 scores
    assert compiled_attention.memory_analysis().temp_size_in_bytes \
        < 0.15 * (1 << 30)
