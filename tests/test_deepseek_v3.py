"""The latent-attention decoder (`gluon/model_zoo/decoder.py` kinds
`latent_attention`, shared experts, an untied head) and the operators
under it (`ops/lm_blocks.py` `_contrib_LatentAttention`, interleaved
rotary; `ops/attention.py` at two head widths) against the plain float32
reference `benchmarks/reference/deepseek_v3.py`, at a small size on the
CPU with seeded weights: float32 on both sides, so only the order of the
arithmetic differs."""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import compare  # noqa: E402
from benchmarks.models import common as models_common  # noqa: E402
from benchmarks.models import deepseek_v3 as family  # noqa: E402
from benchmarks.reference import common as ref_common  # noqa: E402
from benchmarks.reference import deepseek_v3 as reference  # noqa: E402
from mxnet_tpu import profiler  # noqa: E402
from mxnet_tpu.ops import attention  # noqa: E402
from mxnet_tpu.ops.registry import get_op  # noqa: E402

SEED = 2 ** 31 + 5


def config(**changes):
    cfg = {"family": "deepseek_v3", "hidden_size": 64,
           "intermediate_size": 128, "moe_intermediate_size": 32,
           "n_shared_experts": 2, "num_experts_per_tok": 3,
           "router_experts": 16, "n_routed_experts": 4, "first_expert": 4,
           "num_attention_heads": 4, "kv_lora_rank": 32,
           "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
           "rope_theta": 1000000, "rope_interleave": True,
           "rope_scaling": None, "q_lora_rank": None, "n_group": 1,
           "topk_group": 1, "rms_norm_eps": 1e-6,
           "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
           "norm_topk_prob": True, "first_k_dense_replace": 1,
           "tie_word_embeddings": False, "num_hidden_layers": 3,
           "vocab_size": 96, "expert_bias_scale": 0.05,
           "initializer_range": 0.02,
           "train": {"optimizer": "sgd", "lr": 0.01, "momentum": 0.9,
                     "wd": 0.0, "multi_precision": False,
                     "sequence_length": 32, "per_chip_batch": 4}}
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
    """``fn(*args)`` (arrays only) as one jitted program whose float32
    contractions are exact."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(fn)(*args)


# -- the whole model ----------------------------------------------------------
KINDS = {
    "dense": dict(num_hidden_layers=1),
    "shared-and-routed": dict(num_hidden_layers=1, first_k_dense_replace=0),
    "routed-alone": dict(num_hidden_layers=1, first_k_dense_replace=0,
                         n_shared_experts=0),
    "all": {},
    "tied-head": dict(tie_word_embeddings=True, num_hidden_layers=2),
}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_logits_loss_and_every_gradient_agree_with_the_reference(kind):
    import mxnet_tpu as mx
    cfg = config(**KINDS[kind])
    net, loss, names, params = seeded(cfg)
    assert ("head" in names) == (kind != "tied-head")
    (x, y), = family.batches(cfg, SEED, 1, 4)
    got = net(mx.nd.array(x, dtype="int32")).asnumpy()
    want = highest(lambda p: reference.logits(p, cfg, x), params)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-4, atol=2e-6)

    # the loss, and each leaf's gradient as the optimizer got it
    # (|mom_1| = lr * g), through the trainer every cell runs
    trainer = models_common.make_trainer(net, loss, cfg["train"],
                                         jax.devices()[:1])
    got_loss = float(trainer.fit_batch(x, y))
    value, grads = highest(jax.value_and_grad(
        lambda p: reference.loss_sum(p, cfg, x, y)), params)
    assert got_loss == pytest.approx(float(value) / 4, rel=1e-5)
    assert set(names) == set(grads)
    lr = cfg["train"]["lr"]
    for ref_name, prog_name in names.items():
        g = -np.asarray(trainer._opt_state[prog_name][0]) / lr
        w = np.asarray(grads[ref_name]) / 4
        scale = max(np.abs(w).max(), 1e-12)
        assert np.abs(g - w).max() <= 2e-4 * scale, ref_name


def test_three_trainer_steps_follow_the_reference():
    cfg = config()
    train = cfg["train"]
    table = reference.param_table(cfg)
    net, loss, names, params = seeded(cfg)
    batches = family.batches(cfg, SEED, 3, 4)
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
            rows_per_block=2, first_update=first)
    for name, (value, detail) in compare.training_numbers(
            got, ref, names).items():
        assert value <= 1e-4, (name, value, detail)


def test_the_step_s_nodes_carry_the_new_scopes():
    """`mx.mla` and its three phases under every latent attention node,
    `mx.moe.shared` under the shared experts' node and nowhere else, in
    the step the trainer compiles."""
    import re
    cfg = config()
    net, loss, _, _ = seeded(cfg)
    trainer = models_common.make_trainer(net, loss, cfg["train"],
                                         jax.devices()[:1])
    (x, y), = family.batches(cfg, SEED, 1, 4)
    t0 = time.perf_counter()
    trainer.fit_batch(x, y)
    scopes = set(profiler.scope_map("parallel_step").values())
    text = "\n".join(sorted(scopes))
    nodes = set(re.findall(r"_contrib_LatentAttention:\w+", text))
    assert len(nodes) == 3
    for node in nodes:
        for way in (r"jvp\(%s\)", r"transpose\(jvp\(%s\)\)"):
            for scope in ("mx.mla.project", "mx.mla.assemble", "mx.mla.out"):
                assert re.search(way % node + r"/mx\.mla/"
                                 + re.escape(scope) + "/", text), (node, scope)
    # interleaved rotary's gradient is a product, not a scatter
    assert not [s for s in scopes
                if "mx.mla.assemble" in s and "scatter" in s]
    shared = [s for s in scopes if "/mx.moe.shared" in s]
    assert all("_contrib_SharedExperts:" in s for s in shared)
    under = set(re.findall(
        r"(_contrib_SharedExperts:\w+)\)*/mx\.moe\.shared/", text))
    # the two routed layers' shared experts, and not the dense layer's MLP
    assert len(under) == 2
    assert len(set(re.findall(r"_contrib_GatedMLP:\w+", text))) == 1
    plans = [s for s in profiler.spans(since=t0) if s.name == "mx.mla.plan"]
    assert plans and plans[0].args["heads"] == 4
    assert plans[0].args["kv_lora_rank"] == 32
    assert (plans[0].args["qk_nope_head_dim"],
            plans[0].args["qk_rope_head_dim"],
            plans[0].args["v_head_dim"]) == (16, 8, 16)
    # q and k assembled at heads x (nope + rope), float32 here
    assert plans[0].args["assembled_k_bytes"] == 4 * 4 * 32 * 24 * 4


def test_a_decoder_without_the_new_kinds_fails_in_build_at_once(monkeypatch):
    """What the parent commit does with this family's files laid over it:
    `build` raises before anything is made."""
    from mxnet_tpu.gluon.model_zoo import decoder
    monkeypatch.setattr(decoder, "OPERATOR_KINDS",
                        ("conv", "full_attention"))
    with pytest.raises(RuntimeError, match="no latent_attention layer"):
        family.build(config())


@pytest.mark.parametrize("key,value,said", [
    ("q_lora_rank", 1536, "query compression"),
    ("n_group", 8, "group-limited routing"),
    ("rope_scaling", {"type": "yarn"}, "scaled rotary"),
])
def test_what_is_not_built_raises(key, value, said):
    with pytest.raises(ValueError, match=said):
        family.build(config(**{key: value}))


def test_an_unknown_layer_kind_names_the_three():
    from mxnet_tpu.gluon.model_zoo.decoder import get_decoder_lm
    with pytest.raises(ValueError, match="latent_attention"):
        get_decoder_lm(vocab=8, dim=8, layer_types=["windowed"],
                       num_dense_layers=1, dense_hidden=8, expert_hidden=8,
                       num_experts=2, num_experts_per_tok=1)
    with pytest.raises(ValueError, match="kv_lora_rank"):
        get_decoder_lm(vocab=8, dim=8, layer_types=["latent_attention"],
                       num_dense_layers=1, dense_hidden=8, expert_hidden=8,
                       num_experts=2, num_experts_per_tok=1)


# -- the latent attention operator --------------------------------------------
def mla_inputs(cfg, batch=2, seq=24, seed=3):
    rng = np.random.default_rng(seed)
    table = reference.param_table(dict(cfg, num_hidden_layers=1))

    def leaf(name):
        return jnp.asarray(0.2 * rng.normal(size=table["l0." + name][0]),
                           jnp.float32)

    p = {"l0." + n: leaf(n) for n in ("wq", "wkv_a", "wkv_b", "wo")}
    p["l0.kv_norm"] = jnp.asarray(
        1 + 0.1 * rng.normal(size=(cfg["kv_lora_rank"],)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(batch, seq, cfg["hidden_size"])),
                    jnp.float32)
    return p, x


def mla_op(cfg, p, x):
    return get_op("_contrib_LatentAttention").fn(
        x, p["l0.wq"], p["l0.wkv_a"], p["l0.kv_norm"], p["l0.wkv_b"],
        p["l0.wo"], num_heads=cfg["num_attention_heads"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=cfg["rope_theta"],
        rope_interleave=True, eps=cfg["rms_norm_eps"])


@pytest.mark.parametrize("widths", [(16, 8, 16), (16, 8, 24), (8, 16, 8)])
def test_latent_attention_forward_and_gradients(widths):
    nope, rope, vd = widths
    cfg = config(qk_nope_head_dim=nope, qk_rope_head_dim=rope,
                 v_head_dim=vd)
    p, x = mla_inputs(cfg)

    def ours(p, x):
        return mla_op(cfg, p, x)

    def theirs(p, x):
        return reference.attention(p, "l0.", cfg, x, False)

    got = highest(ours, p, x)
    want = highest(theirs, p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    g_got = highest(jax.grad(lambda p, x: jnp.sum(jnp.sin(ours(p, x))),
                             argnums=(0, 1)), p, x)
    g_want = highest(jax.grad(lambda p, x: jnp.sum(jnp.sin(theirs(p, x))),
                              argnums=(0, 1)), p, x)
    for a, b in zip(jax.tree_util.tree_leaves(g_got),
                    jax.tree_util.tree_leaves(g_want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(b).max()))


def test_one_rope_key_serves_every_head():
    """Changing the rope columns of `wkv_a` moves every head's output;
    changing one head's `k_nope` columns of `wkv_b` moves that head
    alone (seen in front of the output projection: `wo` the identity)."""
    cfg = config(hidden_size=64, num_attention_heads=4, v_head_dim=16)
    p, x = mla_inputs(cfg)
    p["l0.wo"] = jnp.eye(64, dtype=jnp.float32)
    base = highest(lambda p, x: mla_op(cfg, p, x), p, x)
    rank = cfg["kv_lora_rank"]
    moved = dict(p)
    moved["l0.wkv_a"] = p["l0.wkv_a"].at[rank:].multiply(1.5)
    by_head = np.abs(np.asarray(
        highest(lambda p, x: mla_op(cfg, p, x), moved, x) - base)
    ).reshape(2, 24, 4, 16).max(axis=(0, 1, 3))
    assert (by_head > 1e-4).all()
    moved = dict(p)
    # head 2's k_nope rows of wkv_b: rows 2 * (nope + v) .. + nope
    lo = 2 * (16 + 16)
    moved["l0.wkv_b"] = p["l0.wkv_b"].at[lo:lo + 16].multiply(1.5)
    by_head = np.abs(np.asarray(
        highest(lambda p, x: mla_op(cfg, p, x), moved, x) - base)
    ).reshape(2, 24, 4, 16).max(axis=(0, 1, 3))
    assert by_head[2] > 1e-4 and (by_head[[0, 1, 3]] == 0).all()


# -- rotary positions ---------------------------------------------------------
def test_interleaved_rotary_is_the_pairs_and_gives_the_source_s_scores():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 3, 40, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 1, 40, 8)), jnp.float32)
    def rot(x, theta, interleaved=False):
        # attributes by keyword: what follows the data positionally is an
        # input (the positions, since PR 33)
        return get_op("_contrib_RotaryEmbedding").fn(
            x, theta=theta, interleaved=interleaved)

    # the pairs by hand: (x[2i], x[2i+1]) turned by pos * theta^(-2i/d)
    pos = np.arange(40)[:, None]
    ang = pos * (1e6 ** (-np.arange(0, 8, 2) / 8))[None, :]
    x = np.asarray(q)
    by_hand = np.empty_like(x)
    by_hand[..., 0::2] = x[..., 0::2] * np.cos(ang) - x[..., 1::2] * np.sin(ang)
    by_hand[..., 1::2] = x[..., 1::2] * np.cos(ang) + x[..., 0::2] * np.sin(ang)
    np.testing.assert_allclose(np.asarray(rot(q, 1e6, True)), by_hand,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(reference.rotary(q, 1e6)), by_hand,
                               rtol=1e-5, atol=1e-6)
    # the source de-interleaves and then rotates halves: other vectors,
    # the same scores
    ours = jnp.einsum("bhqd,bhkd->bhqk", rot(q, 1e6, True),
                      jnp.broadcast_to(rot(k, 1e6, True), q.shape))
    source_q = reference.rotary_source_order(q, 1e6)
    source_k = reference.rotary_source_order(k, 1e6)
    assert float(jnp.abs(source_q - rot(q, 1e6, True)).max()) > 0.1
    theirs = jnp.einsum("bhqd,bhkd->bhqk", source_q,
                        jnp.broadcast_to(source_k, q.shape))
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs),
                               rtol=1e-5, atol=1e-5)
    # ... and it is not rotate-half, which stays the default
    half = rot(q, 1e6)
    np.testing.assert_array_equal(np.asarray(half),
                                  np.asarray(rot(q, 1e6, False)))
    assert float(jnp.abs(half - rot(q, 1e6, True)).max()) > 0.1


# -- the routed layer at this family's numbers --------------------------------
D, F, E, K, HELD = 32, 16, 128, 6, 16


def routed_inputs(tokens=256, seed=0):
    rng = np.random.default_rng(seed)
    return {"x": jnp.asarray(rng.normal(size=(tokens, D)), jnp.float32),
            "router": jnp.asarray(0.3 * rng.normal(size=(E, D)), jnp.float32),
            "expert_w1": jnp.asarray(0.2 * rng.normal(size=(E, D, F)),
                                     jnp.float32),
            "expert_w3": jnp.asarray(0.2 * rng.normal(size=(E, D, F)),
                                     jnp.float32),
            "expert_w2": jnp.asarray(0.2 * rng.normal(size=(E, F, D)),
                                     jnp.float32)}


def routed_cfg(scale):
    return {"router_experts": E, "num_experts_per_tok": K,
            "n_routed_experts": E, "first_expert": 0,
            "expert_bias_scale": scale, "norm_topk_prob": True,
            "routed_scaling_factor": 2.448, "n_shared_experts": 0}


def routed_op(v, first, held, bias):
    sl = slice(first, first + held)
    return get_op("_contrib_RoutedExperts").fn(
        v["x"], v["router"], v["expert_w1"][sl], v["expert_w3"][sl],
        v["expert_w2"][sl], expert_bias=tuple(bias), num_experts_per_tok=K,
        first_expert=first, norm_topk_prob=True,
        routed_scaling_factor=2.448)


def test_the_128_way_top_6_route_is_the_reference_s():
    """The operator's choice and weights (scores + bias for the choice
    alone, the chosen scores normalised, times 2.448) against the
    reference's, whose denominator is the source's (+ 1e-20 where the
    operator adds 1e-6: 3e-7 of a sum near 3)."""
    from mxnet_tpu.ops.lm_blocks import _route
    v = routed_inputs()
    cfg = routed_cfg(0.3)
    bias = reference.expert_bias(cfg)
    assert len(bias) == 128 and bias.max() == 0.3 == -bias.min()
    chosen, weights = _route(v["x"], v["router"], tuple(bias), K, True,
                             2.448)
    want_chosen, want_weights = reference.route(cfg, v["x"], v["router"])
    np.testing.assert_array_equal(np.asarray(chosen),
                                  np.asarray(want_chosen))
    np.testing.assert_allclose(np.asarray(weights),
                               np.asarray(want_weights), rtol=2e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.448,
                               rtol=1e-5)
    # the bias moves the choice and never the weights
    plain, _ = reference.route(routed_cfg(0.0), v["x"], v["router"])
    assert (np.sort(np.asarray(plain), -1)
            != np.sort(np.asarray(want_chosen), -1)).any()


def test_the_eight_shares_and_the_shared_experts_once_add_up_to_the_layer():
    """What eight chips, each holding 16 of the 128 experts, compute for
    the same tokens (each its routed part; the shared experts, which all
    eight compute alike, counted once) adds up to the uncut reference
    layer's feed-forward."""
    v = routed_inputs()
    rng = np.random.default_rng(1)
    cfg = dict(routed_cfg(0.3), n_shared_experts=2,
               moe_intermediate_size=F)
    shared = {k: jnp.asarray(0.2 * rng.normal(size=s), jnp.float32)
              for k, s in (("shared_w1", (2 * F, D)),
                           ("shared_w3", (2 * F, D)),
                           ("shared_w2", (D, 2 * F)))}
    p = {"l." + k: a for k, a in dict(v, **shared).items() if k != "x"}
    whole = highest(
        lambda p, x: reference.shared(p, "l.", cfg, x, False)
        + reference.routed(p, "l.", cfg, x, False, 0, E), p, v["x"])
    bias = reference.expert_bias(cfg)
    shares = [routed_op(v, first, HELD, bias) for first in range(0, E, HELD)]
    once = get_op("_contrib_SharedExperts").fn(
        v["x"], shared["shared_w1"], shared["shared_w3"],
        shared["shared_w2"])
    np.testing.assert_allclose(np.asarray(sum(shares) + once),
                               np.asarray(whole), rtol=1e-5, atol=2e-6)
    # one share is the reference's own share, and the shares differ
    sl = slice(2 * HELD, 3 * HELD)
    own = dict(p, **{"l." + k: v[k][sl]
                     for k in ("expert_w1", "expert_w3", "expert_w2")})
    np.testing.assert_allclose(
        np.asarray(shares[2]),
        np.asarray(highest(lambda p, x: reference.routed(
            p, "l.", cfg, x, False, 2 * HELD, HELD), own, v["x"])),
        rtol=1e-5, atol=2e-6)
    assert np.abs(np.asarray(shares[2] - shares[3])).max() > 1e-3
    # eight times the shared experts would be wrong by seven of them
    assert np.abs(np.asarray(once)).max() > 1e-3


# -- the flash kernels at two head widths -------------------------------------
def qkv(b, h, sq, sk, d, d_v, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, h, sq, d), dtype),
            jax.random.normal(ks[1], (b, h, sk, d), dtype),
            jax.random.normal(ks[2], (b, h, sk, d_v), dtype),
            jax.random.normal(ks[3], (b, h, sq, d_v), jnp.float32))


def out_and_grads(fn, q, k, v, w):
    return jax.value_and_grad(
        lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * w),
        argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("d,d_v,sq,sk,tiles,dq", [
    (192, 128, 40, 56, dict(blk_q=16, blk_k=16, res_k=32), "vmem"),
    (192, 128, 24, 40, {}, "vmem"),         # the plan's own tiles
    (24, 16, 40, 56, dict(blk_q=16, blk_k=16), "vmem"),  # padded to 128
    (24, 16, 44, 20, dict(blk_q=8, blk_k=8, res_q=16, res_k=8), "vmem"),
    (64, 128, 160, 160, dict(blk_q=16, blk_k=16), "vmem"),  # v the wider
    # several K/V blocks: dq accumulates across grid steps, in VMEM ...
    (192, 128, 64, 64, dict(blk_q=16, blk_k=16, res_q=32, res_k=16), "vmem"),
    (192, 128, 40, 100, dict(blk_q=8, blk_k=16, res_q=8, res_k=32), "vmem"),
    # ... and as partials in HBM
    (192, 128, 64, 64, dict(blk_q=16, blk_k=16, res_q=32, res_k=16), "hbm"),
    (24, 16, 44, 20, dict(blk_q=8, blk_k=8, res_q=16, res_k=8), "hbm"),
])
def test_flash_kernels_at_two_widths_interpret(d, d_v, sq, sk, tiles, dq,
                                               causal, dq_accumulates_in):
    """Forward and the three gradients of the Pallas kernels (interpret
    mode) with keys *d* wide and values *d_v* wide against
    `attention_reference`."""
    q, k, v, w = qkv(1, 2, sq, sk, d, d_v)
    scale = d ** -0.5
    dq_accumulates_in(dq)
    assert attention._flash_plan(sq, sk, d, q.dtype, d_v=d_v,
                                 **tiles).dq_accumulator == dq

    def flash(q, k, v):
        out = attention._flash_fwd_pallas(q, k, v, causal, scale,
                                          interpret=True, **tiles)
        assert out.shape == (1, 2, sq, d_v)
        return out

    with jax.default_matmul_precision("highest"):
        want, want_g = out_and_grads(
            lambda q, k, v: attention.attention_reference(
                q, k, v, causal, scale), q, k, v, w)
        got = jnp.sum(flash(q, k, v) * w)
        out, lse = attention._flash_fwd_pallas(
            q, k, v, causal, scale, interpret=True, with_lse=True, **tiles)
        got_g = attention._flash_bwd_pallas(
            q, k, v, out, lse, w.astype(q.dtype), causal, scale,
            interpret=True, **tiles)
    assert float(got) == pytest.approx(float(want), rel=2e-5, abs=2e-4)
    for name, a, b in zip("qkv", got_g, want_g):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5, err_msg="d" + name)


@pytest.mark.parametrize("d,d_v", [(192, 128), (24, 16)])
def test_flash_attention_s_paths_agree_at_two_widths(d, d_v):
    """The public call, interpreted kernels and the chunked scan (what a
    CPU lowers), with its own gradients, against the oracle."""
    q, k, v, w = qkv(1, 2, 48, 48, d, d_v, seed=1)
    with jax.default_matmul_precision("highest"):
        want = out_and_grads(lambda *a: attention.attention_reference(
            *a, causal=True), q, k, v, w)
        for path in (
                lambda *a: attention.flash_attention(*a, causal=True,
                                                     interpret=True),
                lambda *a: attention.flash_attention(*a, causal=True),
                lambda *a: attention._chunked_attention(*a, causal=True,
                                                        chunk=16)):
            got = out_and_grads(path, q, k, v, w)
            for a, b in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=2e-4, atol=2e-4)


_TILES = attention._Tiles


def test_the_plan_at_one_width_is_the_parent_s_written_out():
    """OPT-1.3B's and LFM2's shapes (d = 64, bf16) plan as they did before
    the kernels took a second width: the values of commit 889eb0f, the
    one backward kernel at the resident blocks dk/dv had (its looped
    sub-tile is 256 query columns by 512 key rows: the sweep's)."""
    plan = attention._flash_plan(2048, 2048, 64, jnp.bfloat16)
    whole = _TILES(2048, 2048, 256, 256)
    assert plan[:7] == (64, 2048, 2048, 2048, 2048, whole, whole)
    plan = attention._flash_plan(8192, 8192, 64, jnp.bfloat16)
    assert plan[:7] == (64, 8192, 8192, 8192, 8192,
                        _TILES(1024, 4096, 256, 512),
                        _TILES(4096, 1024, 256, 512))
    for s in (2048, 8192):
        one = attention._flash_plan(s, s, 64, jnp.bfloat16)
        assert (one.dv_block, one.dq_accumulator) == (64, "vmem")
        assert one == attention._flash_plan(s, s, 64, jnp.bfloat16, d_v=64)
    # ... and so do the bytes the plan's model asks for a resident row
    for kernel, want in (("fwd", (2624, 1024)), ("bwd", (1152, 3072))):
        assert attention._side_bytes(kernel, 64, 64, 2) == want, kernel
    # 192 wide takes two lane tiles in VMEM, like 256
    for kernel, want in (("fwd", (4160, 2048)), ("bwd", (2176, 6144))):
        assert attention._side_bytes(kernel, 192, 192, 2) == want, kernel


@pytest.mark.parametrize("s,d,d_v,blocks,dq,limit", [
    # OPT-1.3B: the whole head, inside Mosaic's default 16 MiB
    (2048, 64, 64, 8.25, 2, 16),
    # LFM2: 4096 query rows stream past 1024 resident key rows
    (8192, 64, 64, 7.5, 8, 22.5),
    # Kanana: 2048 past 1024, at 192 / 128
    (8192, 192, 128, 7.75, 16, 30.75),
])
def test_the_backward_s_vmem_is_counted_and_asked_for(s, d, d_v, blocks, dq,
                                                      limit):
    """The one backward kernel's VMEM by the plan's model, in MiB at the
    three LM cells' shapes: its resident blocks and a tile's temporaries
    inside `_VMEM_BUDGET` as ever, dq's f32 accumulator and output block
    over the head's whole sequence beside them, and the call's
    `vmem_limit_bytes` that sum and Mosaic's own share."""
    MiB = 1 << 20
    plan = attention._flash_plan(s, s, d, jnp.bfloat16, d_v=d_v)
    t = plan.bwd
    per_q, per_k = attention._side_bytes("bwd", plan.d_block, plan.dv_block,
                                         2)
    tile = attention._tile_bytes(t.sub_q, t.sub_k)
    assert per_q * t.res_q + per_k * t.res_k == blocks * MiB
    assert blocks * MiB + tile <= attention._VMEM_BUDGET
    assert plan.dq_accumulator == "vmem"
    assert attention._dq_bytes("vmem", s, plan.d_block, 2) == dq * MiB
    need = attention._vmem_bytes("bwd", plan, 2)
    assert need == blocks * MiB + tile + dq * MiB
    rec = attention._plan_args(plan, s, s, d, jnp.bfloat16, True, d_v)
    assert rec["bwd"]["vmem_bytes"] == need
    assert rec["bwd"]["vmem_limit_bytes"] == limit * MiB >= need
    assert rec["bwd"]["dq_accumulator"] == "vmem"
    # ... and the limit is the one the call hands Mosaic
    text = str(jax.make_jaxpr(lambda q, k, v, o, lse, do:
                              attention._flash_bwd_pallas(
                                  q, k, v, o, lse, do, True, 0.125))(
        *(jax.ShapeDtypeStruct((1, 1, s, w), jnp.bfloat16)
          for w in (d, d, d_v, d_v)),
        jax.ShapeDtypeStruct((1, 1, s), jnp.float32),
        jax.ShapeDtypeStruct((1, 1, s, d_v), jnp.bfloat16)))
    assert "vmem_limit_bytes=%d" % (limit * MiB) in text


def test_a_sequence_too_long_for_the_accumulator_leaves_dq_in_hbm():
    """From shapes alone: where dq's accumulator and output block over
    the whole sequence would pass `_VMEM_DQ`, each K/V block's share
    leaves as an f32 partial, and the call asks for its blocks alone."""
    long = attention._flash_plan(65536, 65536, 128, jnp.bfloat16)
    assert attention._dq_bytes("vmem", 65536, 128, 2) > attention._VMEM_DQ
    assert long.dq_accumulator == "hbm"
    rec = attention._plan_args(long, 65536, 65536, 128, jnp.bfloat16, True)
    # 7.5 MiB of blocks, 3 of a tile's temporaries, 4 of a partial's
    assert rec["bwd"]["vmem_bytes"] == attention._vmem_bytes(
        "bwd", long, 2) == 14.5 * (1 << 20)
    assert rec["bwd"]["vmem_limit_bytes"] == 18.5 * (1 << 20)
    half = attention._flash_plan(32768, 32768, 128, jnp.bfloat16)
    assert half.dq_accumulator == "vmem"
    assert half.bwd == long.bwd         # the same tiles either way
    # the longest the accumulator holds at this width: 48 MiB exactly
    most = attention._flash_plan(49152, 49152, 128, jnp.bfloat16)
    assert most.dq_accumulator == "vmem"
    assert attention._dq_bytes("vmem", 49152, 128, 2) == attention._VMEM_DQ


@pytest.mark.parametrize("s,d,d_v,dtype,limit_mib", [
    (2048, 64, 64, "bfloat16", 16), (8192, 64, 64, "bfloat16", 22.5),
    (8192, 192, 128, "bfloat16", 30.75),
    # the longest sequence whose accumulator `_VMEM_DQ` holds, by width
    (49152, 128, 128, "bfloat16", 64), (24576, 256, 256, "bfloat16", 62.25),
    (12288, 512, 512, "bfloat16", 69.125), (32768, 128, 128, "float32", 61.75),
    (16384, 256, 256, "float32", 68.125), (8192, 512, 512, "float32", 81.0625),
    # past it
    (65536, 128, 128, "bfloat16", 18.5), (131072, 64, 64, "bfloat16", 18.5),
])
def test_the_backward_never_asks_for_more_vmem_than_its_three_shares(
        s, d, d_v, dtype, limit_mib):
    """An absolute cap, whatever the shape: blocks and a tile's
    temporaries inside `_VMEM_BUDGET` (unless one sub-tile of keys, the
    least a block holds, is more), dq's accumulator inside `_VMEM_DQ`
    (or a partial's block), Mosaic's share, which grows with a row's
    bytes past 256 lanes of bf16; two thirds of the chip's 128 MiB at
    the widest, half at the cells' widths."""
    MiB = 1 << 20
    plan = attention._flash_plan(s, s, d, dtype, d_v=d_v)
    itemsize = jnp.dtype(dtype).itemsize
    t = plan.bwd
    per_q, per_k = attention._side_bytes("bwd", plan.d_block, plan.dv_block,
                                         itemsize)
    blocks = per_q * t.res_q + per_k * t.res_k \
        + attention._tile_bytes(t.sub_q, t.sub_k)
    if t.res_k > t.sub_k:
        assert blocks <= attention._VMEM_BUDGET
    else:       # one sub-tile of keys is the least a block holds
        assert blocks <= attention._VMEM_BUDGET + per_k * t.sub_k
    need = attention._vmem_bytes("bwd", plan, itemsize)
    limit = attention._vmem_limit(plan, itemsize)
    assert limit == limit_mib * MiB
    mosaic = attention._VMEM_MOSAIC * max(1, d * itemsize // 512)
    assert need + mosaic <= limit
    assert limit <= max(attention._VMEM_BUDGET, blocks) \
        + attention._VMEM_DQ + mosaic <= 84 * MiB
    if d * itemsize <= 512:
        assert limit <= 64 * MiB
    if plan.dq_accumulator == "hbm":
        assert limit <= 20 * MiB


def test_dq_s_partials_in_hbm_are_bounded_a_group_of_heads_at_a_time():
    """The fallback's HBM: `nkr` f32 copies of a head's dq, so the heads
    go through the kernel in the largest groups whose partials `_HBM_DQ`
    holds, and a sequence whose one head passes it is refused by name."""
    GiB = 1 << 30
    assert attention._HBM_DQ == 2 * GiB
    # S 65536 at d 128: 64 K/V blocks x 32 MiB, one head at a time
    assert attention._dq_head_groups(32, 2 * GiB, 65536) == 1
    assert attention._dq_head_groups(32, GiB // 4, 65536) == 8
    assert attention._dq_head_groups(12, GiB // 2, 65536) == 4
    assert attention._dq_head_groups(6, 1 << 20, 4096) == 6
    with pytest.raises(ValueError, match="131072 is too long.*8.0 GiB"):
        attention._dq_head_groups(32, 8 * GiB, 131072)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("heads_a_call", [1, 2, 6])
def test_dq_from_partials_is_the_same_whatever_the_groups(
        heads_a_call, causal, dq_accumulates_in, monkeypatch):
    """Six heads through the `hbm` fallback one, two and six a call (the
    first two as a loop over groups): dq, dk, dv against the oracle."""
    sq = sk = 64
    tiles = dict(blk_q=16, blk_k=16, res_q=32, res_k=16)
    dq_accumulates_in("hbm")
    plan = attention._flash_plan(sq, sk, 24, jnp.float32, d_v=16, **tiles)
    partial = (plan.sk_bwd // plan.bwd.res_k) * plan.sq_bwd \
        * plan.d_block * 4
    monkeypatch.setattr(attention, "_HBM_DQ", heads_a_call * partial)
    assert attention._dq_head_groups(6, partial, sq) == heads_a_call
    q, k, v, w = qkv(2, 3, sq, sk, 24, 16)
    with jax.default_matmul_precision("highest"):
        _, want = out_and_grads(lambda q, k, v: attention.attention_reference(
            q, k, v, causal, 0.2), q, k, v, w)
        out, lse = attention._flash_fwd_pallas(
            q, k, v, causal, 0.2, interpret=True, with_lse=True, **tiles)
        got = attention._flash_bwd_pallas(q, k, v, out, lse, w, causal, 0.2,
                                          interpret=True, **tiles)
    text = str(jax.make_jaxpr(lambda *a: attention._flash_bwd_pallas(
        *a, causal, 0.2, interpret=True, **tiles))(q, k, v, out, lse, w))
    assert ("scan" in text) == (heads_a_call < 6)
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5, err_msg="d" + name)


def test_the_plan_at_two_widths_sizes_each_block_at_its_own():
    """192-wide q, k, dq, dk blocks take two lane tiles in VMEM, 128-wide
    v, o, dO, dv blocks one: the resident blocks are larger than three
    192-wide arrays would allow, and everything stays inside the VMEM."""
    two = attention._flash_plan(8192, 8192, 192, jnp.bfloat16, d_v=128)
    one = attention._flash_plan(8192, 8192, 192, jnp.bfloat16)
    assert (two.d_block, two.dv_block) == (192, 128)
    assert (one.d_block, one.dv_block) == (192, 192)
    assert two.bwd.res_k > one.bwd.res_k
    rec = attention._plan_args(two, 8192, 8192, 192, jnp.bfloat16, True, 128)
    assert (rec["d"], rec["d_v"], rec["d_block"], rec["dv_block"]) == \
        (192, 128, 192, 128)
    assert rec["fwd"]["vmem_bytes"] <= attention._VMEM_BUDGET
    assert rec["bwd"]["vmem_bytes"] <= rec["bwd"]["vmem_limit_bytes"] \
        - attention._VMEM_MOSAIC
    for kernel in attention._KERNELS:
        # the looped sub-tiles, as at any S = 8192
        assert rec[kernel]["sub_tile"] == list(attention._SUB_LOOPED)
    # widths that are no multiple of 64 are padded to the lane tile, each
    # on its own
    ragged = attention._flash_plan(40, 56, 24, jnp.float32, d_v=16)
    assert (ragged.d_block, ragged.dv_block) == (128, 128)
    assert attention._flash_plan(40, 56, 192, jnp.float32,
                                 d_v=80).dv_block == 128


def test_the_plan_span_records_the_values_width():
    @jax.jit
    def f(q, k, v):
        return attention.flash_attention(q, k, v, causal=True,
                                         interpret=True)

    q, k, v, _ = qkv(1, 1, 32, 32, 192, 128)
    t0 = time.perf_counter()
    f(q, k, v).block_until_ready()
    span, = [s for s in profiler.spans(since=t0)
             if s.name == "mx.flash.plan"]
    assert (span.args["d"], span.args["d_v"], span.args["d_block"],
            span.args["dv_block"]) == (192, 128, 192, 128)
    assert sorted(k for k in span.args if isinstance(span.args[k], dict)) \
        == ["bwd", "fwd"]
    assert span.args["bwd"]["dq_accumulator"] == "vmem"
    assert span.args["bwd"]["vmem_limit_bytes"] >= \
        span.args["bwd"]["vmem_bytes"]


def test_the_two_width_kernels_lower_for_the_tpu_without_a_chip():
    """Mosaic's block rules at the cell's own shape (one head of it):
    192-wide and 128-wide blocks side by side, forward and backward."""
    import re
    q = jax.ShapeDtypeStruct((1, 2, 8192, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 2, 8192, 128), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(attention.flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
        q, q, v).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert set(re.findall(r"mx_flash_\w+", text)) == \
        {"mx_flash_fwd", "mx_flash_bwd"}
    assert text.count("tpu_custom_call") == 2


# ---------------------------------------------------------------------------
# Compiled for a described v5e, without a chip (`benchmarks/rehearse.py`):
# XLA:TPU and Mosaic for real, nothing runs.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture()
def no_persistent_cache():
    # a chipless compile is written to the persistent cache but cannot be
    # read back without a chip
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


_SMALL_LM = {"family": "transformer_lm", "vocab_size": 1024,
             "hidden_size": 256, "ffn_dim": 1024, "num_attention_heads": 2,
             "num_hidden_layers": 2, "max_position_embeddings": 1024,
             "init_std": 0.02,
             "train": {"optimizer": "sgd", "lr": 0.01, "momentum": 0.9,
                       "wd": 0.0, "multi_precision": True,
                       "sequence_length": 1024, "per_chip_batch": 2}}


@pytest.mark.parametrize("chips", [1, 4])
def test_a_training_step_for_the_described_chip_holds_two_mosaic_calls_a_layer(
        v5e, no_persistent_cache, chips):
    """The rehearsal's compile of a small LM step: the forward kernel and
    the one backward kernel of every layer are in the TPU program, the
    step has arguments, one chip needs no collective and data
    parallelism over four brings its all-reduces."""
    from benchmarks import rehearse
    out = rehearse.step_memory(_SMALL_LM, chips, v5e)
    assert out["mosaic_calls"] == 2 * _SMALL_LM["num_hidden_layers"]
    assert out["argument_size_in_bytes"] > 0
    if chips == 1:
        assert out["all_gathers"] == out["all_reduces"] == 0
    else:
        assert out["all_reduces"] > 0


@pytest.mark.parametrize("s,d,d_v,dtype,dq,limit_mib", [
    # the three LM cells' shapes
    (2048, 64, 64, "bfloat16", "vmem", 16),
    (8192, 64, 64, "bfloat16", "vmem", 22.5),
    (8192, 192, 128, "bfloat16", "vmem", 30.75),
    # the most the plan may ask Mosaic for: the longest accumulator
    # `_VMEM_DQ` holds beside full blocks, at three rows' widths
    (49152, 128, 128, "bfloat16", "vmem", 64),
    (16384, 384, 384, "bfloat16", "vmem", 62.625),
    (16384, 256, 256, "float32", "vmem", 68.125),
    # past it: partials in HBM, one head of the two at a time
    (65536, 128, 128, "bfloat16", "hbm", 18.5),
])
def test_the_backward_compiles_for_the_described_chip_at_what_it_asks_for(
        v5e, no_persistent_cache, s, d, d_v, dtype, dq, limit_mib):
    """Mosaic and XLA:TPU accept the backward call with the
    `vmem_limit_bytes` the plan counted, up to the 64 MiB that
    `_VMEM_BUDGET + _VMEM_DQ + _VMEM_MOSAIC` allow at the cells' widths
    and what wider rows add (16384 x 256 of f32 was refused by 212 KiB
    while Mosaic's share was 4 MiB at every width); the `hbm` fallback's
    temporaries stay at one group's partials."""
    from jax.sharding import SingleDeviceSharding
    plan = attention._flash_plan(s, s, d, dtype, d_v=d_v)
    assert plan.dq_accumulator == dq
    assert attention._vmem_limit(plan, jnp.dtype(dtype).itemsize) == \
        limit_mib * (1 << 20)
    one = SingleDeviceSharding(v5e.devices[0])

    def aval(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    compiled = jax.jit(lambda q, k, v, o, lse, do: attention._flash_bwd_pallas(
        q, k, v, o, lse, do, True, d ** -0.5)).lower(
            aval(1, 2, s, d), aval(1, 2, s, d), aval(1, 2, s, d_v),
            aval(1, 2, s, d_v), aval(2, 1, s, dt=jnp.float32),
            aval(1, 2, s, d_v)).compile()
    text = compiled.as_text()
    assert "mx_flash_bwd" in text
    temp = compiled.memory_analysis().temp_size_in_bytes
    if dq == "vmem":
        assert temp < 64 << 20          # delta and lse rows, no partials
    else:
        assert (2 << 30) <= temp < (2 << 30) + (256 << 20)


def test_a_latent_attention_layer_at_published_widths_prices_itself(
        v5e, no_persistent_cache):
    """The compiled layer's text says what every instruction moves and
    multiplies (`profiler.cost_map`): Kanana's latent attention at its
    published widths (8192 tokens, 32 heads of 128 + 64 / 128 over a
    512-wide latent), forward and backward, compiled for the described
    chip.  Every instruction of the ENTRY computation has a record, the
    two Mosaic kernels are found by name with no FLOPs of their own, the
    MXU work is the projections' (the attention core is the kernels'), and
    the map's sum is within a tenth of XLA's own `bytes accessed` (an
    async pair, which the map counts once, counted at both ends as XLA
    counts it)."""
    from jax.sharding import SingleDeviceSharding
    from mxnet_tpu.observability import costs
    one = SingleDeviceSharding(v5e.devices[0])
    s, d, heads, latent, nope, rope, vd = 8192, 2048, 32, 512, 128, 64, 128

    def aval(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    shapes = [(heads * (nope + rope), d), (latent + rope, d), (latent,),
              (heads * (nope + vd), latent), (d, heads * vd)]

    def step(x, weights, dout):
        def objective(x, w):
            out = get_op("_contrib_LatentAttention").fn(
                x, *w, num_heads=heads, qk_nope_head_dim=nope,
                qk_rope_head_dim=rope, v_head_dim=vd, rope_theta=1e6,
                rope_interleave=True, eps=1e-6)
            return jnp.sum(out.astype(jnp.float32) * dout)
        return jax.grad(objective, argnums=(0, 1))(x, weights)

    compiled = jax.jit(step).lower(
        aval(1, s, d), [aval(*shape) for shape in shapes],
        aval(1, s, d, dt=jnp.float32)).compile()
    text = compiled.as_text()
    profiler.set_scope_map("a-latent-attention-layer", text,
                           compiled.cost_analysis())
    try:
        records = profiler.cost_map("a-latent-attention-layer")
        totals = profiler.cost_totals("a-latent-attention-layer")
    finally:
        profiler._compiled.pop("a-latent-attention-layer")
    entry, comps = costs.parse_optimized_hlo(text)
    assert len(comps[entry]) > 50
    assert {i.name for i in comps[entry]} <= set(records)
    kernels = {r["kernel"]: r for r in records.values()
               if r.get("target") == "tpu_custom_call"}
    assert set(kernels) == {"mx_flash_fwd", "mx_flash_bwd"}
    assert all(r["mxu_flops"] is None and r["bytes_read"] > 0
               for r in kernels.values())
    # forward, input gradient and weight gradient of the four projections
    # (the objective is linear in the output, so the output projection's
    # forward product is not in the program)
    macs = s * (3 * (d * heads * (nope + rope) + d * (latent + rope)
                     + latent * heads * (nope + vd)) + 2 * heads * vd * d)
    assert totals["mxu_flops"] == pytest.approx(2 * macs, rel=0.02)
    assert totals["mxu_flops"] == pytest.approx(totals["xla"]["flops"],
                                                rel=0.05)
    moved = totals["bytes_read"] + totals["bytes_written"]
    pairs = sum(r["bytes_read"] + r["bytes_written"]
                for r in records.values() if r["opcode"].endswith("-start"))
    assert moved + pairs == pytest.approx(totals["xla"]["bytes_accessed"],
                                          rel=0.1)
    assert moved > 0.75 * totals["xla"]["bytes_accessed"]
    # the on-chip memory is read from the layouts' memory-space marks
    assert totals["onchip_bytes_read"] > 0 and totals["hbm_bytes_read"] > 0
