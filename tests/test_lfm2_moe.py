"""The layered decoder (`gluon/model_zoo/decoder.py`) and the operators
under it (`ops/lm_blocks.py`) against the plain float32 reference
`benchmarks/reference/lfm2_moe.py`, at a small size on the CPU with
seeded weights: float32 on both sides, so only the order of the
arithmetic differs."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import compare  # noqa: E402
from benchmarks.models import common as models_common  # noqa: E402
from benchmarks.models import lfm2_moe as family  # noqa: E402
from benchmarks.reference import common as ref_common  # noqa: E402
from benchmarks.reference import lfm2_moe as reference  # noqa: E402
from mxnet_tpu import profiler  # noqa: E402
from mxnet_tpu.ops import lm_blocks  # noqa: E402
from mxnet_tpu.ops.registry import get_op  # noqa: E402

SEED = 2 ** 31 + 5


def config(layer_types, num_dense_layers, **changes):
    cfg = {"family": "lfm2_moe", "hidden_size": 64, "intermediate_size": 128,
           "moe_intermediate_size": 32, "layer_types": list(layer_types),
           "num_dense_layers": num_dense_layers, "num_experts": 4,
           "num_routed_experts": 8, "first_expert": 0,
           "num_experts_per_tok": 2, "num_attention_heads": 4,
           "num_key_value_heads": 2, "rope_theta": 1000000,
           "norm_eps": 1e-5, "conv_L_cache": 3, "vocab_size": 96,
           "norm_topk_prob": True, "routed_scaling_factor": 1,
           "expert_bias_scale": 0.05, "initializer_range": 0.02,
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


# one net for each kind of layer, and all of them together (what the
# benchmark's configuration stacks), each with the tied head
KINDS = {
    "dense-conv": (["conv"], 1),
    "routed-attention": (["full_attention"], 0),
    "routed-conv": (["conv"], 0),
    "all": (["conv", "full_attention", "conv"], 1),
}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_logits_loss_and_every_gradient_agree_with_the_reference(kind):
    import mxnet_tpu as mx
    cfg = config(*KINDS[kind])
    net, loss, names, params = seeded(cfg)
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
    assert set(names) == set(grads)                 # one `embed` leaf
    lr = cfg["train"]["lr"]
    for ref_name, prog_name in names.items():
        g = -np.asarray(trainer._opt_state[prog_name][0]) / lr
        w = np.asarray(grads[ref_name]) / 4
        scale = max(np.abs(w).max(), 1e-12)
        assert np.abs(g - w).max() <= 2e-4 * scale, ref_name


def test_three_trainer_steps_follow_the_reference():
    cfg = config(*KINDS["all"])
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


# -- the routed layer ---------------------------------------------------------
D, F, E, K = 32, 16, 8, 2


def routed_inputs(tokens=48, seed=0):
    rng = np.random.default_rng(seed)
    return {"x": jnp.asarray(rng.normal(size=(tokens, D)), jnp.float32),
            "router": jnp.asarray(0.3 * rng.normal(size=(E, D)), jnp.float32),
            "expert_w1": jnp.asarray(0.2 * rng.normal(size=(E, D, F)),
                                     jnp.float32),
            "expert_w3": jnp.asarray(0.2 * rng.normal(size=(E, D, F)),
                                     jnp.float32),
            "expert_w2": jnp.asarray(0.2 * rng.normal(size=(E, F, D)),
                                     jnp.float32)}


def routed_op(v, first, held, bias=(), x=None):
    sl = slice(first, first + held)
    return get_op("_contrib_RoutedExperts").fn(
        v["x"] if x is None else x, v["router"], v["expert_w1"][sl],
        v["expert_w3"][sl], v["expert_w2"][sl], expert_bias=tuple(bias),
        num_experts_per_tok=K, first_expert=first)


def routed_cfg(scale=0.0):
    return {"num_routed_experts": E, "num_experts_per_tok": K,
            "num_experts": E, "first_expert": 0, "expert_bias_scale": scale}


def reference_layer(v, cfg, first=0, held=E):
    p = {"l." + k: a for k, a in v.items() if k != "x"}
    sl = slice(first, first + held)
    for k in ("expert_w1", "expert_w3", "expert_w2"):
        p["l." + k] = p["l." + k][sl]
    return highest(lambda p, x: reference.routed(p, "l.", cfg, x, False,
                                                 first, held), p, v["x"])


def test_the_four_shares_of_a_routed_layer_add_up_to_the_uncut_layer():
    """What four chips, each holding a quarter of the experts, compute
    for the same tokens adds up to the reference's whole layer."""
    v = routed_inputs()
    cfg = routed_cfg(0.2)
    bias = reference.expert_bias(cfg)
    whole = reference_layer(v, cfg)
    shares = [routed_op(v, first, E // 4, bias)
              for first in range(0, E, E // 4)]
    np.testing.assert_allclose(np.asarray(sum(shares)), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)
    # ... and one share is the reference's own share, not a quarter of it
    np.testing.assert_allclose(
        np.asarray(shares[1]),
        np.asarray(reference_layer(v, cfg, E // 4, E // 4)),
        rtol=1e-5, atol=1e-6)
    assert np.abs(np.asarray(shares[1] - whole / 4)).max() > 1e-3


def test_nothing_is_dropped_when_every_token_goes_to_one_held_expert():
    """A bias that sends every token's first choice to expert 1 and its
    second to expert 0: 2 x tokens pairs on two of the four held experts,
    none dropped; the two experts left without a token get a zero
    gradient, not a NaN."""
    v = routed_inputs()
    bias = [50.0, 100.0] + [0.0] * (E - 2)

    def held_part(w1, w3, w2, x):
        u = dict(v, expert_w1=w1, expert_w3=w3, expert_w2=w2)
        return routed_op(u, 0, 4, bias, x=x)

    out = jax.jit(held_part)(v["expert_w1"], v["expert_w3"], v["expert_w2"],
                             v["x"])
    # by hand: every token through experts 0 and 1, weighted by its scores
    s = jax.nn.sigmoid(v["x"] @ v["router"].T)
    w = s[:, :2] / (s[:, :1] + s[:, 1:2] + 1e-6)
    want = sum(w[:, e:e + 1] * highest(
        lambda *a: reference.gated(*a, False), v["x"], v["expert_w1"][e],
        v["expert_w3"][e], v["expert_w2"][e]) for e in (0, 1))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(out).min(axis=1).max()) > 0    # every token served
    grads = jax.jit(jax.grad(lambda *a: jnp.sum(held_part(*a) ** 2),
                             argnums=(0, 1, 2, 3)))(
        v["expert_w1"], v["expert_w3"], v["expert_w2"], v["x"])
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()
    for g in grads[:3]:
        assert float(jnp.abs(g[:2]).max()) > 0
        assert float(jnp.abs(g[2:4]).max()) == 0.0


# a shape with two buffers: 2 x 1024 pairs at worst, 1024 rows (two row
# tiles of 512; uniform routing lands 512 pairs on 2 held experts of 8)
TOKENS, HELD, ROWS = 1024, 2, 1024
NAMES = ("x", "router", "expert_w1", "expert_w3", "expert_w2")


@pytest.fixture
def ragged(monkeypatch):
    """XLA's ragged dot named outright (what this platform takes anyway):
    the choice by platform and the kernels' own bodies hold `cond`s, and
    the tests below count the pair buffer's."""
    monkeypatch.setattr(lm_blocks, "GROUPED_PATH", "ragged")
    return monkeypatch


def out_and_gradients(layer, v):
    """``layer(*arrays)`` and its gradient by every one of them, as one
    program: ``(its jaxpr, its values)``."""
    def both(*a):
        out, vjp = jax.vjp(layer, *a)
        return (out,) + vjp(jnp.cos(out))
    args = [v[n] for n in NAMES]
    return jax.make_jaxpr(both)(*args), jax.jit(both)(*args)


def held_share(bias=()):
    return lambda *a: routed_op(dict(zip(NAMES, a)), 0, HELD, bias)


def test_the_bounded_buffer_gives_the_worst_case_s_numbers_bit_for_bit(
        ragged):
    """Where the pairs fit the bounded buffer, the output and the
    gradients by the tokens and the router are the worst-case body's to
    the bit: the same pairs in the same groups from row 0, so the same
    sums in the same order.  The experts' weights' gradients too, but for
    how XLA's ragged dot on the CPU blocks a sum over the buffer's rows,
    which follows their number (w2's differs in float32's last places);
    the kernels' row tiles do not (`tools/moe_sweep.py` reads a gap of
    0.0 for the output and all three on the chip)."""
    assert lm_blocks._buffer_rows(TOKENS, K, HELD, E) == ROWS < TOKENS * K
    v = routed_inputs(tokens=TOKENS)
    chosen, _ = lm_blocks._route(v["x"], v["router"], (0.0,) * E, K, True,
                                 1.0)
    counts = lm_blocks.routed_expert_counts(chosen, E, 0, HELD, ROWS)
    assert 0 < int(counts[E]) <= ROWS
    assert [int(n) for n in counts[E + 2:]] == [ROWS, 0]
    jaxpr, bounded = out_and_gradients(held_share(), v)
    assert str(jaxpr).count(" cond[") == 2           # forward and backward
    ragged.setattr(lm_blocks, "_buffer_rows",
                   lambda tokens, top_k, *_: tokens * top_k)
    jaxpr, worst = out_and_gradients(held_share(), v)
    assert " cond[" not in str(jaxpr)
    for name, got, want in zip(("out",) + NAMES, bounded, worst):
        assert got.shape == want.shape
        got, want = np.asarray(got), np.asarray(want)
        assert np.abs(want).max() > 0, name
        if name.startswith("expert_w"):
            np.testing.assert_allclose(
                got, want, rtol=0, atol=4e-6 * np.abs(want).max(),
                err_msg=name)
        else:
            np.testing.assert_array_equal(got, want, err_msg=name)


def test_more_pairs_than_rows_take_the_worst_case_and_nothing_is_dropped(
        ragged):
    """A bias that sends every token's two choices to the two held
    experts, at the shape with two buffers: twice the bounded buffer's
    rows, so the worst-case branch runs (the bounded one would gather
    from beyond its rows), and output and gradients are the float32
    reference's: every token through both experts, by hand."""
    v = routed_inputs(tokens=TOKENS)
    bias = [50.0, 100.0] + [0.0] * (E - 2)
    with profiler.collect_step_stats() as stats:      # eager: real counts
        eager = held_share(bias)(*[v[n] for n in NAMES])
    (row,) = stats["moe_expert_counts"]
    assert [int(n) for n in row[E:]] == [TOKENS * K, 0, TOKENS * K, 1]
    assert TOKENS * K > ROWS

    def by_hand(x, router, w1, w3, w2):
        s = jax.nn.sigmoid(x @ router.T)[:, :2]
        w = s / (jnp.sum(s, -1, keepdims=True) + 1e-6)
        return sum(w[:, e:e + 1] * reference.gated(x, w1[e], w3[e], w2[e],
                                                   False) for e in (0, 1))

    jaxpr, got = out_and_gradients(held_share(bias), v)
    assert str(jaxpr).count(" cond[") == 2
    with jax.default_matmul_precision("highest"):
        _, want = out_and_gradients(by_hand, v)
    np.testing.assert_allclose(np.asarray(eager), np.asarray(want[0]),
                               rtol=1e-5, atol=1e-6)
    for name, g, w in zip(("out",) + NAMES, got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-6, err_msg=name)
    assert float(jnp.abs(got[0]).min(axis=1).max()) > 0  # every token served
    # the experts that are not held get no gradient from this share
    assert float(jnp.abs(got[3][HELD:]).max()) == 0.0


def test_all_the_router_s_experts_held_leaves_one_path_and_no_cond(ragged):
    """The choice between one buffer and two is made from shapes: with
    every expert held here (or a shape under a row tile) the worst case is
    the only buffer, and the program holds no `cond`."""
    assert lm_blocks._buffer_rows(TOKENS, K, E, E) == TOKENS * K
    assert lm_blocks._buffer_rows(48, K, 4, E) == 48 * K
    v = routed_inputs(tokens=TOKENS)
    jaxpr, _ = out_and_gradients(
        lambda *a: routed_op(dict(zip(NAMES, a)), 0, E), v)
    assert " cond[" not in str(jaxpr)
    assert str(jaxpr).count("ragged_dot_general[") == 3 + 8


def test_the_choice_is_on_score_plus_bias_and_the_weight_on_the_score():
    """With a bias that reverses the scores' order the experts chosen are
    the LOWEST scoring, and their weights are their own scores over the
    sum of the chosen scores."""
    v = routed_inputs(tokens=16)
    scores = np.asarray(jax.nn.sigmoid(v["x"] @ v["router"].T))
    lowest = np.argsort(scores, axis=1)[:, :K]
    # a bias so large and ordered that the choice is by bias alone:
    # experts E-1 and E-2 for every token, whatever their scores
    bias = 10.0 * np.arange(E)
    chosen, weights = lm_blocks._route(
        v["x"], v["router"], tuple(bias), K, True, 1.0)
    assert (np.sort(np.asarray(chosen), 1) == [E - 2, E - 1]).all()
    picked = scores[:, [E - 1, E - 2]]
    np.testing.assert_allclose(
        np.asarray(weights), picked / (picked.sum(1, keepdims=True) + 1e-6),
        rtol=1e-6)
    # ... and without a bias, the highest scoring
    chosen, _ = lm_blocks._route(v["x"], v["router"], (0.0,) * E, K, True,
                                 1.0)
    assert (np.sort(np.asarray(chosen), 1)
            == np.sort(np.argsort(-scores, axis=1)[:, :K], 1)).all()
    assert not (np.sort(np.asarray(chosen), 1) == np.sort(lowest, 1)).all()


@pytest.mark.parametrize("first", [0, 2, 4])
def test_the_routed_layer_s_gradients_agree_with_the_reference(first):
    v = routed_inputs()
    cfg = routed_cfg(0.2)
    bias = reference.expert_bias(cfg)
    names = ("x", "router", "expert_w1", "expert_w3", "expert_w2")

    def program(*a):
        return jnp.sum(jnp.sin(routed_op(dict(zip(names, a)), first, 4,
                                         bias)))

    def plain(*a):
        return jnp.sum(jnp.sin(reference_layer(dict(zip(names, a)), cfg,
                                               first, 4)))

    args = [v[n] for n in names]
    got = jax.jit(jax.grad(program, argnums=range(5)))(*args)
    want = highest(jax.grad(plain, argnums=range(5)), *args)
    for n, g, w in zip(names, got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-6, err_msg=n)
    # the experts that are not held get no gradient from this share
    held = np.zeros(E, bool)
    held[first:first + 4] = True
    assert float(jnp.abs(got[2][~held]).max()) == 0.0
    assert float(jnp.abs(got[2][held]).min(axis=(1, 2)).max()) > 0


@pytest.mark.parametrize("tokens, rows", [(2048, 3072), (16384, 24576)])
def test_the_kernel_path_lowers_for_the_tpu_without_a_chip(tokens, rows):
    """The public op lowered for the TPU platform from this CPU host at
    the cell's widths (and, second, its tokens): at the bounded buffer,
    whose rows divide into the row tile, the grouped products are the
    Mosaic kernels (3 forward; backward 5, the hidden states again among
    them, and 3 transposed ones for the weights' gradients); the
    worst-case branch holds as many ragged dots and no kernel."""
    d, f, held = 2048, 1792, 8
    avals = [jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in (
        (tokens, d), (32, d), (held, d, f), (held, d, f), (held, f, d))]
    fn = get_op("_contrib_RoutedExperts").fn
    assert lm_blocks._buffer_rows(tokens, 4, held, 32) == rows < tokens * 4
    assert lm_blocks._tiles(lm_blocks.GROUPED_TILES, rows, d, f) \
        == (512, 1024, 896) and rows % 512 == 0

    def fwd(*a):
        return fn(*a, num_experts_per_tok=4)

    def loss(*a):
        return jnp.sum(fwd(*a).astype(jnp.float32))

    def calls(f):
        text = jax.jit(f).trace(*avals).lower(
            lowering_platforms=("tpu",)).as_text()
        assert "tpu_custom_call" in text
        return (text.count("call @gmm"), text.count("call @tgmm"),
                text.count('"chlo.ragged_dot"('))

    assert calls(fwd) == (3, 0, 3)
    # the loss needs no forward product: its gradient is all that runs
    assert calls(jax.grad(loss, argnums=(0, 2, 3, 4))) == (5, 3, 8)


def test_the_short_convolution_lowers_for_the_tpu_without_a_chip():
    """`_contrib_GatedShortConv` lowered for the TPU platform from this
    CPU host at the cell's shape: the forward is one Mosaic kernel between
    the two projections; the gradient holds the forward kernel (whose
    ``gated`` the output projection's own gradient needs) and the backward
    one, and five products: ``bcx`` again, ``dgated``, and the three
    gradients of the two projections.  Nothing in float32 of the size of
    the activations is kept from the forward pass for the backward: ``u``
    and ``conv`` are computed again from ``bcx``.  At the tests' widths
    there is no kernel, and `mx.shortconv.plan` says which path it was."""
    fn = get_op("_contrib_GatedShortConv").fn
    bf = jnp.bfloat16

    def avals(b, s, d, taps=3):
        return [jax.ShapeDtypeStruct(shape, bf) for shape in (
            (b, s, d), (3 * d, d), (d, taps), (d, d))]

    def loss(*a):
        return jnp.sum(fn(*a).astype(jnp.float32))

    def text(f, at):
        return jax.jit(f).trace(*at).lower(
            lowering_platforms=("tpu",)).as_text()

    def counts(t):
        return (t.count("stablehlo.custom_call @tpu_custom_call"),
                t.count("stablehlo.dot_general"))

    since = max([s.id for s in profiler.spans()] or [0])
    cell = avals(2, 8192, 2048)
    forward = text(fn, cell)
    assert counts(forward) == (1, 2) and "mx_shortconv_fwd" in forward
    backward = text(jax.grad(loss, argnums=(0, 1, 2, 3)), cell)
    assert counts(backward) == (2, 5)
    assert "mx_shortconv_fwd" in backward and "mx_shortconv_bwd" in backward
    # what `jax.vjp` keeps: its pullback is a pytree of the residuals
    kept = jax.tree.leaves(jax.eval_shape(
        lambda *a: jax.vjp(loss, *a)[1], *cell))
    shapes = [(a.shape, a.dtype) for a in kept]
    assert ((2, 8192, 3 * 2048), bf) in shapes              # bcx
    assert ((2, 8192, 2048), bf) in shapes                  # gated
    assert not [a for a in kept if a.dtype == jnp.float32
                and a.size >= 2 * 8192 * 2048], shapes
    small = avals(2, 32, 64)
    assert counts(text(fn, small))[0] == 0
    assert counts(text(jax.grad(loss, argnums=(0, 1, 2, 3)), small))[0] == 0
    plans = [s.args for s in profiler.spans()
             if s.name == "mx.shortconv.plan" and s.id > since]
    at_cell = [p for p in plans if p["shape"] == [2, 8192, 3 * 2048]]
    tiles = lm_blocks.SHORTCONV_TILES
    assert at_cell and all(p == {
        "shape": [2, 8192, 6144], "dtype": "bfloat16", "taps": 3,
        "path": "kernel", "halo_rows": 16, "channel_tile": tiles["channels"],
        "seq_tile": {"fwd": tiles["fwd"], "bwd": tiles["bwd"]},
        "residual_bytes": 2 * 8192 * 6144 * 2 + 2048 * 3 * 2}
        for p in at_cell)
    at_small = [p for p in plans if p["shape"] == [2, 32, 192]]
    assert at_small and all(
        p["path"] == "xla" and p["seq_tile"] is None
        and p["residual_bytes"] is None for p in at_small)


def test_the_kernels_are_left_to_one_device_and_to_shapes_they_tile():
    """What `_shortconv_plan` reads in its input: a width of whole lane
    tiles, a sequence in whole tiles, 2 to 8 taps, blocks inside the VMEM
    budget, and no mesh of several devices around it (XLA does not
    partition a Mosaic kernel)."""
    from jax.sharding import Mesh
    from mxnet_tpu.parallel import mesh as mesh_mod
    bf = jnp.bfloat16

    def plan(b, s, d, taps=3, dtype=bf):
        return lm_blocks._shortconv_plan(
            jax.ShapeDtypeStruct((b, s, 3 * d), dtype),
            jax.ShapeDtypeStruct((d, taps), dtype))

    assert plan(2, 8192, 2048) == lm_blocks.SHORTCONV_TILES
    assert plan(1, 256, 512) == lm_blocks.SHORTCONV_TILES
    # the channel chunk is cut to divide the width: 1536 = 3 x 512, 640 =
    # 5 x 128; half a lane tile has no chunk
    assert plan(2, 8192, 1536)["channels"] == 512
    assert plan(2, 8192, 640)["channels"] == 128
    assert plan(2, 8192, 2048 + 64) is None
    assert plan(2, 8192 + 64, 2048) is None         # half a tile
    assert plan(2, 8192, 2048, taps=1) is None
    assert plan(2, 8192, 2048, taps=9) is None
    assert plan(2, 8192, 2048, dtype=jnp.float32) is None   # over the VMEM
    assert plan(2, 8192, 8192) is None                      # budget
    assert plan(2, 8192, 1024, dtype=jnp.float32) is not None
    with mesh_mod.use_mesh(Mesh(np.array(jax.devices()[:2]), ("dp",))):
        assert plan(2, 8192, 2048) is None
    with mesh_mod.use_mesh(Mesh(np.array(jax.devices()[:1]), ("dp",))):
        assert plan(2, 8192, 2048) is not None


# -- the other operators ------------------------------------------------------
def test_the_short_convolution_is_causal_and_agrees_with_the_reference():
    cfg = config(["conv"], 0)
    rng = np.random.default_rng(3)
    d = cfg["hidden_size"]
    x = jnp.asarray(rng.normal(size=(2, 12, d)), jnp.float32)
    p = {"l.conv_in": jnp.asarray(0.2 * rng.normal(size=(3 * d, d)),
                                  jnp.float32),
         "l.conv_w": jnp.asarray(rng.normal(size=(d, 3)), jnp.float32),
         "l.conv_out": jnp.asarray(0.2 * rng.normal(size=(d, d)),
                                   jnp.float32)}
    fn = get_op("_contrib_GatedShortConv").fn

    def run(x):
        return fn(x, p["l.conv_in"], p["l.conv_w"], p["l.conv_out"])

    np.testing.assert_allclose(
        np.asarray(run(x)),
        np.asarray(highest(
            lambda p, x: reference.short_conv(p, "l.", cfg, x, False), p,
            x)),
        rtol=1e-5, atol=1e-6)
    # a change at t + 1 moves nothing at or before t, and does move t + 1
    t = 6
    moved = np.asarray(run(x.at[:, t + 1].add(1.0)) - run(x))
    assert np.abs(moved[:, :t + 1]).max() == 0.0
    assert np.abs(moved[:, t + 1]).max() > 1e-3
    # by hand at one position: taps t-2, t-1, t
    bcx = x @ p["l.conv_in"].T
    u = np.asarray(bcx[..., :d] * bcx[..., 2 * d:])
    w = np.asarray(p["l.conv_w"])
    c = u[:, t - 2] * w[:, 0] + u[:, t - 1] * w[:, 1] + u[:, t] * w[:, 2]
    want = (np.asarray(bcx[:, t, d:2 * d]) * c) @ np.asarray(
        p["l.conv_out"]).T
    np.testing.assert_allclose(np.asarray(run(x))[:, t], want, rtol=1e-4,
                               atol=1e-6)


# -- the short convolution's kernels, interpreted ----------------------------
def conv_arguments(batch, seq, d, taps, dtype, seed=5):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(a, jnp.float32).astype(dtype) for a in (
        rng.normal(size=(batch, seq, d)),
        0.2 * rng.normal(size=(3 * d, d)), rng.normal(size=(d, taps)),
        0.2 * rng.normal(size=(d, d))))


def with_the_body(x, w_in, w_conv, w_out):
    """The operator with `_gate_body` under JAX's own differentiation:
    what the tests' small widths and every platform but the TPU run."""
    return lm_blocks._dot(
        lm_blocks._gate_body(lm_blocks._dot(x, w_in), w_conv), w_out)


@pytest.fixture
def interpreted(monkeypatch):
    """Steers the registered operator onto its TPU branch on this CPU
    host, the two kernels interpreted: ``interpreted(fwd, bwd)`` sets the
    sequence tiles (channel chunks of 128), small enough for a test."""
    def take_tpu(*args, tpu, default):
        return tpu(*args, interpret=True)

    def tiles(fwd, bwd):
        monkeypatch.setattr(lm_blocks, "SHORTCONV_TILES",
                            {"fwd": fwd, "bwd": bwd, "channels": 128})
        monkeypatch.setattr(jax.lax, "platform_dependent", take_tpu)
        return get_op("_contrib_GatedShortConv").fn

    return tiles


@pytest.mark.parametrize("batch", [1, 2])
@pytest.mark.parametrize("seq, fwd, bwd", [(32, 32, 32), (96, 32, 16)],
                         ids=["one-tile", "several-tiles"])
@pytest.mark.parametrize("taps", [2, 3, 4])
def test_the_kernels_give_the_body_s_value_and_all_four_gradients(
        interpreted, taps, seq, fwd, bwd, batch):
    """Float32 on both sides, so only the order of a few sums differs
    (the backward kernel adds the taps' gradient tile by tile)."""
    args = conv_arguments(batch, seq, 256, taps, jnp.float32)
    weight = jnp.asarray(np.random.default_rng(7).normal(
        size=(batch, seq, 256)), jnp.float32)
    since = max([s.id for s in profiler.spans()] or [0])
    op = interpreted(fwd, bwd)

    def loss(f):
        return lambda *a: jnp.sum(f(*a) * weight)

    def close(g, w, name):
        # a sum of hundreds of terms that cancel: to a float32 rounding of
        # its largest, not of each
        w = np.asarray(w)
        np.testing.assert_allclose(np.asarray(g), w, rtol=1e-5,
                                   atol=3e-6 * np.abs(w).max(), err_msg=name)

    close(op(*args), with_the_body(*args), "value")
    got = jax.grad(loss(op), argnums=(0, 1, 2, 3))(*args)
    want = jax.grad(loss(with_the_body), argnums=(0, 1, 2, 3))(*args)
    for name, g, w in zip(("data", "in_weight", "conv_weight", "out_weight"),
                          got, want):
        close(g, w, name)
    plans = [s.args for s in profiler.spans()
             if s.name == "mx.shortconv.plan" and s.id > since]
    assert plans and all(p["path"] == "kernel" and p["halo_rows"] == 8
                         and p["seq_tile"] == {"fwd": fwd, "bwd": bwd}
                         for p in plans)


def test_the_kernel_path_is_causal_and_keeps_batch_rows_apart(interpreted):
    op = interpreted(32, 16)
    x, w_in, w_conv, w_out = conv_arguments(2, 96, 256, 3, jnp.float32)
    base = np.asarray(op(x, w_in, w_conv, w_out))
    # a change at t + 1 moves nothing at or before t: across a tile's edge
    # (t + 1 = 32 and 64 open a tile) and inside a tile
    for t in (31, 40, 63):
        moved = np.asarray(op(x.at[:, t + 1].add(1.0), w_in, w_conv, w_out)
                           ) - base
        assert np.abs(moved[:, :t + 1]).max() == 0.0
        assert np.abs(moved[:, t + 1]).max() > 1e-3
        # ... and reaches taps - 1 = 2 rows on, over the edge, no further
        assert np.abs(moved[:, t + 3]).max() > 1e-3
        assert np.abs(moved[:, t + 4:]).max() == 0.0
    # the last rows of batch row 0 move nothing in batch row 1, whose
    # first rows see zeros before them
    moved = np.asarray(op(x.at[0, -2:].add(1.0), w_in, w_conv, w_out)) - base
    assert np.abs(moved[1]).max() == 0.0 and np.abs(moved[0, -2:]).max() > 0
    # the backward pass: the loss of rows up to t has no gradient after t,
    # and none in the other batch row
    t = 47

    def upto(x):
        return jnp.sum(op(x, w_in, w_conv, w_out)[0, :t + 1] ** 2)

    dx = np.asarray(jax.grad(upto)(x))
    assert np.abs(dx[0, t + 1:]).max() == 0.0 and np.abs(dx[1]).max() == 0.0
    assert np.abs(dx[0, t]).max() > 0 and np.abs(dx[0, 0]).max() > 0


@pytest.mark.parametrize("taps", [2, 3, 4])
def test_in_bf16_the_forward_kernel_equals_the_body_to_the_last_bit(taps):
    """To the last bit, not to a rounding: with bf16 taps every product
    under a sum (``b * x``, ``w_j * u``) is exact in float32, so only the
    order of the taps' sum could differ, and it is the body's.  The
    backward kernel's ``dbcx`` is the body's derivative to one bf16
    rounding (JAX's sums ``du`` in another order) and the taps' gradient
    to float32 sums in another order, rounded to bf16."""
    bf = jnp.bfloat16
    rng = np.random.default_rng(taps)
    bcx = jnp.asarray(rng.normal(size=(2, 96, 3 * 256)), bf)
    w = jnp.asarray(rng.normal(size=(256, taps)), bf)
    dgated = jnp.asarray(rng.normal(size=(2, 96, 256)), bf)
    got = lm_blocks._shortconv_fwd_pallas(bcx, w, rows=32, channels=128,
                                          interpret=True)
    want = lm_blocks._gate_body(bcx, w)
    assert got.dtype == want.dtype == bf
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    dbcx, dw = lm_blocks._shortconv_bwd_pallas(bcx, w, dgated, rows=16,
                                               channels=128, interpret=True)
    want_dbcx, want_dw = lm_blocks._body_backward(bcx, w, dgated)
    assert dbcx.dtype == bf and dw.dtype == bf and dw.shape == w.shape
    np.testing.assert_allclose(np.asarray(dbcx, np.float32),
                               np.asarray(want_dbcx, np.float32),
                               rtol=2 ** -7, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dw, np.float32),
                               np.asarray(want_dw, np.float32),
                               rtol=2 ** -7, atol=2 ** -5)


def test_at_a_shape_the_kernels_tile_every_other_platform_runs_the_body():
    """On the CPU `_gate` at a tiled shape is the body, and its backward
    pass JAX's derivative of the body from ``bcx`` again: the same numbers
    as the body differentiated in place."""
    args = conv_arguments(1, 256, 512, 3, jnp.float32)
    op = get_op("_contrib_GatedShortConv").fn
    assert lm_blocks._shortconv_plan(
        jnp.zeros((1, 256, 1536)), args[2]) is not None

    def loss(f):
        return lambda *a: jnp.sum(f(*a) ** 2)

    np.testing.assert_array_equal(np.asarray(jax.jit(op)(*args)),
                                  np.asarray(jax.jit(with_the_body)(*args)))
    got = jax.jit(jax.grad(loss(op), argnums=(0, 1, 2, 3)))(*args)
    want = jax.jit(jax.grad(loss(with_the_body), argnums=(0, 1, 2, 3)))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6,
                                   atol=1e-6)


def test_grouped_query_attention_against_the_plain_attention():
    """Rotary positions, the norm of each q and k head and 4 query heads
    over 2 key/value heads: the Gluon block against the reference's
    attention, which repeats nothing."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.contrib.nn import GroupedQueryAttention
    cfg = config(["full_attention"], 0)
    d, hd = cfg["hidden_size"], 16
    rng = np.random.default_rng(4)
    shapes = {"wq": (4 * hd, d), "wk": (2 * hd, d), "wv": (2 * hd, d),
              "wo": (d, 4 * hd)}
    p = {"l." + k: jnp.asarray(0.2 * rng.normal(size=s), jnp.float32)
         for k, s in shapes.items()}
    p["l.q_norm"] = jnp.asarray(1 + 0.3 * rng.normal(size=hd), jnp.float32)
    p["l.k_norm"] = jnp.asarray(1 + 0.3 * rng.normal(size=hd), jnp.float32)
    x = rng.normal(size=(2, 24, d)).astype(np.float32)
    blk = GroupedQueryAttention(d, 4, 2, hd, rope_theta=1000000.0)
    blk.initialize()
    for param, key in zip(blk.collect_params().values(),
                          ("wq", "wk", "wv", "wo", "q_norm", "k_norm")):
        param.set_data(mx.nd.array(np.asarray(p["l." + key])))
    got = blk(mx.nd.array(x)).asnumpy()
    want = highest(lambda p, x: reference.attention(p, "l.", cfg, x, False),
                   p, jnp.asarray(x))
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-4, atol=2e-6)
    # positions matter: the same token later in the sequence reads
    # differently (a rotation, not a no-op)
    rolled = blk(mx.nd.array(np.roll(x, 1, axis=1))).asnumpy()
    assert np.abs(np.roll(rolled, -1, axis=1)[:, 2:-1]
                  - got[:, 2:-1]).max() > 1e-4


def test_rms_norm_and_the_gated_mlp_by_hand():
    rng = np.random.default_rng(5)
    x = rng.normal(size=(3, 5, 8)).astype(np.float32)
    g = rng.normal(size=8).astype(np.float32)
    got = get_op("_contrib_RMSNorm").fn(jnp.asarray(x), jnp.asarray(g),
                                        eps=1e-5)
    want = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * g
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5)
    w1, w3 = (rng.normal(size=(6, 8)).astype(np.float32) for _ in range(2))
    w2 = rng.normal(size=(8, 6)).astype(np.float32)
    got = get_op("_contrib_GatedMLP").fn(jnp.asarray(x), w1, w3, w2)
    a = x @ w1.T
    want = ((a / (1 + np.exp(-a))) * (x @ w3.T)) @ w2.T
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)


# -- what leaves the step -----------------------------------------------------
def test_the_counters_equal_the_reference_s_counts_and_the_plan_is_recorded():
    cfg = config(*KINDS["all"])
    net, loss, _, params = seeded(cfg)
    batches = family.batches(cfg, SEED, 1, 4)
    trainer = models_common.make_trainer(net, loss, cfg["train"],
                                         jax.devices()[:1])
    names = ("moe_stat_steps_total", "moe_stat_layers_total",
             "moe_assignments_total", "moe_local_assignments_total",
             "moe_tokens_without_local_expert_total",
             "moe_expert_load_max_over_mean_sum", "moe_buffer_rows_total",
             "moe_worst_case_buffer_layers_total")
    before = {n: profiler.counter_value(n) for n in names}
    since = max([s.id for s in profiler.spans()] or [0])
    dispatches = profiler.counter_value("parallel_step_dispatches")
    x, y = batches[0]
    trainer.fit_batch(x, y)
    trainer.flush_step_stats()
    assert profiler.counter_value("parallel_step_dispatches") \
        == dispatches + 1                       # the counts cost no dispatch
    got = {n: profiler.counter_value(n) - before[n] for n in names}
    load = np.asarray(highest(
        lambda p: reference.expert_counts(p, cfg, x), params))
    tokens, top_k = x.size, cfg["num_experts_per_tok"]
    assert load.shape == (2, 8) and (load.sum(1) == tokens * top_k).all()
    assert got["moe_stat_steps_total"] == 1
    assert got["moe_stat_layers_total"] == 2
    assert got["moe_assignments_total"] == load.sum()
    assert got["moe_local_assignments_total"] == load[:, :4].sum()
    assert got["moe_expert_load_max_over_mean_sum"] == pytest.approx(
        (load.max(1) / load.mean(1)).sum())
    assert 0 <= got["moe_tokens_without_local_expert_total"] <= 2 * tokens
    assert got["moe_buffer_rows_total"] == 2 * tokens * top_k
    assert got["moe_worst_case_buffer_layers_total"] == 0
    plans = [s for s in profiler.spans()
             if s.name == "mx.moe.plan" and s.id > since]
    assert plans and plans[0].args["experts_held"] == 4
    assert plans[0].args["router_experts"] == 8
    assert plans[0].args["pair_bound"] == tokens * top_k
    # ... which is the only buffer at this size: under one row tile
    assert plans[0].args["buffer_rows"] == tokens * top_k
    assert plans[0].args["bound"].startswith("worst case")
    assert plans[0].args["path"] == lm_blocks.GROUPED_PATH


def test_counts_fold_only_once_they_are_ready_and_in_order():
    """`fit_batch` folds the counts of earlier steps that have arrived
    and never waits; `flush_step_stats` takes the rest."""
    cfg = config(["conv"], 0)
    net, loss, _, _ = seeded(cfg)
    trainer = models_common.make_trainer(net, loss, cfg["train"],
                                         jax.devices()[:1])
    before = profiler.counter_value("moe_stat_steps_total")
    for x, y in family.batches(cfg, SEED, 3, 4):
        trainer.fit_batch(x, y)
    folded = profiler.counter_value("moe_stat_steps_total") - before
    assert 0 <= folded <= 3 and folded + len(trainer._stats_pending) == 3
    trainer.flush_step_stats()
    assert profiler.counter_value("moe_stat_steps_total") - before == 3
    assert not trainer._stats_pending


def test_a_value_emitted_outside_a_collection_is_dropped():
    profiler.emit_step_stat("moe_expert_counts", np.zeros((1, 12)))
    with profiler.collect_step_stats() as stats:
        profiler.emit_step_stat("a", 1)
        with profiler.collect_step_stats() as inner:
            profiler.emit_step_stat("a", 2)
        profiler.emit_step_stat("a", 3)
    assert stats == {"a": [1, 3]} and inner == {"a": [2]}


def test_the_expert_bias_is_the_configuration_s_and_not_a_parameter():
    cfg = config(["conv"], 0, expert_bias_scale=0.25)
    net, _ = family.build(cfg)
    bias = reference.expert_bias(cfg)
    assert bias[0] == 0.25 and bias.min() == pytest.approx(-0.25)
    attrs = net.layers[0].feed_forward._attrs
    np.testing.assert_allclose(attrs["expert_bias"], bias)
    assert len(net.collect_params()) == len(reference.param_table(cfg))
    assert not any("bias" in n for n in net.collect_params())
