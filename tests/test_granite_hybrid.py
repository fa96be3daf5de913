"""State-space and attention layers mixed by layer: the selective state-space
recurrence in its chunkwise form (`ops/state_space.py`:
`_contrib_StateSpaceScan` with a backward of its own, the step sizes' gates),
the short convolution with a bias and the gated norm with the gate first
(`ops/delta_rule.py`), `gluon.contrib.nn.StateSpaceMixer`,
`GroupedQueryAttention` with no q/k norm and a scale of its own, the decoder
kind `mamba` and the net's three multipliers, against the recurrence token by
token in float64 numpy (`benchmarks/ssm_counts.py`) and the plain float32
reference `benchmarks/reference/granitemoehybrid.py`, at a small size on the
CPU with seeded weights: float32 on both sides, so only the order of the
arithmetic differs."""

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

from benchmarks import compare, ssm_counts  # noqa: E402
from benchmarks.models import common as models_common  # noqa: E402
from benchmarks.models import granitemoehybrid as family  # noqa: E402
from benchmarks.reference import common as ref_common  # noqa: E402
from benchmarks.reference import granitemoehybrid as reference  # noqa: E402
from mxnet_tpu import profiler  # noqa: E402
from mxnet_tpu.ops import delta_rule, state_space  # noqa: E402

SEED = 2 ** 31 + 11
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4


def config(**changes):
    """The cell's shapes, small: one whole period of ten layers, 4 heads of
    16 with a state of 8 (not square), one group, 4 taps, two chunks of 16,
    2 key/value heads under 4 query heads."""
    cfg = {"family": "granitemoehybrid", "model_type": "granitemoehybrid",
           "hidden_size": 32, "intermediate_size": 48,
           "shared_intermediate_size": 48, "num_attention_heads": 4,
           "num_key_value_heads": 2, "hidden_act": "silu",
           "attention_bias": False, "attention_multiplier": 0.2,
           "embedding_multiplier": 12, "residual_multiplier": 0.22,
           "logits_scaling": 8, "mamba_n_heads": 4, "mamba_d_head": 16,
           "mamba_d_state": 8, "mamba_n_groups": 1, "mamba_d_conv": 4,
           "mamba_chunk_size": 16, "mamba_expand": 2,
           "mamba_conv_bias": True, "mamba_proj_bias": False,
           "position_embedding_type": "nope", "num_local_experts": 0,
           "num_experts_per_tok": 0, "normalization_function": "rmsnorm",
           "layer_types": list(PERIOD), "num_hidden_layers": 10,
           "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
           "vocab_size": 64, "initializer_range": 0.15,
           "conv_initializer_range": 0.2887, "gate_init_seed": 0,
           "train": {"optimizer": "sgd", "lr": 0.01, "momentum": 0.9,
                     "wd": 0.0, "multi_precision": False,
                     "sequence_length": 32, "per_chip_batch": 2}}
    cfg.update(changes)
    return cfg


def cut(kinds, seq=32, **changes):
    cfg = config(layer_types=list(kinds), num_hidden_layers=len(kinds),
                 **changes)
    cfg["train"] = dict(cfg["train"], sequence_length=seq)
    return cfg


def seeded(cfg, seed=SEED, build=None):
    """``(net, loss, names, reference parameters)`` from one seed."""
    table = reference.param_table(cfg)
    net, loss = family.build(build or cfg)
    names = models_common.seeded_net(
        net, table, ref_common.init_params(table, seed))
    return net, loss, names, ref_common.init_params(table, seed)


def highest(fn, *args):
    with jax.default_matmul_precision("highest"):
        return jax.jit(fn)(*args)


# -- the scan against the recurrence in float64 ---------------------------------
def recurrence_backward(x, dt, a, b, c, d, dout):
    """The six gradients of `ssm_counts.recurrence`'s output against *dout*,
    by its own reverse walk in float64 numpy: every state kept, each line of
    the forward step undone in turn."""
    x, dt, a, b, c, d, dout = (np.asarray(v, np.float64)
                               for v in (x, dt, a, b, c, d, dout))
    batch, seq, heads, width = x.shape
    groups, size = b.shape[2:]
    grads = [np.zeros_like(v) for v in (x, dt, a, b, c, d)]
    dx, ddt, da, db, dc, dd = grads
    for i in range(batch):
        for h in range(heads):
            g = h // (heads // groups)
            states = [np.zeros((width, size))]
            for t in range(seq):
                states.append(np.exp(dt[i, t, h] * a[h]) * states[-1]
                              + np.outer(dt[i, t, h] * x[i, t, h],
                                         b[i, t, g]))
            dstate = np.zeros((width, size))
            for t in reversed(range(seq)):
                e = np.exp(dt[i, t, h] * a[h])
                dstate = dstate + np.outer(dout[i, t, h], c[i, t, g])
                dc[i, t, g] += states[t + 1].T @ dout[i, t, h]
                dd[h] += dout[i, t, h] @ x[i, t, h]
                into = dstate @ b[i, t, g]              # d(dt x)
                dx[i, t, h] = d[h] * dout[i, t, h] + dt[i, t, h] * into
                db[i, t, g] += dt[i, t, h] * (dstate.T @ x[i, t, h])
                decayed = e * np.sum(dstate * states[t])
                ddt[i, t, h] = x[i, t, h] @ into + a[h] * decayed
                da[h] += dt[i, t, h] * decayed
                dstate = e * dstate
    return grads


def scan_inputs(regime, batch=2, seq=64, heads=4, width=8, groups=1, size=16,
                seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(batch, seq, heads, width))
    dt = np.exp(rng.uniform(np.log(0.001), np.log(0.5), (batch, seq, heads)))
    a = -rng.uniform(1.0, 16.0, heads)
    if regime == "decays-near-0":
        dt = rng.uniform(1.0, 3.0, (batch, seq, heads))
    elif regime == "decays-near-1":
        dt = rng.uniform(0.0, 1e-4, (batch, seq, heads))
    b, c = rng.normal(size=(2, batch, seq, groups, size))
    return x, dt, a, b, c, rng.normal(size=heads)


REGIMES = ("usual", "decays-near-0", "decays-near-1")


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("chunk", [8, 16])
@pytest.mark.parametrize("regime", REGIMES)
def test_the_scan_and_its_six_gradients_are_the_recurrence_s(regime, chunk,
                                                            groups):
    """Float32 inputs against float64: 2e-5 of the largest entry, what
    float32 sums in another order give over 64 tokens; a state kept in bf16
    (2^-8 a rounding) or a term left out fails it by orders.  ``a``'s
    gradient passes through the cumulative sums of ``dt a``, where the
    diagonal of the decay matrix adds and takes away the same number of the
    size of the output's gradient: float32 leaves 1e-6 of it a token, times
    ``dt``, summed over the tokens (where every decay is near 0 the true
    gradient is next to nothing and that is what is left)."""
    inputs = scan_inputs(regime, groups=groups)
    want, _ = ssm_counts.recurrence(*inputs)
    dout = np.random.default_rng(1).normal(size=want.shape)
    f32 = [jnp.asarray(v, jnp.float32) for v in inputs]
    got, pull = jax.vjp(lambda *v: state_space._state_space_scan_op(
        *v, chunk=chunk), *f32)
    assert np.abs(np.asarray(got) - want).max() <= 2e-5 * np.abs(want).max()
    for name, mine, theirs in zip(
            "x dt a b c d".split(), pull(jnp.asarray(dout, jnp.float32)),
            recurrence_backward(*inputs, dout)):
        assert mine.shape == theirs.shape, name
        floor = 1e-6 * inputs[1].sum() / 4 if name == "a" else 0.0
        assert np.abs(np.asarray(mine) - theirs).max() \
            <= 2e-5 * np.abs(theirs).max() + floor, name
    # each term matters at these inputs: without the decay or the skip the
    # recurrence is another one
    for sight in ({"decay": False}, {"skip": False}):
        other, _ = ssm_counts.recurrence(*inputs, **sight)
        if regime != "decays-near-1" or "skip" in sight:
            assert np.abs(other - want).max() > 1e-2 * np.abs(want).max()


@pytest.mark.parametrize("regime", REGIMES)
def test_the_result_does_not_depend_on_the_chunk(regime):
    """The chunk is the algorithm's, not the model's: 8, 16, 32 and the whole
    sequence agree to float32 rounding (1e-5 of the largest entry)."""
    f32 = [jnp.asarray(v, jnp.float32) for v in scan_inputs(regime)]
    outs = [np.asarray(state_space.state_space_scan(*f32, chunk))
            for chunk in (8, 16, 32, 64)]
    for out in outs[1:]:
        assert np.abs(out - outs[0]).max() <= 1e-5 * np.abs(outs[0]).max()


def test_a_sequence_that_is_not_whole_chunks_is_refused():
    f32 = [jnp.asarray(v, jnp.float32) for v in scan_inputs("usual", seq=40)]
    with pytest.raises(ValueError, match="a sequence of 40 tokens is not a "
                                         "multiple of the chunk of 16"):
        state_space._state_space_scan_op(*f32, chunk=16)
    with pytest.raises(ValueError, match=r"b and c \(B, S, G, N\)"):
        state_space._state_space_scan_op(f32[0], f32[1], f32[2],
                                         f32[3][:, :, 0], f32[4], f32[5])


def test_bf16_inputs_come_back_in_bf16_and_the_state_stays_float32():
    """x, B and C in bf16 as the block hands them over, dt and A float32:
    against the recurrence in float64 ON THE ROUNDED INPUTS the output is one
    rounding away (2^-8 of the largest entry).  A state kept in bf16 would
    round 128 times over at decays near 1 and reads 20 times that."""
    x, dt, a, b, c, d = scan_inputs("decays-near-1", seq=128)
    bf = [jnp.asarray(v, jnp.bfloat16) for v in (x, b, c)]
    rounded = [np.asarray(v, np.float64) for v in bf]
    want, _ = ssm_counts.recurrence(rounded[0], dt, a, rounded[1],
                                    rounded[2], d)
    got = state_space._state_space_scan_op(
        bf[0], jnp.asarray(dt, jnp.float32), jnp.asarray(a, jnp.float32),
        bf[1], bf[2], jnp.asarray(d, jnp.float32), chunk=32)
    assert got.dtype == jnp.bfloat16
    assert np.abs(np.asarray(got, np.float64) - want).max() \
        <= 2.0 ** -8 * np.abs(want).max()
    grads = jax.grad(lambda *v: jnp.sum(state_space.state_space_scan(
        *v, 32).astype(jnp.float32)), argnums=(0, 1, 3))(
            bf[0], jnp.asarray(dt, jnp.float32),
            jnp.asarray(a, jnp.float32), bf[1], bf[2],
            jnp.asarray(d, jnp.bfloat16))
    assert [g.dtype for g in grads] == [jnp.bfloat16, jnp.float32,
                                        jnp.bfloat16]


def test_the_plan_span_says_what_a_call_keeps():
    since = max([s.id for s in profiler.spans()] or [0])
    x = jnp.zeros((1, 4096, 64, 64), jnp.bfloat16)
    maps = jnp.zeros((1, 4096, 1, 128), jnp.bfloat16)
    jax.eval_shape(lambda *v: state_space._state_space_scan_op(*v), x,
                   jnp.zeros((1, 4096, 64), jnp.float32),
                   jnp.zeros((64,), jnp.float32), maps, maps,
                   jnp.zeros((64,), jnp.bfloat16))
    plan, = [s.args for s in profiler.spans()
             if s.name == "mx.ssm.plan" and s.id > since]
    kept = ssm_counts.state_kept_bytes(1, 4096, 64, 64, 128, 256)
    assert kept == 16 * 64 * 64 * 128 * 4
    assert plan == {
        "batch": 1, "seq": 4096, "heads": 64, "head_dim": 64, "state": 128,
        "groups": 1, "chunk": 256, "chunks": 16, "dtype": "bfloat16",
        "path": "xla", "why": "no kernel computes this recurrence yet",
        "state_kept_bytes": kept, "per_token_state_bytes": 256 * kept}


def test_the_gates_are_float32():
    rng = np.random.default_rng(2)
    dt = jnp.asarray(rng.normal(size=(2, 8, 4)), jnp.bfloat16)
    a_log, bias = (jnp.asarray(rng.normal(size=4), jnp.bfloat16)
                   for _ in range(2))
    step, rate = state_space._state_space_gates(dt, a_log, bias)
    assert step.dtype == rate.dtype == jnp.float32
    np.testing.assert_allclose(
        step, jax.nn.softplus(dt.astype(jnp.float32)
                              + bias.astype(jnp.float32)), rtol=1e-6)
    np.testing.assert_allclose(rate, -jnp.exp(a_log.astype(jnp.float32)),
                               rtol=1e-6)


# -- the convolution with a bias, the norm with the gate first ------------------
def test_the_convolution_with_a_bias_is_the_reference_s():
    """`_contrib_ShortConvSilu` and its three gradients against the plain
    shifted products (float32 both sides: 1e-6); a packed row does not see
    the row before it; the span says why no kernel runs."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 24, 12)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(12, 4)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=12), jnp.float32)
    dout = jnp.asarray(rng.normal(size=(2, 24, 12)), jnp.float32)
    since = max([s.id for s in profiler.spans()] or [0])
    got, pull = jax.vjp(delta_rule._short_conv_silu, x, w, bias)
    want, theirs = jax.vjp(reference.conv_bias_silu, x, w, bias)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    for mine, other in zip(pull(dout), theirs(dout)):
        np.testing.assert_allclose(mine, other, rtol=1e-5, atol=1e-6)
    # the bias is not nothing, and the first row of a batch entry starts
    # from zeros
    assert np.abs(np.asarray(got) - np.asarray(
        reference.conv_bias_silu(x, w, 0 * bias))).max() > 0.1
    np.testing.assert_allclose(
        got[1, 0], jax.nn.silu(x[1, 0] * w[:, 3] + bias), rtol=1e-6)
    span, = [s.args for s in profiler.spans()
             if s.name == "mx.ssmconv.plan" and s.id > since]
    assert span["path"] == "xla" and "no heads to norm" in span["why"]
    assert span["residual_bytes"] == 4 * (2 * 24 * 12 + 12 * 4 + 12)
    with pytest.raises(ValueError, match="12 channels under taps"):
        delta_rule._short_conv_silu(x, w, bias[:5])


@pytest.mark.parametrize("gamma_by", ["head", "channel"])
def test_the_norm_with_the_gate_first_is_the_reference_s(gamma_by):
    """``rms(x * silu(z)) * gamma`` over each group against the reference's
    `rms` (float32: 1e-6), forward and the three gradients; the gate behind
    the norm, the op as it was, is another function."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(2, 6, 2, 8)), jnp.float32)
    z = jnp.asarray(rng.normal(size=(2, 6, 16)), jnp.float32)
    gamma = jnp.asarray(rng.normal(size=8 if gamma_by == "head" else 16),
                        jnp.float32)

    def plain(x, z, gamma):
        y = x * jax.nn.silu(z.reshape(x.shape))
        return reference.rms(y, gamma.reshape(-1, 8), 1e-5).reshape(2, 6, 16)

    dout = jnp.asarray(rng.normal(size=(2, 6, 16)), jnp.float32)
    got, pull = jax.vjp(lambda *v: delta_rule._gated_rms_norm(
        *v, eps=1e-5, gate_first=True), x, z, gamma)
    want, theirs = jax.vjp(plain, x, z, gamma)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    for mine, other in zip(pull(dout), theirs(dout)):
        np.testing.assert_allclose(mine, other, rtol=1e-5, atol=1e-6)
    if gamma_by == "head":
        behind = delta_rule._gated_rms_norm(x, z, gamma, eps=1e-5)
        assert np.abs(np.asarray(behind) - np.asarray(want)).max() > 0.1


# -- the blocks and the decoder ---------------------------------------------------
def test_the_attention_block_without_a_norm_and_with_a_scale():
    from mxnet_tpu.gluon.contrib.nn import GroupedQueryAttention
    bare = GroupedQueryAttention(32, 4, 2, 8, qk_norm=None, scale=0.2,
                                 rope={"rope_theta": None})
    assert not hasattr(bare, "q_gamma") and not hasattr(bare, "k_gamma")
    assert sorted(p.name.split("_", 1)[1]
                  for p in bare.collect_params().values()) == [
        "key_weight", "out_weight", "query_weight", "value_weight"]
    assert bare._scale == 0.2 and bare._rotary == {"rotary": False}
    assert bare._group == {"__scope__": "mx.gqa.project"}
    # a scale alone takes the scopes too; without one the scale is the usual
    scaled = GroupedQueryAttention(32, 4, 2, 8, scale=0.5)
    assert scaled._after["out"] == {"__scope__": "mx.gqa.out"}
    plain = GroupedQueryAttention(32, 4, 2, 8)
    assert plain._scale == 8 ** -0.5 and plain._group == {}
    with pytest.raises(ValueError, match="no norm and rotary positions"):
        GroupedQueryAttention(32, 4, 2, 8, qk_norm=None)


@pytest.mark.parametrize("scale", [0.2, None])
def test_the_bare_attention_block_is_plain_attention(scale):
    """The block against the reference's attention at the same weights
    (float32, 2e-5 of the largest entry): no norm, no positions, the given
    scale; the usual ``head_dim ** -0.5`` without one."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.contrib.nn import GroupedQueryAttention
    cfg = config(attention_multiplier=scale or 8 ** -0.5)
    rng = np.random.default_rng(5)
    params = {"l0." + n: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
              for n, s in (("wq", (32, 32)), ("wk", (16, 32)),
                           ("wv", (16, 32)), ("wo", (32, 32)))}
    block = GroupedQueryAttention(32, 4, 2, 8, qk_norm=None, scale=scale,
                                  rope={"rope_theta": None})
    block.initialize()
    for leaf, name in (("wq", "q_weight"), ("wk", "k_weight"),
                       ("wv", "v_weight"), ("wo", "out_weight")):
        getattr(block, name).set_data(mx.nd.array(
            np.asarray(params["l0." + leaf])))
    x = rng.normal(size=(2, 32, 32)).astype(np.float32)
    got = block(mx.nd.array(x)).asnumpy()
    want = np.asarray(highest(lambda p: reference.attention(
        p, 0, cfg, jnp.asarray(x)), params))
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()


def test_a_decoder_layer_of_the_kind_needs_its_widths():
    from mxnet_tpu.gluon.model_zoo import decoder
    assert "mamba" in decoder.OPERATOR_KINDS
    base = dict(vocab=64, dim=32, layer_types=["mamba"], num_dense_layers=1,
                dense_hidden=48, expert_hidden=0, num_experts=0,
                num_experts_per_tok=0, heads=4)
    with pytest.raises(ValueError, match="a mamba layer needs state_space"):
        decoder.get_decoder_lm(**base)
    with pytest.raises(ValueError, match="4 heads do not make 3 groups"):
        decoder.get_decoder_lm(**base, state_space={
            "n_heads": 4, "d_head": 16, "d_state": 8, "n_groups": 3})
    net = decoder.get_decoder_lm(**base, state_space={
        "n_heads": 4, "d_head": 16, "d_state": 8})
    mixer = net.layers[0].operator
    assert type(mixer).__name__ == "StateSpaceMixer"
    assert (mixer._chunk, mixer._groups, mixer._rows) == (256, 1,
                                                          64 + 80 + 4)
    assert mixer.in_weight.shape == (148, 32)
    assert mixer.conv_weight.shape == (80, 4)
    with pytest.raises(ValueError, match="a Mamba-2 state-space mixer"):
        decoder.get_decoder_lm(**dict(base, layer_types=["mamba2"]))


def test_the_family_builds_the_published_words():
    net, _ = family.build(config())
    assert [type(layer.operator).__name__ for layer in net.layers] == [
        "StateSpaceMixer"] * 5 + ["GroupedQueryAttention"] + [
        "StateSpaceMixer"] * 4
    assert {layer._residual for layer in net.layers} == {0.22}
    assert (net._embedding_multiplier, net._logits_scaling) == (12.0, 8.0)
    assert net.head_weight is None
    attn = net.layers[5].operator
    assert attn._scale == 0.2 and attn._rotary == {"rotary": False}


REFUSED = {
    "routed-experts": ({"num_local_experts": 8}, "routed experts"),
    "positions": ({"position_embedding_type": "rope"}, "no positions"),
    "no-conv-bias": ({"mamba_conv_bias": False}, "a bias on the conv"),
    "a-projection-bias": ({"mamba_proj_bias": True}, "a bias on the conv"),
    "an-untied-head": ({"tie_word_embeddings": False}, "the embedding"),
    "another-kind": ({"layer_types": ["mamba"] * 9 + ["linear_attention"]},
                     "are not built"),
    "groups": ({"mamba_n_groups": 3}, "groups divide"),
    "expand": ({"mamba_expand": 4}, "mamba_expand x hidden_size"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_the_reference_refuses_what_it_does_not_compute(case):
    changes, why = REFUSED[case]
    with pytest.raises(ValueError, match=why):
        reference.check_supported(config(**changes))


def test_the_compiled_layers_lie_under_their_scopes():
    """The lowered step names the four groups of a mamba layer and the three
    of the attention layer, forward and backward, and the plan spans say
    which path each took."""
    cfg = cut(["mamba", "attention"])
    net, loss, _, _ = seeded(cfg)
    (x, y), = family.batches(cfg, SEED, 1, 2)
    trainer = models_common.make_trainer(net, loss, cfg["train"],
                                         jax.devices()[:1])
    since = max([s.id for s in profiler.spans()] or [0])
    trainer.fit_batch(x, y)
    names = [n for n in profiler.scope_map("parallel_step").values() if n]
    for scope in ("mx.ssm.project", "mx.ssm.conv", "mx.ssm.scan",
                  "mx.ssm.out", "mx.gqa.project", "mx.gqa.attention",
                  "mx.gqa.out"):
        found = [n for n in names
                 if re.search(r"[/(]%s/" % re.escape(scope), n)]
        assert found, scope
        # forward and backward both: the scan's own backward keeps the name
        assert any("transpose(" in n for n in found), scope
        assert any("transpose(" not in n for n in found), scope
    spans = [s for s in profiler.spans() if s.id > since]
    assert {s.args["path"] for s in spans if s.name == "mx.ssm.plan"} \
        == {"xla"}
    assert [s.name for s in spans].count("mx.ssmconv.plan") \
        == [s.name for s in spans].count("mx.ssm.plan") > 0
    # q and k go to the attention as projected: no head-rope op, no plan
    assert not [s for s in spans if s.name == "mx.headrope.plan"]
    trainer.flush_step_stats()
    from mxnet_tpu.observability import metrics
    kept = ssm_counts.state_kept_bytes(2, 32, 4, 16, 8, 16)
    assert "mxnet_ssm_state_kept_bytes %s" % float(kept) \
        in metrics.exposition()


# -- the whole model ----------------------------------------------------------
KINDS = {"a-mamba-layer": cut(["mamba"]),
         "an-attention-layer": cut(["attention"]),
         "the-period-of-ten": config(),
         "the-period-at-three-chunks": cut(PERIOD, seq=48),
         "two-groups": cut(["mamba", "attention", "mamba"],
                           mamba_n_groups=2)}


def gradients_of(trainer, names, lr):
    return {ref: -np.asarray(trainer._opt_state[prog][0]) / lr
            for ref, prog in names.items()}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_logits_loss_and_every_gradient_agree_with_the_reference(kind):
    """Through `ParallelTrainer.fit_batch`: the loss a step reports is the
    mean next-token cross-entropy, and every leaf's gradient is the
    reference's.  Tolerances: float32 on both sides, summed in another
    order (2e-4 of a leaf's largest entry, as the other families')."""
    import mxnet_tpu as mx
    cfg = KINDS[kind]
    seq = cfg["train"]["sequence_length"]
    net, loss, names, params = seeded(cfg)
    (x, y), = family.batches(cfg, SEED, 1, 2)
    got = net(mx.nd.array(x, dtype="int32")).asnumpy()
    assert got.shape == (2, seq, 64)
    want = highest(lambda p: reference.logits(p, cfg, x), params)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-4, atol=2e-6)

    trainer = models_common.make_trainer(net, loss, cfg["train"],
                                         jax.devices()[:1])
    got_loss = float(trainer.fit_batch(x, y))
    value, grads = highest(jax.value_and_grad(
        lambda p: reference.loss_sum(p, cfg, x, y)), params)
    assert got_loss == pytest.approx(float(value) / 2, rel=1e-5)
    assert set(names) == set(grads)
    for name, g in gradients_of(trainer, names, cfg["train"]["lr"]).items():
        w = np.asarray(grads[name]) / 2
        scale = max(np.abs(w).max(), 1e-12)
        assert np.abs(g - w).max() <= 2e-4 * scale, name
        assert np.abs(w).max() > 0, name


def test_a_net_refuses_a_sequence_that_is_not_whole_chunks():
    import mxnet_tpu as mx
    cfg = cut(["mamba"], seq=40)
    net, _, _, _ = seeded(cfg)
    (x, _), = family.batches(cfg, SEED, 1, 2)
    with pytest.raises(Exception, match="not a multiple of the chunk of 16"):
        net(mx.nd.array(x, dtype="int32")).asnumpy()


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


MULTIPLIERS = {"residual_multiplier": 1.0, "embedding_multiplier": 1.0,
               "logits_scaling": 1.0, "attention_multiplier": 16 ** -0.5}


@pytest.mark.parametrize("which", sorted(MULTIPLIERS))
def test_each_multiplier_matters(which):
    """A net built with one of the family's four multipliers at 1 (the
    attention's scale at the usual ``head_dim ** -0.5``) is another model:
    against the reference at the published values its logits, or where the
    logits hardly move (the attention's scale, at seeded weights) the
    attention layer's gradients, fail the comparison the whole model
    passes."""
    import mxnet_tpu as mx
    cfg = config(hidden_size=64, num_attention_heads=4,
                 mamba_d_head=32, attention_multiplier=1.0)
    net, loss, names, params = seeded(cfg, build=dict(
        cfg, **{which: MULTIPLIERS[which]}))
    (x, y), = family.batches(cfg, SEED, 1, 2)
    want = np.asarray(highest(lambda p: reference.logits(p, cfg, x), params))
    got = net(mx.nd.array(x, dtype="int32")).asnumpy()
    if which != "attention_multiplier":
        assert np.abs(got - want).max() > 0.02 * np.abs(want).max()
        return
    trainer = models_common.make_trainer(net, loss, cfg["train"],
                                         jax.devices()[:1])
    trainer.fit_batch(x, y)
    grads = highest(jax.grad(
        lambda p: reference.loss_sum(p, cfg, x, y)), params)
    mine = gradients_of(trainer, names, cfg["train"]["lr"])["l5.wq"]
    theirs = np.asarray(grads["l5.wq"]) / 2
    assert np.abs(mine - theirs).max() > 0.5 * np.abs(theirs).max()


@pytest.mark.parametrize("sight", ["no_decay", "no_skip"])
def test_the_recurrence_s_controls_move_the_reference(sight):
    """With the decay or the skip left out the reference is another model:
    its gradients move, the mamba layers' most."""
    cfg = config()
    _, _, _, params = seeded(cfg)
    (x, y), = family.batches(cfg, SEED, 1, 2)
    own, grads = highest(jax.value_and_grad(
        lambda p: reference.loss_sum(p, cfg, x, y)), params)
    other, moved = highest(jax.value_and_grad(
        lambda p: reference.loss_sum(p, cfg, x, y, sight=sight)), params)
    # (at seeded weights the loss is the logarithm of the rows held whatever
    # the layers compute: it moves in the fifth digit at most, the gradients do not)
    assert float(own) == pytest.approx(float(other), rel=1e-4)
    gap = {n: float(jnp.linalg.norm(moved[n] - grads[n])
                    / jnp.linalg.norm(grads[n])) for n in grads}
    assert gap["l0.w_in"] > 0.05 and gap["l1.w_out"] > 0.02, gap
    with pytest.raises(ValueError, match="sight"):
        reference.mamba(params, 0, cfg, jnp.zeros((1, 32, 32)),
                        sight="no_erase")


def test_the_vocabulary_s_slice_is_the_uncut_model_s_first_columns():
    """Logits over the rows held are the uncut reference's first columns
    when the ids come from the slice: the tied embedding's rows are what a
    share of the vocabulary cuts, and nothing else."""
    whole = config(vocab_size=8 * 64)
    table = reference.param_table(whole)
    params = ref_common.init_params(table, SEED)
    held = dict(params, embed=params["embed"][:64])
    cfg = config()
    net, _ = family.build(cfg)
    models_common.seeded_net(net, reference.param_table(cfg), held)
    (x, _), = family.batches(cfg, SEED, 1, 2)       # ids from the rows held
    import mxnet_tpu as mx
    got = net(mx.nd.array(x, dtype="int32")).asnumpy()
    want = highest(lambda p: reference.logits(p, whole, x), params)
    assert want.shape == (2, 32, 512)
    np.testing.assert_allclose(got, np.asarray(want)[..., :64], rtol=2e-4,
                               atol=2e-6)
