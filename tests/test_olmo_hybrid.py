"""Linear and full attention mixed by layer: the gated delta rule in its
chunkwise form (`ops/delta_rule.py`: `_contrib_GatedDeltaRule` with a
backward of its own, the short convolution with its norms, the gates, the
gated norm), `gluon.contrib.nn.GatedDeltaNet`, `GroupedQueryAttention`'s
norm over the width and its switched-off rotary positions, the decoder kind
`linear_attention` and the norm's place by layer kind, against the
recurrence token by token in float64 numpy (`benchmarks/gdn_counts.py`) and
the plain float32 reference `benchmarks/reference/olmo_hybrid.py`, at a
small size on the CPU with seeded weights: float32 on both sides, so only
the order of the arithmetic differs."""

import hashlib
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

from benchmarks import compare, gdn_counts  # noqa: E402
from benchmarks.models import common as models_common  # noqa: E402
from benchmarks.models import olmo_hybrid as family  # noqa: E402
from benchmarks.reference import common as ref_common  # noqa: E402
from benchmarks.reference import olmo_hybrid as reference  # noqa: E402
from mxnet_tpu import profiler  # noqa: E402
from mxnet_tpu.ops import attention, delta_rule, lm_blocks  # noqa: E402

SEED = 2 ** 31 + 11


def config(**changes):
    """The cell's shapes, small: both kinds of layer, a state that is not
    square (dk != dv), 3 heads, 4 taps, two chunks of 64."""
    cfg = {"family": "olmo_hybrid", "model_type": "olmo_hybrid",
           "hidden_size": 48, "intermediate_size": 80,
           "num_attention_heads": 3, "num_key_value_heads": 3,
           "hidden_act": "silu", "attention_bias": False,
           "linear_num_key_heads": 3, "linear_num_value_heads": 3,
           "linear_key_head_dim": 8, "linear_value_head_dim": 16,
           "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
           "rope_parameters": {"rope_theta": None},
           "layer_types": ["linear_attention", "linear_attention",
                           "full_attention"],
           "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
           "num_hidden_layers": 3, "vocab_size": 96,
           "initializer_range": 0.02, "embedding_initializer_range": 1.0,
           "conv_initializer_range": 0.2887, "gate_init_seed": 0,
           "train": {"optimizer": "sgd", "lr": 0.01, "momentum": 0.9,
                     "wd": 0.0, "multi_precision": False,
                     "sequence_length": 128, "per_chip_batch": 2}}
    cfg.update(changes)
    return cfg


def cut(kinds, seq=128, **changes):
    cfg = config(layer_types=list(kinds), num_hidden_layers=len(kinds),
                 **changes)
    cfg["train"] = dict(cfg["train"], sequence_length=seq)
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


# -- the rule against the recurrence in float64 ---------------------------------
def recurrence_backward(q, k, v, g, b, dout):
    """The five gradients of `gdn_counts.recurrence`'s output against
    *dout*, by its own reverse walk in float64 numpy: every state kept, each
    line of the forward step undone in turn."""
    q, k, v, g, b, dout = (np.asarray(x, np.float64)
                           for x in (q, k, v, g, b, dout))
    batch, seq, heads, dk = q.shape
    dv = v.shape[-1]
    grads = [np.zeros_like(x) for x in (q, k, v, g, b)]
    dq, dk_, dv_, dg, db = grads
    for i in range(batch):
        for h in range(heads):
            states = [np.zeros((dk, dv))]
            for t in range(seq):
                decayed = np.exp(g[i, t, h]) * states[-1]
                states.append(decayed + np.outer(
                    b[i, t, h] * k[i, t, h],
                    v[i, t, h] - decayed.T @ k[i, t, h]))
            dstate = np.zeros((dk, dv))
            for t in reversed(range(seq)):
                a, kt, bt = np.exp(g[i, t, h]), k[i, t, h], b[i, t, h]
                before, after = states[t], states[t + 1]
                decayed = a * before
                held = v[i, t, h] - decayed.T @ kt
                dq[i, t, h] = after @ dout[i, t, h]
                dstate = dstate + np.outer(q[i, t, h], dout[i, t, h])
                db[i, t, h] = kt @ dstate @ held
                dk_[i, t, h] = bt * (dstate @ held)
                dheld = bt * (dstate.T @ kt)
                dv_[i, t, h] = dheld
                dk_[i, t, h] -= decayed @ dheld
                ddecayed = dstate - np.outer(kt, dheld)
                dg[i, t, h] = a * np.sum(ddecayed * before)
                dstate = a * ddecayed
    return grads


def rule_inputs(regime, batch=2, seq=128, heads=2, dk=8, dv=16, seed=0):
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.normal(size=(batch, seq, heads, dk))) / np.sqrt(dk)
    k = unit(rng.normal(size=(batch, seq, heads, dk)))
    v = rng.normal(size=(batch, seq, heads, dv))
    g = -rng.uniform(0.0, 1.6, (batch, seq, heads))
    b = rng.uniform(0.0, 2.0, (batch, seq, heads))
    if regime == "decays-near-0":
        g = -rng.uniform(5.0, 30.0, (batch, seq, heads))
    elif regime == "decays-near-1":
        g = -rng.uniform(0.0, 1e-4, (batch, seq, heads))
    elif regime == "b-near-2":
        b = rng.uniform(1.9, 2.0, (batch, seq, heads))
        g = -rng.uniform(0.0, 0.05, (batch, seq, heads))
    elif regime == "keys-nearly-alike":
        # the triangular system at its hardest: every A[t, j] near b_t
        k = unit(k[:, :1] + 0.05 * rng.normal(size=k.shape))
        g = -rng.uniform(0.0, 0.02, (batch, seq, heads))
    return q, k, v, g, b


REGIMES = ("usual", "decays-near-0", "decays-near-1", "b-near-2",
           "keys-nearly-alike")
#: float32 in chunks against float64 token by token, of the largest entry
RULE_TOLERANCE = 2e-5


def rule_and_gradients(path, f32, dout, chunk):
    """``(o, the five gradients)`` by the op as it runs here (``xla``: the
    `jax.numpy` path, which is what `gated_delta_rule` lowers to on the
    CPU) or by the kernel pair, interpreted: at the plan's block of the
    solve (``kernel``: a chunk of 64 is one block, substitution alone) or at
    blocks of 16 that products pair (``kernel-16``)."""
    if path == "xla":
        got, pull = jax.vjp(lambda *a: delta_rule.gated_delta_rule(
            *a, chunk), *f32)
        return got, pull(dout)
    solve = 16 if path == "kernel-16" else delta_rule._gdn_plan(
        f32[0], f32[2], chunk)[0]["solve"]
    tiles = dict(chunk=chunk, solve=solve, interpret=True)
    got, starts = delta_rule._gdn_fwd_pallas(*f32, **tiles)
    return got, delta_rule._gdn_bwd_pallas(*f32, starts, dout, **tiles)


#: the five regimes at every chunk that tiles, and the usual one at the
#: cell's widths, which the lanes pad (96 to 128, 192 to 256): two heads,
#: three chunks
RULE_CASES = [(regime, chunk, {}) for regime in REGIMES
              for chunk in (16, 32, 64)] + [
    ("usual", 64, dict(batch=1, seq=192, heads=2, dk=96, dv=192))]


@pytest.mark.parametrize("path", ["xla", "kernel", "kernel-16"])
@pytest.mark.parametrize(
    "regime,chunk,shape", RULE_CASES,
    ids=["%s-%d%s" % (r, c, "-96x192" if s else "") for r, c, s in RULE_CASES])
def test_the_rule_and_its_five_gradients_are_the_recurrence_s(regime, chunk,
                                                              shape, path):
    q, k, v, g, b = rule_inputs(regime, **shape)
    want, _ = gdn_counts.recurrence(q, k, v, g, b)
    dout = np.random.default_rng(7).normal(size=want.shape)
    f32 = [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, b)]
    got, grads = rule_and_gradients(path, f32, jnp.asarray(dout, jnp.float32),
                                    chunk)
    scale = np.abs(want).max()
    assert np.abs(np.asarray(got) - want).max() <= RULE_TOLERANCE * scale
    for name, mine, theirs in zip("qkvgb", grads, recurrence_backward(
            q, k, v, g, b, dout)):
        scale = max(np.abs(theirs).max(), 1e-30)
        assert np.abs(np.asarray(mine) - theirs).max() \
            <= 10 * RULE_TOLERANCE * scale, (name, regime, chunk)


def test_the_kernels_and_the_jax_numpy_path_agree_on_bf16_inputs():
    """The cell's dtypes (q, k, v, b in bf16, g in float32): the same dtypes
    out of both paths, and the same numbers to float32's rounding (then
    rounded once to bf16: an entry or two may land on the neighbour)."""
    bf = jnp.bfloat16
    q, k, v, g, b = rule_inputs("usual", dk=32, dv=64)
    args = [jnp.asarray(x, t) for x, t in zip(
        (q, k, v, g, b), (bf, bf, bf, jnp.float32, bf))]
    dout = jnp.asarray(np.random.default_rng(9).normal(size=v.shape), bf)
    want, want_grads = rule_and_gradients("xla", args, dout, 64)
    got, grads = rule_and_gradients("kernel", args, dout, 64)
    assert got.dtype == want.dtype == bf
    for mine, theirs, x in zip((got,) + tuple(grads),
                               (want,) + tuple(want_grads),
                               [args[2]] + args):
        assert mine.dtype == theirs.dtype == x.dtype
        mine, theirs = (np.asarray(a, np.float64) for a in (mine, theirs))
        # one bf16 step of the largest entry, where the two roundings part
        assert np.abs(mine - theirs).max() <= 2.0 ** -7 * np.abs(theirs).max()
        assert np.linalg.norm(mine - theirs) <= 1e-3 * np.linalg.norm(theirs)


@pytest.mark.parametrize("block", [8, 16, 32, 64])
@pytest.mark.parametrize("regime", ["b-near-2", "keys-nearly-alike"])
def test_the_block_inverse_is_the_inverse(regime, block):
    """``(I + A)^-1`` of a chunk's system where it is hardest (entries of
    ``A`` near ``b``, near 2, where the nilpotent series' terms grow before
    they cancel): substitution in the diagonal blocks and products to pair
    them, against `numpy.linalg.inv` in float64."""
    q, k, v, g, b = rule_inputs(regime, batch=1, seq=64, heads=1)
    k, g, b = k[0, :, 0], g[0, :, 0], b[0, :, 0]
    cum = np.cumsum(g)
    a = np.tril(b[:, None] * np.exp(cum[:, None] - cum[None, :]) * (k @ k.T),
                -1)
    assert np.abs(a).max() > 1.5
    want = np.linalg.inv(np.eye(64) + a)
    got = jax.jit(lambda a: delta_rule._unit_lower_inverse(a, block))(
        jnp.asarray(a, jnp.float32))
    assert np.abs(np.asarray(got) - want).max() \
        <= RULE_TOLERANCE * np.abs(want).max()
    assert np.abs(np.triu(np.asarray(got), 1)).max() == 0.0


@pytest.mark.parametrize("regime", REGIMES)
def test_the_result_does_not_depend_on_the_chunk(regime):
    """The chunk is a property of the algorithm: 16, 32 and 64 agree to
    float32 rounding, forward and backward."""
    f32 = [jnp.asarray(x, jnp.float32) for x in rule_inputs(regime)]
    dout = jnp.asarray(np.random.default_rng(8).normal(
        size=f32[2].shape), jnp.float32)
    outs = {}
    for chunk in (16, 32, 64):
        got, pull = jax.vjp(
            lambda *a: delta_rule.gated_delta_rule(*a, chunk), *f32)
        outs[chunk] = (np.asarray(got),) + tuple(
            np.asarray(x) for x in pull(dout))
    for chunk in (16, 32):
        for i, (mine, theirs) in enumerate(zip(outs[chunk], outs[64])):
            scale = max(np.abs(theirs).max(), 1e-30)
            # the gradients are sums of products that cancel (the decay's,
            # where the decay is all but zero, is 1e-3 of its terms)
            assert np.abs(mine - theirs).max() <= (
                10 if i else 1) * RULE_TOLERANCE * scale


def test_a_sequence_that_is_not_whole_chunks_is_refused():
    q = jnp.zeros((1, 100, 2, 8))
    with pytest.raises(ValueError, match="100 .*not a multiple of the "
                                         "chunk of 64"):
        delta_rule._gated_delta_rule_op(q, q, jnp.zeros((1, 100, 2, 16)),
                                        jnp.zeros((1, 100, 2)),
                                        jnp.zeros((1, 100, 2)))
    with pytest.raises(ValueError, match="query and key"):
        delta_rule._gated_delta_rule_op(q[:, :64], q[:, :64, :1],
                                        jnp.zeros((1, 64, 2, 16)),
                                        jnp.zeros((1, 64, 2)),
                                        jnp.zeros((1, 64, 2)))


def test_bf16_inputs_come_back_in_bf16_and_the_state_stays_float32():
    q, k, v, g, b = rule_inputs("usual")
    want, _ = gdn_counts.recurrence(q, k, v, g, b)
    bf = jnp.bfloat16
    got = delta_rule.gated_delta_rule(
        jnp.asarray(q, bf), jnp.asarray(k, bf), jnp.asarray(v, bf),
        jnp.asarray(g, jnp.float32), jnp.asarray(b, bf), 64)
    assert got.dtype == bf
    # inputs rounded to 3 digits, nothing else: far inside a percent
    assert np.abs(np.asarray(got, np.float64) - want).max() \
        <= 2e-2 * np.abs(want).max()
    jaxpr = str(jax.make_jaxpr(lambda *a: delta_rule.gated_delta_rule(
        *a, 64))(*(jnp.asarray(x, bf) for x in (q, k, v)),
                 jnp.asarray(g, jnp.float32), jnp.asarray(b, bf)))
    assert "f32[2,2,8,16]" in jaxpr and "bf16[2,2,8,16]" not in jaxpr


def traced_plan(name, op, avals, devices=1):
    """The arguments of the one span *name* that tracing *op* at *avals*
    records, under a mesh of *devices* devices."""
    from jax.sharding import Mesh
    from mxnet_tpu.parallel import mesh as mesh_mod
    since = max([s.id for s in profiler.spans()] or [0])
    with mesh_mod.use_mesh(Mesh(np.array(jax.devices()[:devices]), ("dp",))):
        jax.eval_shape(op, *avals)
    plan, = [s.args for s in profiler.spans()
             if s.name == name and s.id > since]
    return plan


def plan_of(q, v, chunk=64, devices=1):
    """The `mx.gdn.plan` span of one traced call at these shapes."""
    gate = q.shape[:3]
    return traced_plan(
        "mx.gdn.plan",
        lambda *a: delta_rule._gated_delta_rule_op(*a, chunk=chunk),
        (q, q, v, jax.ShapeDtypeStruct(gate, jnp.float32),
         jax.ShapeDtypeStruct(gate, v.dtype)), devices)


def test_the_plan_span_and_the_step_stat_say_what_a_call_keeps():
    q = jax.ShapeDtypeStruct((1, 4096, 30, 96), jnp.bfloat16)
    with profiler.collect_step_stats() as stats:
        plan = plan_of(q, jax.ShapeDtypeStruct((1, 4096, 30, 192),
                                               jnp.bfloat16))
    assert plan["path"] == "kernel" and plan["why"] is None \
        and plan["chunk"] == 64 and plan["chunks"] == 64 \
        and plan["heads"] == 30
    assert plan["solve_block"] == min(64, delta_rule.GDN_TILES["solve"])
    # the kernels keep what the `jax.numpy` path keeps
    assert plan["state_kept_bytes"] == 64 * 30 * 96 * 192 * 4 \
        == gdn_counts.state_kept_bytes(1, 4096, 30, 96, 192, 64)
    assert plan["per_token_state_bytes"] == 64 * plan["state_kept_bytes"]
    assert len(stats["gdn_state_kept_bytes"]) == 1      # one a traced call
    profiler.fold_step_stats(
        {"gdn_state_kept_bytes": np.asarray([plan["state_kept_bytes"]] * 3,
                                            np.float32)})
    from mxnet_tpu.observability import metrics
    assert "mxnet_gdn_state_kept_bytes 424673280.0" in metrics.exposition()


REFUSALS = {
    # (sequence, heads, dk, dv, dtype, chunk, devices) -> why not
    "a-mesh-of-two": ((3072, 30, 96, 192, "bfloat16", 64, 2),
                      "a mesh of several devices"),
    "a-chunk-the-solve-s-block-does-not-divide": (
        (3072, 30, 96, 192, "float32", 96, 1),
        "a chunk of 96 is not whole blocks of %d of the solve"
        % delta_rule.GDN_TILES["solve"]),
    "a-chunk-of-half-a-bf16-tile": (
        (3072, 30, 96, 192, "bfloat16", 8, 1), "a chunk of 8 is not whole"),
    "a-state-over-the-vmem-budget": (
        (3072, 4, 1024, 1024, "bfloat16", 64, 1),
        "a state of 1024 x 1024 with its blocks over the VMEM budget"),
}


@pytest.mark.parametrize("case", sorted(REFUSALS))
def test_the_plan_says_why_a_call_stays_jax_numpy(case):
    (seq, heads, dk, dv, dtype, chunk, devices), why = REFUSALS[case]
    plan = plan_of(jax.ShapeDtypeStruct((1, seq, heads, dk), dtype),
                   jax.ShapeDtypeStruct((1, seq, heads, dv), dtype),
                   chunk, devices)
    assert plan["path"] == "xla" and plan["why"].startswith(why)
    assert plan["solve_block"] is None
    assert plan["state_kept_bytes"] == 4 * (seq // chunk) * heads * dk * dv


def test_a_wide_state_that_fits_takes_the_kernels():
    """The budget is the grid step's: the blocks of one chunk of one head,
    each held twice, the carried state and the temporaries."""
    wide = plan_of(jax.ShapeDtypeStruct((1, 128, 16, 256), jnp.bfloat16),
                   jax.ShapeDtypeStruct((1, 128, 16, 512), jnp.bfloat16))
    assert wide["path"] == "kernel"
    at = dict(chunk=64, itemsize=2)
    assert delta_rule._gdn_blocks("bwd", dk=256, dv=512, **at) \
        <= delta_rule._GDN_VMEM < delta_rule._gdn_blocks(
            "bwd", dk=1024, dv=1024, **at)


# -- the operators around the rule -----------------------------------------------
def test_the_convolution_its_silu_and_the_norms_are_the_reference_s():
    rng = np.random.default_rng(3)
    heads, dk, dv, seq = 3, 8, 16, 40
    x = jnp.asarray(rng.normal(size=(2, seq, heads * (2 * dk + dv))),
                    jnp.float32)
    w = jnp.asarray(rng.normal(size=(x.shape[-1], 4)) * 0.3, jnp.float32)

    def theirs(x, w):
        y = reference.conv_silu(x, w)

        def unit(z):
            z = z.reshape(2, seq, heads, dk)
            return z / jnp.sqrt(jnp.sum(z * z, -1, keepdims=True) + 1e-6)

        return (unit(y[..., :heads * dk]) / np.sqrt(dk),
                unit(y[..., heads * dk:2 * heads * dk]),
                y[..., 2 * heads * dk:].reshape(2, seq, heads, dv))

    def mine(x, w):
        return delta_rule._short_conv_heads(x, w, num_heads=heads,
                                            key_dim=dk, eps=1e-6)

    got, pull = jax.vjp(mine, x, w)
    want, pull_want = jax.vjp(theirs, x, w)
    douts = tuple(jnp.asarray(rng.normal(size=o.shape), jnp.float32)
                  for o in want)
    for a, b in zip(got + pull(douts), want + pull_want(douts)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-6)
    # position 0 sees zeros before it: its convolution is the last tap's
    first = jax.nn.silu(x[:, 0] * w[:, 3])
    np.testing.assert_allclose(
        np.asarray(got[2][:, 0]).reshape(2, -1),
        np.asarray(first[:, 2 * heads * dk:]), rtol=1e-6)
    with pytest.raises(ValueError, match="channels"):
        delta_rule._short_conv_heads(x[..., :-1], w[:-1], num_heads=heads,
                                     key_dim=dk)


# -- the short convolution's kernels, interpreted ---------------------------------
#: the cell's widths: 30 heads of 96 | 192 (11520 channels, q's border with k
#: at channel 2880 in the middle of a lane tile), 4 taps
CELL = dict(heads=30, dk=96, dv=192)
#: two heads: one group of q and k and one of v, the border at channel 192
NARROW = dict(heads=2, dk=96, dv=192)


def conv_inputs(heads, dk, dv, seq, dtype, batch=1, taps=4, seed=6):
    rng = np.random.default_rng(seed)
    width = heads * (2 * dk + dv)
    x = jnp.asarray(rng.normal(size=(batch, seq, width)), dtype)
    w = jnp.asarray(rng.normal(size=(width, taps)) * 0.3, dtype)
    douts = tuple(jnp.asarray(rng.normal(size=(batch, seq, heads, d)), dtype)
                  for d in (dk, dk, dv))
    return x, w, douts


def conv_pair(path, x, w, douts, heads, dk, rows=32, channels=384, piece=16,
              **_):
    """``(q, k, v, d data, d taps)`` by `_conv_heads_body` and JAX's
    derivative of it (``xla``) or by the kernel pair, interpreted, at tiles
    of *rows* rows in pieces of *piece* (``kernel``)."""
    at = dict(heads=heads, dk=dk, eps=1e-6)
    if path == "xla":
        out, pull = jax.vjp(lambda x, w: delta_rule._conv_heads_body(
            x, w, **at), x, w)
        return out + pull(douts)
    tiles = dict(at, rows=rows, channels=channels, piece=piece,
                 interpret=True)
    return delta_rule._gdnconv_fwd_pallas(x, w, **tiles) \
        + delta_rule._gdnconv_bwd_pallas(x, w, douts, **tiles)


def assert_close(mine, theirs, dtype, what):
    """float32: the two orders of the same float32 arithmetic; bf16: one
    bf16 rounding of the largest entry, where the two roundings part."""
    assert mine.dtype == theirs.dtype == dtype and mine.shape == theirs.shape
    mine, theirs = (np.asarray(a, np.float64) for a in (mine, theirs))
    most = np.abs(theirs).max()
    if dtype == jnp.float32:
        assert np.abs(mine - theirs).max() <= 2e-6 * most, what
    else:
        assert np.abs(mine - theirs).max() <= 2.0 ** -7 * most, what
        assert np.linalg.norm(mine - theirs) \
            <= 2e-3 * np.linalg.norm(theirs), what


CONV_NAMES = ("q", "k", "v", "d data", "d taps")


@pytest.mark.parametrize("piece", [16, 32])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_convolution_s_kernels_are_the_body_at_the_cell_s_widths(dtype,
                                                                     piece):
    """Forward, the input's gradient and the taps' at 30 heads of 96 | 192
    over three tiles of rows, two pieces or one a tile."""
    dtype = jnp.dtype(dtype)
    x, w, douts = conv_inputs(seq=96, dtype=dtype, **CELL)
    want = conv_pair("xla", x, w, douts, **CELL)
    got = conv_pair("kernel", x, w, douts, piece=piece, **CELL)
    for name, mine, theirs in zip(CONV_NAMES, got, want):
        assert_close(mine, theirs, dtype, (name, piece))
    # the head on each side of channel 2880: q's last takes dk ** -0.5, k's
    # first does not, and both are unit vectors before it
    q, k = (np.asarray(a, np.float64) for a in got[:2])
    np.testing.assert_allclose(np.linalg.norm(q[0, :, -1], axis=-1),
                               96 ** -0.5, rtol=1e-2)
    np.testing.assert_allclose(np.linalg.norm(k[0, :, 0], axis=-1), 1.0,
                               rtol=1e-2)


@pytest.mark.parametrize("only", [0, 1, 2])
def test_each_output_s_gradient_reaches_the_input_and_the_taps(only):
    """The gradient of q alone, of k alone and of v alone (the other two
    zero), in float32 at the cell's widths."""
    x, w, douts = conv_inputs(seq=64, dtype=jnp.float32, **CELL)
    douts = tuple(d if i == only else jnp.zeros_like(d)
                  for i, d in enumerate(douts))
    want = conv_pair("xla", x, w, douts, **CELL)
    got = conv_pair("kernel", x, w, douts, **CELL)
    for name, mine, theirs in zip(CONV_NAMES[3:], got[3:], want[3:]):
        assert_close(mine, theirs, jnp.float32, (name, only))
        assert np.abs(np.asarray(theirs)).max() > 0


def test_a_packed_row_does_not_see_the_row_before_it():
    """A batch of two rows: the second row's outputs and gradients are what
    they are alone, whatever the first row holds, and its first ``taps -
    1`` positions see zeros (position 0 is the last tap's)."""
    x, w, douts = conv_inputs(seq=64, dtype=jnp.float32, batch=2, **NARROW)
    got = conv_pair("kernel", x, w, douts, **NARROW)
    for name, mine, theirs in zip(CONV_NAMES, got, conv_pair(
            "xla", x, w, douts, **NARROW)):
        assert_close(mine, theirs, jnp.float32, name)
    other = conv_pair("kernel", x.at[0].set(7.0), w, douts, **NARROW)
    alone = conv_pair("kernel", x[1:], w, tuple(d[1:] for d in douts),
                      **NARROW)
    for name, a, b, c in zip(CONV_NAMES[:4], got, other, alone):
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]),
                                      err_msg=name)
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(c[0]),
                                      err_msg=name)
    first = jax.nn.silu(x[:, 0] * w[:, 3])
    np.testing.assert_allclose(
        np.asarray(got[2][:, 0]).reshape(2, -1),
        np.asarray(first[:, 2 * 2 * 96:]), rtol=1e-6)


@pytest.mark.parametrize("at", [31, 32, 34, 63])
def test_a_gradient_at_a_tile_s_edge_crosses_it_both_ways(at):
    """One position's gradient, at a tile's last row, at the next tile's
    first rows and at the sequence's end, reaches the ``taps - 1`` positions
    before it through the taps (the backward kernel's rows after the tile)
    and its own head through the norm, and no other."""
    x, w, douts = conv_inputs(seq=64, dtype=jnp.float32, **NARROW)
    douts = tuple(jnp.zeros_like(d).at[:, at].set(d[:, at]) for d in douts)
    want = conv_pair("xla", x, w, douts, **NARROW)
    got = conv_pair("kernel", x, w, douts, **NARROW)
    for name, mine, theirs in zip(CONV_NAMES[3:], got[3:], want[3:]):
        assert_close(mine, theirs, jnp.float32, (name, at))
    reached = np.flatnonzero(np.abs(np.asarray(got[3][0])).max(-1))
    assert reached.tolist() == list(range(at - 3, at + 1))


def conv_plan_of(shape, dtype, taps=4, heads=30, dk=96, devices=1):
    """The arguments of the one `mx.gdnconv.plan` span that tracing the op
    at these shapes records."""
    return traced_plan(
        "mx.gdnconv.plan",
        lambda x, w: delta_rule._short_conv_heads(
            x, w, num_heads=heads, key_dim=dk),
        (jax.ShapeDtypeStruct(shape, dtype),
         jax.ShapeDtypeStruct((shape[-1], taps), dtype)), devices)


def test_the_convolution_s_plan_span_says_what_the_cell_runs():
    plan = conv_plan_of((1, 3072, 11520), jnp.bfloat16)
    tiles = delta_rule.GDNCONV_TILES
    assert plan == {
        "shape": [1, 3072, 11520], "dtype": "bfloat16", "taps": 4,
        "path": "kernel", "why": None,
        "seq_tile": {k: min(3072, tiles[k][0]) for k in ("fwd", "bwd")},
        "piece_rows": {k: tiles[k][1] for k in ("fwd", "bwd")},
        "channel_tile": plan["channel_tile"], "halo_rows": 16,
        # the two inputs and nothing float32
        "residual_bytes": 2 * (3072 * 11520 + 11520 * 4)}
    # whole groups of lcm(96, 128) that divide q and k's 5760 and v's 5760
    assert plan["channel_tile"] % 384 == 0 and 5760 % plan["channel_tile"] == 0
    assert plan["channel_tile"] <= max(384, tiles["channels"])


CONV_REFUSALS = {
    # (shape, dtype, taps, heads, dk, devices) -> why not
    "a-mesh-of-two": (((1, 3072, 11520), "bfloat16", 4, 30, 96, 2),
                      "a mesh of several devices"),
    "one-byte-numbers": (((1, 3072, 11520), "float8_e4m3fn", 4, 30, 96, 1),
                         "not (batch, seq, channels) in a dtype of 2 or 4"),
    "heads-that-make-no-whole-group": (
        ((1, 3072, 3 * 384), "bfloat16", 4, 3, 96, 1),
        "576 channels of q and k and 576 of v are not whole groups of 384"),
    "keys-of-eight": (((2, 128, 96), "float32", 4, 3, 8, 1),
                      "48 channels of q and k and 48 of v are not whole "
                      "groups of 128"),
    "values-whose-heads-straddle-a-block": (
        ((1, 3072, 12 * (2 * 96 + 160)), "bfloat16", 4, 12, 96, 1),
        "v's heads of 160 do not make whole blocks of 384 channels"),
    "one-tap": (((1, 3072, 11520), "bfloat16", 1, 30, 96, 1),
                "1 taps are not 2 to 7"),
    "taps-over-the-rows-their-gradient-is-summed-in": (
        ((1, 3072, 11520), "bfloat16", 8, 30, 96, 1),
        "8 taps are not 2 to 7"),
    "a-sequence-of-part-pieces": (
        ((1, 3072 + 8, 11520), "bfloat16", 4, 30, 96, 1),
        "a sequence of 3080 is not whole pieces of"),
    "a-group-over-the-vmem-budget": (
        ((1, 3072, 4 * 97 * 128), "bfloat16", 4, 128, 97, 1),
        "groups of 12416 channels with their blocks over the VMEM budget"),
}


@pytest.mark.parametrize("case", sorted(CONV_REFUSALS))
def test_the_convolution_s_plan_says_why_a_call_stays_jax_numpy(case):
    (shape, dtype, taps, heads, dk, devices), why = CONV_REFUSALS[case]
    plan = conv_plan_of(shape, jnp.dtype(dtype), taps, heads, dk, devices)
    assert plan["path"] == "xla" and plan["why"].startswith(why)
    assert plan["seq_tile"] is None and plan["channel_tile"] is None \
        and plan["piece_rows"] is None and plan["halo_rows"] is None
    assert plan["shape"] == list(shape) and plan["taps"] == taps
    assert plan["residual_bytes"] == jnp.dtype(dtype).itemsize * (
        int(np.prod(shape)) + shape[-1] * taps)


def test_a_refused_call_runs_the_body_and_a_planned_one_the_body_off_the_tpu():
    """On the CPU both are `_conv_heads_body` to the bit: the kernels are
    the other branch of `platform_dependent`."""
    for shape in ((1, 512, 2 * 384), (1, 40, 2 * 384)):
        x, w, douts = conv_inputs(seq=shape[1], dtype=jnp.float32, **NARROW)
        assert (delta_rule._gdnconv_plan(x, w, 2, 96)[0] is None) \
            == (shape[1] == 40)
        def op(x, w):
            return delta_rule._short_conv_heads(x, w, num_heads=2, key_dim=96)

        got, pull = jax.vjp(op, x, w)
        for mine, theirs in zip(got + pull(douts), conv_pair(
                "xla", x, w, douts, **NARROW)):
            np.testing.assert_array_equal(np.asarray(mine),
                                          np.asarray(theirs))
        # the planned call's trace names both kernels (the other branch),
        # the refused call's neither
        text = str(jax.make_jaxpr(
            lambda x, w, d: jax.vjp(op, x, w)[1](d))(x, w, douts))
        for kernel in ("mx_gdnconv_fwd", "mx_gdnconv_bwd"):
            assert (kernel in text) == (shape[1] == 512), (kernel, shape)


def test_the_taps_are_the_gated_short_convolution_s():
    """One definition of the depthwise causal taps, in `ops/lm_blocks.py`:
    `causal_taps` over ``B * X`` is what `_gate_body` multiplies by ``C``,
    and what the linear-attention block's convolution calls."""
    assert delta_rule.causal_taps is lm_blocks.causal_taps
    rng = np.random.default_rng(4)
    bcx = jnp.asarray(rng.normal(size=(2, 24, 3 * 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 3)), jnp.float32)
    b, c, x = bcx[..., :16], bcx[..., 16:32], bcx[..., 32:]
    np.testing.assert_array_equal(
        np.asarray(c * lm_blocks.causal_taps(b * x, w)),
        np.asarray(lm_blocks._gate_body(bcx, w)))


def test_the_gates_are_float32_and_the_doubling_is_a_flag():
    a = jnp.asarray([[[0.3, -2.0]]], jnp.bfloat16)
    b = jnp.asarray([[[0.0, 5.0]]], jnp.bfloat16)
    a_log, dt = jnp.asarray([0.5, 2.0]), jnp.asarray([-1.0, 0.2])
    g, beta = delta_rule._delta_rule_gates(a, b, a_log, dt,
                                           allow_neg_eigval=True)
    assert g.dtype == jnp.float32 and beta.dtype == jnp.bfloat16
    want = -np.exp([0.5, 2.0]) * np.log1p(np.exp(
        np.asarray(a, np.float64)[0, 0] + [-1.0, 0.2]))
    np.testing.assert_allclose(np.asarray(g)[0, 0], want, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(beta, np.float32)[0, 0],
                               [1.0, 2 / (1 + np.exp(-5.0))], rtol=1e-2)
    _, single = delta_rule._delta_rule_gates(a, b, a_log, dt)
    np.testing.assert_allclose(np.asarray(single, np.float32) * 2,
                               np.asarray(beta, np.float32), rtol=1e-2)


def test_the_gated_norm_is_the_reference_s():
    rng = np.random.default_rng(5)
    o = jnp.asarray(rng.normal(size=(2, 10, 3, 16)), jnp.float32)
    z = jnp.asarray(rng.normal(size=(2, 10, 48)), jnp.float32)
    gamma = jnp.asarray(rng.normal(size=(16,)), jnp.float32)

    def theirs(o, z, gamma):
        return (reference.rms(o, gamma, 1e-6) * jax.nn.silu(
            z.reshape(2, 10, 3, 16))).reshape(2, 10, 48)

    got, pull = jax.vjp(lambda *a: delta_rule._gated_rms_norm(*a, eps=1e-6),
                        o, z, gamma)
    want, pull_want = jax.vjp(theirs, o, z, gamma)
    dout = jnp.asarray(rng.normal(size=want.shape), jnp.float32)
    for a, b in zip((got,) + pull(dout), (want,) + pull_want(dout)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-6)


# -- what the accepted cells run is what they ran --------------------------------
def jaxpr_sha(fn, *avals):
    text = re.sub(r" at \S+:\d+", "", str(jax.make_jaxpr(fn)(*avals)))
    return hashlib.sha256(text.encode()).hexdigest()


YARN = {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5}
#: `GroupedQueryAttention` forward and backward at 512 positions in bf16 at
#: the arguments the accepted cells build it with, traced on the parent tree
#: (commit 186ea64)
PARENT_BLOCKS = {
    "lfm2-plain": (
        (2048, 32, 8, 64, 1000000.0, 1e-5), {},
        "60a577acfa1b821a600607e2205a171f7c89a35b2c9cca9a7d6cb238fc2e74b6"),
    "sdar-block-diffusion": (
        (2048, 32, 4, 128, 1000000.0, 1e-6), {"diffusion_block": 4},
        "7379895a85ffea5bddc00d92cfe68eb893d1347ab1637aeefc9695c6da1e321a"),
    "laguna-window-gate": (
        (3072, 72, 8, 128), {
            "epsilon": 1e-6, "gate": True, "window": 128,
            "rope": {"rope_type": "default", "rope_theta": 10000,
                     "partial_rotary_factor": 1}},
        "8edcf6de1172a5cdc172dca60a478a938fbab74376b779dc45f876423504c7d5"),
    "laguna-full-gate-yarn": (
        (3072, 48, 8, 128), {"epsilon": 1e-6, "gate": True, "rope": YARN},
        "08942b09294a13d698fe9a51e77cb4ef01ace12848c24d8556f3e1b16b22e14f"),
}


def block_step(block, seq, dim):
    """``(step, avals)``: a Gluon block's graph, forward and backward in
    bf16, as a function of its arguments."""
    import mxnet_tpu as mx
    from mxnet_tpu import executor
    graph = executor._build_eval(block(mx.sym.var("x")), True)
    bf = jnp.bfloat16
    avals = {p.name: jax.ShapeDtypeStruct(p.shape, bf)
             for p in block.collect_params().values()}
    avals["x"] = jax.ShapeDtypeStruct((1, seq, dim), bf)

    def step(args, dout):
        def objective(args):
            out, = graph(args, {}, None)[0]
            return jnp.sum(out.astype(jnp.float32) * dout)
        return jax.grad(objective)(args)

    return step, (avals, jax.ShapeDtypeStruct((1, seq, dim), jnp.float32))


@pytest.mark.parametrize("case", sorted(PARENT_BLOCKS))
def test_grouped_query_attention_at_today_s_arguments_is_the_parent_s(case):
    from mxnet_tpu.gluon.contrib.nn import GroupedQueryAttention
    args, kwargs, want = PARENT_BLOCKS[case]
    step, avals = block_step(
        GroupedQueryAttention(*args, prefix="attn_", **kwargs), 512,
        args[0])
    assert jaxpr_sha(step, *avals) == want


def test_the_gated_short_convolution_is_the_parent_s():
    bf = jnp.bfloat16
    avals = (jax.ShapeDtypeStruct((1, 512, 2048), bf),
             jax.ShapeDtypeStruct((3 * 2048, 2048), bf),
             jax.ShapeDtypeStruct((2048, 3), bf),
             jax.ShapeDtypeStruct((2048, 2048), bf))

    def conv(*a):
        return jax.grad(lambda *a: jnp.sum(lm_blocks._gated_short_conv(
            *a).astype(jnp.float32)), argnums=(0, 1, 2, 3))(*a)

    assert jaxpr_sha(conv, *avals) == \
        "2535d566acaa8484993f379e0d0228ba28d62f935b9912dbff8fcdc60a24dcdf"


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
def test_the_new_cell_s_flash_kernels_trace_as_the_parent_s(kernel):
    """The six language cells' kernels are pinned by `tests/test_laguna.py`;
    this cell's full layer calls the causal pair at 3072 positions and heads
    of 128, which is the parent's program to the letter."""
    s, d = 3072, 128
    q = jax.ShapeDtypeStruct((1, 2, s, d), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((2, 1, s), jnp.float32)
    if kernel == "fwd":
        got = jaxpr_sha(lambda q, k, v: attention._flash_fwd_pallas(
            q, k, v, True, d ** -0.5, with_lse=True), q, q, q)
    else:
        got = jaxpr_sha(
            lambda q, k, v, o, l, do: attention._flash_bwd_pallas(
                q, k, v, o, l, do, True, d ** -0.5), q, q, q, q, lse, q)
    assert got == {
        "fwd": "28971073db70a223f8416226abeeb7d4268ac412ce40cd52a17ca3c"
               "98c59052d",
        "bwd": "a6413071d686a237f491346c282e6c0468aea9b706ccf95c67cdc99"
               "facf3bc90"}[kernel]


#: the short convolution's pair at the cell's arguments and `GDNCONV_TILES`,
#: traced on this tree (PR 51): what the Olmo cell runs is what it ran
CONV_PINS = {
    "fwd": "f3d8af547074872aa2b9fd759471d4a537492f2d18ac18c948aa8ae9fd1f9c1d",
    "bwd": "96b03f8a4522c98cfff1730e49f53b8bbda688a284ac9a3fe9caf3a4a6a5e16b"}


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
def test_the_cell_s_short_convolution_kernels_trace_as_pinned(kernel):
    bf = jnp.bfloat16
    x = jax.ShapeDtypeStruct((1, 3072, 11520), bf)
    w = jax.ShapeDtypeStruct((11520, 4), bf)
    douts = tuple(jax.ShapeDtypeStruct((1, 3072, 30, d), bf)
                  for d in (96, 96, 192))
    tiles, _ = delta_rule._gdnconv_plan(x, w, 30, 96)
    at = dict(heads=30, dk=96, eps=1e-6, channels=tiles["channels"],
              **tiles[kernel])
    if kernel == "fwd":
        got = jaxpr_sha(lambda x, w: delta_rule._gdnconv_fwd_pallas(
            x, w, **at), x, w)
    else:
        got = jaxpr_sha(lambda x, w, d: delta_rule._gdnconv_bwd_pallas(
            x, w, d, **at), x, w, douts)
    assert got == CONV_PINS[kernel]


# -- the blocks and the decoder ---------------------------------------------------
def test_the_attention_block_s_two_departures():
    from mxnet_tpu.gluon.contrib.nn import GroupedQueryAttention
    plain = GroupedQueryAttention(48, 3, 3, 16)
    assert plain._group == {} and plain.q_gamma.shape == (16,)
    wide = GroupedQueryAttention(48, 3, 3, 16, qk_norm="width",
                                 rope={"rope_theta": None})
    assert wide.q_gamma.shape == (48,) and wide.k_gamma.shape == (48,)
    assert wide._rotary == {"rotary": False, "norm_over": "width"}
    assert wide._group == {"__scope__": "mx.gqa.project"}
    assert wide._after["out"] == {"__scope__": "mx.gqa.out"}
    # either departure alone takes the scopes; a theta keeps the positions
    assert GroupedQueryAttention(48, 3, 3, 16, rope={"rope_theta": None}
                                 )._group
    turned = GroupedQueryAttention(48, 3, 3, 16, qk_norm="width",
                                   rope={"rope_theta": 10000.0})
    assert "rotary" not in turned._rotary \
        and turned._rotary["norm_over"] == "width"
    with pytest.raises(ValueError, match="qk_norm 'rows' is neither"):
        GroupedQueryAttention(48, 3, 3, 16, qk_norm="rows")


@pytest.mark.parametrize("norm_over,rotary", [("width", False),
                                              ("width", True),
                                              ("head", False)])
def test_the_head_norm_op_s_departures_say_why_they_stay_in_xla(norm_over,
                                                                rotary):
    rng = np.random.default_rng(6)
    y = jnp.asarray(rng.normal(size=(2, 12, 3 * 16)), jnp.float32)
    gamma = jnp.asarray(rng.normal(size=(48 if norm_over == "width"
                                         else 16,)), jnp.float32)
    since = max([s.id for s in profiler.spans()] or [0])
    got = lm_blocks._head_norm_rotary_op(
        y, gamma, num_heads=3, theta=10000.0, eps=1e-6, norm_over=norm_over,
        rotary=rotary)
    if norm_over == "width":
        normed = reference.rms(y, gamma, 1e-6).reshape(2, 12, 3, 16)
    else:
        normed = reference.rms(y.reshape(2, 12, 3, 16), gamma, 1e-6)
    want = normed.transpose(0, 2, 1, 3)
    if rotary:
        want = lm_blocks._rotary(want, 10000.0, False, None, ())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    plan, = [s.args for s in profiler.spans()
             if s.name == "mx.headrope.plan" and s.id > since]
    assert plan["path"] == "xla"
    assert ("whole width of 48" in plan["why"]) == (norm_over == "width")
    assert ("no rotary positions" in plan["why"]) == (not rotary)
    assert plan["rotary_dim"] == (16 if rotary else 0)


def test_a_decoder_layer_of_the_kind_needs_its_widths():
    from mxnet_tpu.gluon.model_zoo import decoder
    assert "linear_attention" in decoder.OPERATOR_KINDS
    with pytest.raises(ValueError, match="needs linear: num_key_heads"):
        decoder.get_decoder_lm(
            vocab=32, dim=48, layer_types=["linear_attention"],
            num_dense_layers=1, dense_hidden=64, expert_hidden=0,
            num_experts=0, num_experts_per_tok=0)
    with pytest.raises(ValueError, match="one state a head"):
        decoder.get_decoder_lm(
            vocab=32, dim=48, layer_types=["linear_attention"],
            num_dense_layers=1, dense_hidden=64, expert_hidden=0,
            num_experts=0, num_experts_per_tok=0,
            linear={"num_key_heads": 2, "num_value_heads": 4,
                    "key_head_dim": 8, "value_head_dim": 16,
                    "conv_kernel_dim": 4})
    with pytest.raises(ValueError, match="the gated delta rule's linear "
                                         "attention"):
        decoder.get_decoder_lm(
            vocab=32, dim=48, layer_types=["recurrent"], num_dense_layers=1,
            dense_hidden=64, expert_hidden=0, num_experts=0,
            num_experts_per_tok=0)
    with pytest.raises(ValueError, match="on the input or on the output"):
        decoder.get_decoder_lm(
            vocab=32, dim=48, layer_types=["full_attention"],
            num_dense_layers=1, dense_hidden=64, expert_hidden=0,
            num_experts=0, num_experts_per_tok=0, heads=3,
            norm_place={"full_attention": "middle"})


def test_the_family_builds_the_norms_places_by_kind():
    net, _ = family.build(config())
    kinds = [type(layer.operator).__name__ for layer in net.layers]
    assert kinds == ["GatedDeltaNet", "GatedDeltaNet",
                     "GroupedQueryAttention"]
    assert [layer._norm_output for layer in net.layers] == [False, False,
                                                            True]
    gdn, full = net.layers[0].operator, net.layers[2].operator
    assert (gdn._heads, gdn._dk, gdn._dv, gdn._neg) == (3, 8, 16, True)
    assert gdn.qkv_weight.shape == (3 * (8 + 8 + 16), 48)
    assert gdn.conv_weight.shape == (96, 4)
    assert full._rotary == {"rotary": False, "norm_over": "width"}
    # pre-norm stays the default: a net built without the mapping has it
    from mxnet_tpu.gluon.model_zoo import decoder
    net = decoder.get_decoder_lm(
        vocab=32, dim=48, layer_types=["full_attention"],
        num_dense_layers=1, dense_hidden=64, expert_hidden=0, num_experts=0,
        num_experts_per_tok=0, heads=3)
    assert not net.layers[0]._norm_output


def test_the_compiled_layers_lie_under_their_scopes():
    """The lowered step names the four groups of a linear layer and the
    three of a full one, and the plan spans say which path each took."""
    cfg = cut(["linear_attention", "full_attention"])
    net, loss, _, _ = seeded(cfg)
    (x, y), = family.batches(cfg, SEED, 1, 2)
    trainer = models_common.make_trainer(net, loss, cfg["train"],
                                         jax.devices()[:1])
    since = max([s.id for s in profiler.spans()] or [0])
    trainer.fit_batch(x, y)
    names = [n for n in profiler.scope_map("parallel_step").values() if n]
    for scope in ("mx.gdn.project", "mx.gdn.conv", "mx.gdn.scan",
                  "mx.gdn.out", "mx.gqa.project", "mx.gqa.attention",
                  "mx.gqa.out"):
        found = [n for n in names
                 if re.search(r"[/(]%s/" % re.escape(scope), n)]
        assert found, scope
        # forward and backward both: the rule's own backward keeps the name
        assert any("transpose(" in n for n in found), scope
        assert any("transpose(" not in n for n in found), scope
    spans = [s for s in profiler.spans() if s.id > since]
    # two heads of 8 x 16 at chunks of 64 tile: on the TPU this is the kernel
    # pair; lowered for the CPU, as here, the same plan runs the `jax.numpy`
    # path, under the same scope
    assert {s.args["path"] for s in spans if s.name == "mx.gdn.plan"} == \
        {"kernel"}
    whys = {s.args["why"] for s in spans if s.name == "mx.headrope.plan"}
    assert whys == {"one norm over the whole width of 48, not a head's 16 "
                    "and no rotary positions: nothing to turn"}


def traced_step(family, cfg, seeded_net, seed):
    """``(the graph's operators, the training step's jaxpr as text, the
    names of the spans its tracing recorded)`` of a small decoder: the step
    is traced as `fit_batch` would trace it, and neither compiled nor
    run.  The jaxpr holds both branches of every `platform_dependent`, so
    a Mosaic kernel shows in it by name on the CPU too."""
    net, loss = seeded_net(cfg)[:2]
    (x, y), = family.batches(cfg, seed, 1, cfg["train"]["per_chip_batch"])
    trainer = models_common.make_trainer(net, loss, cfg["train"],
                                         jax.devices()[:1])
    since = max([s.id for s in profiler.spans()] or [0])
    trainer._ensure_built(x, y)
    trainer._refresh_frozen(x.shape, y.shape)
    text = str(jax.make_jaxpr(trainer._step_fn)(*trainer._step_args(x, y)))
    return ({n.op.name for n in trainer._graph._topo() if not n.is_var},
            text, {s.name for s in profiler.spans() if s.id > since})


@pytest.mark.parametrize("other", ["sdar_moe", "lfm2_moe", "laguna",
                                   "olmo_hybrid"])
def test_no_other_family_s_step_runs_the_short_convolution_over_heads(other):
    """`_contrib_ShortConvHeads` has one caller, `GatedDeltaNet`, and one
    family builds it: the traced step of a small SDAR, LFM2 and Laguna
    decoder (their own tests' configurations) has no such node, no
    ``mx_gdnconv_*`` call in either branch and no `mx.gdnconv.plan` span,
    so a change to the operator cannot reach their cells.  Olmo's own small
    decoder, traced the same way, has the node and the span (its heads of 8
    make no whole group, so its plan says ``xla`` and no kernel is traced:
    the kernels' own tests above trace them at the cell's widths)."""
    import importlib
    theirs = importlib.import_module("test_" + other)
    cfg = {"sdar_moe": lambda: theirs.config(),
           "lfm2_moe": lambda: theirs.config(*theirs.KINDS["all"]),
           "laguna": lambda: theirs.config(),
           "olmo_hybrid": lambda: config()}[other]()
    ops, text, spans = traced_step(theirs.family, cfg, theirs.seeded,
                                   theirs.SEED)
    assert "dot_general" in text and "mx_gdnconv" not in text
    assert ("_contrib_ShortConvHeads" in ops) == (other == "olmo_hybrid")
    assert ("mx.gdnconv.plan" in spans) == (other == "olmo_hybrid")
    assert ("mx.gdn.plan" in spans) == (other == "olmo_hybrid")


# -- the whole model ----------------------------------------------------------
KINDS = {"a-linear-layer": cut(["linear_attention"]),
         "a-full-layer": cut(["full_attention"]),
         "all-at-two-chunks": config(),
         "all-at-three-chunks": cut(config()["layer_types"], seq=192),
         "b-not-doubled": config(linear_allow_neg_eigval=False)}


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
    assert got.shape == (2, seq, 96)
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


def test_a_net_refuses_a_sequence_that_is_not_whole_chunks():
    import mxnet_tpu as mx
    cfg = cut(["linear_attention"], seq=100)
    net, _, _, _ = seeded(cfg)
    (x, _), = family.batches(cfg, SEED, 1, 2)
    with pytest.raises(Exception, match="not a multiple of the chunk of 64"):
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


@pytest.mark.parametrize("sight", ["no_erase", "single_b"])
def test_the_rule_s_controls_move_the_reference(sight):
    """With the erase term left out, or ``b`` not doubled, the reference is
    another model: its gradients move, the linear layers' most."""
    cfg = config()
    _, _, _, params = seeded(cfg)
    (x, y), = family.batches(cfg, SEED, 1, 2)
    own, grads = highest(jax.value_and_grad(
        lambda p: reference.loss_sum(p, cfg, x, y)), params)
    other, moved = highest(jax.value_and_grad(
        lambda p: reference.loss_sum(p, cfg, x, y, sight=sight)), params)
    # (at seeded weights the loss is the logarithm of the rows held whatever
    # the layers compute: it moves in the sixth digit, the gradients do not)
    assert float(own) != float(other)
    gap = {n: float(jnp.linalg.norm(moved[n] - grads[n])
                    / jnp.linalg.norm(grads[n])) for n in grads}
    assert gap["l0.wqkv"] > 0.05 and gap["l1.wo"] > 0.02
    with pytest.raises(ValueError, match="sight"):
        reference.linear_attention(params, 0, cfg, jnp.zeros((1, 64, 48)),
                                   sight="causal")


def test_the_vocabulary_s_slice_is_the_uncut_model_s_first_columns():
    """Logits over the rows held are the uncut reference's first columns:
    the embedding's and the head's rows are what a share of the vocabulary
    cuts, and nothing else."""
    whole = config(vocab_size=8 * 96)
    table = reference.param_table(whole)
    params = ref_common.init_params(table, SEED)
    held = dict(params, embed=params["embed"][:96], head=params["head"][:96])
    cfg = config()
    net, _ = family.build(cfg)
    models_common.seeded_net(net, reference.param_table(cfg), held)
    (x, _), = family.batches(cfg, SEED, 1, 2)       # ids from the rows held
    import mxnet_tpu as mx
    got = net(mx.nd.array(x, dtype="int32")).asnumpy()
    want = highest(lambda p: reference.logits(p, whole, x), params)
    assert want.shape == (2, 128, 768)
    np.testing.assert_allclose(got, np.asarray(want)[..., :96], rtol=2e-4,
                               atol=2e-6)


def test_the_step_counts_the_state_it_keeps():
    cfg = cut(["linear_attention", "linear_attention"])
    net, loss, _, _ = seeded(cfg)
    (x, y), = family.batches(cfg, SEED, 1, 2)
    trainer = models_common.make_trainer(net, loss, cfg["train"],
                                         jax.devices()[:1])
    trainer.fit_batch(x, y)
    trainer.flush_step_stats()
    from mxnet_tpu.observability import metrics
    kept = 2 * gdn_counts.state_kept_bytes(2, 128, 3, 8, 16, 64)
    assert "mxnet_gdn_state_kept_bytes %s" % float(kept) \
        in metrics.exposition()


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


def scan_lengths(jaxpr):
    """The trip counts of every loop of a jaxpr, nested ones too (a
    `while` that is no scan counts as -1)."""
    found = []
    for eqn in jaxpr.eqns if hasattr(jaxpr, "eqns") else jaxpr.jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append(eqn.params["length"])
        elif eqn.primitive.name == "while":
            found.append(-1)
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) \
                    else [value]:
                if hasattr(inner, "eqns") or hasattr(inner, "jaxpr"):
                    found.extend(scan_lengths(inner))
    return found


def test_a_linear_layer_compiles_for_the_described_chip_with_no_square_array(
        one_chip, no_cache):
    """The cell's linear layer, forward and backward, at 4096 positions and
    30 heads of 96 x 192 in bf16, compiled for a v5e: the rule is the two
    Mosaic kernels and no loop of XLA's, no array of the compiled program
    has two axes of the sequence, none is a state a token ``(S, H, dk,
    dv)`` in any order of its axes, no chunk's solve ``(N, B, H, C, dk +
    dv)`` leaves the kernels, and what the forward keeps is the plan's."""
    import mxnet_tpu as mx
    from mxnet_tpu import executor
    from mxnet_tpu.gluon.contrib.nn import GatedDeltaNet
    seq, dim, heads, dk, dv = 4096, 3840, 30, 96, 192
    block = GatedDeltaNet(dim, heads, dk, dv, conv_kernel=4,
                          allow_neg_eigval=True, epsilon=1e-6)
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
    text = compiled.as_text()
    for kernel in ("mx_gdn_fwd", "mx_gdn_bwd"):
        calls = [line for line in text.splitlines()
                 if "tpu_custom_call" in line and kernel + "/" in line]
        assert len(calls) == 1 and "mx.gdn.scan/" in calls[0], kernel
    assert " while(" not in text
    assert not re.search(r"\[(\d+,)*%d,(\d+,)*%d[,\]]" % (seq, seq), text)
    shapes = {tuple(int(n) for n in m.split(","))
              for m in re.findall(r"\[((?:\d+,)+\d+)\]", text)}
    per_token = seq * heads * dk * dv
    assert not [s for s in shapes if int(np.prod(s)) >= per_token]
    # the largest arrays are the chunk-boundary states: S / C a head
    assert (seq // 64, 1, heads, dk, dv) in shapes
    # the solves of all chunks at once were `f32[64,1,30,64,288]`
    assert not [s for s in shapes if s[-2:] == (64, dk + dv)]
    # the `jax.numpy` path is still the other branch of the traced program:
    # its two scans over the chunks, 64 steps each; the other loops are the
    # short convolution's kernels' walks over the pieces of a tile's rows,
    # q and k's and v's, forward and backward
    fwd, bwd = (rows // piece for rows, piece in (
        delta_rule.GDNCONV_TILES[k] for k in ("fwd", "bwd")))
    assert sorted(scan_lengths(jax.make_jaxpr(step)(
        avals, jax.ShapeDtypeStruct((1, seq, dim), jnp.float32)))) \
        == sorted([seq // 64] * 2 + [fwd] * 2 + [bwd] * 2)
    for kernel in ("mx_gdnconv_fwd", "mx_gdnconv_bwd"):
        calls = [line for line in text.splitlines()
                 if "tpu_custom_call" in line and kernel + "/" in line]
        assert len(calls) == 1 and "mx.gdn.conv/" in calls[0], kernel
    # q, k and v reach the rule's kernels as the convolution's wrote them:
    # no copy turns heads of 96 around
    assert not re.search(r"copy\(.*mx\.gdn\.(conv|scan)", text)
    plan, = {tuple(sorted((k, v) for k, v in s.args.items()))
             for s in profiler.spans()
             if s.name == "mx.gdn.plan" and s.id > since}
    assert dict(plan)["state_kept_bytes"] == 141557760
    assert dict(plan)["path"] == "kernel"
    mem = compiled.memory_analysis()
    # the step's temporaries: the projections, the kept states and the
    # head-major copies around the kernels, 1.40 GB (2.5e9 bounded the
    # `jax.numpy` path's; a state a token is 9 GB a layer)
    assert mem.temp_size_in_bytes < 1.6e9
