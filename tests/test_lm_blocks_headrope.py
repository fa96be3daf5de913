"""`ops/lm_blocks.py` `_head_norm_rotary`: a projection's output to normed,
turned heads in head-major layout, the Mosaic pair `mx_headrope_fwd` and
`mx_headrope_bwd` (interpreted here) against what the sparse attention
operator ran before it, `_rotary(_rms_norm(y by head, gamma).transpose(0, 2,
1, 3))`, the path `_contrib_SparseAttention` chooses for an input, the operator
`_contrib_HeadNormRotary` as `GroupedQueryAttention` reaches the same pair,
and the pair over a part of a head at given frequencies (two rolls and a
third table) against `_rotary_given` over `_rms_norm`, with what must not
move beside it: the whole-head calls' jaxprs."""

import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import profiler
from mxnet_tpu.ops import lm_blocks
from mxnet_tpu.ops.registry import get_op

D, SEQ, THETA, SECTIONS, EPS = 128, 64, 1e7, (16, 24, 24), 1e-6


def today(y, gamma, heads, positions=None):
    batch, seq, _ = y.shape
    return lm_blocks._rotary(
        lm_blocks._rms_norm(y.reshape(batch, seq, heads, -1), gamma,
                            EPS).transpose(0, 2, 1, 3),
        THETA, False, positions, SECTIONS)


def arguments(heads, dtype, batch=2, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(batch, SEQ, heads * D)), dtype),
            jnp.asarray(1 + 0.3 * rng.normal(size=D), dtype),
            jnp.asarray(rng.normal(size=(batch, heads, SEQ, D)), dtype))


def three_axes(batch=2):
    """Positions as a vision tower's tokens have them: the three axes
    differ, and so do the batch rows."""
    rng = np.random.default_rng(1)
    return jnp.asarray(rng.integers(0, 300, size=(3, batch, SEQ)),
                       jnp.float32)


POSITIONS = {"text": lambda: None, "three-axis": three_axes}


def plans_since(since):
    return [s.args for s in profiler.spans()
            if s.name == "mx.headrope.plan" and s.id > since]


def last_span():
    return max([s.id for s in profiler.spans()] or [0])


def close(got, want, name):
    # sums of a hundred and more terms in another order: to a float32
    # rounding of the largest, not of each
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5,
                               atol=2e-6 * np.abs(want).max(), err_msg=name)


@pytest.mark.parametrize("positions", sorted(POSITIONS))
@pytest.mark.parametrize("heads", [32, 4])
def test_the_kernels_give_today_s_value_and_gradients(
        interpreted_headrope, heads, positions):
    """Float32 on both sides, so only the order of a few sums differs (and
    the one rounding the kernels leave out rounds nothing)."""
    y, gamma, dout = arguments(heads, jnp.float32)
    pos = POSITIONS[positions]()
    cos, sin = lm_blocks._rotary_tables(SEQ, D, THETA, pos, SECTIONS)
    assert cos.shape == sin.shape == (1 if pos is None else 2, SEQ, D)
    assert lm_blocks._headrope_plan(y, heads, pos, SECTIONS)[0] == {
        "fwd": 32, "bwd": 16, "heads": min(heads, 8)}
    got, back = jax.vjp(lambda y, g: lm_blocks._head_norm_rotary(
        y, g, (cos, sin), heads, EPS), y, gamma)
    want, want_back = jax.vjp(lambda y, g: today(y, g, heads, pos), y, gamma)
    assert got.shape == (2, heads, SEQ, D)
    close(got, want, "value")
    for name, g, w in zip(("y", "gamma"), back(dout), want_back(dout)):
        close(g, w, "d" + name)


@pytest.mark.parametrize("positions", sorted(POSITIONS))
def test_the_gradients_reach_the_weights_through_the_operator(
        monkeypatch, interpreted_headrope, positions):
    """`_contrib_SparseAttention` at the cell's 32 query and 4 key/value
    heads of 128 with the pair in q's and k's place, against the same
    operator as every platform but the TPU runs it: both outputs and the
    gradient on the input, the projections' weights and the norms'
    scales."""
    op = get_op("_contrib_SparseAttention").fn
    rng = np.random.default_rng(3)
    width, shapes = 64, [(32 * D, 64), (4 * D, 64), (4 * D, 64), (64, 32 * D),
                         (D,), (D,), (16, 64), (8, 64), (2, 64)]
    weights = [jnp.asarray(1 + 0.3 * rng.normal(size=s) if len(s) == 1
                           else 0.2 * rng.normal(size=s), jnp.float32)
               for s in shapes]
    x = jnp.asarray(rng.normal(size=(2, SEQ, width)), jnp.float32)
    weight = jnp.asarray(rng.normal(size=(2, SEQ, width)), jnp.float32)
    pos = POSITIONS[positions]()
    attrs = dict(num_heads=32, num_kv_heads=4, index_heads=2, topk=8,
                 rope_theta=THETA, mrope_section=SECTIONS, eps=EPS,
                 use_positions=pos is not None)

    def objective(x, weights):
        out, term = op(x, *weights, *(() if pos is None else (pos,)), **attrs)
        return jnp.sum(out * weight) + term[0], (out, term)

    def run():
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(
                objective, argnums=(0, 1), has_aux=True))(x, weights)

    since = last_span()
    (_, (out, term)), (dx, dw) = run()
    plans = plans_since(since)
    assert [(p["path"], p["heads"]) for p in plans] == [("kernel", 32),
                                                        ("kernel", 4)]
    monkeypatch.undo()
    (_, (want_out, want_term)), (want_dx, want_dw) = run()
    close(out, want_out, "out")
    close(term, want_term, "term")
    close(dx, want_dx, "d data")
    for i in (0, 1, 2, 3, 4, 5):
        close(dw[i], want_dw[i], "d weight %d" % i)
        assert np.abs(np.asarray(want_dw[i])).max() > 0


@pytest.mark.parametrize("heads", [32, 4])
def test_in_bf16_the_result_is_one_rounding_from_float32(heads):
    """From bf16 operands the kernels round once, at the output: every
    element lies within half a bf16 step of today's arithmetic carried out
    in float32 (today's own result, rounded after the norm and again after
    the rotation, lies within one and a half).  The backward kernel's ``dy``
    is the float32 derivative's to one rounding, the scale's gradient a
    float32 sum rounded once."""
    bf = jnp.bfloat16
    y, gamma, dout = arguments(heads, bf)
    cos, sin = lm_blocks._rotary_tables(SEQ, D, THETA, None, SECTIONS)
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    exact, back = jax.vjp(lambda y, g: today(y, g, heads), f32(y), f32(gamma))
    kw = dict(heads=heads, eps=EPS, at_once=min(heads, 8), interpret=True)
    got = lm_blocks._headrope_fwd_pallas(y, gamma, (cos, sin), rows=32, **kw)
    dy, dgamma = lm_blocks._headrope_bwd_pallas(y, gamma, (cos, sin), dout,
                                                rows=16, **kw)
    assert got.dtype == dy.dtype == dgamma.dtype == bf
    assert dy.shape == y.shape and dgamma.shape == gamma.shape
    step = 2.0 ** -8        # half a step of bf16's 8 bits, relative
    for name, g, w in zip(("value", "dy", "dgamma"), (got, dy, dgamma),
                          (exact,) + back(f32(dout))):
        w = np.asarray(w)
        assert np.all(np.abs(np.asarray(g, np.float32) - w)
                      <= step * np.abs(w) + 1e-6), name
    # and the body, which every other platform runs at a tiled shape, is
    # today's arithmetic to the bit
    np.testing.assert_array_equal(
        np.asarray(lm_blocks._headrope_body(y, gamma, (cos, sin), heads, EPS),
                   np.float32),
        np.asarray(today(y, gamma, heads), np.float32))


CASES = {
    # (seq, head width, devices of the mesh) -> path, and why not
    "the-cell-s-shape": ((16384, 128, 1), "kernel", None),
    "a-64-wide-head": ((16384, 64, 1), "xla", "a head of 64"),
    "not-whole-tiles": ((16384 + 64, 128, 1), "xla", "a sequence of 16448"),
    "a-mesh-of-two": ((16384, 128, 2), "xla", "a mesh of several devices"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_path_is_chosen_from_the_input(case):
    """`mx.headrope.plan`, one span for q and one for k each time the
    operator is traced: the kernels at the cell's shape on one device,
    `_rotary` over `_rms_norm` as before at a head that is not whole lane
    tiles, a sequence that is not whole tiles, or under a mesh of several
    devices (XLA does not partition a Mosaic kernel)."""
    from jax.sharding import Mesh
    from mxnet_tpu.parallel import mesh as mesh_mod
    (seq, d, devices), path, why = CASES[case]
    bf, width = jnp.bfloat16, 2048
    shapes = [(32 * d, width), (4 * d, width), (4 * d, width),
              (width, 32 * d), (d,), (d,), (1024, width), (64, width),
              (16, width)]
    avals = [jax.ShapeDtypeStruct(s, bf) for s in [(1, seq, width)] + shapes]
    op = functools.partial(
        get_op("_contrib_SparseAttention").fn, num_heads=32, num_kv_heads=4,
        index_heads=16, topk=2048, rope_theta=THETA,
        mrope_section=(d // 8, 3 * d // 16, 3 * d // 16))
    since = last_span()
    with mesh_mod.use_mesh(Mesh(np.array(jax.devices()[:devices]), ("dp",))):
        jax.eval_shape(op, *avals)
    plans = plans_since(since)
    assert [p["heads"] for p in plans] == [32, 4]
    tiles = lm_blocks.HEADROPE_TILES
    for p in plans:
        assert p["path"] == path and p["head_dim"] == d
        assert p["shape"] == [1, seq, p["heads"] * d]
        if path == "xla":
            assert p["why"].startswith(why)
            assert p["seq_tile"] is p["table_bytes"] is p["residual_bytes"] \
                is None
            continue
        assert p["why"] is None
        assert p["seq_tile"] == {"fwd": tiles["fwd"], "bwd": tiles["bwd"]}
        assert p["head_tile"] == min(p["heads"], tiles["heads"])
        # cos and sin over the sequence in float32, once an op; kept: the
        # projection as the product wrote it, and the scale
        assert p["table_bytes"] == 2 * seq * d * 4
        assert p["residual_bytes"] == seq * p["heads"] * d * 2 + d * 4


def test_the_pair_lowers_for_the_tpu_under_the_projection_s_scope():
    """Lowered for the TPU from this CPU host at the cell's two widths: one
    Mosaic call each way a width, named, each under the pair's own scope
    `mx.headrope`, nested in whatever scope the caller stands in (here the
    sparse block's `mx.dsa.project`; the compiled program joins the two:
    `tests/test_keye_vl2.py` reads it there), and nothing of the
    activations' size kept for the backward pass but the projection itself
    and the tables."""
    bf = jnp.bfloat16
    cos, sin = lm_blocks._rotary_tables(16384, D, THETA, None, SECTIONS)

    def loss(y, gamma, heads):
        with jax.named_scope("mx.dsa.project"):
            return jnp.sum(lm_blocks._head_norm_rotary(
                y, gamma, (cos, sin), heads, EPS).astype(jnp.float32))

    for heads in (32, 4):
        avals = (jax.ShapeDtypeStruct((1, 16384, heads * D), bf),
                 jax.ShapeDtypeStruct((D,), bf))
        f = functools.partial(loss, heads=heads)
        text = jax.jit(jax.value_and_grad(f, argnums=(0, 1))).trace(
            *avals).lower(lowering_platforms=("tpu",)).as_text(
                debug_info=True)
        assert text.count("stablehlo.custom_call @tpu_custom_call") == 2
        for way in ("fwd", "bwd"):
            assert '"mx.headrope/mx_headrope_%s/pallas_call"' % way in text
            assert "mx.dsa.project))/cond/branch_0_fun/jit(_headrope_%s_" \
                "pallas)" % way in text.replace("jvp(mx.dsa.project)/",
                                                "jvp(mx.dsa.project))/")
        kept = jax.tree.leaves(jax.eval_shape(
            lambda *a: jax.vjp(f, *a)[1], *avals))
        big = [(a.shape, a.dtype) for a in kept if a.size >= 16384 * D]
        assert sorted(big, key=str) == sorted(
            [((1, 16384, heads * D), bf), ((1, 16384, D), jnp.float32),
             ((1, 16384, D), jnp.float32)], key=str), big


# ---------------------------------------------------------------------------
# A part of a head at given frequencies: two rolls and a third table.
# ---------------------------------------------------------------------------

#: Laguna-S-2.1's full layers' entry of ``rope_parameters``
YARN = {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5}


def given_for(kind, part=0.5):
    """``(rotary_dim, inv_freq, scale)`` over *part* of a head of `D`: YaRN's
    as published, or one theta's with a scale of its own."""
    if kind == "yarn":
        turn = lm_blocks.rope_frequencies(
            dict(YARN, partial_rotary_factor=part), D)
        return turn["rotary_dim"], turn["inv_freq"], turn["table_scale"]
    turn = lm_blocks.rope_frequencies(
        {"rope_type": "default", "rope_theta": 10000,
         "partial_rotary_factor": part}, D)
    return turn["rotary_dim"], turn["inv_freq"], 0.75


def body_given(y, gamma, heads, given):
    batch, seq, _ = y.shape
    return lm_blocks._rotary_given(
        lm_blocks._rms_norm(y.reshape(batch, seq, heads, -1), gamma,
                            EPS).transpose(0, 2, 1, 3), *given)


def test_the_third_table_splits_the_sine_by_the_way_its_roll_brings():
    """`_rotary_tables` with *given* over 64 of 128 lanes: cos is the scaled
    cosine on the turned lanes and 1 beyond them; ``sin_up`` holds ``+sin``
    on lanes 32 .. 63 (beside ``roll(x, 32)``, which brings ``x[j - 32]``),
    ``sin_down`` ``-sin`` on lanes 0 .. 31 (beside ``roll(x, 96)``, which
    brings ``x[j + 32]``), both 0 elsewhere; over the whole head the two are
    one table, the two-table call's."""
    given = given_for("yarn")
    assert given[0] == 64 and given[2] != 1.0
    cos, up, down = (np.asarray(t) for t in lm_blocks._rotary_tables(
        SEQ, D, 0.0, None, (), given))
    assert cos.shape == up.shape == down.shape == (1, SEQ, D)
    assert cos.dtype == up.dtype == down.dtype == np.float32
    ang = np.arange(SEQ)[:, None] * np.asarray(given[1])[None, :]
    want_cos = (np.cos(ang) * given[2]).astype(np.float32)
    want_sin = (np.sin(ang) * given[2]).astype(np.float32)
    np.testing.assert_array_equal(cos[0, :, :32], want_cos)
    np.testing.assert_array_equal(cos[0, :, 32:64], want_cos)
    assert (cos[..., 64:] == 1.0).all()
    np.testing.assert_array_equal(up[0, :, 32:64], want_sin)
    np.testing.assert_array_equal(down[0, :, :32], -want_sin)
    assert not up[..., :32].any() and not up[..., 64:].any()
    assert not down[..., 32:].any()
    assert lm_blocks._headrope_rolls(D, 64) == (32, 96)
    assert lm_blocks._headrope_rolls(D, D) == lm_blocks._headrope_rolls(
        D, None) == (64,)
    with pytest.raises(ValueError, match="frequencies do not turn"):
        lm_blocks._rotary_tables(SEQ, D, 0.0, None, (), (64, (1.0, 0.5), 1.0))


@pytest.mark.parametrize("kind", ["default", "yarn"])
@pytest.mark.parametrize("heads", [48, 8])
def test_the_kernels_over_a_part_of_a_head_are_the_body_s(
        interpreted_headrope, heads, kind):
    """The interpreted pair at ``rotary_dim < d`` against `_rotary_given`
    over `_rms_norm` and `jax.vjp` of it, float32 on both sides (the
    whole-head test's tolerance), at the full layers' 48 and 8 heads; which
    pins `pltpu.roll`'s direction, that one roll by ``d / 2`` never told
    apart.  The lanes beyond the part are the normed input, bit for bit."""
    given = given_for(kind)
    y, gamma, dout = arguments(heads, jnp.float32)
    tables = lm_blocks._rotary_tables(SEQ, D, 0.0, None, (), given)
    assert len(tables) == 3
    assert lm_blocks._headrope_plan(y, heads, rotary_dim=given[0])[0] == {
        "fwd": 32, "bwd": 16, "heads": 8}
    got, back = jax.vjp(lambda y, g: lm_blocks._head_norm_rotary(
        y, g, tables, heads, EPS, given[0]), y, gamma)
    want, want_back = jax.vjp(
        lambda y, g: body_given(y, g, heads, given), y, gamma)
    assert got.shape == (2, heads, SEQ, D)
    close(got, want, "value")
    for name, g, w in zip(("y", "gamma"), back(dout), want_back(dout)):
        close(g, w, "d" + name)
    normed = lm_blocks._rms_norm(y.reshape(2, SEQ, heads, D), gamma,
                                 EPS).transpose(0, 2, 1, 3)
    np.testing.assert_array_equal(np.asarray(got)[..., given[0]:],
                                  np.asarray(normed)[..., given[0]:])
    # and the turned lanes are not the input's: the test would pass on a
    # table of ones otherwise
    assert np.abs(np.asarray(got)[..., :given[0]]
                  - np.asarray(normed)[..., :given[0]]).max() > 0.1


@pytest.mark.parametrize("part", [0.25, 0.5, 0.75])
def test_the_body_from_three_tables_is_rotary_given_to_the_bit(part):
    """What every platform but the TPU runs at a tiled shape, in bf16:
    `_headrope_body` from the three tables is `_rotary_given` over
    `_rms_norm`, value and both gradients, bit for bit (primitive by
    primitive: XLA's CPU backend fuses two programs otherwise)."""
    given = given_for("yarn", part)
    assert given[0] == int(D * part)
    y, gamma, dout = arguments(3, jnp.bfloat16)
    tables = lm_blocks._rotary_tables(SEQ, D, 0.0, None, (), given)
    with jax.disable_jit():
        got, back = jax.vjp(lambda y, g: lm_blocks._headrope_body(
            y, g, tables, 3, EPS, given[0]), y, gamma)
        want, want_back = jax.vjp(
            lambda y, g: body_given(y, g, 3, given), y, gamma)
        grads, want_grads = back(dout), want_back(dout)
    f32 = functools.partial(np.asarray, dtype=np.float32)
    np.testing.assert_array_equal(f32(got), f32(want))
    for g, w in zip(grads, want_grads):
        assert np.abs(f32(w)).max() > 0
        np.testing.assert_array_equal(f32(g), f32(w))


@pytest.mark.parametrize("heads", [48, 8])
def test_over_a_part_in_bf16_the_result_is_one_rounding_from_float32(heads):
    """As the whole head's: from bf16 operands the kernels round once."""
    bf, given = jnp.bfloat16, given_for("yarn")
    y, gamma, dout = arguments(heads, bf)
    tables = lm_blocks._rotary_tables(SEQ, D, 0.0, None, (), given)
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    exact, back = jax.vjp(lambda y, g: body_given(y, g, heads, given),
                          f32(y), f32(gamma))
    kw = dict(heads=heads, eps=EPS, at_once=8, rotary_dim=given[0],
              interpret=True)
    got = lm_blocks._headrope_fwd_pallas(y, gamma, tables, rows=32, **kw)
    dy, dgamma = lm_blocks._headrope_bwd_pallas(y, gamma, tables, dout,
                                                rows=16, **kw)
    assert got.dtype == dy.dtype == dgamma.dtype == bf
    step = 2.0 ** -8
    for name, g, w in zip(("value", "dy", "dgamma"), (got, dy, dgamma),
                          (exact,) + back(f32(dout))):
        w = np.asarray(w)
        assert np.all(np.abs(np.asarray(g, np.float32) - w)
                      <= step * np.abs(w) + 1e-6), name


def test_given_frequencies_over_the_whole_head_are_the_two_table_call(
        interpreted_headrope):
    """``rotary_dim == d`` through *given*, one theta's frequencies and a
    scale of 1: two tables, `_rotary_tables`' own bit for bit, so the
    operator with `inv_freq` traces the jaxpr it traces without and gives
    the same bits through the kernels, value and gradients."""
    heads, theta = 8, 1e6
    inv = tuple(1.0 / (theta ** (np.arange(D // 2, dtype=np.float64)
                                 / (D // 2))))
    got = lm_blocks._rotary_tables(SEQ, D, 0.0, None, (), (D, inv, 1.0))
    want = lm_blocks._rotary_tables(SEQ, D, theta, None, ())
    assert len(got) == len(want) == 2
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    y, gamma, dout = arguments(heads, jnp.float32)
    op = lm_blocks._head_norm_rotary_op
    through = {"given": dict(rotary_dim=D, inv_freq=inv, table_scale=1.0),
               "theta": dict(theta=theta)}
    since, results, jaxprs = last_span(), {}, {}
    for name, attrs in through.items():
        f = functools.partial(op, num_heads=heads, eps=EPS, **attrs)
        jaxprs[name] = str(jax.make_jaxpr(f)(y, gamma))
        out, back = jax.vjp(f, y, gamma)
        results[name] = (out,) + back(dout)
    assert jaxprs["given"] == jaxprs["theta"]
    for g, w in zip(results["given"], results["theta"]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    plans = plans_since(since)
    assert plans and all(
        (p["path"], p["rotary_dim"], p["tables"]) == ("kernel", D, 2)
        for p in plans)


PART_CASES = {
    # (seq, head width, devices) -> path, and what the span's `why` holds
    "the-full-layers-shape": ((4096, 128, 1), "kernel", ()),
    "a-64-wide-head": ((4096, 64, 1), "xla", (
        "rotary over 32 of a head's 64 at given frequencies",
        "a head of 64 is not whole 128-lane tiles")),
    "not-whole-tiles": ((4096 + 64, 128, 1), "xla", (
        "rotary over 64 of a head's 128 at given frequencies",
        "a sequence of 4160")),
    "a-mesh-of-two": ((4096, 128, 2), "xla", (
        "rotary over 64 of a head's 128 at given frequencies",
        "a mesh of several devices")),
}


@pytest.mark.parametrize("case", sorted(PART_CASES))
def test_the_path_over_a_part_is_chosen_from_the_input(case):
    """`_contrib_HeadNormRotary` with `inv_freq` at the full layers' 48 and
    8 heads: the plan refuses what it refuses a whole head (and the span
    says the part beside the reason), and where it gives tiles the span
    carries ``rotary_dim`` and three tables, 24 of q's 48 heads a grid
    step under the cap of 32."""
    from jax.sharding import Mesh
    from mxnet_tpu.parallel import mesh as mesh_mod
    (seq, d, devices), path, whys = PART_CASES[case]
    turn = lm_blocks.rope_frequencies(YARN, d)
    tiles = lm_blocks.HEADROPE_TILES
    with mesh_mod.use_mesh(Mesh(np.array(jax.devices()[:devices]), ("dp",))):
        for heads in (48, 8):
            since = last_span()
            jax.eval_shape(
                functools.partial(lm_blocks._head_norm_rotary_op,
                                  num_heads=heads, eps=EPS, **turn),
                jax.ShapeDtypeStruct((1, seq, heads * d), jnp.bfloat16),
                jax.ShapeDtypeStruct((d,), jnp.bfloat16))
            p, = plans_since(since)
            assert (p["path"], p["heads"], p["rotary_dim"]) == (
                path, heads, d // 2)
            if path == "xla":
                assert all(w in p["why"] for w in whys)
                assert p["tables"] is p["table_bytes"] is p["seq_tile"] \
                    is None
                continue
            assert p["why"] is None and p["tables"] == 3
            assert p["table_bytes"] == 3 * seq * d * 4
            assert p["head_tile"] == min(heads, 24)
            assert p["seq_tile"] == {k: tiles[k] for k in ("fwd", "bwd")}
            assert p["residual_bytes"] == seq * heads * d * 2 + d * 4


def test_a_grid_step_counts_the_third_table_s_rows():
    """`_headrope_blocks` with three tables is one more float32 block of
    ``rows x d``, held twice; at the sweep's cap of 32 heads of 128 the
    backward kernel's step still fits the budget, so the plan takes the
    same heads a step as with two."""
    two = lm_blocks._headrope_blocks("bwd", 256, 32, D, jnp.bfloat16)
    three = lm_blocks._headrope_blocks("bwd", 256, 32, D, jnp.bfloat16, 3)
    assert three - two == 2 * 256 * D * 4
    assert two < three <= lm_blocks._HEADROPE_VMEM
    y = jax.ShapeDtypeStruct((1, 4096, 32 * D), jnp.bfloat16)
    assert lm_blocks._headrope_plan(y, 32, rotary_dim=64)[0] == \
        lm_blocks._headrope_plan(y, 32)[0]


def test_the_pair_over_a_part_lowers_for_the_tpu_with_three_tables():
    """Lowered for the TPU from this CPU host at the full layers' widths:
    one Mosaic call each way a width, and what is kept for the backward
    pass is the projection and three tables."""
    bf, given = jnp.bfloat16, given_for("yarn")
    tables = lm_blocks._rotary_tables(4096, D, 0.0, None, (), given)

    def loss(y, gamma, heads):
        with jax.named_scope("mx.gqa.project"):
            return jnp.sum(lm_blocks._head_norm_rotary(
                y, gamma, tables, heads, EPS, given[0]).astype(jnp.float32))

    for heads in (48, 8):
        avals = (jax.ShapeDtypeStruct((1, 4096, heads * D), bf),
                 jax.ShapeDtypeStruct((D,), bf))
        f = functools.partial(loss, heads=heads)
        text = jax.jit(jax.value_and_grad(f, argnums=(0, 1))).trace(
            *avals).lower(lowering_platforms=("tpu",)).as_text(
                debug_info=True)
        assert text.count("stablehlo.custom_call @tpu_custom_call") == 2
        for way in ("fwd", "bwd"):
            assert '"mx.headrope/mx_headrope_%s/pallas_call"' % way in text
        kept = jax.tree.leaves(jax.eval_shape(
            lambda *a: jax.vjp(f, *a)[1], *avals))
        big = [(a.shape, a.dtype) for a in kept if a.size >= 4096 * D]
        assert sorted(big, key=str) == sorted(
            [((1, 4096, heads * D), bf)] + [((1, 4096, D), jnp.float32)] * 3,
            key=str), big


# ---------------------------------------------------------------------------
# What must not move: the whole-head calls trace to the parent's jaxprs.
# ---------------------------------------------------------------------------

def pallas_calls(jaxpr):
    """Every `pallas_call` equation of *jaxpr*, nested ones included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for inner in jax.core.jaxprs_in_params(eqn.params):
            found.extend(pallas_calls(inner))
    return found


#: commit 8e5d163's jaxprs by what is traced (`whole_head_calls`), source
#: locations left out
PARENT_SHA = {
    "the-forward-kernel":
        "fc0639fc8ae557edfb1f255591f43a56b61773b0a8e09096743a3ef35e36c177",
    "the-backward-kernel":
        "52fb7184cb4c4f01112b3bb816e16419e26eaff57ac6e95f381f220e5cabf176",
    "the-operator-both-ways":
        "22b09b26a85f7a41823f844699995eb63cce834d799cedbf8b362fc768dc5f35",
    "the-operator-with-positions":
        "cf91218d081809428f1b8b66ae36957c700f6327117e2437bd50678c50662fa3",
    "the-operator-at-heads-of-64":
        "605c9ff3ef6803df25ba27013854faf1d5327d49c0684b508ab899acb1559196",
    "the-sparse-attention-operator":
        "d828b984e33a9ea71cda504bd5e18ab91e556ab9b583497c7d40283d9a868b22",
}


def whole_head_calls():
    """name -> ``(function, its arguments' shapes)``: the two kernels at 4
    heads of 128 over 512 positions, the operator's value and gradient
    there (counted positions, positions as an operand, heads of 64), and
    the sparse attention operator's at 4 and 2 heads."""
    bf, f32, S = jnp.bfloat16, jnp.float32, jax.ShapeDtypeStruct
    y, g, t = S((1, 512, 4 * D), bf), S((D,), f32), S((1, 512, D), f32)
    kw = dict(heads=4, eps=1e-6, rows=256, at_once=4)
    width = 64
    weights = [(4 * D, width), (2 * D, width), (2 * D, width), (width, 4 * D),
               (D,), (D,), (16, width), (8, width), (2, width)]

    def op(*inputs, **attrs):
        return lm_blocks._head_norm_rotary_op(
            *inputs, num_heads=4, theta=1e6, eps=1e-6, **attrs)

    def sparse(x, *w):
        out, term = get_op("_contrib_SparseAttention").fn(
            x, *w, num_heads=4, num_kv_heads=2, index_heads=2, topk=64,
            rope_theta=1e7, mrope_section=SECTIONS, eps=1e-6)
        return out.astype(f32) + term[0]

    def both_ways(f, leaves=(0, 1)):
        return jax.value_and_grad(
            lambda *a: jnp.sum(f(*a).astype(f32)), argnums=leaves)

    return {
        "the-forward-kernel": (
            lambda y, g, c, s: lm_blocks._headrope_fwd_pallas(
                y, g, (c, s), **kw), y, g, t, t),
        "the-backward-kernel": (
            lambda y, g, c, s, do: lm_blocks._headrope_bwd_pallas(
                y, g, (c, s), do, **kw), y, g, t, t, S((1, 4, 512, D), bf)),
        "the-operator-both-ways": (both_ways(op), y, g),
        "the-operator-with-positions": (
            both_ways(functools.partial(op, use_positions=True)),
            y, g, S((1, 1, 512), jnp.int32)),
        "the-operator-at-heads-of-64": (
            both_ways(op), S((1, 512, 4 * 64), bf), S((64,), f32)),
        "the-sparse-attention-operator": (
            both_ways(sparse, tuple(range(10))),
            S((1, 512, width), bf), *[S(w, bf) for w in weights]),
    }


@pytest.mark.parametrize("name", sorted(PARENT_SHA))
def test_the_whole_head_calls_trace_as_the_parent_s(name):
    """A part of a head is one more static description: without `inv_freq`
    the two kernels (two table operands and one roll a head each), the
    operator (value and gradient; with positions as an operand, SDAR's; at
    heads of 64, the body LFM2 keeps) and `_contrib_SparseAttention`
    (Keye's) are commit 8e5d163's to the letter (a JAX that prints jaxprs
    another way re-pins them)."""
    fn, *avals = whole_head_calls()[name]
    jaxpr = jax.make_jaxpr(fn)(*avals)
    text = re.sub(r" at \S+:\d+", "", str(jaxpr))
    if name.endswith("kernel"):
        call, = pallas_calls(jaxpr.jaxpr)
        # y, the scale, cos and one sine (and the cotangent) go in
        assert [v.aval.shape for v in call.invars[2:4]] == [(1, 512, D)] * 2
        assert len(call.invars) == (4 if name == "the-forward-kernel" else 5)
        rolls = [e for e in call.params["jaxpr"].eqns
                 if e.primitive.name == "roll"]
        assert len(rolls) == 4                  # one a head, by d / 2
        assert {int(e.invars[1].val) for e in rolls} == {D // 2}
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_SHA[name]


# ---------------------------------------------------------------------------
# `_contrib_HeadNormRotary`, and `GroupedQueryAttention` over it.
# ---------------------------------------------------------------------------

def written_out(F, x, positions, weights, heads, kv_heads, d, theta, eps,
                mask):
    """`GroupedQueryAttention` as it was before the operator: `RMSNorm`
    over each head of the reshaped product, `transpose`, then
    `RotaryEmbedding`."""
    def by_head(w, n, gamma=None):
        h = F.FullyConnected(x, w, no_bias=True, flatten=False,
                             num_hidden=n * d)
        h = F.Reshape(h, shape=(0, 0, n, -1))
        if gamma is None:
            return F.transpose(h, axes=(0, 2, 1, 3))
        h = F.transpose(F.contrib.RMSNorm(h, gamma, eps=eps),
                        axes=(0, 2, 1, 3))
        if positions is None:
            return F.contrib.RotaryEmbedding(h, theta=theta)
        return F.contrib.RotaryEmbedding(h, positions, theta=theta,
                                         use_positions=True)

    wq, wk, wv, wo, q_gamma, k_gamma = weights
    q, k, v = by_head(wq, heads, q_gamma), by_head(wk, kv_heads, k_gamma), \
        by_head(wv, kv_heads)
    if heads // kv_heads > 1:
        k = F.repeat(k, repeats=heads // kv_heads, axis=1)
        v = F.repeat(v, repeats=heads // kv_heads, axis=1)
    att = F.contrib.DotProductAttention(q, k, v, sm_scale=d ** -0.5, **mask)
    att = F.Reshape(F.transpose(att, axes=(0, 2, 1, 3)), shape=(0, 0, -1))
    return F.FullyConnected(att, wo, no_bias=True, flatten=False,
                            num_hidden=x.shape[-1])


def block_and_composition(d, seq, dtype, positions, diffusion_block):
    """``(value, every parameter's gradient)`` of the block and of
    `written_out` on the same weights, input and cotangent; 4 query heads
    over 2 key/value heads of *d*."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon.contrib.nn import GroupedQueryAttention
    units, heads, kv_heads, theta, eps = 64, 4, 2, 1e6, 1e-6
    rng = np.random.default_rng(7)
    block = GroupedQueryAttention(units, heads, kv_heads, d, theta, eps,
                                  diffusion_block=diffusion_block)
    block.initialize()
    block.cast(dtype)
    shapes = [(heads * d, units), (kv_heads * d, units),
              (kv_heads * d, units), (units, heads * d), (d,), (d,)]
    values = [mx.nd.array(1 + 0.3 * rng.normal(size=s) if len(s) == 1
                          else 0.2 * rng.normal(size=s), dtype=dtype)
              for s in shapes]
    for param, value in zip(block.collect_params().values(), values):
        param.set_data(value)
    x = mx.nd.array(rng.normal(size=(2, seq, units)), dtype=dtype)
    dout = mx.nd.array(rng.normal(size=(2, seq, units)), dtype=dtype)
    pos = None if positions == "counted" else mx.nd.array(
        np.broadcast_to(np.arange(seq) % (seq // 2), (1, 2, seq)),
        dtype="int32")
    with autograd.record():
        out = block(x) if pos is None else block(x, pos)
    out.backward(dout)
    got = [out] + [p.grad() for p in block.collect_params().values()]
    weights = [v.copy() for v in values]
    for w in weights:
        w.attach_grad()
    mask = {"mask": "block_diffusion", "mask_block": diffusion_block} \
        if diffusion_block else {"causal": True}
    with autograd.record():
        want = written_out(mx.nd, x, pos, weights, heads, kv_heads, d,
                           theta, eps, mask)
    want.backward(dout)
    return ([a.asnumpy().astype(np.float32) for a in got],
            [a.asnumpy().astype(np.float32)
             for a in [want] + [w.grad for w in weights]])


LEAVES = ("value", "query_weight", "key_weight", "value_weight",
          "out_weight", "query_norm_gamma", "key_norm_gamma")

LAYER_SHAPES = {
    # (head width, sequence) -> what the plan says
    "heads-of-128": ((128, 256), "kernel", None),
    "heads-of-64": ((64, 256), "xla", "a head of 64"),
    "heads-of-16": ((16, 256), "xla", "a head of 16"),
    "a-sequence-of-200": ((128, 200), "xla", "a sequence of 200"),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("diffusion_block", [None, 4])
@pytest.mark.parametrize("positions", ["counted", "an-input"])
@pytest.mark.parametrize("shape", sorted(LAYER_SHAPES))
def test_grouped_query_attention_is_the_written_out_composition_to_the_bit(
        shape, positions, diffusion_block, dtype):
    """On the CPU, where the pair is `_headrope_body` at a shape the plan
    takes and `_rotary` over `_rms_norm` at one it refuses: the block's
    value and the gradient of each of its six parameters are the
    composition's, bit for bit.  Primitive by primitive
    (`jax.disable_jit`), since XLA's CPU backend fuses one operator's
    arithmetic otherwise than four operators' and contracts other
    multiply-adds."""
    (d, seq), path, why = LAYER_SHAPES[shape]
    since = last_span()
    with jax.disable_jit():
        got, want = block_and_composition(d, seq, dtype, positions,
                                          diffusion_block)
    for name, g, w in zip(LEAVES, got, want):
        assert np.abs(w).max() > 0, name
        np.testing.assert_array_equal(g, w, err_msg=name)
    plans = [p for p in plans_since(since) if p["dtype"] == dtype]
    assert plans and {p["heads"] for p in plans} == {4, 2}
    for p in plans:
        assert p["path"] == path and p["head_dim"] == d
        assert p["why"] is None if why is None else p["why"].startswith(why)


@pytest.mark.parametrize("diffusion_block", [None, 4])
@pytest.mark.parametrize("positions", ["counted", "an-input"])
def test_grouped_query_attention_through_the_kernels(
        interpreted_headrope, positions, diffusion_block):
    """The same comparison with the two kernels in the block's path
    (interpreted), float32 on both sides: the order of a few sums
    differs."""
    since = last_span()
    with jax.default_matmul_precision("highest"):
        got, want = block_and_composition(128, 64, "float32", positions,
                                          diffusion_block)
    assert {(p["path"], p["heads"], p["head_tile"])
            for p in plans_since(since)} == {("kernel", 4, 4),
                                             ("kernel", 2, 2)}
    for name, g, w in zip(LEAVES, got, want):
        close(g, w, name)


@pytest.mark.parametrize("positions", ["counted", "an-input"])
@pytest.mark.parametrize("d,seq", [(128, 256), (64, 48)])
def test_the_operator_gives_one_value_through_nd_sym_and_a_hybridized_block(
        d, seq, positions):
    """`_contrib_HeadNormRotary` as `mx.nd.contrib.HeadNormRotary`, as a
    symbol bound and run, and inside a hybridized block: one value, which
    is `RMSNorm`, `transpose`, `RotaryEmbedding` composed."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import HybridBlock
    heads, theta, eps = 3, 1e6, 1e-6
    rng = np.random.default_rng(11)
    y = mx.nd.array(rng.normal(size=(2, seq, heads * d)))
    gamma = mx.nd.array(1 + 0.3 * rng.normal(size=d))
    pos = None if positions == "counted" else mx.nd.array(
        rng.integers(0, 500, size=(1, 2, seq)), dtype="int32")
    attrs = dict(num_heads=heads, theta=theta, eps=eps,
                 use_positions=pos is not None)
    inputs = [y, gamma] + ([] if pos is None else [pos])

    through_nd = mx.nd.contrib.HeadNormRotary(*inputs, **attrs).asnumpy()
    assert through_nd.shape == (2, heads, seq, d)

    names = ["data", "gamma", "positions"][:len(inputs)]
    sym = mx.sym.contrib.HeadNormRotary(*[mx.sym.var(n) for n in names],
                                        **attrs)
    assert sym.list_arguments() == names
    assert sym.infer_shape(**{n: a.shape for n, a in zip(names, inputs)})[1] \
        == [(2, heads, seq, d)]
    through_sym = sym.bind(mx.cpu(), dict(zip(names, inputs))).forward()[
        0].asnumpy()

    class Turn(HybridBlock):
        def hybrid_forward(self, F, *inputs):
            return F.contrib.HeadNormRotary(*inputs, **attrs)

    block = Turn()
    block.hybridize()
    through_block = block(*inputs).asnumpy()

    np.testing.assert_array_equal(through_sym, through_nd)
    np.testing.assert_array_equal(through_block, through_nd)
    h = mx.nd.transpose(mx.nd.contrib.RMSNorm(
        mx.nd.Reshape(y, shape=(0, 0, heads, -1)), gamma, eps=eps),
        axes=(0, 2, 1, 3))
    want = mx.nd.contrib.RotaryEmbedding(
        h, *inputs[2:], theta=theta, use_positions=pos is not None)
    np.testing.assert_allclose(through_nd, want.asnumpy(), rtol=1e-6,
                               atol=1e-6)
