"""`ops/lm_blocks.py` the routed layer's combine: `_combine_rows` (the pair
buffer's rows gathered into token order, then the Mosaic kernel
``mx_moe_combine`` over `_token_order`'s work list, interpreted here)
against `_sum_pairs`, the gather a choice that every other platform and the
worst-case buffer keep; `_contrib_RoutedExperts` through it against a dense
reference; the path `_combine_plan` chooses, as `mx.moe.plan` says it; and
the counter of the buffer rows the combine reads."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import profiler
from mxnet_tpu.ops import lm_blocks
from mxnet_tpu.ops.registry import get_op

BF = jnp.bfloat16
#: tokens a tile and rows a chunk, small enough for a test
TILES = (32, 16)


@pytest.fixture
def interpreted_combine(monkeypatch):
    """Steers `_combine` onto its TPU branch on this CPU host, the kernel
    interpreted, at `TILES` and a pair buffer in row tiles of 16; every
    other choice by platform (the grouped products') stays the CPU's."""
    real = jax.lax.platform_dependent

    def choose(*args, tpu, default):
        if getattr(tpu, "func", None) is lm_blocks._combine_rows:
            return tpu(*args, interpret=True)
        return real(*args, tpu=tpu, default=default)

    monkeypatch.setattr(lm_blocks, "COMBINE_TILES", TILES)
    monkeypatch.setattr(lm_blocks, "GROUPED_TILES", (16, 1024, 1024))
    monkeypatch.setattr(jax.lax, "platform_dependent", choose)
    # an operator traced before is not traced again, and one traced here
    # must not serve a later test
    jax.clear_caches()
    yield
    jax.clear_caches()


def plans_since(since):
    return [s.args for s in profiler.spans()
            if s.name == "mx.moe.plan" and s.id > since]


def last_span():
    return max([s.id for s in profiler.spans()] or [0])


# -- the pass against the gather a choice ------------------------------------
K, HELD, E, D = 4, 4, 8, 128
NONE, ONE, ALL = [4, 5, 6, 7], [5, 1, 6, 7], [3, 0, 2, 1]


def layout(name):
    """``(tokens x K choices, rows of the pair buffer)``: experts 0-3 are
    held, 4-7 are not."""
    if name == "none-one-and-all-of-a-token-s-pairs":
        # tile 0 mixes tokens of 0, 1 and K pairs (53 pairs: its run
        # crosses three chunk edges), tile 1 holds 32 pairs exactly
        rows = [(NONE, ONE, ALL)[t % 3] for t in range(32)] + [ONE] * 32
        return rows, 96
    if name == "a-tile-with-no-pair":
        return [ONE] * 32 + [NONE] * 32 + [ALL] * 32, 176
    if name == "a-run-that-ends-on-a-chunk-edge":
        return [ALL] * 4 + [NONE] * 28 + [ONE] * 32, 48
    if name == "a-full-buffer":
        return [ALL] * 8 + [NONE] * 24 + [ONE] * 32, 64
    if name == "no-pair-at-all":
        return [NONE] * 64, 16
    if name == "one-pair-in-the-last-token":
        return [NONE] * 63 + [ONE], 16
    raise KeyError(name)


LAYOUTS = ("none-one-and-all-of-a-token-s-pairs", "a-tile-with-no-pair",
           "a-run-that-ends-on-a-chunk-edge", "a-full-buffer",
           "no-pair-at-all", "one-pair-in-the-last-token")


def sorted_pairs(chosen, rows, seed=0):
    """The pair buffer as the grouped products leave it: numbers in the
    rows that hold a pair, NaN in every row past the last of them."""
    chosen = jnp.asarray(chosen, jnp.int32)
    order, inverse, sizes = lm_blocks._sort_pairs(chosen, 0, HELD)
    total = int(jnp.sum(sizes))
    assert total <= rows
    rng = np.random.default_rng(seed)
    y = rng.normal(size=(rows, D)).astype(np.float32)
    y[total:] = np.nan
    weights = jnp.asarray(rng.uniform(0.05, 1.0, size=chosen.shape),
                          jnp.float32)
    return order, inverse, sizes, jnp.asarray(y, BF), weights, total


def close_to_a_rounding(got, want, scale):
    """Equal but for the order of a token's up to K addends: to a rounding
    of the result's dtype, and a float32 rounding of the addends."""
    got = np.asarray(got.astype(jnp.float32))
    assert np.isfinite(got).all()
    want, scale = np.asarray(want), np.asarray(scale)
    assert (np.abs(got - want)
            <= 2.0 ** -8 * np.abs(want) + 2.0 ** -21 * scale).all()


@pytest.mark.parametrize("weighted", [True, False],
                         ids=["forward", "backward"])
@pytest.mark.parametrize("name", LAYOUTS)
def test_the_pass_is_the_gather_a_choice(name, weighted):
    """`_combine_rows` (kernel interpreted) against `_sum_pairs` in
    float32, with NaN in every row past the last existing pair: finite,
    and equal to a rounding."""
    chosen, rows = layout(name)
    order, inverse, sizes, y, weights, total = sorted_pairs(chosen, rows)
    w = (weights,) if weighted else ()
    run, read = lm_blocks._token_order(order, inverse, sizes, K, rows, TILES)
    got = lm_blocks._combine_rows(y, run, *w, tokens=len(chosen),
                                  tiles=TILES, interpret=True)
    assert got.shape == (len(chosen), D) and got.dtype == BF
    place, exists = lm_blocks._places(inverse, sizes, K)
    close_to_a_rounding(got, lm_blocks._sum_pairs(y, place, exists, *w),
                        lm_blocks._sum_pairs(jnp.abs(y), place, exists, *w))
    # a token with no pair here gets exactly 0
    assert not np.asarray(got)[~np.asarray(exists).any(1)].any()
    assert int(exists.sum()) == total


WORK = {
    # (tile, chunk, flags) of each item, and the rows the chunks cover
    "none-one-and-all-of-a-token-s-pairs": (
        [(0, 0, 3), (0, 1, 2), (0, 2, 2), (0, 3, 6), (1, 3, 3), (1, 4, 2),
         (1, 5, 6)], 7 * 16),
    "a-tile-with-no-pair": (
        [(0, 0, 3), (0, 1, 6), (1, 1, 5), (2, 2, 3)] + [
            (2, c, 2) for c in range(3, 9)] + [(2, 9, 6)], 10 * 16),
    "a-run-that-ends-on-a-chunk-edge": (
        [(0, 0, 7), (1, 1, 3), (1, 2, 6)], 3 * 16),
    "a-full-buffer": ([(0, 0, 3), (0, 1, 6), (1, 2, 3), (1, 3, 6)], 4 * 16),
    "no-pair-at-all": ([(0, 0, 5), (1, 0, 5)], 0),
    "one-pair-in-the-last-token": ([(0, 0, 5), (1, 0, 7)], 16),
}


@pytest.mark.parametrize("name", LAYOUTS)
def test_the_work_list_covers_each_tile_s_run_in_whole_chunks(name):
    """`_token_order`: the existing pairs in id order, a tile's items the
    chunks its run touches (one that adds nothing for a tile with no
    pair), the items past the last tile's doing nothing, and the rows
    read the chunks of every item that adds."""
    chosen, rows = layout(name)
    order, inverse, sizes, _, _, total = sorted_pairs(chosen, rows)
    (perm, code, of, reads, flags, n), read = lm_blocks._token_order(
        order, inverse, sizes, K, rows, TILES)
    assert int(n[0]) == total
    assert code.shape == (rows // TILES[1], 1, TILES[1])
    # a row's code: whose it is, and which of the token's choices
    token, choice = np.divmod(np.asarray(code).reshape(-1),
                              lm_blocks._COMBINE_CHOICES)
    ids = np.flatnonzero(np.asarray(inverse) < total)
    assert (token * K + choice)[:total].tolist() == ids.tolist()
    assert (token[total:] == len(chosen)).all()
    assert np.asarray(perm)[:total].tolist() == \
        np.asarray(inverse)[ids].tolist()
    assert sorted(np.asarray(perm).tolist()) == list(range(rows))
    items, want_read = WORK[name]
    got = list(zip(*(np.asarray(a).tolist() for a in (of, reads, flags))))
    assert len(got) == len(chosen) // TILES[0] + rows // TILES[1]
    assert got[:len(items)] == items
    assert all(f == 0 and (t, c) == got[len(items) - 1][:2]
               for t, c, f in got[len(items):])
    assert int(read) == want_read


# -- the operator through the pass -------------------------------------------
#: (experts a token, held, of the router's) at the ratio tokens x top_k /
#: buffer rows of three cells, 128 tokens wide 128
RATIOS = {"lfm2-2.67": (4, 8, 32, 192), "sdar-5.33": (8, 16, 128, 192),
          "laguna-20": (10, 8, 256, 64)}
TOKENS, HIDDEN = 128, 32


def operands(top_k, held, router, seed=0, dtype=BF):
    rng = np.random.default_rng(seed)

    def normal(scale, *shape):
        return jnp.asarray(scale * rng.normal(size=shape), dtype)

    return (normal(1.0, TOKENS, D), normal(0.3, router, D),
            normal(0.2, held, D, HIDDEN), normal(0.2, held, D, HIDDEN),
            normal(0.2, held, HIDDEN, D))


def routed(top_k, bias=(), **kw):
    return functools.partial(get_op("_contrib_RoutedExperts").fn,
                             num_experts_per_tok=top_k,
                             expert_bias=tuple(bias), **kw)


def dense(top_k, held, bias=()):
    """The held experts' part written out in float32: every token through
    every held expert, weighted by the router's choice of it or by 0."""
    def fn(x, router, w1, w3, w2):
        x, router, w1, w3, w2 = (a.astype(jnp.float32)
                                 for a in (x, router, w1, w3, w2))
        scores = jax.nn.sigmoid(jnp.dot(x, router.T, precision="highest"))
        biased = scores + (jnp.asarray(bias, jnp.float32) if bias else 0)
        chosen = jax.lax.top_k(biased, top_k)[1]
        picked = jnp.take_along_axis(scores, chosen, 1)
        weights = picked / (picked.sum(-1, keepdims=True) + 1e-6)
        out = 0.0
        for e in range(held):
            w_e = jnp.sum(jnp.where(chosen == e, weights, 0), -1)[:, None]
            h = jnp.dot(x, w1[e], precision="highest")
            g = jnp.dot(x, w3[e], precision="highest")
            out = out + w_e * jnp.dot(h * jax.nn.sigmoid(h) * g, w2[e],
                                      precision="highest")
        return out
    return fn


def out_and_gradients(fn, args, cot):
    out, vjp = jax.vjp(lambda *a: fn(*a).astype(jnp.float32), *args)
    return (out,) + vjp(cot)


NAMES = ("out", "x", "router", "w1", "w3", "w2")


@pytest.mark.parametrize("cell", sorted(RATIOS))
def test_the_operator_s_gradients_are_the_dense_reference_s(
        interpreted_combine, cell):
    """`_contrib_RoutedExperts` in bf16 at three cells' ratios of pairs
    gathered to buffer rows, forward and backward: against the dense
    float32 reference to bf16's roundings, and against the same operator
    through `_sum_pairs` to the order of a token's addends."""
    top_k, held, router, rows = RATIOS[cell]
    args = operands(top_k, held, router)
    assert lm_blocks._buffer_rows(TOKENS, top_k, held, router) == rows
    cot = jnp.asarray(np.random.default_rng(1).normal(size=(TOKENS, D)),
                      jnp.float32)
    since = last_span()
    with profiler.collect_step_stats() as stats:
        got = out_and_gradients(routed(top_k), args, cot)
    (plan,) = [p["combine"] for p in plans_since(since)]
    ratio = TOKENS * top_k / rows
    assert plan["path"] == ("kernel" if ratio >= lm_blocks.COMBINE_RATIO
                            else "xla")
    (row,), (read,) = stats["moe_expert_counts"], stats["moe_combine_rows"]
    pairs = int(row[router])
    assert 0 < pairs <= rows and int(row[-1]) == 0         # no overflow
    if plan["path"] == "kernel":
        assert plan["why"] is None and pairs <= int(read) < TOKENS * top_k
    else:
        assert plan["why"] and int(read) == TOKENS * top_k
    want = out_and_gradients(dense(top_k, held), args, cot)
    for name, g, w in zip(NAMES, got, want):
        g, w = (np.asarray(a, np.float32) for a in (g, w))
        assert np.abs(w).max() > 0, name
        assert np.abs(g - w).max() <= 0.03 * np.abs(w).max(), name
    # the same operator with the gather a choice in the pass's place
    jax.clear_caches()
    with pytest.MonkeyPatch.context() as m:
        m.setattr(lm_blocks, "COMBINE_RATIO", float("inf"))
        since = last_span()
        gathered = out_and_gradients(routed(top_k), args, cot)
        assert plans_since(since)[0]["combine"]["path"] == "xla"
    for name, g, w in zip(NAMES, got, gathered):
        g, w = (np.asarray(a, np.float32) for a in (g, w))
        assert np.abs(g - w).max() <= 2.0 ** -7 * np.abs(w).max(), name


def test_more_pairs_than_rows_take_the_gathers_and_drop_nothing(
        interpreted_combine):
    """A bias that sends every token's choices to the held experts: eight
    times the bounded buffer's rows, so the `cond`'s worst-case branch
    runs, which gathers a choice at a time (no second kernel), and the
    result is the dense reference's: nothing dropped."""
    top_k, held, router, rows = RATIOS["sdar-5.33"]
    bias = [100.0] * top_k + [0.0] * (router - top_k)
    args = operands(top_k, held, router)
    cot = jnp.ones((TOKENS, D), jnp.float32)
    fn = routed(top_k, bias)
    text = str(jax.make_jaxpr(lambda *a: out_and_gradients(fn, a, cot))(
        *args))
    # the kernel stands in the bounded branch of each direction alone
    assert text.count("name=mx_moe_combine") == 2
    with profiler.collect_step_stats() as stats:
        got = out_and_gradients(fn, args, cot)
    (row,), (read,) = stats["moe_expert_counts"], stats["moe_combine_rows"]
    assert [int(n) for n in row[router:]] == [
        TOKENS * top_k, 0, TOKENS * top_k, 1]
    assert TOKENS * top_k > rows and int(read) == TOKENS * top_k
    want = out_and_gradients(dense(top_k, held, bias), args, cot)
    for name, g, w in zip(NAMES, got, want):
        g, w = (np.asarray(a, np.float32) for a in (g, w))
        assert np.isfinite(g).all(), name
        assert np.abs(g - w).max() <= 0.03 * np.abs(w).max(), name


# -- which path, and how far it engages --------------------------------------
#: (tokens, experts a token, held, of the router's, width, dtype, devices)
#: -> the combine's path, and how its reason starts
CASES = {
    "sdar-and-keye": ((16384, 8, 16, 128, 2048, BF, 1), "kernel", None),
    "laguna": ((4096, 10, 8, 256, 3072, BF, 1), "kernel", None),
    "kanana": ((8192, 6, 16, 128, 2048, BF, 1), "kernel", None),
    "every-expert-held": ((4096, 2, 8, 8, 2048, BF, 1), "xla",
                          "4096 x 2 pairs gathered for 8192 rows is 1.00"),
    "float32": ((4096, 8, 16, 128, 2048, jnp.float32, 1), "xla",
                "not 2-byte rows"),
    "twenty-experts-a-token": ((4096, 20, 16, 256, 2048, BF, 1), "xla",
                               "20 experts a token, over the 16"),
    "a-width-of-192": ((4096, 8, 16, 128, 192, BF, 1), "xla",
                       "not 2-byte rows"),
    "tokens-that-are-no-whole-tiles": (
        (4000, 8, 16, 128, 2048, BF, 1), "xla", "4000 tokens and 6144 rows"),
    "a-mesh-of-two": ((16384, 8, 16, 128, 2048, BF, 2), "xla",
                      "a mesh of several devices"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_path_is_chosen_from_what_the_operator_sees(case):
    """`mx.moe.plan`'s ``combine`` entry, each time the operator is traced:
    the kernel at the routed cells' shapes on one device, the gather a
    choice where it reads no more (every expert held: the buffer is the
    worst case's), at rows the kernel does not take, and under a mesh of
    several devices (XLA does not partition a Mosaic kernel)."""
    from jax.sharding import Mesh
    from mxnet_tpu.parallel import mesh as mesh_mod
    (tokens, top_k, held, router, d, dtype, devices), path, why = CASES[case]
    avals = [jax.ShapeDtypeStruct(s, dtype) for s in (
        (tokens, d), (router, d), (held, d, 256), (held, d, 256),
        (held, 256, d))]
    since = last_span()
    with mesh_mod.use_mesh(Mesh(np.array(jax.devices()[:devices]), ("dp",))):
        jax.eval_shape(routed(top_k), *avals)
    (plan,) = plans_since(since)
    combine = plan["combine"]
    assert combine["path"] == path
    if path == "kernel":
        assert combine["why"] is None
        assert combine["token_tile"] == lm_blocks.COMBINE_TILES[0]
        assert combine["chunk_rows"] == lm_blocks.COMBINE_TILES[1]
        assert plan["pair_bound"] >= lm_blocks.COMBINE_RATIO \
            * plan["buffer_rows"]
    else:
        assert combine["why"].startswith(why), combine["why"]
        assert combine["token_tile"] is combine["chunk_rows"] is None


def test_lfm2_s_ratio_falls_where_the_sweep_put_the_threshold():
    """16384 tokens x 4 choices over 24576 rows is 2.67: the side of
    `COMBINE_RATIO` it stands on is the sweep's finding
    (docs/PERF_NOTES.md, PR 46), and the plan says which."""
    avals = [jax.ShapeDtypeStruct(s, BF) for s in (
        (16384, 2048), (32, 2048), (8, 2048, 256), (8, 2048, 256),
        (8, 256, 2048))]
    since = last_span()
    jax.eval_shape(routed(4), *avals)
    (plan,) = plans_since(since)
    assert plan["pair_bound"] / plan["buffer_rows"] == pytest.approx(8 / 3)
    if lm_blocks.COMBINE_RATIO <= 8 / 3:
        assert plan["combine"]["path"] == "kernel"
    else:
        assert plan["combine"]["path"] == "xla"
        assert "is 2.67, under" in plan["combine"]["why"]


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_the_kernel_stands_where_the_program_is_lowered_for_the_tpu(platform):
    """Lowered from this CPU host for either platform at a shape the plan
    takes: for the TPU one ``mx_moe_combine`` each way, under the
    operator's own `mx.moe.combine` scope and in the bounded branch alone;
    for the CPU none."""
    top_k, held, router, d = 8, 2, 16, 256
    avals = [jax.ShapeDtypeStruct(s, BF) for s in (
        (1024, d), (router, d), (held, d, 128), (held, d, 128),
        (held, 128, d))]

    def loss(*a):
        return jnp.sum(routed(top_k)(*a).astype(jnp.float32))

    since = last_span()
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 2))).trace(
        *avals).lower(lowering_platforms=(platform,)).as_text(
            debug_info=True)
    assert plans_since(since)[0]["combine"]["path"] == "kernel"
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "mx_moe_combine" in line]
    if platform == "cpu":
        assert not calls and "mx_moe_combine" not in text
        return
    assert len(calls) == 2
    # ... each where the choice by platform put it, inside the scope
    where = [line for line in text.splitlines()
             if line.startswith("#loc") and "mx_moe_combine/pallas_call"
             in line]
    assert where and all(
        "mx.moe.combine/cond/branch_0_fun/mx_moe_combine" in line
        for line in where)


def test_the_counter_reads_the_rows_the_combine_covers(interpreted_combine):
    """A seeded step through the operator, the counts folded as a trainer
    folds them: `moe_combine_rows_read_total` over
    `moe_local_assignments_total` is the rows read for each pair that
    exists, a third or less of the gather a choice's."""
    top_k, held, router, rows = RATIOS["sdar-5.33"]
    names = ("moe_combine_rows_read_total", "moe_local_assignments_total")
    before = [profiler.counter_value(n) for n in names]
    with profiler.collect_step_stats() as stats:
        routed(top_k)(*operands(top_k, held, router, seed=3))
        routed(top_k)(*operands(top_k, held, router, seed=4))
    profiler.fold_step_stats({k: np.stack(v) for k, v in stats.items()})
    read, pairs = (profiler.counter_value(n) - b
                   for n, b in zip(names, before))
    assert pairs == sum(int(r[router]) for r in stats["moe_expert_counts"])
    assert read == sum(int(r) for r in stats["moe_combine_rows"])
    assert read % TILES[1] == 0
    assert 1 <= read / pairs <= 2 * TOKENS * top_k / pairs / 3
