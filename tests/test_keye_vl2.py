"""The sparse-attention decoder (`gluon/model_zoo/decoder.py` kind
`sparse_attention`, a softmax router, an untied head) and the operators
under it (`ops/lm_blocks.py` `_contrib_SparseAttention`, three-axis rotary,
`_route`'s softmax; `ops/sparse_attention.py` `index_select` and
`alignment_term`; `ops/attention.py` `selected_attention`) against the plain
float32 reference `benchmarks/reference/keye_vl2.py`, at a small size on the
CPU with seeded weights: float32 on both sides, so only the order of the
arithmetic differs; the Mosaic kernels interpreted."""

import functools
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

from benchmarks import compare, dsa_counts  # noqa: E402
from benchmarks.models import common as models_common  # noqa: E402
from benchmarks.models import keye_vl2 as family  # noqa: E402
from benchmarks.reference import common as ref_common  # noqa: E402
from benchmarks.reference import keye_vl2 as reference  # noqa: E402
from mxnet_tpu import profiler  # noqa: E402
from mxnet_tpu.ops import attention, lm_blocks, sparse_attention  # noqa: E402
from mxnet_tpu.ops.registry import get_op  # noqa: E402

SEED = 2 ** 31 + 7


def config(**changes):
    cfg = {"family": "keye_vl2", "hidden_size": 64, "intermediate_size": 128,
           "moe_intermediate_size": 32, "num_experts_per_tok": 2,
           "router_experts": 16, "num_experts": 4, "first_expert": 4,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 16, "rope_theta": 10000000,
           "rope_scaling": {"mrope_section": [2, 3, 3],
                            "rope_type": "default", "type": "default"},
           "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                         "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                         "q_chunk_size": 512, "topk": 8},
           "rms_norm_eps": 1e-6, "norm_topk_prob": True,
           "tie_word_embeddings": False, "num_hidden_layers": 4,
           "vocab_size": 96, "alignment_weight": 1.0,
           "initializer_range": 0.02,
           "train": {"optimizer": "sgd", "lr": 0.01, "momentum": 0.9,
                     "wd": 0.0, "multi_precision": False,
                     "sequence_length": 48, "per_chip_batch": 2}}
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
        jax.random.fold_in(jax.random.PRNGKey(33), i), shape, jnp.float32)


# -- the whole model ----------------------------------------------------------
KINDS = {"one-layer": dict(num_hidden_layers=1), "all": {},
         "every-key-chosen": dict(
             num_hidden_layers=2,
             sa_config=dict(config()["sa_config"], topk=64)),
         "tied-head": dict(tie_word_embeddings=True, num_hidden_layers=2),
         # the cell's own: the embedding at unit scale beside 0.02 matrices
         "unit-embedding": dict(embedding_initializer_range=1.0,
                                num_hidden_layers=2)}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_logits_loss_and_every_gradient_agree_with_the_reference(kind):
    """Through `ParallelTrainer.fit_batch`: the loss a step reports is the
    cross-entropy, and every leaf's gradient is the OBJECTIVE's (the
    indexer's three from the alignment term, the mean over the layers).
    Tolerances: float32 on both sides, summed in another order (2e-4 of a
    leaf's largest entry, as the other families')."""
    import mxnet_tpu as mx
    cfg = config(**KINDS[kind])
    net, loss, names, params = seeded(cfg)
    (x, y), = family.batches(cfg, SEED, 1, 2)
    got = net(mx.nd.array(x, dtype="int32"))[0].asnumpy()
    want = highest(lambda p: reference.logits(p, cfg, x), params)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-4, atol=2e-6)

    trainer = models_common.make_trainer(net, loss, cfg["train"],
                                         jax.devices()[:1])
    got_loss = float(trainer.fit_batch(x, y))
    value, grads = highest(jax.value_and_grad(
        lambda p: reference.loss_sum(p, cfg, x, y)), params)
    total, xent = highest(
        lambda p: reference.objective_sum(p, cfg, x, y), params)
    assert float(value) == pytest.approx(float(xent), rel=1e-6)
    assert float(total) > float(xent)          # the term is there
    assert got_loss == pytest.approx(float(xent) / 2, rel=1e-5)
    assert set(names) == set(grads)
    lr = cfg["train"]["lr"]
    for ref_name, prog_name in names.items():
        g = -np.asarray(trainer._opt_state[prog_name][0]) / lr
        w = np.asarray(grads[ref_name]) / 2
        scale = max(np.abs(w).max(), 1e-12)
        assert np.abs(g - w).max() <= 2e-4 * scale, ref_name
        if ref_name.split(".")[-1] in reference.INDEXER_LEAVES:
            assert np.abs(w).max() > 0, ref_name


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


def test_the_counters_and_the_gauge_say_what_a_step_chose():
    """`dsa_selected_keys_total` from the bits the kernels were handed,
    `dsa_visible_keys_total` the causal pairs, `dsa_alignment_loss` the
    mean of the layers' terms: the reference's."""
    from mxnet_tpu.observability import metrics
    cfg = config(num_hidden_layers=2)
    net, loss, names, params = seeded(cfg)
    (x, y), = family.batches(cfg, SEED, 1, 2)
    trainer = models_common.make_trainer(net, loss, cfg["train"],
                                         jax.devices()[:1])
    before = {k: profiler.counter_value(k) for k in (
        "dsa_selected_keys_total", "dsa_visible_keys_total")}
    trainer.fit_batch(x, y)
    trainer.flush_step_stats()
    moved = {k: profiler.counter_value(k) - v for k, v in before.items()}
    assert moved["dsa_visible_keys_total"] == 2 * 2 * 48 * 49 // 2
    # min(t + 1, topk) keys a query, and the ties on top: a token id that
    # comes twice in a row of 48 gives layer 0's indexer the same key twice
    # (it reads no positions), and the counter shows it
    assert dsa_counts.selected_pairs(48, 8) == 36 + 40 * 8
    assert 2 * 2 * 356 <= moved["dsa_selected_keys_total"] \
        < moved["dsa_visible_keys_total"]
    want = highest(lambda p: reference.alignment_loss(p, cfg, x), params)
    assert metrics.snapshot()["dsa_alignment_loss"]["value"] == \
        pytest.approx(float(jnp.mean(want)), rel=1e-4)
    plans = [s for s in profiler.spans() if s.name == "mx.dsa.plan"]
    assert plans and plans[-1].args["topk"] == 8
    assert plans[-1].args["select"] == "xla"        # 48 rows: no whole block
    assert plans[-1].args["align"] == "xla"
    assert plans[-1].args["align_rows_a_block"] == 48
    assert "masked flash" in plans[-1].args["form"]


def test_the_routed_counters_equal_the_reference_s_counts():
    """The softmax router's choices as the routed op counts them (experts
    4 to 7 of 16 held here) against `reference.expert_counts`."""
    cfg = config(num_hidden_layers=2)
    net, loss, _, params = seeded(cfg)
    (x, y), = family.batches(cfg, SEED, 1, 2)
    trainer = models_common.make_trainer(net, loss, cfg["train"],
                                         jax.devices()[:1])
    names = ("moe_stat_layers_total", "moe_assignments_total",
             "moe_local_assignments_total",
             "moe_expert_load_max_over_mean_sum")
    before = {n: profiler.counter_value(n) for n in names}
    trainer.fit_batch(x, y)
    trainer.flush_step_stats()
    got = {n: profiler.counter_value(n) - before[n] for n in names}
    load = np.asarray(highest(
        lambda p: reference.expert_counts(p, cfg, x), params))
    assert load.shape == (2, 16) and (load.sum(1) == x.size * 2).all()
    assert got["moe_stat_layers_total"] == 2
    assert got["moe_assignments_total"] == load.sum()
    assert got["moe_local_assignments_total"] == load[:, 4:8].sum()
    assert got["moe_expert_load_max_over_mean_sum"] == pytest.approx(
        (load.max(1) / load.mean(1)).sum())


# -- the operator -------------------------------------------------------------
def layer_weights(width=64, heads=4, kv=2, hd=16, ih=2, iw=8):
    shapes = [(heads * hd, width), (kv * hd, width), (kv * hd, width),
              (width, heads * hd), (hd,), (hd,), (ih * iw, width),
              (iw, width), (ih, width)]
    return [1.0 + rand(i, *s, scale=0.1) if len(s) == 1
            else rand(i, *s, scale=0.2) for i, s in enumerate(shapes)]


ATTRS = dict(num_heads=4, num_kv_heads=2, index_heads=2, topk=8,
             rope_theta=1e7, mrope_section=(2, 3, 3), eps=1e-6)


def sparse_op(x, weights, **attrs):
    return get_op("_contrib_SparseAttention").fn(
        x, *weights, **dict(ATTRS, **attrs))


def test_each_gradient_has_one_source():
    """The op's two outputs: the first's gradient on the indexer's three
    matrices is exactly zero, the term's on everything else is exactly
    zero, and the term's on the indexer follows the term's own cotangent
    (so the loss's scale, whatever it is)."""
    x, weights = rand(20, 2, 48, 64), layer_weights()
    dout = rand(21, 2, 48, 64)

    def grads_of(of):
        return highest(jax.grad(lambda x, w: of(*sparse_op(x, w)),
                                argnums=(0, 1)), x, weights)

    out, term = highest(sparse_op, x, weights)
    assert out.shape == x.shape and term.shape == (1,)
    assert term.dtype == jnp.float32 and float(term[0]) > 0
    dx, dw = grads_of(lambda out, term: jnp.sum(out * dout))
    assert all(float(jnp.abs(g).max()) == 0.0 for g in dw[6:])
    assert all(float(jnp.abs(g).max()) > 0.0 for g in dw[:6])
    tx, tw = grads_of(lambda out, term: term[0])
    assert float(jnp.abs(tx).max()) == 0.0
    assert all(float(jnp.abs(g).max()) == 0.0 for g in tw[:6])
    assert all(float(jnp.abs(g).max()) > 0.0 for g in tw[6:])
    # both at once: each leaf has the one source (another compiled
    # program, so to float32's rounding and not to the bit)
    bx, bw = grads_of(lambda out, term: jnp.sum(out * dout) + term[0])
    for a, b in zip([dx] + dw[:6] + tw[6:], [bx] + bw):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=1e-5,
            atol=1e-6 * float(jnp.abs(a).max()))
    hw = grads_of(lambda out, term: 0.5 * term[0])[1]
    for a, b in zip(hw[6:], tw[6:]):
        np.testing.assert_allclose(np.asarray(a), 0.5 * np.asarray(b),
                                   rtol=1e-6, atol=1e-12)


def test_the_term_s_gradient_takes_the_loss_s_scale():
    """`AlignedLoss`: the rows it returns are the cross-entropy's to the
    bit, and under a plain `backward()` (the rows summed, not a trainer's
    mean) every gradient, the indexer's included, is the reference's
    objective's summed over the rows; half the head gradient halves all."""
    import mxnet_tpu as mx
    cfg = config(num_hidden_layers=2)
    net, loss, names, params = seeded(cfg)
    (x, y), = family.batches(cfg, SEED, 1, 2)
    xs, ys = mx.nd.array(x, dtype="int32"), mx.nd.array(y)
    leaves = net.collect_params()
    leaves.setattr("grad_req", "write")     # `seeded_net` leaves none
    got = {}
    for head in (1.0, 0.5):
        with mx.autograd.record():
            logits, term = net(xs)
            rows = loss((logits, term), ys)
        rows.backward(mx.nd.ones(rows.shape) * head)
        got[head] = {n: leaves[n].grad().asnumpy().copy()
                     for n in names.values()}
    assert rows.shape == (2,) and term.shape == (1,)
    assert np.array_equal(rows.asnumpy(), loss.loss(logits, ys).asnumpy())
    want = highest(jax.grad(
        lambda p: reference.loss_sum(p, cfg, x, y)), params)
    for ref_name, prog_name in names.items():
        w = np.asarray(want[ref_name])
        scale = max(np.abs(w).max(), 1e-12)
        assert np.abs(got[1.0][prog_name] - w).max() <= 2e-4 * scale, \
            ref_name
        np.testing.assert_allclose(got[0.5][prog_name],
                                   0.5 * got[1.0][prog_name],
                                   rtol=1e-5, atol=1e-12 * scale)
        if ref_name.split(".")[-1] in reference.INDEXER_LEAVES:
            assert np.abs(w).max() > 0, ref_name


def test_every_key_chosen_is_grouped_query_attention():
    """`topk` at least the sequence: the output is the `full_attention`
    kind's (`GroupedQueryAttention`) on the same weights."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.contrib.nn import (GroupedQueryAttention,
                                            SparseAttention)
    x, weights = rand(30, 2, 48, 64), layer_weights()
    sparse = SparseAttention(64, 4, 2, 16, index_heads=2, index_head_dim=8,
                             topk=48, rope_theta=1e7, mrope_section=(2, 3, 3))
    dense = GroupedQueryAttention(64, 4, 2, 16, rope_theta=1e7, epsilon=1e-6)
    for block, values in ((sparse, weights), (dense, weights[:6])):
        block.initialize()
        for p, v in zip(block.collect_params().values(), values):
            p.shape = v.shape
            p.set_data(mx.nd.array(np.asarray(v)))
    with jax.default_matmul_precision("highest"):
        got = sparse(mx.nd.array(np.asarray(x)))[0].asnumpy()
        want = dense(mx.nd.array(np.asarray(x))).asnumpy()
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)


def indexer(b=2, s=48, j=2, di=8):
    return rand(40, b, s, j * di), rand(41, b, s, di), \
        rand(42, b, s, j, scale=0.3)


@pytest.mark.parametrize("seq,topk", [(48, 8), (48, 1), (48, 48), (512, 64)])
def test_the_selection_is_the_reference_s_key_for_key(seq, topk):
    """`min(t + 1, topk)` keys a query, causal, the reference's set, both
    ways round; the kept logsumexp is the chosen scores'."""
    qi, ki, w = indexer(s=seq)
    sel_q, sel_k, lse = highest(
        lambda *a: sparse_attention.index_select(*a, topk), qi, ki, w)
    got = np.asarray(attention.unpack_selection(sel_q, seq))
    with jax.default_matmul_precision("highest"):
        scores = reference.index_scores(qi.reshape(2, seq, 2, 8), ki, w)
        want = np.asarray(reference.selection(scores, 0, topk))
    assert np.array_equal(got, want)
    assert not np.triu(got, 1).any()
    # min(t + 1, topk) keys a query, and more only where keys tie with the
    # k-th largest (two indexer heads: a score is exactly 0 a pair in four)
    least = np.broadcast_to(np.minimum(np.arange(seq) + 1, topk), (2, seq))
    masked = np.where(np.tril(np.ones((seq, seq), bool)), np.asarray(scores),
                      -np.inf)
    kth = -np.sort(-masked, -1)[..., min(topk, seq) - 1]
    tied = (masked == kth[..., None]).sum(-1) > 1
    assert (got.sum(-1) >= least).all()
    assert np.array_equal(got.sum(-1)[~tied], least[~tied])
    assert (~tied).sum() > seq or topk >= seq
    assert np.array_equal(
        np.asarray(attention.unpack_selection(sel_k, seq)),
        got.transpose(0, 2, 1))
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(jax.nn.logsumexp(
            jnp.where(want, scores, -jnp.inf), -1)), rtol=1e-5, atol=1e-5)


def test_ties_with_the_kth_largest_are_all_kept():
    """What the program does at a tie, and the reference with it: every key
    that scores what the k-th largest does is chosen."""
    qi, ki, w = indexer()
    ki = ki.at[:, 1::2].set(ki[:, 0::2])          # pairs of equal keys
    sel_q, _, _ = highest(
        lambda *a: sparse_attention.index_select(*a, 7), qi, ki, w)
    got = np.asarray(attention.unpack_selection(sel_q, 48))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.selection(reference.index_scores(
            qi.reshape(2, 48, 2, 8), ki, w), 0, 7))
    assert np.array_equal(got, want)
    # pairs of equal keys: the 7th largest and its twin come together (and
    # the zeros, where a row's 7th largest is one)
    counts = got.sum(-1)[:, 16:]
    assert counts.min() >= 7 and (counts == 8).sum() > counts.size // 2


def test_the_select_kernel_is_the_body_bit_for_bit():
    """`mx_dsa_select` interpreted at a sequence in whole blocks: the
    selection both ways round and the logsumexp of `_select_rows`."""
    qi, ki, w = indexer(s=512)
    want = highest(lambda *a: sparse_attention._select_rows(*a, 64),
                   qi, ki, w)
    with jax.default_matmul_precision("highest"):
        got = sparse_attention.index_select(qi, ki, w, 64, interpret=True)
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]),
                               rtol=1e-5, atol=1e-5)


def masked_oracle(q, k, v, mask, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    s = jnp.where(mask[:, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v), \
        jax.nn.logsumexp(s, -1)


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["body", "kernels"])
def test_backward_and_forward_apply_one_selection(interpret):
    """`selected_attention` with a selection operand (the flash kernels
    interpreted, and the `jax.numpy` body): output, logsumexp and all three
    gradients are a dense masked attention's under the SAME mask; a key
    counted in on one side and out on the other would show in dq, dk or
    dv."""
    b, h, s, d = 2, 3, 160, 16
    q, k, v, do = (rand(50 + i, b, h, s, d) for i in range(4))
    mask = (np.asarray(jax.random.uniform(jax.random.PRNGKey(5),
                                          (b, s, s))) < 0.3)
    mask = jnp.asarray((mask | np.eye(s, dtype=bool))
                       & np.tril(np.ones((s, s), bool)))
    sel_q = attention.pack_selection(mask)
    sel_k = attention.pack_selection(mask.transpose(0, 2, 1))
    assert sel_q.shape == (b, 5, s) and sel_q.dtype == jnp.int32
    assert np.array_equal(np.asarray(attention.unpack_selection(sel_q, s)),
                          np.asarray(mask))
    with jax.default_matmul_precision("highest"):
        (o, lse), vjp = jax.vjp(lambda q, k, v: attention.selected_attention(
            q, k, v, sel_q, sel_k, 0.25, interpret=interpret), q, k, v)
        (o2, lse2), vjp2 = jax.vjp(
            lambda q, k, v: masked_oracle(q, k, v, mask, 0.25), q, k, v)
        got, want = vjp((do, jnp.zeros_like(lse))), \
            vjp2((do, jnp.zeros_like(lse2)))
    np.testing.assert_allclose(np.asarray(o), np.asarray(o2), atol=2e-6)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse2), atol=2e-6)
    for name, a, c in zip("qkv", got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=2e-4,
                                   atol=5e-6, err_msg="d" + name)


def test_the_plan_span_says_a_selection_is_an_operand():
    q = rand(60, 1, 2, 256, 16)
    mask = jnp.tril(jnp.ones((1, 256, 256), bool))
    sel = attention.pack_selection(mask)
    since = profiler.spans()[-1].id if profiler.spans() else -1
    attention.selected_attention(q, q, q, sel, sel, interpret=True)
    plan = [s for s in profiler.spans() if s.name == "mx.flash.plan"
            and s.id > since][-1].args
    assert plan["selection"] == "bits"
    for kernel in ("fwd", "bwd"):
        assert plan[kernel]["tiles_masked"] == plan[kernel]["tiles_visited"]
        assert plan[kernel]["vmem_limit_bytes"] > plan[kernel]["vmem_bytes"]
    # ... and a call without one says nothing of it
    attention.flash_attention(q, q, q, causal=True, interpret=True)
    plain = [s for s in profiler.spans() if s.name == "mx.flash.plan"][-1]
    assert "selection" not in plain.args
    assert "vmem_limit_bytes" not in plain.args["fwd"]


#: sequence, keys a query, heads, key/value heads, what marks the case; the
#: first is the `jax.numpy` body's, the others `mx_dsa_align`'s
ALIGN_CASES = {
    "body": (48, 8, 4, 2, None),
    "kernel": (512, 64, 4, 2, None),
    # several row blocks a column tile, every tile under the diagonal whole
    "kernel-1024": (1024, 64, 4, 2, None),
    # rows 1024 to 1279 end in the middle of the columns' third tile
    "kernel-1536": (1536, 96, 4, 2, None),
    # the cell's eight query heads a key/value head
    "kernel-grouped-8": (512, 64, 8, 1, None),
    # pairs of equal keys: rows that hold more than `topk` keys
    "kernel-ties": (512, 63, 4, 2, "ties"),
    # no row has `topk` causal keys: every causal key is chosen
    "kernel-every-causal-key": (512, 2048, 4, 2, None),
    # queries so long that a key left out scores e^89 times what the
    # chosen ones do: float32's exponential of it is inf, and discarded
    "kernel-unseen-overflows": (512, 64, 4, 2, "long queries")}


@pytest.mark.parametrize("case", list(ALIGN_CASES))
def test_the_alignment_term_and_its_gradient(case):
    """`alignment_term` (the `jax.numpy` body, and `mx_dsa_align`
    interpreted) against the term written out densely: its value, and its
    gradient on the indexer's queries, key and head weights."""
    s, topk, h, kv, mark = ALIGN_CASES[case]
    interpret, b, d = case != "body", 2, 16
    qi, ki, w = indexer(s=s)
    if mark == "ties":
        ki = ki.at[:, 1::2].set(ki[:, 0::2])
    q, k, v = rand(70, b, h, s, d, scale=100 if mark == "long queries" else 1
                   ), rand(71, b, kv, s, d), rand(72, b, kv, s, d)
    with jax.default_matmul_precision("highest"):
        sel_q, sel_k, lse_i = sparse_attention.index_select(qi, ki, w, topk)
        mask = attention.unpack_selection(sel_q, s)
        if mark == "ties":
            kept = np.asarray(mask).sum(-1)
            assert (kept > topk).sum() > kept.size // 4
        k_all, v_all = jnp.repeat(k, h // kv, 1), jnp.repeat(v, h // kv, 1)
        _, lse = attention.selected_attention(q, k_all, v_all, sel_q, sel_k,
                                              0.25)
        att = jnp.einsum("bhqd,bhkd->bhqk", q, k_all) * 0.25
        target = jnp.mean(jax.nn.softmax(jnp.where(mask[:, None], att,
                                                   -jnp.inf), -1), 1)
        if mark == "long queries":
            left_out = jnp.tril(jnp.ones((s, s), bool)) & ~mask
            assert float(jnp.where(left_out[:, None], att - lse[..., None],
                                   0.0).max()) > 89

        def dense(qi, ki, w):
            scores = reference.index_scores(qi.reshape(b, s, 2, 8), ki, w)
            logp = jax.nn.log_softmax(jnp.where(mask, scores, -jnp.inf), -1)
            return jnp.sum(jnp.where(
                target > 0, target * (
                    jnp.log(jnp.where(target > 0, target, 1.0))
                    - jnp.where(mask, logp, 0.0)), 0.0)) / (b * s)

        want = jax.value_and_grad(dense, (0, 1, 2))(qi, ki, w)
        got = jax.value_and_grad(
            lambda *a: sparse_attention.alignment_term(
                *a, q, k, lse, lse_i, sel_q, 0.25, interpret=interpret),
            (0, 1, 2))(qi, ki, w)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    assert float(want[0]) > 0.01
    for name, a, c in zip(("qi", "ki", "w"), got[1], want[1]):
        scale = float(jnp.abs(c).max())
        assert float(jnp.abs(a - c).max()) <= 2e-5 * scale, name


def kernel_dots(jaxpr):
    """Every `dot_general` of a jaxpr, the ones inside its kernels, loops
    and branches among them."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) \
                    else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from kernel_dots(inner)


def test_the_alignment_kernel_forms_each_product_of_a_tile_once(monkeypatch):
    """`mx_dsa_align` at the cell's head counts: 32 products for the heads'
    scores, 16 for the indexer's pre-activations and 32 that carry ``dI``
    back to qI and kI (the pre-activations stay on chip for them), each
    contracting its left operand's last axis: none turns a tile to
    contract its first.  The plan says so where the kernels run."""
    s, h, kv, d, j, di = 512, 32, 4, 128, 16, 64
    bf = jnp.bfloat16
    jaxpr = jax.make_jaxpr(functools.partial(
        sparse_attention._align_pallas, sm_scale=d ** -0.5))(
        jnp.zeros((1, s, j * di), bf), jnp.zeros((1, s, di), bf),
        jnp.zeros((1, s, j)), jnp.zeros((1, h, s, d), bf),
        jnp.zeros((1, kv, s, d), bf), jnp.zeros((1, h, s)),
        jnp.zeros((1, s)), jnp.zeros((1, s // 32, s), jnp.int32))
    dots = list(kernel_dots(jaxpr.jaxpr))
    assert len(dots) == 32 + 16 + 32
    shapes = {}
    for eqn in dots:
        (lhs, rhs), batch = eqn.params["dimension_numbers"]
        assert lhs == (1,) and rhs in ((0,), (1,)) and batch == ((), ())
        assert {v.aval.dtype for v in eqn.invars} == {jnp.dtype(bf)}
        assert eqn.outvars[0].aval.dtype == jnp.float32
        key = tuple(v.aval.shape for v in eqn.invars)
        shapes[key] = shapes.get(key, 0) + 1
    cols, rows = sparse_attention.ALIGN_COLS, sparse_attention.ROWS
    # the tile is keys by queries; g_j, (cols, rows), is the latched
    # operand of both its products
    assert shapes == {((cols, d), (rows, d)): h,        # k . q^T
                      ((cols, di), (di, rows)): j,      # kI . qI_j^T
                      ((di, cols), (cols, rows)): j,    # dqI_j^T = kI^T . g_j
                      ((di, rows), (cols, rows)): j}    # dkI^T = qI_j^T . g_j^T
    monkeypatch.setattr(sparse_attention, "mosaic_runs_here", lambda: True)
    plan = sparse_attention.align_plan(16384, j, di, h, kv, d, bf)
    assert plan["align"] == "kernel"
    assert plan["align_products_a_tile"] == len(dots) == 80
    assert (plan["align_rows_on_chip"], plan["align_cols"]) == (rows, cols)
    kept = j * rows * cols * 4
    assert plan["align_kept"].endswith("float32: %d bytes" % kept)
    # what the kernel asks Mosaic for counts what it keeps and kI's
    # gradient, which spans the sequence, twice
    assert plan["align_vmem_limit_bytes"] > kept + 2 * 16384 * di * 4
    assert sparse_attention.align_plan(48, j, di, h, kv, d, bf)["align"] \
        == "xla"


# -- the router ---------------------------------------------------------------
def test_the_softmax_router_is_the_reference_s():
    cfg = config()
    x, router = rand(80, 96, 64), rand(81, 16, 64, scale=0.3)
    chosen, weights = highest(lambda x, r: lm_blocks._route(
        x, r, (0.0,) * 16, 2, True, 1.0, "softmax"), x, router)
    want_c, want_w = highest(lambda x, r: reference.route(cfg, x, r), x,
                             router)
    assert np.array_equal(np.asarray(chosen), np.asarray(want_c))
    np.testing.assert_allclose(np.asarray(weights), np.asarray(want_w),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)
    raw = highest(lambda x, r: lm_blocks._route(
        x, r, (0.0,) * 16, 2, False, 1.0, "softmax")[1], x, router)
    assert (np.asarray(raw).sum(-1) < 1.0).all()
    with pytest.raises(ValueError, match="no expert_bias"):
        lm_blocks._route(x, router, (0.1,) * 16, 2, True, 1.0, "softmax")
    with pytest.raises(ValueError, match="sigmoid or softmax"):
        lm_blocks._route(x, router, (0.0,) * 16, 2, True, 1.0, "tanh")


def jaxpr_sha(fn, *avals):
    text = re.sub(r" at \S+:\d+", "", str(jax.make_jaxpr(fn)(*avals)))
    return hashlib.sha256(text.encode()).hexdigest()


def test_the_older_cells_router_and_rotary_trace_as_the_parent_s():
    """`_route`'s sigmoid path and `_rotary` with one axis, as text (source
    locations cut), are commit ae34ec0's to the letter: the hashes are that
    commit's (a JAX that prints jaxprs another way re-pins them)."""
    x = jax.ShapeDtypeStruct((64, 32), jnp.bfloat16)
    r = jax.ShapeDtypeStruct((16, 32), jnp.float32)
    bias = tuple(0.01 * i for i in range(16))
    assert jaxpr_sha(lambda x, r: lm_blocks._route(x, r, bias, 3, True, 2.5),
                     x, r) == \
        "81ce850824d6c7805ef438b49298e0f11c5e93642706a711507474f43f3ed751"
    d = jax.ShapeDtypeStruct((2, 4, 48, 16), jnp.bfloat16)
    assert jaxpr_sha(lambda d: lm_blocks._rotary(d, 1e6), d) == \
        "431bfd1293a7ba2a672e940c724a3067f2f824245d81860fc7b744761845c903"
    assert jaxpr_sha(lambda d: lm_blocks._rotary(d, 1e6, True), d) == \
        "e5ab5e177c189207b2db562d3a647b6609d16f4b95c4d4a3ab4f280c2bebfc82"
    # text positions with three axes are the one-axis arithmetic
    assert jaxpr_sha(lambda d: lm_blocks._rotary(
        d, 1e6, mrope_section=(2, 3, 3)), d) == \
        "431bfd1293a7ba2a672e940c724a3067f2f824245d81860fc7b744761845c903"


@pytest.mark.parametrize("s,d,d_v,sha", [
    (2048, 64, 64,
     "7b1a57f9e47f01055a3f621edc3d25d77492ce4522fe182c144cdd280195bd6a"),
    (8192, 64, 64,
     "bdbb555b4c54de4d3a35ae0911502d8c9dbfdd26fe458dc879595836f50fe4e2"),
    (8192, 192, 128,
     "ea6c3eb0c6dbace358e57fa9bf8b380cc93996ff763dd492b0bd8de9e38c8d37"),
])
def test_the_backward_kernel_s_jaxpr_with_no_selection_is_the_parent_s(
        s, d, d_v, sha):
    """The backward call at the three older LM cells' shapes with no
    selection operand, as text, is commit ae34ec0's to the letter (the
    forward's is pinned in `test_attention.py`): the selection operand
    leaves `mx_flash_bwd` the program it was for them."""
    q = jax.ShapeDtypeStruct((1, 2, s, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 2, s, d_v), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((2, 1, s), jnp.float32)
    assert jaxpr_sha(lambda q, k, v, o, l, do: attention._flash_bwd_pallas(
        q, k, v, o, l, do, True, d ** -0.5), q, q, v, v, lse, v) == sha


# -- rotary positions ---------------------------------------------------------
def test_three_axis_rotary_positions():
    x = rand(90, 2, 4, 48, 16)
    pos = jnp.stack([jnp.broadcast_to(jnp.arange(48.0), (2, 48)),
                     jnp.asarray(np.random.RandomState(1).randint(
                         0, 9, (2, 48)), jnp.float32),
                     jnp.asarray(np.random.RandomState(2).randint(
                         0, 7, (2, 48)), jnp.float32)])
    got = lm_blocks._rotary(x, 1e7, False, pos, (2, 3, 3))
    want = reference.rope3(x, pos, 1e7, (2, 3, 3))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    # the axes matter ...
    assert float(jnp.abs(got - lm_blocks._rotary(x, 1e7)).max()) > 0.1
    # ... and where they are equal it is the one-axis operator
    text = reference.text_positions(2, 48)
    np.testing.assert_allclose(
        np.asarray(lm_blocks._rotary(x, 1e7, False, text, (2, 3, 3))),
        np.asarray(lm_blocks._rotary(x, 1e7)), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(reference.rope3(x, text, 1e7, (2, 3, 3))),
        np.asarray(lm_blocks._rotary(x, 1e7)), atol=1e-6)
    # through the registered operator, positions as an input
    op = get_op("_contrib_RotaryEmbedding")
    assert op.input_names_for({}) == ("data",)
    assert op.input_names_for({"use_positions": True}) == ("data",
                                                           "positions")
    np.testing.assert_allclose(
        np.asarray(op.fn(x, pos, theta=1e7, mrope_section=(2, 3, 3),
                         use_positions=True)), np.asarray(want), atol=1e-6)
    with pytest.raises(ValueError, match="cover"):
        lm_blocks._rotary(x, 1e7, False, pos, (2, 3, 2))


def test_positions_reach_the_sparse_layers_through_the_decoder():
    import mxnet_tpu as mx
    cfg = config(num_hidden_layers=2)
    net, _, _, params = seeded(cfg)
    (x, _), = family.batches(cfg, SEED, 1, 2)
    pos = np.stack([np.broadcast_to(np.arange(48.0), (2, 48)),
                    np.random.RandomState(3).randint(0, 9, (2, 48)),
                    np.random.RandomState(4).randint(0, 9, (2, 48))]
                   ).astype(np.float32)
    got = net(mx.nd.array(x, dtype="int32"), mx.nd.array(pos))[0].asnumpy()
    want = highest(lambda p: reference.logits(
        p, cfg, x, positions=jnp.asarray(pos)), params)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-4, atol=2e-6)
    text = net(mx.nd.array(x, dtype="int32"))[0].asnumpy()
    assert np.abs(got - text).max() > 1e-4


def test_the_cell_s_routed_layers_run_the_op_s_own_pair_buffer():
    """No layer sizes the routed op's pair buffer: at the cell's shape it is
    the op's `BUFFER_FACTOR` (1.5) times the 16384 pairs of uniform routing,
    and the plan of a softmax-routed layer says so."""
    assert lm_blocks.BUFFER_FACTOR == 1.5
    assert lm_blocks._buffer_rows(16384, 8, 16, 128) == 24576
    x, router = rand(110, 4096, 64), rand(111, 16, 64, scale=0.3)
    w1, w3 = rand(112, 4, 64, 32, scale=0.2), rand(113, 4, 64, 32, scale=0.2)
    w2 = rand(114, 4, 32, 64, scale=0.2)
    since = profiler.spans()[-1].id if profiler.spans() else -1
    get_op("_contrib_RoutedExperts").fn(
        x, router, w1, w3, w2, num_experts_per_tok=2, first_expert=4,
        scoring_func="softmax")
    plan = [s for s in profiler.spans() if s.name == "mx.moe.plan"
            and s.id > since][-1].args
    # 4096 x 2 pairs, 4 of 16 held: 2048 if even
    assert plan["pair_bound"] == 8192 and plan["buffer_rows"] == 3072
    assert plan["bound"].startswith("1.5 x")
    from mxnet_tpu.gluon.contrib.nn import RoutedExperts
    assert set(RoutedExperts(64, 32, 16, 2, 4, scoring_func="softmax")
               ._attrs) == {"expert_bias", "num_experts_per_tok",
                            "first_expert", "norm_topk_prob",
                            "routed_scaling_factor", "scoring_func"}


# -- the share ----------------------------------------------------------------
def test_the_eight_shares_parts_of_a_routed_layer_add_up_to_the_uncut_one():
    """The guide's share test: each of eight chips holds 2 of 16 experts
    and routes over all 16 with the softmax router; their parts add up to
    the reference's uncut layer."""
    cfg = config(num_experts=16, first_expert=0)
    x = rand(100, 2, 48, 64)
    router = rand(101, 16, 64, scale=0.3)
    w1, w3 = rand(102, 16, 64, 32, scale=0.2), rand(103, 16, 64, 32, scale=0.2)
    w2 = rand(104, 16, 32, 64, scale=0.2)
    p = {"l0.router": router, "l0.expert_w1": w1, "l0.expert_w3": w3,
         "l0.expert_w2": w2}
    whole = highest(lambda p, x: reference.routed(p, "l0.", cfg, x, False),
                    p, x)
    op = get_op("_contrib_RoutedExperts").fn
    total = 0.0
    for share in range(8):
        at = slice(2 * share, 2 * share + 2)
        with jax.default_matmul_precision("highest"):
            total = total + op(x, router, w1[at], w3[at], w2[at],
                               num_experts_per_tok=2, first_expert=2 * share,
                               scoring_func="softmax")
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=2e-4, atol=2e-6)


def test_a_decoder_without_the_kind_s_widths_says_what_is_missing():
    from mxnet_tpu.gluon.model_zoo.decoder import (OPERATOR_KINDS,
                                                   get_decoder_lm)
    assert OPERATOR_KINDS[:4] == ("conv", "full_attention",
                                  "latent_attention", "sparse_attention")
    with pytest.raises(ValueError, match="index_heads"):
        get_decoder_lm(vocab=32, dim=64, layer_types=["sparse_attention"],
                       num_dense_layers=1, dense_hidden=64, expert_hidden=32,
                       num_experts=4, num_experts_per_tok=1, heads=4,
                       kv_heads=2, head_dim=16)


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
def compiled_layer(v5e):
    """The operator's forward and backward at the cell's size (16384 tokens,
    32 / 4 heads of 128, 16 indexer heads of 64, 2048 keys a query),
    compiled once for the described chip with the persistent cache off
    (such a compile is written to it and cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(v5e.devices[0])
    s, d = 16384, 2048

    def aval(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    shapes = [(4096, d), (512, d), (512, d), (d, 4096), (128,), (128,),
              (1024, d), (64, d), (16, d)]
    attrs = dict(num_heads=32, num_kv_heads=4, index_heads=16, topk=2048,
                 rope_theta=1e7, mrope_section=(16, 24, 24))

    def step(x, weights, dout):
        def objective(x, w):
            out, term = get_op("_contrib_SparseAttention").fn(x, *w, **attrs)
            return jnp.sum(out.astype(jnp.float32) * dout) + term[0]
        return jax.grad(objective, argnums=(0, 1))(x, weights)

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return jax.jit(step).lower(
            aval(1, s, d), [aval(*shape) for shape in shapes],
            aval(1, s, d, dt=jnp.float32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()


def test_a_layer_compiles_for_the_described_chip_with_no_square_array(
        compiled_layer):
    """Mosaic and XLA:TPU take the four kernels with what they ask for, and
    no array in the compiled program has two axes of 16384 (no S x S scores,
    mask or probabilities; the selection is (1, 512, 16384) words)."""
    text = compiled_layer.as_text()
    for kernel in ("mx_dsa_select", "mx_flash_fwd", "mx_flash_bwd",
                   "mx_dsa_align"):
        assert kernel in text, kernel
    # `mx_dsa_align` keeps its tile's relu(a_j) on chip: Mosaic placed that
    # within what the kernel asks for, and the ask is half the chip's VMEM
    assert sparse_attention._align_kept_bytes(16) == 8 << 20
    assert sparse_attention._align_vmem(16384, 16, 64, 32, 4, 128, 2) \
        + sparse_attention._VMEM_SPARE < 64 << 20
    assert not re.search(r"\[[0-9,]*16384,[0-9,]*16384", text)
    assert "s32[1,512,16384]" in text
    # all the temporaries together (q, the repeated k and v, the gradients
    # of all of them) are 1.2 GB: what ONE S x S array of float32 a head
    # would be 32 times
    assert compiled_layer.memory_analysis().temp_size_in_bytes \
        < 1.6 * (1 << 30)


def written_arrays(hlo_text):
    """``[(opcode, result type)]`` of the instructions whose results a
    compiled program writes to memory: all but the ones inside fused
    computations, and the parameters."""
    fused = set(re.findall(r"fusion\(.*?calls=%([\w.\-]+)", hlo_text))
    out, inside = [], False
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if head:
            inside = head.group(1) in fused
            continue
        m = re.match(r"^\s+(?:ROOT )?%[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if m and not inside and m.group(2) != "parameter":
            out.append((m.group(2), m.group(1)))
    return out


def test_the_compiled_layer_writes_no_float32_array_of_q_s_size(
        compiled_layer):
    """The per-head norms, the rotary positions and the move to the
    head-major layout are the two kernels `mx_headrope_fwd` and
    `mx_headrope_bwd`, for q and for k: the program writes no float32 array
    of q's 67.1 M elements, in any order of its axes, nor one of its halves
    (before PR 34 it wrote ``f32[1,32,16384,128]`` twice and the rotation's
    ``f32[1,32,16384,64]`` eight times), and each kernel lies under
    `mx.dsa.project` and the pair's own scope inside it."""
    text = compiled_layer.as_text()
    half = 32 * 16384 * 128 // 2
    large = [(op, shape) for op, result in written_arrays(text)
             for shape, dims in re.findall(r"(f32\[([0-9,]+)\])", result)
             if np.prod([int(n) for n in dims.split(",")]) >= half]
    assert not large, large
    assert len(written_arrays(text)) > 100      # the parse found the program
    for kernel, calls in (("mx_headrope_fwd", 2), ("mx_headrope_bwd", 2)):
        found = re.findall(
            r'custom-call\(.*op_name="[^"]*/mx\.dsa\.project/[^"]*/'
            r'mx\.headrope/%s/' % kernel, text)
        assert len(found) == calls, (kernel, len(found))
