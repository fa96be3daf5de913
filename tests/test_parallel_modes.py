"""Pipeline (pp) and expert (ep) parallelism on the 8-device CPU mesh
(completing the tp/pp/dp/sp/ep mode set; reference has DP + manual
placement only, SURVEY §2.3)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.parallel import make_mesh, pipeline_apply, moe_apply


def _stage_fn(w, x):
    return jnp.tanh(x @ w)


def test_pipeline_matches_sequential():
    mesh = make_mesh({"pp": 8})
    rng = np.random.RandomState(0)
    S, M, B, D = 8, 4, 2, 16
    ws = jnp.asarray(rng.randn(S, D, D).astype(np.float32) * 0.3)
    xm = jnp.asarray(rng.randn(M, B, D).astype(np.float32))
    out = pipeline_apply(_stage_fn, ws, xm, axis_name="pp", mesh=mesh)
    # sequential reference: stages applied in order per microbatch
    ref = xm
    for s in range(S):
        ref = jnp.tanh(ref @ ws[s])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_gradients_flow():
    mesh = make_mesh({"pp": 8})
    rng = np.random.RandomState(1)
    S, M, B, D = 8, 3, 2, 8
    ws = jnp.asarray(rng.randn(S, D, D).astype(np.float32) * 0.3)
    xm = jnp.asarray(rng.randn(M, B, D).astype(np.float32))

    def loss_pp(ws):
        return jnp.sum(pipeline_apply(_stage_fn, ws, xm, mesh=mesh) ** 2)

    def loss_ref(ws):
        ref = xm
        for s in range(S):
            ref = jnp.tanh(ref @ ws[s])
        return jnp.sum(ref ** 2)

    g_pp = jax.grad(loss_pp)(ws)
    g_ref = jax.grad(loss_ref)(ws)
    np.testing.assert_allclose(np.asarray(g_pp), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)


def _expert_fn(w, x):
    return jnp.tanh(x @ w)


def test_moe_top1_dispatch_matches_dense_routing():
    mesh = make_mesh({"ep": 8})
    rng = np.random.RandomState(0)
    E, B, D = 8, 64, 16          # B tokens total, sharded 8 ways
    ew = jnp.asarray(rng.randn(E, D, D).astype(np.float32) * 0.3)
    x = jnp.asarray(rng.randn(B, D).astype(np.float32))
    gw = jnp.asarray(rng.randn(D, E).astype(np.float32) * 0.1)
    out = moe_apply(_expert_fn, ew, x, gw, axis_name="ep", mesh=mesh,
                    capacity_factor=8.0)  # big capacity: nothing drops
    # dense reference: every token through its argmax expert
    probs = jax.nn.softmax(x @ gw, axis=-1)
    idx = jnp.argmax(probs, axis=-1)
    gate = jnp.take_along_axis(probs, idx[:, None], axis=1)[:, 0]
    ref = jnp.stack([jnp.tanh(x[i] @ ew[idx[i]]) * gate[i]
                     for i in range(B)])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_moe_capacity_drops_overflow():
    mesh = make_mesh({"ep": 8})
    rng = np.random.RandomState(2)
    E, B, D = 8, 64, 8
    ew = jnp.asarray(rng.randn(E, D, D).astype(np.float32) * 0.3)
    # gate forces every token to expert 0 -> heavy overflow at cap=1
    gw = jnp.zeros((D, E), jnp.float32).at[:, 0].set(1.0)
    x = jnp.asarray(np.abs(rng.randn(B, D)).astype(np.float32))
    out = np.asarray(moe_apply(_expert_fn, ew, x, gw, mesh=mesh,
                               capacity_factor=1.0))
    # per device: 8 local tokens, cap = 8/8 = 1 -> exactly 1 kept each
    kept_rows = (np.abs(out).sum(axis=1) > 0).reshape(8, 8).sum(axis=1)
    np.testing.assert_array_equal(kept_rows, np.ones(8))


def test_moe_gradients_flow_to_gate_and_experts():
    mesh = make_mesh({"ep": 8})
    rng = np.random.RandomState(3)
    E, B, D = 8, 32, 8
    ew = jnp.asarray(rng.randn(E, D, D).astype(np.float32) * 0.3)
    gw = jnp.asarray(rng.randn(D, E).astype(np.float32) * 0.1)
    x = jnp.asarray(rng.randn(B, D).astype(np.float32))

    def loss(ew, gw):
        return jnp.sum(moe_apply(_expert_fn, ew, x, gw, mesh=mesh,
                                 capacity_factor=8.0) ** 2)

    ge, gg = jax.grad(loss, argnums=(0, 1))(ew, gw)
    assert np.isfinite(np.asarray(ge)).all()
    assert np.abs(np.asarray(ge)).sum() > 0
    assert np.abs(np.asarray(gg)).sum() > 0  # gate learns via the prob


def _norm_fn(w, x):
    # normalization-style fn: non-finite value/Jacobian at zero input —
    # the NaN-leak repro for bubble/padding slots
    h = x @ w
    return h / jnp.linalg.norm(h, axis=-1, keepdims=True)


def test_pipeline_norm_stage_gradients_finite():
    mesh = make_mesh({"pp": 8})
    rng = np.random.RandomState(5)
    S, M, B, D = 8, 3, 2, 8
    ws = jnp.asarray(rng.randn(S, D, D).astype(np.float32) * 0.5)
    xm = jnp.asarray(rng.randn(M, B, D).astype(np.float32))

    def loss(ws):
        return jnp.sum(pipeline_apply(_norm_fn, ws, xm, mesh=mesh) ** 2)

    g = jax.grad(loss)(ws)
    assert np.isfinite(np.asarray(g)).all()
    # and the forward matches sequential
    ref = xm
    for s in range(S):
        ref = _norm_fn(ws[s], ref)
    out = pipeline_apply(_norm_fn, ws, xm, mesh=mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_moe_norm_expert_gradients_finite():
    mesh = make_mesh({"ep": 8})
    rng = np.random.RandomState(6)
    E, B, D = 8, 32, 8
    ew = jnp.asarray(rng.randn(E, D, D).astype(np.float32) * 0.5)
    gw = jnp.asarray(rng.randn(D, E).astype(np.float32) * 0.1)
    x = jnp.asarray(rng.randn(B, D).astype(np.float32))

    def loss(ew, gw):
        return jnp.sum(moe_apply(_norm_fn, ew, x, gw, mesh=mesh,
                                 capacity_factor=8.0) ** 2)

    ge, gg = jax.grad(loss, argnums=(0, 1))(ew, gw)
    assert np.isfinite(np.asarray(ge)).all()
    assert np.isfinite(np.asarray(gg)).all()
    # forward stays finite even with heavy overflow dropping
    gw0 = jnp.zeros((D, E), jnp.float32).at[:, 0].set(1.0)
    out = np.asarray(moe_apply(_norm_fn, ew, x, gw0, mesh=mesh,
                               capacity_factor=1.0))
    assert np.isfinite(out).all()


def test_parallel_trainer_checkpoint_resume_exact():
    """save_checkpoint/load_checkpoint restore params, optimizer state
    (momentum), BN-free aux, and the update counter: a resumed trainer
    reproduces the original's losses bit-for-bit (SURVEY §5.4 at the
    compiled-step layer)."""
    import tempfile
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel.data_parallel import ParallelTrainer

    def make(momentum, mp):
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu"), nn.BatchNorm(),
                nn.Dense(4))
        net.initialize()
        params = {"learning_rate": 0.1}
        if momentum:
            params["momentum"] = momentum
        return ParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), optimizer="sgd",
            optimizer_params=params, mesh=make_mesh({"dp": 8}),
            multi_precision=mp)

    rs = np.random.RandomState(0)
    x = mx.nd.array(rs.randn(16, 8).astype(np.float32))
    y = mx.nd.array(rs.randint(0, 4, (16,)).astype(np.float32))
    # stateless sgd, momentum sgd, and bf16 multi-precision all resume
    for momentum, mp in ((0.0, False), (0.9, False), (0.9, True)):
        t1 = make(momentum, mp)
        for _ in range(5):
            t1.fit_batch(x, y)
        with tempfile.TemporaryDirectory() as td:
            prefix = td + "/ck"
            t1.save_checkpoint(prefix, 3)
            ref = [float(np.asarray(t1.fit_batch(x, y)))
                   for _ in range(3)]
            t2 = make(momentum, mp)  # fresh, differently initialized
            t2.fit_batch(x, y)       # build, then restore over it
            t2.load_checkpoint(prefix, 3)
            got = [float(np.asarray(t2.fit_batch(x, y)))
                   for _ in range(3)]
        np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-7)
        assert t2._num_update == 8


def test_coalesced_small_param_apply_matches_per_param():
    """coalesce_small fuses the LARS norms + (mp_)sgd updates of every
    small parameter into one flat-buffer computation; it must reproduce
    the per-parameter path numerically (ResNet's ~110 BN tensors are the
    real target — here a conv+BN+dense net stands in)."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel.data_parallel import ParallelTrainer

    def make(coalesce, optimizer, mp, momentum):
        net = nn.HybridSequential()
        net.add(nn.Conv2D(8, 3, padding=1), nn.BatchNorm(),
                nn.Activation("relu"), nn.Dense(5))
        net.initialize(mx.init.Xavier(rnd_type="gaussian"))
        params = {"learning_rate": 0.05, "eta": 0.01, "wd": 1e-4}
        if momentum:
            params["momentum"] = momentum
        tr = ParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(),
            optimizer=optimizer, optimizer_params=params,
            mesh=make_mesh({"dp": 8}), multi_precision=mp,
            coalesce_small=coalesce)
        return tr, net

    rs = np.random.RandomState(3)
    x = mx.nd.array(rs.randn(16, 3, 8, 8).astype(np.float32))
    y = mx.nd.array(rs.randint(0, 5, (16,)).astype(np.float32))
    for optimizer, mp, momentum in (("lbsgd", True, 0.9),
                                    ("lbsgd", False, 0.0),
                                    ("sgd", False, 0.9)):
        ta, neta = make(False, optimizer, mp, momentum)
        tb, netb = make(True, optimizer, mp, momentum)
        # identical starting point: params materialize lazily at the
        # first forward, so run one dummy forward through each net and
        # copy a's values into b by structural position BEFORE the
        # trainers gather state
        neta(mx.nd.array(np.zeros((1, 3, 8, 8), np.float32)))
        netb(mx.nd.array(np.zeros((1, 3, 8, 8), np.float32)))
        psa = list(neta.collect_params().values())
        psb = list(netb.collect_params().values())
        assert len(psa) == len(psb)
        for a, b in zip(psa, psb):
            assert a.shape == b.shape
            b.set_data(a.data().copy())
        la = [float(np.asarray(ta.fit_batch(x, y))) for _ in range(4)]
        lb = [float(np.asarray(tb.fit_batch(x, y))) for _ in range(4)]
        np.testing.assert_allclose(lb, la, rtol=2e-4, atol=2e-5)
        if optimizer == "lbsgd":
            small = [n for n in tb.param_names
                     if tb._params[n].size <= 8192]
            assert len(small) >= 2
        for na, nb in zip(ta.param_names, tb.param_names):
            np.testing.assert_allclose(
                np.asarray(ta._params[na], dtype=np.float32),
                np.asarray(tb._params[nb], dtype=np.float32),
                rtol=3e-3 if mp else 1e-5, atol=3e-3 if mp else 1e-6)


def test_parallel_trainer_rnn_frozen_begin_states():
    """Graph args with no backing Parameter (the fused RNN op's
    auto-created begin-state vars) are zero-filled frozen inputs under
    ParallelTrainer — simple_bind's unbound-arg semantics at the
    compiled-step layer (the zoo's LSTM LM, ``get_lstm_lm``)."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo.lm import get_lstm_lm
    from mxnet_tpu.parallel.data_parallel import ParallelTrainer

    net = get_lstm_lm(30, 16, 2)
    net.initialize()
    tr = ParallelTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                         optimizer="sgd",
                         optimizer_params={"learning_rate": 0.1,
                                           "momentum": 0.9},
                         mesh=make_mesh({"dp": 8}))
    rs = np.random.RandomState(0)
    x = mx.nd.array(rs.randint(0, 30, (8, 12)).astype(np.float32))
    y = mx.nd.array(rs.randint(0, 30, (8, 12)).astype(np.float32))
    losses = [float(np.asarray(tr.fit_batch(x, y))) for _ in range(6)]
    assert losses[-1] < losses[0]
    # the begin-state args stayed frozen zeros with empty opt state
    assert tr._frozen
    for n in tr._frozen:
        assert tr._opt_state[n] == ()
        assert float(jnp.sum(jnp.abs(tr._params[n]))) == 0.0


def test_parallel_trainer_frozen_states_batch_resize():
    """A different batch size rebuilds the frozen begin-state zeros
    (jit retraces; the frozen inputs must follow the batch geometry)."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo.lm import get_lstm_lm
    from mxnet_tpu.parallel.data_parallel import ParallelTrainer

    net = get_lstm_lm(20, 8, 1)
    net.initialize()
    tr = ParallelTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                         optimizer="sgd",
                         optimizer_params={"learning_rate": 0.1},
                         mesh=make_mesh({"dp": 8}))
    rs = np.random.RandomState(0)
    for bs in (16, 8, 16):
        x = mx.nd.array(rs.randint(0, 20, (bs, 6)).astype(np.float32))
        y = mx.nd.array(rs.randint(0, 20, (bs, 6)).astype(np.float32))
        loss = float(np.asarray(tr.fit_batch(x, y)))
        assert np.isfinite(loss)

def _tp_equivalence(net_fn, specs, x, y, steps=5, rtol=1e-5, atol=1e-6,
                    opt_params=None):
    """Train the same model replicated (dp=8) and tp-sharded (dp2xtp4)
    from identical weights; assert equal loss curves.  Returns the
    sharded trainer for further assertions."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel.data_parallel import ParallelTrainer

    opt_params = opt_params or {"learning_rate": 0.1, "momentum": 0.9}

    def make(param_specs, mesh_axes):
        net = net_fn()
        net.initialize()
        return ParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(),
            optimizer="sgd", optimizer_params=dict(opt_params),
            mesh=make_mesh(mesh_axes), param_specs=param_specs), net

    ta, neta = make({}, {"dp": 8})
    tb, netb = make(specs, {"dp": 2, "tp": 4})
    zero = mx.nd.array(np.zeros((1,) + tuple(x.shape[1:]), np.float32))
    neta(zero)
    netb(zero)
    for a, b in zip(neta.collect_params().values(),
                    netb.collect_params().values()):
        b.set_data(a.data().copy())
    la = [float(np.asarray(ta.fit_batch(x, y))) for _ in range(steps)]
    lb = [float(np.asarray(tb.fit_batch(x, y))) for _ in range(steps)]
    np.testing.assert_allclose(lb, la, rtol=rtol, atol=atol)
    return tb



def test_parallel_trainer_tensor_parallel_param_specs():
    """param_specs shards weights megatron-style over a dp x tp mesh
    (fc1 column-parallel, fc2 row-parallel); XLA closes the tp
    collectives and the loss curve must match the fully replicated
    run."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import nn
    from jax.sharding import PartitionSpec as P

    def net_fn():
        net = nn.HybridSequential()
        net.add(nn.Dense(32, activation="relu", prefix="fc1_"),
                nn.Dense(8, prefix="fc2_"))
        return net

    rs = np.random.RandomState(0)
    x = mx.nd.array(rs.randn(16, 12).astype(np.float32))
    y = mx.nd.array(rs.randint(0, 8, (16,)).astype(np.float32))
    tb = _tp_equivalence(net_fn,
                         {r"fc1_weight": P("tp", None),   # (hidden, in)
                          r"fc2_weight": P(None, "tp")},  # (out, hidden)
                         x, y, steps=6)
    # the weight really is tp-sharded on device
    w1 = tb._params[[n for n in tb.param_names
                     if "fc1_weight" in n][0]]
    spec = w1.sharding.spec
    assert tuple(spec)[:1] == ("tp",), spec


def test_transformer_lm_tensor_parallel_preset():
    """model_zoo.transformer.tensor_parallel_specs shards the LM's
    attention/MLP projections over a dp x tp mesh; the loss curve must
    match the fully replicated run (megatron pattern end to end
    through ParallelTrainer)."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.transformer import (
        get_transformer_lm, tensor_parallel_specs)

    rs = np.random.RandomState(0)
    x = mx.nd.array(rs.randint(0, 24, (8, 8)).astype(np.float32))
    y = mx.nd.array(rs.randint(0, 24, (8, 8)).astype(np.float32))
    tb = _tp_equivalence(
        lambda: get_transformer_lm(vocab=24, dim=16, heads=4, layers=2,
                                   max_seq=16),
        tensor_parallel_specs(), x, y, steps=5, rtol=2e-5, atol=2e-6)
    # at least one projection is really tp-sharded on device
    qn = [n for n in tb.param_names if n.endswith("query_weight")][0]
    assert tuple(tb._params[qn].sharding.spec)[:1] == ("tp",)


def test_pipeline_trainer_matches_sequential():
    """Trainer-grade PP (VERDICT r4 item 9): a 4-block net trained via
    PipelineTrainer on a dp x pp mesh gives the SAME loss trajectory as
    the plain sequential ParallelTrainer, with stacked weights and
    optimizer state sharded along pp."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel.data_parallel import (ParallelTrainer,
                                                  PipelineTrainer)

    D = 16

    def build():
        net2 = nn.HybridSequential()
        for i in range(4):
            net2.add(nn.Dense(D, activation="tanh",
                              prefix="blk%d_" % i))
        net2.initialize()
        net2(mx.nd.array(np.zeros((2, D), np.float32)))
        return net2

    rs = np.random.RandomState(0)
    X = rs.randn(16, D).astype(np.float32)
    Y = rs.randn(16, D).astype(np.float32)
    lossfn = gluon.loss.L2Loss()

    net_a = build()
    tr_a = ParallelTrainer(
        net_a, lossfn, optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        mesh=make_mesh({"dp": 1}, jax.devices()[:1]))
    net_b = build()
    pa = {p.name: p for p in net_a.collect_params().values()}
    for p in net_b.collect_params().values():
        p.set_data(mx.nd.array(pa[p.name].data().asnumpy()))
    tr_b = PipelineTrainer(
        net_b, lossfn, microbatches=4, optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        mesh=make_mesh({"dp": 2, "pp": 4}))

    for _ in range(3):
        la = float(tr_a.fit_batch(X, Y))
        lb = float(tr_b.fit_batch(X, Y))
        assert abs(la - lb) < 1e-4 * max(1.0, abs(la)), (la, lb)

    # stacked leaves and their optimizer state live stage-local
    for n, w in tr_b._params.items():
        assert tuple(w.sharding.spec)[:1] == ("pp",), (n, w.sharding)
        for s in tr_b._opt_state[n]:
            assert tuple(s.sharding.spec)[:1] == ("pp",), n

    # evaluate/predict run the pipeline in inference mode; predict
    # equals the sequential forward with the current stacked weights,
    # and evaluate equals the L2 loss of that forward
    ev = float(tr_b.evaluate_batch(X, Y))
    pred = np.asarray(tr_b.predict_batch(X)).astype(np.float32)
    Wst = np.asarray(tr_b._params["pp:weight"]).astype(np.float32)
    Bst = np.asarray(tr_b._params["pp:bias"]).astype(np.float32)
    h = X.copy()
    for i in range(4):
        h = np.tanh(h @ Wst[i].T + Bst[i])
    np.testing.assert_allclose(pred, h, rtol=1e-4, atol=1e-5)
    # L2Loss: mean over batch of mean-per-sample 0.5*(h-y)^2
    want_ev = float(np.mean(0.5 * (h - Y) ** 2))
    np.testing.assert_allclose(ev, want_ev, rtol=1e-4)


def test_pipeline_trainer_rejects_nonuniform_stages():
    import pytest
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel.data_parallel import PipelineTrainer

    net2 = nn.HybridSequential()
    net2.add(nn.Dense(16, prefix="a_"), nn.Dense(8, prefix="b_"),
             nn.Dense(16, prefix="c_"), nn.Dense(16, prefix="d_"))
    net2.initialize()
    net2(mx.nd.array(np.zeros((2, 16), np.float32)))
    tr = PipelineTrainer(net2, gluon.loss.L2Loss(), microbatches=2,
                         optimizer="sgd",
                         optimizer_params={"learning_rate": 0.1},
                         mesh=make_mesh({"dp": 2, "pp": 4}))
    with pytest.raises(Exception):
        tr.fit_batch(np.zeros((8, 16), np.float32),
                     np.zeros((8, 16), np.float32))


def test_moe_ffn_block_matches_manual_routing():
    """The GShard-einsum MoE op (contrib.nn.MoEFFN): outputs equal
    manual top-1 capacity routing, gradients reach gate and experts."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, nd
    from mxnet_tpu.gluon.contrib.nn import MoEFFN

    rs = np.random.RandomState(0)
    blk = MoEFFN(in_units=16, hidden=32, num_experts=4,
                 capacity_factor=2.0)
    blk.initialize()
    x = nd.array(rs.randn(24, 16).astype(np.float32))
    y = blk(x)
    gw = blk.gate_weight.data().asnumpy()
    w1 = blk.expert_w1.data().asnumpy()
    w2 = blk.expert_w2.data().asnumpy()
    xx = x.asnumpy()
    logits = xx @ gw
    probs = np.exp(logits - logits.max(1, keepdims=True))
    probs /= probs.sum(1, keepdims=True)
    eidx = probs.argmax(1)
    want = np.zeros_like(xx)
    cap = int(np.ceil(2.0 * 24 / 4))
    counts = dict.fromkeys(range(4), 0)
    for i in range(24):
        e = eidx[i]
        if counts[e] >= cap:
            continue
        counts[e] += 1
        h = np.maximum(xx[i] @ w1[e], 0)
        want[i] = probs[i, e] * (h @ w2[e])
    np.testing.assert_allclose(y.asnumpy(), want, rtol=1e-4, atol=1e-5)

    with autograd.record():
        loss = nd.sum(nd.square(blk(x)))
    loss.backward()
    for p in blk.collect_params().values():
        assert np.abs(p.grad().asnumpy()).sum() > 0, p.name


def test_moe_trainer_level_expert_parallel():
    """Trainer-grade EP: expert weights AND optimizer state sharded
    P('ep') over a dp x ep mesh via param_specs, with the loss
    trajectory identical to the replicated run (XLA closes the token
    all-to-alls inside the compiled step)."""
    import jax
    from jax.sharding import PartitionSpec as P
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.contrib.nn import MoEFFN
    from mxnet_tpu.parallel.data_parallel import ParallelTrainer

    D, H, E = 16, 32, 4
    rs = np.random.RandomState(0)
    X = rs.randn(32, D).astype(np.float32)
    Y = rs.randn(32, D).astype(np.float32)

    def build():
        net2 = nn.HybridSequential()
        net2.add(MoEFFN(D, H, E, capacity_factor=2.0, prefix="moe_"))
        net2.initialize()
        net2(mx.nd.array(np.zeros((2, D), np.float32)))
        return net2

    net_a = build()
    tr_a = ParallelTrainer(net_a, gluon.loss.L2Loss(), optimizer="sgd",
                           optimizer_params={"learning_rate": 0.05},
                           mesh=make_mesh({"dp": 1}, jax.devices()[:1]))
    net_b = build()
    pa = {p.name: p for p in net_a.collect_params().values()}
    for p in net_b.collect_params().values():
        p.set_data(mx.nd.array(pa[p.name].data().asnumpy()))
    tr_b = ParallelTrainer(net_b, gluon.loss.L2Loss(), optimizer="sgd",
                           optimizer_params={"learning_rate": 0.05},
                           mesh=make_mesh({"dp": 2, "ep": 4}),
                           param_specs={r"expert_w": P("ep", None,
                                                       None)})
    for _ in range(3):
        la = float(tr_a.fit_batch(X, Y))
        lb = float(tr_b.fit_batch(X, Y))
        assert abs(la - lb) < 1e-4 * max(1.0, abs(la)), (la, lb)
    for n, w in tr_b._params.items():
        if "expert_w" in n:
            assert tuple(w.sharding.spec)[:1] == ("ep",), (n, w.sharding)
            for s in tr_b._opt_state[n]:
                assert tuple(s.sharding.spec)[:1] == ("ep",), n
