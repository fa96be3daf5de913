"""Fused train step (Module.forward_backward_update): equivalence with
the legacy per-parameter Updater loop, checkpoint interop across the
fused/legacy boundary, and the one-XLA-program-per-step property
(profiler dispatch counters).  See docs/perf_fused_step.md."""

import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym
from mxnet_tpu import optimizer as opt
from mxnet_tpu import profiler as prof
from mxnet_tpu.io import DataBatch

# per-dtype tolerances: the fused step compiles the update into a larger
# XLA program, so fusion/reassociation wiggles the last float bits
TOL = {"float32": dict(rtol=1e-5, atol=1e-6),
       "float16": dict(rtol=2e-3, atol=2e-3)}


def _mlp():
    data = sym.var("data")
    net = sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=4, name="fc2")
    return sym.SoftmaxOutput(net, name="softmax")


def _mlp_init(rng):
    return {
        "fc1_weight": nd.array(rng.randn(16, 8).astype(np.float32) * 0.1),
        "fc1_bias": nd.array(np.zeros(16, np.float32)),
        "fc2_weight": nd.array(rng.randn(4, 16).astype(np.float32) * 0.1),
        "fc2_bias": nd.array(np.zeros(4, np.float32)),
    }


def _toy_batches(rng, n_batches=4, batch=16, dim=8):
    X = rng.randn(n_batches * batch, dim).astype(np.float32)
    Y = rng.randint(0, 4, n_batches * batch).astype(np.float32)
    return [DataBatch(data=[nd.array(X[i * batch:(i + 1) * batch])],
                      label=[nd.array(Y[i * batch:(i + 1) * batch])])
            for i in range(n_batches)]


def _run_module(fused, symbol, init_args, batches, optimizer, opt_params,
                n_steps, data_shape=(16, 8), contexts=None, kvstore=None):
    os.environ["MXNET_MODULE_FUSED_STEP"] = "1" if fused else "0"
    try:
        mod = mx.Module(symbol, context=contexts or mx.cpu())
        mod.bind([("data", data_shape)],
                 [("softmax_label", (data_shape[0],))])
        mod.init_params(arg_params={k: v.copy()
                                    for k, v in init_args.items()})
        mod.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                           optimizer_params=dict(opt_params))
        for i in range(n_steps):
            mod.forward_backward_update(batches[i % len(batches)])
    finally:
        os.environ.pop("MXNET_MODULE_FUSED_STEP", None)
    return mod


def _assert_params_close(mod_a, mod_b, **tol):
    a, auxa = mod_a.get_params()
    b, auxb = mod_b.get_params()
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_allclose(a[k].asnumpy(), b[k].asnumpy(),
                                   err_msg=k, **tol)
    for k in auxa:
        np.testing.assert_allclose(auxa[k].asnumpy(), auxb[k].asnumpy(),
                                   err_msg=k, **tol)


@pytest.mark.parametrize("optimizer,opt_params", [
    ("sgd", {"learning_rate": 0.1}),
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}),
    ("adam", {"learning_rate": 0.01}),
    ("adagrad", {"learning_rate": 0.1}),
    ("rmsprop", {"learning_rate": 0.01}),
    ("nag", {"learning_rate": 0.1, "momentum": 0.9}),
])
def test_fused_matches_legacy(optimizer, opt_params):
    rng = np.random.RandomState(0)
    init = _mlp_init(rng)
    batches = _toy_batches(rng)
    legacy = _run_module(False, _mlp(), init, batches, optimizer,
                         opt_params, n_steps=6)
    fused = _run_module(True, _mlp(), init, batches, optimizer,
                        opt_params, n_steps=6)
    assert fused._fused and fused._fused["mode"] == "full"
    _assert_params_close(legacy, fused, **TOL["float32"])


def test_fused_mp_sgd_tree_matches_legacy_updater():
    """Multi-precision (fp16 weight + f32 master) tree sweep vs the
    legacy Updater, same kernels, same state nesting."""
    from mxnet_tpu.optimizer import tree_opt
    rng = np.random.RandomState(5)
    w0 = (rng.randn(6, 4) * 0.5).astype(np.float16)
    grads = [(rng.randn(6, 4) * 0.1).astype(np.float16) for _ in range(4)]
    kw = dict(learning_rate=0.1, momentum=0.9, wd=1e-3,
              multi_precision=True, rescale_grad=0.5, clip_gradient=1.0)

    opt_l = opt.create("sgd", **kw)
    upd = opt.get_updater(opt_l)
    w_l = nd.array(w0.copy())
    for g in grads:
        upd(0, nd.array(g), w_l)

    opt_f = opt.create("sgd", **kw)
    assert tree_opt.supports_fused(opt_f)
    import jax.numpy as jnp
    params = {"w": jnp.asarray(w0)}
    idx = {"w": 0}
    state = tree_opt.init_tree_state(opt_f, {"w": nd.array(w0)}, idx)
    fn = tree_opt.make_tree_update(opt_f)
    for g in grads:
        ts, lrs, wds = tree_opt.host_hyper(opt_f, ["w"], idx)
        params, state = fn({"w": jnp.asarray(g)}, params, state,
                           lrs, wds, ts)
    np.testing.assert_allclose(np.asarray(params["w"], np.float32),
                               w_l.asnumpy().astype(np.float32),
                               **TOL["float16"])
    # f32 master copies agree to f32 tolerance
    np.testing.assert_allclose(np.asarray(state["w"][1]),
                               np.asarray(upd.states[0][1].asnumpy()),
                               rtol=1e-5, atol=1e-6)


def test_host_hyper_keeps_per_index_counts():
    """Indices with diverged update counts (optimizer shared across
    modules, or resumed with dump_optimizer state) each keep their OWN
    t — Adam's bias correction must not borrow another index's count."""
    import math
    from mxnet_tpu.optimizer import tree_opt
    o = opt.create("adam", learning_rate=0.01)
    o._index_update_count = {0: 5}
    o.num_update = 5
    ts, lrs, _ = tree_opt.host_hyper(o, ["a", "b"], {"a": 0, "b": 1})
    assert ts == {"a": 6, "b": 1}
    for n in ("a", "b"):
        t = ts[n]
        want = 0.01 * math.sqrt(1.0 - o.beta2 ** t) / (1.0 - o.beta1 ** t)
        assert abs(lrs[n] - want) < 1e-12


def _emb_net(vocab=50, dim=8):
    data = sym.var("data")
    emb = sym.Embedding(data, input_dim=vocab, output_dim=dim,
                        sparse_grad=True, name="emb")
    feat = sym.mean(emb, axis=1)
    fc = sym.FullyConnected(feat, num_hidden=3, name="fc")
    return sym.SoftmaxOutput(fc, sym.var("softmax_label"), name="softmax")


@pytest.mark.parametrize("optimizer,opt_params", [
    ("sgd", {"learning_rate": 0.5}),                    # lazy rsp rows
    ("sgd", {"learning_rate": 0.5, "momentum": 0.9}),   # lazy rsp + mom
    ("adagrad", {"learning_rate": 0.5}),                # rsp history rows
])
def test_fused_sparse_embedding_matches_legacy(optimizer, opt_params):
    """Embedding(sparse_grad=True): the executor delivers rsp (ids,
    vals) pair grads and the fused sweep applies the functional mirror
    of the eager lazy row updates."""
    vocab, dim = 50, 8
    rng = np.random.RandomState(1)
    X = rng.randint(0, vocab, (64, 6)).astype(np.float32)
    Y = (X.sum(1) % 3).astype(np.float32)
    init = {
        "emb_weight": nd.array(rng.randn(vocab, dim).astype(np.float32)
                               * 0.1),
        "fc_weight": nd.array(rng.randn(3, dim).astype(np.float32) * 0.1),
        "fc_bias": nd.array(np.zeros(3, np.float32)),
    }
    batches = [DataBatch(data=[nd.array(X[i * 16:(i + 1) * 16])],
                         label=[nd.array(Y[i * 16:(i + 1) * 16])])
               for i in range(4)]
    legacy = _run_module(False, _emb_net(vocab, dim), init, batches,
                         optimizer, opt_params, n_steps=6,
                         data_shape=(16, 6))
    fused = _run_module(True, _emb_net(vocab, dim), init, batches,
                        optimizer, opt_params, n_steps=6,
                        data_shape=(16, 6))
    assert fused._fused and fused._fused["mode"] == "full"
    _assert_params_close(legacy, fused, **TOL["float32"])


def test_fused_resume_interop_both_directions(tmp_path):
    """save -> load -> resume crosses the fused/legacy boundary in both
    directions and lands on the same parameters."""
    rng = np.random.RandomState(2)
    init = _mlp_init(rng)
    batches = _toy_batches(rng)
    opt_params = {"learning_rate": 0.01}

    def _train_save(fused):
        mod = _run_module(fused, _mlp(), init, batches, "adam",
                          opt_params, n_steps=3)
        states = str(tmp_path / ("f.states" if fused else "l.states"))
        mod.save_optimizer_states(states)
        return mod, states

    def _resume(fused, arg_params, states, n=3):
        os.environ["MXNET_MODULE_FUSED_STEP"] = "1" if fused else "0"
        try:
            mod = mx.Module(_mlp(), context=mx.cpu())
            mod.bind([("data", (16, 8))], [("softmax_label", (16,))])
            mod.init_params(arg_params=arg_params)
            mod.init_optimizer(optimizer="adam",
                               optimizer_params=dict(opt_params))
            mod.load_optimizer_states(states)
            for i in range(3, 3 + n):
                mod.forward_backward_update(batches[i % len(batches)])
        finally:
            os.environ.pop("MXNET_MODULE_FUSED_STEP", None)
        return mod

    mod_f, st_f = _train_save(True)
    mod_l, st_l = _train_save(False)
    _assert_params_close(mod_f, mod_l, **TOL["float32"])
    args_f, _ = mod_f.get_params()
    args_l, _ = mod_l.get_params()

    # fused-trained state resumed by the legacy loop, and vice versa,
    # match resuming without crossing the boundary
    res_ff = _resume(True, args_f, st_f)
    res_fl = _resume(False, args_f, st_f)
    res_lf = _resume(True, args_l, st_l)
    res_ll = _resume(False, args_l, st_l)
    _assert_params_close(res_ff, res_fl, **TOL["float32"])
    _assert_params_close(res_lf, res_ll, **TOL["float32"])
    _assert_params_close(res_ff, res_ll, **TOL["float32"])


def test_fused_states_serialize_in_legacy_format(tmp_path):
    """A fused-trained module's optimizer-state file deserializes with
    the plain legacy Updater and holds the same moments."""
    import pickle
    rng = np.random.RandomState(3)
    init = _mlp_init(rng)
    batches = _toy_batches(rng)
    fused = _run_module(True, _mlp(), init, batches, "adam",
                        {"learning_rate": 0.01}, n_steps=4)
    legacy = _run_module(False, _mlp(), init, batches, "adam",
                         {"learning_rate": 0.01}, n_steps=4)
    f = str(tmp_path / "o.states")
    fused.save_optimizer_states(f)
    with open(f, "rb") as fh:
        blob = pickle.loads(fh.read())
    # format-2 envelope (resume validation header) around the exact
    # legacy per-index payload: {index: ("tuple", [("nd", arr), ...])}
    assert blob["__format__"] == 2 and blob["opt_class"] == "Adam"
    payload = blob["states"]
    assert set(payload) == set(legacy._updater.states)
    for i, s in legacy._updater.states.items():
        kind, entries = payload[i]
        assert kind == "tuple"
        for got, want in zip(entries, s):
            np.testing.assert_allclose(got[1], want.asnumpy(),
                                       **TOL["float32"])


def _third_step_counters(fused):
    """Dispatch counters of one step after two warm-up steps, and the
    number of parameters the step updates."""
    rng = np.random.RandomState(4)
    init = _mlp_init(rng)
    batches = _toy_batches(rng)
    mod = _run_module(fused, _mlp(), init, batches, "sgd",
                      {"learning_rate": 0.1, "momentum": 0.9}, n_steps=2)
    os.environ["MXNET_MODULE_FUSED_STEP"] = "1" if fused else "0"
    try:
        prof.reset_counters()
        mod.forward_backward_update(batches[0])
        return prof.counters(), len(init)
    finally:
        os.environ.pop("MXNET_MODULE_FUSED_STEP", None)
        prof.reset_counters()


def test_fused_step_single_dispatch_after_warmup():
    """The tentpole property: after warmup one training step is exactly
    ONE jitted computation — no eager per-parameter dispatches, no
    executor-level dispatch, no recompile."""
    c, _ = _third_step_counters(fused=True)
    assert c.get("fused_step_dispatches") == 1, c
    assert c.get("fused_step_compiles", 0) == 0, c
    assert c.get("eager_dispatches", 0) == 0, c
    assert c.get("executor_dispatches", 0) == 0, c


def test_legacy_step_dispatches_once_per_parameter():
    """The other side of the same count: the legacy loop is one
    executor dispatch plus one eager update per parameter, and no
    fused program."""
    c, n_params = _third_step_counters(fused=False)
    assert c.get("fused_step_dispatches", 0) == 0, c
    assert c.get("executor_dispatches") == 1, c
    assert c.get("eager_dispatches") == n_params, c


def test_fused_disabled_by_env_falls_back():
    rng = np.random.RandomState(6)
    init = _mlp_init(rng)
    batches = _toy_batches(rng)
    mod = _run_module(False, _mlp(), init, batches, "sgd",
                      {"learning_rate": 0.1}, n_steps=2)
    assert mod._fused is None           # legacy loop never built it
    assert mod._updater.states          # per-index state store in use


def test_subclass_forward_backward_overrides_fall_back():
    """A Module subclass overriding forward() or backward() (e.g. a
    grad-clipping hook) must take the legacy path: the fused program
    runs the whole step in one XLA call and would silently skip the
    override."""
    calls = {"backward": 0}

    class ClipModule(mx.Module):
        def backward(self, out_grads=None):
            calls["backward"] += 1
            super().backward(out_grads)

    rng = np.random.RandomState(11)
    init = _mlp_init(rng)
    batches = _toy_batches(rng)
    os.environ["MXNET_MODULE_FUSED_STEP"] = "1"
    try:
        mod = ClipModule(_mlp(), context=mx.cpu())
        mod.bind([("data", (16, 8))], [("softmax_label", (16,))])
        mod.init_params(arg_params={k: v.copy() for k, v in init.items()})
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1})
        assert not mod._fused_ok()
        for i in range(3):
            mod.forward_backward_update(batches[i % len(batches)])
    finally:
        os.environ.pop("MXNET_MODULE_FUSED_STEP", None)
    assert mod._fused is None
    assert calls["backward"] == 3   # the hook ran every step


def test_fused_unsupported_optimizer_falls_back():
    """A subclass overriding update (host readbacks, rng) must keep the
    legacy loop — exact-class matching in tree_opt.supports_fused."""
    from mxnet_tpu.optimizer import tree_opt
    assert not tree_opt.supports_fused(opt.create("lbsgd"))
    assert not tree_opt.supports_fused(opt.create("sgld"))
    rng = np.random.RandomState(7)
    init = _mlp_init(rng)
    batches = _toy_batches(rng)
    mod = _run_module(True, _mlp(), init, batches, "lbsgd",
                      {"learning_rate": 0.1}, n_steps=2)
    assert mod._fused is None


def test_fused_multi_device_partial_matches_single_device():
    """2-device data parallel: reduce_grads + ONE jitted tree update +
    broadcast matches the single-device legacy trajectory."""
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    rng = np.random.RandomState(8)
    init = _mlp_init(rng)
    batches = _toy_batches(rng)
    ref = _run_module(False, _mlp(), init, batches, "sgd",
                      {"learning_rate": 0.1, "momentum": 0.9}, n_steps=4)
    par = _run_module(True, _mlp(), init, batches, "sgd",
                      {"learning_rate": 0.1, "momentum": 0.9}, n_steps=4,
                      contexts=[mx.cpu(0), mx.cpu(1)])
    assert par._fused and par._fused["mode"] == "partial"
    _assert_params_close(ref, par, **TOL["float32"])


def _bn_net():
    data = sym.var("data")
    c = sym.Convolution(data, kernel=(3, 3), num_filter=4, name="conv")
    b = sym.BatchNorm(c, name="bn")
    a = sym.Activation(b, act_type="relu")
    fc = sym.FullyConnected(sym.Flatten(a), num_hidden=3, name="fc")
    return sym.SoftmaxOutput(fc, name="softmax")


def test_fused_batchnorm_aux_and_mixed_interleave():
    """BatchNorm moving stats update inside the fused program, and
    interleaving fused steps with legacy update() on ONE module keeps a
    single consistent optimizer state (the device tree hands back to
    the Updater and re-imports)."""
    rng = np.random.RandomState(9)
    X = rng.randn(64, 1, 8, 8).astype(np.float32)
    Y = rng.randint(0, 3, 64).astype(np.float32)
    batches = [DataBatch(data=[nd.array(X[i * 16:(i + 1) * 16])],
                         label=[nd.array(Y[i * 16:(i + 1) * 16])])
               for i in range(4)]
    seed = mx.Module(_bn_net(), context=mx.cpu())
    seed.bind([("data", (16, 1, 8, 8))], [("softmax_label", (16,))])
    seed.init_params(mx.init.Xavier())
    args, aux = seed.get_params()

    def run(schedule):
        mod = mx.Module(_bn_net(), context=mx.cpu())
        mod.bind([("data", (16, 1, 8, 8))], [("softmax_label", (16,))])
        mod.init_params(
            arg_params={k: v.copy() for k, v in args.items()},
            aux_params={k: v.copy() for k, v in aux.items()})
        mod.init_optimizer(optimizer="sgd", optimizer_params={
            "learning_rate": 0.1, "momentum": 0.9})
        try:
            for i, fused in enumerate(schedule):
                os.environ["MXNET_MODULE_FUSED_STEP"] = \
                    "1" if fused else "0"
                mod.forward_backward_update(batches[i % 4])
        finally:
            os.environ.pop("MXNET_MODULE_FUSED_STEP", None)
        return mod

    legacy = run([False] * 6)
    fused = run([True] * 6)
    mixed = run([True, False, True, False, True, False])
    _assert_params_close(legacy, fused, **TOL["float32"])
    _assert_params_close(legacy, mixed, **TOL["float32"])


def test_sparse_weight_shared_with_second_embedding_rejected():
    """Satellite regression: the sparse-consumer check exempts only the
    REGISTERED Embedding node — sharing the weight with a second
    Embedding (even a dense-grad one) must fail validation instead of
    surfacing as a trace-time shape error."""
    from mxnet_tpu.base import MXNetError
    d1, d2 = sym.var("d1"), sym.var("d2")
    w = sym.var("w")
    e1 = sym.Embedding(d1, w, input_dim=10, output_dim=4,
                       sparse_grad=True, name="e1")
    e2 = sym.Embedding(d2, w, input_dim=10, output_dim=4, name="e2")
    out = e1 + e2
    with pytest.raises(MXNetError, match="sparse_grad"):
        out.simple_bind(ctx=mx.cpu(), grad_req="write",
                        d1=(5,), d2=(5,))


def test_fused_rebuilds_on_hyper_mutation():
    """A hyper-param baked into the compiled program (rescale_grad,
    momentum, ...) mutated mid-run must trigger a rebuild — the legacy
    loop re-reads it every step, so a stale baked constant would make
    the two paths silently diverge."""
    rng = np.random.RandomState(11)
    init = _mlp_init(rng)
    batches = _toy_batches(rng)

    def run(fused):
        mod = _run_module(fused, _mlp(), init, batches, "sgd",
                          {"learning_rate": 0.1, "momentum": 0.9},
                          n_steps=3)
        mod._optimizer.rescale_grad = 0.5
        mod._optimizer.momentum = 0.5
        os.environ["MXNET_MODULE_FUSED_STEP"] = "1" if fused else "0"
        try:
            for i in range(3, 6):
                mod.forward_backward_update(batches[i % len(batches)])
        finally:
            os.environ.pop("MXNET_MODULE_FUSED_STEP", None)
        return mod

    legacy = run(False)
    fused = run(True)
    assert fused._fused["hyper"][0] == 0.5    # rebuilt with new values
    _assert_params_close(legacy, fused, **TOL["float32"])


def test_fused_key_advances_when_num_update_stalls():
    """The in-graph PRNG fold must use a value that advances every step
    for THIS module.  Optimizer.num_update only ratchets via max(), so
    sharing an optimizer with a module trained further stalls it — the
    fused step would replay identical dropout masks if it folded
    num_update."""
    rng = np.random.RandomState(12)
    init = _mlp_init(rng)
    batches = _toy_batches(rng)
    mod = _run_module(True, _mlp(), init, batches, "sgd",
                      {"learning_rate": 0.1}, n_steps=1)
    # simulate a shared optimizer whose global count is far ahead
    mod._optimizer.num_update = 100
    steps_seen = []
    real_fn = mod._fused["fn"]
    mod._fused["fn"] = lambda *a: (steps_seen.append(a[-1]),
                                   real_fn(*a))[1]
    os.environ["MXNET_MODULE_FUSED_STEP"] = "1"
    try:
        mod.forward_backward_update(batches[1])
        mod.forward_backward_update(batches[2])
    finally:
        os.environ.pop("MXNET_MODULE_FUSED_STEP", None)
    assert mod._optimizer.num_update == 100      # stalled, by design
    assert steps_seen[0] != steps_seen[1]        # key fold still moves


def test_fused_gated_off_for_overriding_subclasses():
    """A Module subclass customizing forward_backward/update (e.g.
    SVRGModule's variance-reduced gradient rewrite) must keep the
    legacy composition — the fused program would silently skip the
    override."""
    from mxnet_tpu.contrib.svrg_optimization import SVRGModule
    rng = np.random.RandomState(13)
    init = _mlp_init(rng)
    batches = _toy_batches(rng)
    os.environ["MXNET_MODULE_FUSED_STEP"] = "1"
    try:
        mod = SVRGModule(_mlp(), update_freq=2)
        mod.bind([("data", (16, 8))], [("softmax_label", (16,))])
        mod.init_params(arg_params={k: v.copy()
                                    for k, v in init.items()})
        mod.init_optimizer(kvstore=None, optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1})
        assert not mod._fused_ok()
        prof.reset_counters()
        mod.forward_backward_update(batches[0])
        assert prof.counter_value("fused_step_dispatches") == 0
    finally:
        os.environ.pop("MXNET_MODULE_FUSED_STEP", None)


@pytest.mark.parametrize("optimizer", ["nag", "signum"])
def test_fused_momentum_raised_from_zero_mid_run(optimizer):
    """Legacy NAG/Signum pick the kernel per update from ``state is
    not None`` — raising momentum from 0 mid-run must keep the
    existing None states momentumless (and not crash the rebuilt
    fused program)."""
    rng = np.random.RandomState(14)
    init = _mlp_init(rng)
    batches = _toy_batches(rng)

    def run(fused):
        mod = _run_module(fused, _mlp(), init, batches, optimizer,
                          {"learning_rate": 0.05, "momentum": 0.0},
                          n_steps=2)
        mod._optimizer.momentum = 0.9
        os.environ["MXNET_MODULE_FUSED_STEP"] = "1" if fused else "0"
        try:
            for i in range(2, 5):
                mod.forward_backward_update(batches[i % len(batches)])
        finally:
            os.environ.pop("MXNET_MODULE_FUSED_STEP", None)
        return mod

    legacy = run(False)
    fused = run(True)
    _assert_params_close(legacy, fused, **TOL["float32"])
