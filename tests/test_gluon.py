"""Gluon blocks/trainer (reference: tests/python/unittest/test_gluon.py)."""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, autograd
from mxnet_tpu.gluon import nn


def test_parameter():
    p = gluon.Parameter("weight", shape=(10, 10))
    p.initialize(init="xavier", ctx=mx.cpu())
    assert len(p.list_data()) == 1
    assert p.data().shape == (10, 10)
    assert p.grad().shape == (10, 10)


def test_parameter_sharing():
    class Net(gluon.Block):
        def __init__(self, **kwargs):
            super().__init__(**kwargs)
            with self.name_scope():
                self.dense0 = nn.Dense(5, in_units=5)
                self.dense1 = nn.Dense(5, in_units=5)

        def forward(self, x):
            return self.dense1(self.dense0(x))

    net1 = Net(prefix="net1_")
    net2 = Net(prefix="net2_", params=net1.collect_params())
    net1.collect_params().initialize(ctx=mx.cpu())
    net2(nd.zeros((3, 5)))
    net1.save_parameters("/tmp/net1.params")
    net3 = Net(prefix="net3_")
    net3.load_parameters("/tmp/net1.params", mx.cpu())


def test_dense_shape_inference():
    net = nn.Dense(8)
    net.initialize(ctx=mx.cpu())
    out = net(nd.ones((4, 7)))
    assert out.shape == (4, 8)
    assert net.weight.shape == (8, 7)


def test_sequential_training_converges():
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu"), nn.Dense(2))
    net.initialize(mx.init.Xavier(), ctx=mx.cpu())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.5})
    # separable toy data
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(64, 10))
    y = nd.array((rng.randn(64) > 0).astype(np.float32))
    xs = x.asnumpy()
    ys = (xs[:, 0] > 0).astype(np.float32)
    y = nd.array(ys)
    first = None
    for i in range(30):
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(64)
        cur = float(loss.mean().asscalar())
        if first is None:
            first = cur
    assert cur < first * 0.5


def test_hybridize_consistency():
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="tanh"), nn.Dense(4))
    net.initialize(ctx=mx.cpu())
    x = nd.random.normal(shape=(5, 6))
    eager = net(x).asnumpy()
    net.hybridize()
    hybrid = net(x).asnumpy()
    np.testing.assert_allclose(eager, hybrid, rtol=1e-5, atol=1e-6)


def test_hybridize_grad_consistency():
    def build():
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(16, activation="relu"), nn.Dense(1))
        return net

    net = build()
    net.initialize(ctx=mx.cpu())
    x = nd.random.normal(shape=(4, 6))
    with autograd.record():
        y = net(x).sum()
    y.backward()
    g_eager = {k: v.grad().asnumpy().copy()
               for k, v in net.collect_params().items()}
    net.hybridize()
    with autograd.record():
        y = net(x).sum()
    y.backward()
    for k, v in net.collect_params().items():
        np.testing.assert_allclose(v.grad().asnumpy(), g_eager[k],
                                   rtol=1e-5, atol=1e-6)


def test_batchnorm_moving_stats_update():
    net = nn.BatchNorm()
    net.initialize(ctx=mx.cpu())
    x = nd.random.normal(3.0, 2.0, shape=(16, 4, 8, 8))
    net(x)  # first forward resolves deferred init (inference: no update)
    before = net.running_mean.data().asnumpy().copy()
    with autograd.record():
        net(x)
    after = net.running_mean.data().asnumpy()
    assert np.abs(after - before).sum() > 0
    # inference does not touch stats
    before = after.copy()
    net(x)
    np.testing.assert_allclose(net.running_mean.data().asnumpy(), before)


def test_conv2d_layers():
    x = nd.random.normal(shape=(2, 3, 10, 10))
    layer = nn.Conv2D(6, (3, 3), padding=(1, 1))
    layer.initialize(ctx=mx.cpu())
    assert layer(x).shape == (2, 6, 10, 10)
    tlayer = nn.Conv2DTranspose(3, (2, 2), strides=(2, 2))
    tlayer.initialize(ctx=mx.cpu())
    assert tlayer(x).shape == (2, 3, 20, 20)
    pool = nn.MaxPool2D((2, 2))
    assert pool(x).shape == (2, 3, 5, 5)
    gpool = nn.GlobalAvgPool2D()
    assert gpool(x).shape == (2, 3, 1, 1)


def test_embedding_layer():
    emb = nn.Embedding(10, 4)
    emb.initialize(ctx=mx.cpu())
    idx = nd.array([1, 2, 3])
    out = emb(idx)
    assert out.shape == (3, 4)
    with autograd.record():
        loss = (emb(idx) ** 2).sum()
    loss.backward()
    g = emb.weight.grad().asnumpy()
    assert np.abs(g[1:4]).sum() > 0
    assert np.abs(g[5:]).sum() == 0


def test_losses():
    pred = nd.array([[1.0, -1.0], [-1.0, 1.0]])
    label = nd.array([0, 1])
    l = gluon.loss.SoftmaxCrossEntropyLoss()(pred, label)
    expected = -np.log(np.exp(1) / (np.exp(1) + np.exp(-1)))
    np.testing.assert_allclose(l.asnumpy(), [expected] * 2, rtol=1e-5)

    l2 = gluon.loss.L2Loss()(nd.array([1.0, 2.0]), nd.array([0.0, 0.0]))
    np.testing.assert_allclose(l2.asnumpy(), [0.5, 2.0], rtol=1e-5)

    l1 = gluon.loss.L1Loss()(nd.array([1.0, -2.0]), nd.array([0.0, 0.0]))
    np.testing.assert_allclose(l1.asnumpy(), [1.0, 2.0], rtol=1e-5)

    bce = gluon.loss.SigmoidBinaryCrossEntropyLoss()(
        nd.array([0.0]), nd.array([1.0]))
    np.testing.assert_allclose(bce.asnumpy(), [np.log(2)], rtol=1e-5)

    h = gluon.loss.HuberLoss()(nd.array([2.0]), nd.array([0.0]))
    np.testing.assert_allclose(h.asnumpy(), [1.5], rtol=1e-5)


def test_sigmoid_bce_pos_weight():
    rs = np.random.RandomState(3)
    x = rs.randn(4, 3).astype('float32')
    z = (rs.rand(4, 3) > 0.5).astype('float32')
    w = np.array([2.0, 0.5, 3.0], 'float32')
    s = 1 / (1 + np.exp(-x))
    want = (-(w * z * np.log(s) + (1 - z) * np.log(1 - s))).mean(1)
    logit = gluon.loss.SigmoidBinaryCrossEntropyLoss()(
        nd.array(x), nd.array(z), None, nd.array(w))
    np.testing.assert_allclose(logit.asnumpy(), want, rtol=1e-4)
    prob = gluon.loss.SigmoidBinaryCrossEntropyLoss(from_sigmoid=True)(
        nd.array(s.astype('float32')), nd.array(z), None, nd.array(w))
    np.testing.assert_allclose(prob.asnumpy(), want, rtol=1e-3)
    # pos_weight of ones reduces to the unweighted loss
    ones = gluon.loss.SigmoidBinaryCrossEntropyLoss()(
        nd.array(x), nd.array(z), None, nd.array(np.ones(3, 'float32')))
    base = gluon.loss.SigmoidBinaryCrossEntropyLoss()(
        nd.array(x), nd.array(z))
    np.testing.assert_allclose(ones.asnumpy(), base.asnumpy(), rtol=1e-5)


def test_ctc_loss_lengths():
    import pytest
    rs = np.random.RandomState(5)
    pred = rs.randn(2, 6, 5).astype('float32')      # NTC
    label = nd.array([[1.0, 2.0, 0.0], [3.0, 1.0, 2.0]])
    full = gluon.loss.CTCLoss(layout='NTC')(nd.array(pred), label)
    cut = gluon.loss.CTCLoss(layout='NTC')(
        nd.array(pred), label, nd.array([4.0, 6.0]), nd.array([2.0, 3.0]))
    assert np.isfinite(cut.asnumpy()).all()
    # shorter sequences change the alignment -> different loss
    assert not np.allclose(full.asnumpy(), cut.asnumpy())
    # a flag without its tensor (or vice versa) is an error, not a
    # silent full-length loss
    with pytest.raises(TypeError):
        nd.CTCLoss(nd.array(np.zeros((6, 2, 5), 'float32')),
                   nd.array([[1.0, 2.0], [1.0, 2.0]]),
                   use_data_lengths=True)
    with pytest.raises(TypeError):
        nd.CTCLoss(nd.array(np.zeros((6, 2, 5), 'float32')),
                   nd.array([[1.0, 2.0], [1.0, 2.0]]),
                   nd.array([3.0, 4.0]))


def test_trainer_save_load_states(tmp_path):
    net = nn.Dense(4, in_units=3)
    net.initialize(ctx=mx.cpu())
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.01})
    x = nd.ones((2, 3))
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    trainer.step(2)
    f = str(tmp_path / "trainer.states")
    trainer.save_states(f)
    trainer.load_states(f)


def test_zero_grad():
    net = nn.Dense(4, in_units=3)
    net.initialize(ctx=mx.cpu())
    with autograd.record():
        loss = net(nd.ones((2, 3))).sum()
    loss.backward()
    assert np.abs(net.weight.grad().asnumpy()).sum() > 0
    net.collect_params().zero_grad()
    assert np.abs(net.weight.grad().asnumpy()).sum() == 0


def test_export_symbolblock_imports(tmp_path):
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(8, activation="relu"), nn.Dense(3))
    net.initialize(ctx=mx.cpu())
    net.hybridize()
    x = nd.random.normal(shape=(2, 5))
    ref = net(x).asnumpy()
    path = str(tmp_path / "model")
    net.export(path)
    net2 = gluon.SymbolBlock.imports(path + "-symbol.json", ["data0"],
                                     path + "-0000.params", ctx=mx.cpu())
    out = net2(x).asnumpy()
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


def test_block_repr_and_children():
    net = nn.Sequential()
    net.add(nn.Dense(3))
    assert "Dense" in repr(net)
    assert len(net) == 1
    assert isinstance(net[0], nn.Dense)


def test_constant_param():
    class Net(gluon.HybridBlock):
        def __init__(self, **kwargs):
            super().__init__(**kwargs)
            with self.name_scope():
                self.const = self.params.get_constant(
                    "const", nd.array([[1.0, 2.0]]))

        def hybrid_forward(self, F, x, const):
            return x + const

    net = Net()
    net.initialize(ctx=mx.cpu())
    out = net(nd.zeros((1, 2)))
    np.testing.assert_allclose(out.asnumpy(), [[1.0, 2.0]])


def test_split_and_load():
    data = nd.arange(0, 16).reshape(8, 2)
    parts = gluon.split_data(data, 4)
    assert len(parts) == 4 and parts[0].shape == (2, 2)
    loaded = gluon.split_and_load(data, [mx.cpu(), mx.cpu()])
    assert len(loaded) == 2


def test_clip_global_norm():
    arrays = [nd.ones((2, 2)) * 3, nd.ones((2,)) * 4]
    norm = gluon.clip_global_norm(arrays, 1.0)
    total = sum(float((a * a).sum().asscalar()) for a in arrays)
    assert abs(total - 1.0) < 1e-3


def test_model_zoo_pretrained_local_store(tmp_path, monkeypatch):
    """pretrained=True loads from the local model dir (model_store.py
    offline stance; reference: gluon/model_zoo/model_store.py)."""
    from mxnet_tpu.gluon.model_zoo import vision
    monkeypatch.setenv("MXNET_HOME", str(tmp_path))
    net = vision.get_model("squeezenet1_0", classes=10)
    net.initialize()
    x = mx.nd.array(np.random.RandomState(0).randn(
        1, 3, 64, 64).astype(np.float32))
    ref = net(x).asnumpy()
    mdir = tmp_path / "models"
    mdir.mkdir()
    net.save_parameters(str(mdir / "squeezenet1_0.params"))
    net2 = vision.get_model("squeezenet1_0", classes=10, pretrained=True)
    np.testing.assert_allclose(net2(x).asnumpy(), ref, rtol=1e-5)
    with pytest.raises(FileNotFoundError, match="no network egress"):
        vision.get_model("alexnet", pretrained=True)


def test_contrib_multi_head_attention():
    """gluon.contrib MultiHeadAttention: shape, hybridize parity,
    causality, gradient flow, cross-attention (flash-backed on TPU)."""
    from mxnet_tpu.gluon.contrib.nn import MultiHeadAttention
    rs = np.random.RandomState(0)
    mha = MultiHeadAttention(units=16, num_heads=4, causal=True)
    mha.initialize()
    x = mx.nd.array(rs.randn(2, 10, 16).astype(np.float32))
    eager = mha(x)
    assert eager.shape == (2, 10, 16)
    mha.hybridize()
    hybrid = mha(x)
    np.testing.assert_allclose(eager.asnumpy(), hybrid.asnumpy(),
                               rtol=1e-5, atol=1e-5)
    # causal: perturbing future positions leaves earlier outputs alone
    xp = x.asnumpy().copy()
    xp[:, 7:] += 10.0
    pert = mha(mx.nd.array(xp))
    np.testing.assert_allclose(hybrid.asnumpy()[:, :7],
                               pert.asnumpy()[:, :7],
                               rtol=1e-4, atol=1e-4)
    x.attach_grad()
    with autograd.record():
        out = mha(x)
    out.backward()
    assert np.isfinite(x.grad.asnumpy()).all()
    kv = mx.nd.array(rs.randn(2, 6, 16).astype(np.float32))
    cross = MultiHeadAttention(units=16, num_heads=2)
    cross.initialize()
    assert cross(x, kv, kv).shape == (2, 10, 16)


def test_space_to_depth_stem_expresses_conv7():
    """SpaceToDepthStem is a receptive-field superset of the classic
    7x7/s2 stem: embedding a 7x7 kernel at the documented tap mapping
    must reproduce the conv7 output exactly (the TPU MXU-utilization
    stem variant, model_zoo resnet stem='s2d')."""
    from mxnet_tpu.gluon.model_zoo.vision.resnet import SpaceToDepthStem
    rs = np.random.RandomState(0)
    C, O, H = 3, 5, 16
    w7 = rs.randn(O, C, 7, 7).astype(np.float32) * 0.3
    x = rs.randn(2, C, H, H).astype(np.float32)
    xm = nd.array(x)

    conv7 = nn.Conv2D(O, kernel_size=7, strides=2, padding=3,
                      use_bias=False)
    conv7.initialize()
    conv7(xm)
    conv7.weight.set_data(nd.array(w7))
    ref = conv7(xm).asnumpy()

    stem = SpaceToDepthStem(O)
    stem.initialize()
    stem(xm)
    w4 = np.zeros((O, 4 * C, 4, 4), np.float32)
    for a in range(2):
        for b in range(2):
            for c in range(C):
                k = a * 2 * C + b * C + c
                for dp in range(4):
                    for dq in range(4):
                        u, v = 2 * dp + a - 1, 2 * dq + b - 1
                        if 0 <= u < 7 and 0 <= v < 7:
                            w4[:, k, dp, dq] = w7[:, c, u, v]
    stem.conv.weight.set_data(nd.array(w4))
    out = stem(xm).asnumpy()
    assert out.shape == ref.shape
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_resnet_s2d_stem_trains():
    """stem='s2d' builds, matches the conv7 variant's output shape, and
    backprops through the whole net."""
    from mxnet_tpu.gluon.model_zoo import vision
    x = nd.array(np.random.RandomState(1).randn(2, 3, 64, 64)
                 .astype(np.float32))
    net_a = vision.get_model("resnet18_v1", classes=7)
    net_b = vision.get_model("resnet18_v1", classes=7, stem="s2d")
    for net in (net_a, net_b):
        net.initialize()
    ya, yb = net_a(x), net_b(x)
    assert ya.shape == yb.shape == (2, 7)
    with autograd.record():
        loss = nd.sum(nd.square(net_b(x)))
    loss.backward()
    g = net_b.collect_params()
    got = [p.grad() for p in g.values() if p.grad_req != "null"]
    assert any(float(nd.sum(nd.abs(gr)).asnumpy()) > 0 for gr in got)


def test_model_zoo_transformer_lm():
    """TransformerLM (zoo long-context family): eager == hybridized,
    (B,S)->(B,S,V), and a ParallelTrainer step runs (the path of the
    benchmark's LM cells)."""
    from mxnet_tpu.gluon.model_zoo.transformer import get_transformer_lm
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.data_parallel import ParallelTrainer
    rs = np.random.RandomState(0)
    x = nd.array(rs.randint(0, 40, (2, 24)).astype(np.float32))
    net = get_transformer_lm(vocab=40, dim=32, heads=4, layers=2,
                             max_seq=48)
    net.initialize()
    y_eager = net(x).asnumpy()
    assert y_eager.shape == (2, 24, 40)
    net.hybridize()
    y_hybrid = net(x).asnumpy()
    np.testing.assert_allclose(y_hybrid, y_eager, rtol=2e-5, atol=2e-5)
    # shorter sequence reuses the same positional table
    x2 = nd.array(rs.randint(0, 40, (2, 8)).astype(np.float32))
    assert net(x2).shape == (2, 8, 40)

    net2 = get_transformer_lm(vocab=40, dim=32, heads=4, layers=2,
                              max_seq=48)
    net2.initialize()
    tr = ParallelTrainer(net2, gluon.loss.SoftmaxCrossEntropyLoss(),
                         optimizer="sgd",
                         optimizer_params={"learning_rate": 0.05,
                                           "momentum": 0.9},
                         mesh=make_mesh({"dp": 2}, __import__("jax").devices()[:2]))
    yl = nd.array(rs.randint(0, 40, (2, 24)).astype(np.float32))
    losses = [float(np.asarray(tr.fit_batch(x, yl))) for _ in range(6)]
    assert losses[-1] < losses[0]
