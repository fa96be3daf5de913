"""Fused RNN op + gluon.rnn tests.

Reference strategy: tests/python/unittest/test_operator.py RNN cases +
test_gluon_rnn.py — numpy-oracle forward checks, finite-difference
gradient checks, and a small LM convergence run.
"""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon import rnn as grnn
from mxnet_tpu.ops.rnn import rnn_param_size


def _sigmoid(v):
    return 1.0 / (1.0 + np.exp(-v))


def _np_lstm(x, par, h0, c0, H):
    """numpy oracle: single-layer unidirectional LSTM, gates i,f,g,o."""
    T, B, I = x.shape
    off = 0
    w_x = par[off:off + 4 * H * I].reshape(4 * H, I); off += 4 * H * I
    w_h = par[off:off + 4 * H * H].reshape(4 * H, H); off += 4 * H * H
    b_x = par[off:off + 4 * H]; off += 4 * H
    b_h = par[off:off + 4 * H]
    h, c = h0[0], c0[0]
    outs = []
    for t in range(T):
        pre = x[t] @ w_x.T + b_x + h @ w_h.T + b_h
        i, f, g, o = np.split(pre, 4, axis=-1)
        i, f, o = _sigmoid(i), _sigmoid(f), _sigmoid(o)
        g = np.tanh(g)
        c = f * c + i * g
        h = o * np.tanh(c)
        outs.append(h)
    return np.stack(outs), h, c


def _np_gru(x, par, h0, H):
    """numpy oracle: single-layer GRU, gates r,z,n, linear-before-reset."""
    T, B, I = x.shape
    off = 0
    w_x = par[off:off + 3 * H * I].reshape(3 * H, I); off += 3 * H * I
    w_h = par[off:off + 3 * H * H].reshape(3 * H, H); off += 3 * H * H
    b_x = par[off:off + 3 * H]; off += 3 * H
    b_h = par[off:off + 3 * H]
    h = h0[0]
    outs = []
    for t in range(T):
        xp = x[t] @ w_x.T + b_x
        rec = h @ w_h.T + b_h
        xr, xz, xn = np.split(xp, 3, axis=-1)
        hr, hz, hn = np.split(rec, 3, axis=-1)
        r = _sigmoid(xr + hr)
        z = _sigmoid(xz + hz)
        n = np.tanh(xn + r * hn)
        h = (1 - z) * n + z * h
        outs.append(h)
    return np.stack(outs), h


def test_lstm_op_matches_numpy():
    T, B, I, H = 4, 2, 3, 5
    rs = np.random.RandomState(1)
    n = rnn_param_size("lstm", I, H, 1, False)
    par = rs.randn(n).astype(np.float32) * 0.4
    x = rs.randn(T, B, I).astype(np.float32)
    h0 = rs.randn(1, B, H).astype(np.float32)
    c0 = rs.randn(1, B, H).astype(np.float32)
    out, hy, cy = mx.nd.RNN(
        mx.nd.array(x), mx.nd.array(par), mx.nd.array(h0),
        mx.nd.array(c0), state_size=H, num_layers=1, mode="lstm",
        state_outputs=True)
    ref_out, ref_h, ref_c = _np_lstm(x, par, h0, c0, H)
    np.testing.assert_allclose(out.asnumpy(), ref_out, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(hy.asnumpy()[0], ref_h, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(cy.asnumpy()[0], ref_c, rtol=1e-5,
                               atol=1e-5)


def test_gru_op_matches_numpy():
    T, B, I, H = 4, 2, 3, 5
    rs = np.random.RandomState(2)
    n = rnn_param_size("gru", I, H, 1, False)
    par = rs.randn(n).astype(np.float32) * 0.4
    x = rs.randn(T, B, I).astype(np.float32)
    h0 = rs.randn(1, B, H).astype(np.float32)
    out, hy = mx.nd.RNN(
        mx.nd.array(x), mx.nd.array(par), mx.nd.array(h0),
        state_size=H, num_layers=1, mode="gru", state_outputs=True)
    ref_out, ref_h = _np_gru(x, par, h0, H)
    np.testing.assert_allclose(out.asnumpy(), ref_out, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(hy.asnumpy()[0], ref_h, rtol=1e-5,
                               atol=1e-5)


def test_bidirectional_matches_flipped():
    """reverse direction == forward direction on time-flipped input."""
    T, B, I, H = 5, 2, 3, 4
    rs = np.random.RandomState(3)
    n = rnn_param_size("rnn_tanh", I, H, 1, True)
    par = rs.randn(n).astype(np.float32) * 0.4
    x = rs.randn(T, B, I).astype(np.float32)
    h0 = np.zeros((2, B, H), np.float32)
    out, _ = mx.nd.RNN(mx.nd.array(x), mx.nd.array(par), mx.nd.array(h0),
                       state_size=H, num_layers=1, mode="rnn_tanh",
                       bidirectional=True, state_outputs=True)
    out = out.asnumpy()
    # forward half with the fwd weights only
    g = H * (I + H + 2)
    fwd_par = np.concatenate([par[:H * I + H * H],
                              par[2 * (H * I + H * H):
                                  2 * (H * I + H * H) + 2 * H]])
    f_out, _ = mx.nd.RNN(mx.nd.array(x), mx.nd.array(fwd_par),
                         mx.nd.array(h0[:1]), state_size=H, num_layers=1,
                         mode="rnn_tanh", state_outputs=True)
    np.testing.assert_allclose(out[:, :, :H], f_out.asnumpy(), rtol=1e-5,
                               atol=1e-5)
    # reverse half = run rev weights on flipped input, flip back
    rev_par = np.concatenate(
        [par[H * I + H * H:2 * (H * I + H * H)],
         par[2 * (H * I + H * H) + 2 * H:]])
    r_out, _ = mx.nd.RNN(mx.nd.array(x[::-1].copy()), mx.nd.array(rev_par),
                         mx.nd.array(h0[:1]), state_size=H, num_layers=1,
                         mode="rnn_tanh", state_outputs=True)
    np.testing.assert_allclose(out[:, :, H:], r_out.asnumpy()[::-1],
                               rtol=1e-5, atol=1e-5)


def test_rnn_op_gradient_finite_difference():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.registry import get_op
    T, B, I, H = 3, 2, 2, 3
    rs = np.random.RandomState(4)
    n = rnn_param_size("lstm", I, H, 1, False)
    par = rs.randn(n).astype(np.float64) * 0.3
    x = rs.randn(T, B, I).astype(np.float64)
    h0 = np.zeros((1, B, H), np.float64)
    c0 = np.zeros((1, B, H), np.float64)
    op = get_op("RNN")
    key = jax.random.PRNGKey(0)

    def loss(par_):
        out = op.fn(key, jnp.asarray(x), par_, jnp.asarray(h0),
                    jnp.asarray(c0), state_size=H, num_layers=1,
                    mode="lstm", training=False)
        return jnp.sum(out[0] ** 2)

    with jax.enable_x64():
        g = jax.grad(loss)(jnp.asarray(par))
        eps = 1e-6
        for idx in rs.choice(n, size=8, replace=False):
            pp = par.copy(); pp[idx] += eps
            pm = par.copy(); pm[idx] -= eps
            num = (float(loss(jnp.asarray(pp))) -
                   float(loss(jnp.asarray(pm)))) / (2 * eps)
            assert abs(num - float(g[idx])) < 1e-4 * max(1, abs(num)), \
                (idx, num, float(g[idx]))


def test_layer_multilayer_shapes():
    lstm = grnn.LSTM(8, num_layers=2, bidirectional=True)
    lstm.initialize()
    x = mx.nd.array(np.random.randn(5, 3, 4).astype(np.float32))
    out = lstm(x)
    assert out.shape == (5, 3, 16)
    out, st = lstm(x, lstm.begin_state(3))
    assert out.shape == (5, 3, 16)
    assert [s.shape for s in st] == [(4, 3, 8), (4, 3, 8)]


def test_layer_ntc_layout():
    g = grnn.GRU(8, layout="NTC")
    g.initialize()
    x = mx.nd.array(np.random.randn(3, 5, 4).astype(np.float32))
    assert g(x).shape == (3, 5, 8)


def test_cells_unroll():
    x = mx.nd.array(np.random.randn(3, 5, 4).astype(np.float32))
    cell = grnn.LSTMCell(8, input_size=4)
    cell.initialize()
    outs, st = cell.unroll(5, x, layout="NTC")
    assert outs.shape == (3, 5, 8) and len(st) == 2
    seq = grnn.SequentialRNNCell()
    seq.add(grnn.LSTMCell(8, input_size=4))
    seq.add(grnn.GRUCell(6, input_size=8))
    seq.initialize()
    outs, st = seq.unroll(5, x, layout="NTC")
    assert outs.shape == (3, 5, 6) and len(st) == 3
    bi = grnn.BidirectionalCell(grnn.LSTMCell(8, input_size=4),
                                grnn.LSTMCell(8, input_size=4))
    bi.initialize()
    outs, st = bi.unroll(5, x, layout="NTC")
    assert outs.shape == (3, 5, 16) and len(st) == 4


def test_cell_unroll_matches_fused_layer():
    """Pack an LSTMCell's weights into the fused layout — outputs must
    agree (validates the packed-vector convention end to end)."""
    B, T, I, H = 2, 4, 3, 5
    cell = grnn.LSTMCell(H, input_size=I)
    cell.initialize()
    x = mx.nd.array(np.random.randn(T, B, I).astype(np.float32))
    outs, _ = cell.unroll(T, x, layout="TNC")
    par = np.concatenate([
        cell.i2h_weight.data().asnumpy().ravel(),
        cell.h2h_weight.data().asnumpy().ravel(),
        cell.i2h_bias.data().asnumpy(),
        cell.h2h_bias.data().asnumpy()])
    h0 = np.zeros((1, B, H), np.float32)
    fused, _, _ = mx.nd.RNN(
        x, mx.nd.array(par), mx.nd.array(h0), mx.nd.array(h0.copy()),
        state_size=H, num_layers=1, mode="lstm", state_outputs=True)
    np.testing.assert_allclose(outs.asnumpy(), fused.asnumpy(),
                               rtol=1e-5, atol=1e-5)


def test_lstm_lm_trains():
    """Tiny LSTM language model memorizes a repeating sequence
    (the BASELINE LSTM-LM config in miniature)."""
    V, E, H, T, B = 12, 8, 16, 6, 4

    class LM(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.emb = gluon.nn.Embedding(V, E)
                self.lstm = grnn.LSTM(H, input_size=E)
                self.out = gluon.nn.Dense(V, flatten=False)

        def hybrid_forward(self, F, x):
            h = self.emb(x)                    # (T,B,E)
            h = self.lstm(h)                   # (T,B,H)
            return self.out(h)                 # (T,B,V)

    net = LM()
    net.initialize(mx.init.Xavier())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.01})
    rs = np.random.RandomState(0)
    seq = rs.randint(0, V, size=(T + 1, B))
    x = mx.nd.array(seq[:-1].astype(np.float32))
    y = mx.nd.array(seq[1:].astype(np.float32))
    losses = []
    for _ in range(60):
        with mx.autograd.record():
            out = net(x)
            loss = loss_fn(out.reshape(-3, 0), y.reshape(-1))
        loss.backward()
        trainer.step(B)
        losses.append(float(loss.mean().asnumpy()))
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])
