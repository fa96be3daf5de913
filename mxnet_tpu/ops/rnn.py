"""Fused RNN operator — `jax.lax.scan` over time on the MXU.

Reference capability: the single fused multi-layer bidirectional RNN op
(`src/operator/rnn-inl.h:46-109` — kRnnRelu/kRnnTanh/kLstm/kGru — and its
cuDNN path `cudnn_rnn-inl.h`).  The TPU-native design replaces the cuDNN
descriptor machinery with one `lax.scan` per (layer, direction): the
per-step cell is a pair of MXU matmuls + elementwise gate math that XLA
fuses; the scan compiles to a single XLA While loop, so the whole
multi-layer stack is one program with no per-timestep dispatch.

Weight layout matches the reference's packed-vector convention
(`rnn-inl.h` GetParamSize): all weights first — per layer, per direction:
W_i2h (G*H, in), W_h2h (G*H, H) — then all biases in the same order:
b_i2h (G*H,), b_h2h (G*H,).  Gate order: LSTM i,f,g,o; GRU r,z,n
(`src/operator/rnn_impl.h`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ._precision import matmul_precision
from .registry import register_op

_GATES = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}


def rnn_param_size(mode, input_size, state_size, num_layers, bidirectional):
    """Total packed parameter count (reference: rnn-inl.h GetRnnParamSize)."""
    g = _GATES[mode]
    dirs = 2 if bidirectional else 1
    size = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else state_size * dirs
        size += dirs * g * state_size * (in_sz + state_size + 2)
    return size


def _unpack(params, mode, input_size, state_size, num_layers, bidirectional):
    """Slice the packed vector into per-(layer, dir) weight/bias arrays."""
    g = _GATES[mode]
    dirs = 2 if bidirectional else 1
    h = state_size
    weights, biases = [], []
    off = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else h * dirs
        per_layer = []
        for _ in range(dirs):
            w_x = params[off:off + g * h * in_sz].reshape(g * h, in_sz)
            off += g * h * in_sz
            w_h = params[off:off + g * h * h].reshape(g * h, h)
            off += g * h * h
            per_layer.append((w_x, w_h))
        weights.append(per_layer)
    for layer in range(num_layers):
        per_layer = []
        for _ in range(dirs):
            b_x = params[off:off + g * h]
            off += g * h
            b_h = params[off:off + g * h]
            off += g * h
            per_layer.append((b_x, b_h))
        biases.append(per_layer)
    return weights, biases


def _proj(x, w):
    """x @ w.T at the framework's precision policy (f32 operands stay
    f32-exact on the MXU; an unannotated ``@`` multiplies them as bf16
    on TPU — consistency sweep, PR 21)."""
    return jnp.matmul(x, w.T, precision=matmul_precision(x.dtype, w.dtype))


def _scan_direction(mode, x_proj, w_h, b_h, h0, c0):
    """Scan one direction. x_proj: (T, B, G*H) input projections."""
    h = h0.shape[-1]

    if mode in ("rnn_relu", "rnn_tanh"):
        act = jnp.tanh if mode == "rnn_tanh" else \
            (lambda v: jnp.maximum(v, 0))

        def step(carry, xp):
            hy = carry[0]
            nh = act(xp + _proj(hy, w_h) + b_h)
            return (nh,), nh

        (hT,), out = jax.lax.scan(step, (h0,), x_proj)
        return out, hT, None

    if mode == "lstm":
        def step(carry, xp):
            hy, cy = carry
            pre = xp + _proj(hy, w_h) + b_h
            i, f, g, o = jnp.split(pre, 4, axis=-1)
            i = jax.nn.sigmoid(i)
            f = jax.nn.sigmoid(f)
            g = jnp.tanh(g)
            o = jax.nn.sigmoid(o)
            nc = f * cy + i * g
            nh = o * jnp.tanh(nc)
            return (nh, nc), nh

        (hT, cT), out = jax.lax.scan(step, (h0, c0), x_proj)
        return out, hT, cT

    if mode == "gru":
        def step(carry, xp):
            hy = carry[0]
            rec = _proj(hy, w_h) + b_h
            xr, xz, xn = jnp.split(xp, 3, axis=-1)
            hr, hz, hn = jnp.split(rec, 3, axis=-1)
            r = jax.nn.sigmoid(xr + hr)
            z = jax.nn.sigmoid(xz + hz)
            n = jnp.tanh(xn + r * hn)
            nh = (1 - z) * n + z * hy
            return (nh,), nh

        (hT,), out = jax.lax.scan(step, (h0,), x_proj)
        return out, hT, None

    raise ValueError("unknown RNN mode %r" % mode)


def _rnn_inputs(params):
    if params.get("mode", "lstm") == "lstm":
        return ("data", "parameters", "state", "state_cell")
    return ("data", "parameters", "state")


@register_op("RNN", needs_rng=True,
             input_names=("data", "parameters", "state", "state_cell"),
             num_outputs=lambda p: 3 if p.get("mode", "lstm") == "lstm"
                 else 2,
             num_visible_outputs=lambda p:
                 (3 if p.get("mode", "lstm") == "lstm" else 2)
                 if p.get("state_outputs") else 1)
def _rnn(rng, data, parameters, *rest, state_size=0, num_layers=1,
         bidirectional=False, mode="lstm", p=0.0, state_outputs=False,
         lstm_state_clip_min=None, lstm_state_clip_max=None,
         lstm_state_clip_nan=False, training=True):
    """data: (T, B, input) sequence-major; optional state (L*dirs, B, H)
    and, for lstm, state_cell (zeros when omitted).
    Returns (output, hy[, cy])."""
    mode = str(mode)
    dirs = 2 if bidirectional else 1
    h = state_size
    in_size = data.shape[2]
    weights, biases = _unpack(parameters.astype(data.dtype), mode, in_size,
                              h, num_layers, bidirectional)
    sshape = (num_layers * dirs, data.shape[1], h)
    state = rest[0] if rest else jnp.zeros(sshape, data.dtype)
    if mode == "lstm":
        cell0 = rest[1] if len(rest) > 1 else jnp.zeros(sshape, data.dtype)
    else:
        cell0 = None

    x = data
    h_out, c_out = [], []
    for layer in range(num_layers):
        outs = []
        for d in range(dirs):
            w_x, w_h = weights[layer][d]
            b_x, b_h = biases[layer][d]
            idx = layer * dirs + d
            h0 = state[idx]
            c0 = cell0[idx] if cell0 is not None else None
            xs = jnp.flip(x, 0) if d == 1 else x
            # one big (T*B, in) @ (in, G*H) matmul outside the scan —
            # keeps the MXU busy with the large GEMM; only the (B, H)
            # recurrent GEMM remains sequential
            x_proj = _proj(xs, w_x) + b_x
            out, hT, cT = _scan_direction(mode, x_proj, w_h, b_h, h0, c0)
            if d == 1:
                out = jnp.flip(out, 0)
            outs.append(out)
            h_out.append(hT)
            if cT is not None:
                c_out.append(cT)
        x = outs[0] if dirs == 1 else jnp.concatenate(outs, axis=-1)
        if training and p > 0.0 and layer < num_layers - 1:
            keep = 1.0 - p
            mask = jax.random.bernoulli(
                jax.random.fold_in(rng, layer), keep,
                x.shape).astype(x.dtype) / keep
            x = x * mask

    hy = jnp.stack(h_out, 0)
    if mode == "lstm":
        cy = jnp.stack(c_out, 0)
        if lstm_state_clip_min is not None and \
                lstm_state_clip_max is not None:
            if lstm_state_clip_nan:
                # reference semantics: NaN cell states are sanitized to
                # the clip bounds rather than propagated
                cy = jnp.nan_to_num(cy, nan=lstm_state_clip_max)
            cy = jnp.clip(cy, lstm_state_clip_min, lstm_state_clip_max)
        return x, hy, cy
    return x, hy


from .registry import get_op as _get_op  # noqa: E402

# non-LSTM modes consume no cell state; without this a symbolic
# sym.RNN(...) with 3 inputs would auto-create a phantom trainable
# "state_cell" variable (batch-size-dependent shape, saved to
# checkpoints) — same pattern as Convolution dropping "bias"
_get_op("RNN").active_inputs = _rnn_inputs
