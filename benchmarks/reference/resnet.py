"""Plain float32 reference of ResNet v1 with bottleneck units (He et al.,
arXiv:1512.03385, Table 1) as the MXNet/Gluon zoo builds it: 7x7/2 stem,
3x3/2 max-pool, four stages of 1x1 -> 3x3 -> 1x1 units with the stride on
the leading 1x1, a projection shortcut where shape changes, batch norm
(batch statistics, eps 1e-5) after every convolution, ReLU after the
addition, global average pool, dense head; mean cross-entropy.

Straight `jax.numpy`/`lax.conv`, NCHW, no kernels, no import of the
program.  `fp8` is the control's lower precision (see `common`).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import contraction, dot, softmax_xent


def _units(cfg):
    """(stage, unit, in_ch, out_ch, stride, projects) for every unit."""
    in_ch = cfg["stem_channels"]
    for s, (n, width) in enumerate(zip(cfg["units"], cfg["stage_channels"])):
        for u in range(n):
            stride = 2 if (s > 0 and u == 0) else 1
            yield s, u, in_ch, width, stride, (stride != 1 or in_ch != width)
            in_ch = width


def param_table(cfg):
    """Ordered ``name -> (shape, init)`` of the trained leaves, in the
    program's parameter order (running statistics are not leaves: the
    training-mode forward does not read them)."""
    t = {}

    def conv(name, out_ch, in_ch, k):
        t[name] = ((out_ch, in_ch, k, k),
                   ("normal", math.sqrt(2.0 / (in_ch * k * k))))

    def bn(name, ch, scale=1.0):
        t[name + "_g"] = ((ch,), ("const", scale))
        t[name + "_b"] = ((ch,), ("zeros",))

    conv("stem", cfg["stem_channels"], 3, 7)
    bn("stem_bn", cfg["stem_channels"])
    for s, u, in_ch, out_ch, _, projects in _units(cfg):
        p = "s%du%d." % (s, u)
        inner = out_ch // 4
        for j, (o, i, k) in enumerate(((inner, in_ch, 1), (inner, inner, 3),
                                       (out_ch, inner, 1))):
            conv(p + "conv%d" % j, o, i, k)
            # the branch's last scale starts small where the configuration
            # says so: each unit then starts near the identity
            bn(p + "bn%d" % j, o,
               cfg.get("residual_gamma", 1.0) if j == 2 else 1.0)
        if projects:
            conv(p + "proj", out_ch, in_ch, 1)
            bn(p + "proj_bn", out_ch)
    t["fc_w"] = ((cfg["classes"], cfg["stage_channels"][-1]),
                 ("normal", 0.01))
    t["fc_b"] = ((cfg["classes"],), ("zeros",))
    return t


def _conv(x, w, stride, pad, fp8):
    def conv(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return contraction(conv, x, w, fp8)


def _bn(x, g, b, eps=1e-5):
    mu = jnp.mean(x, (0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mu), (0, 2, 3), keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * g.reshape(1, -1, 1, 1)
            + b.reshape(1, -1, 1, 1))


def _unit(p, pre, x, stride, projects, fp8):
    h = x
    for j, (s, pad) in enumerate(((stride, 0), (1, 1), (1, 0))):
        h = _bn(_conv(h, p[pre + "conv%d" % j], s, pad, fp8),
                p[pre + "bn%d_g" % j], p[pre + "bn%d_b" % j])
        if j != 2:
            h = jnp.maximum(0.0, h)
    if projects:
        x = _bn(_conv(x, p[pre + "proj"], stride, 0, fp8),
                p[pre + "proj_bn_g"], p[pre + "proj_bn_b"])
    return jnp.maximum(0.0, h + x)


def _stem(p, x, fp8):
    h = jnp.maximum(0.0, _bn(_conv(x, p["stem"], 2, 3, fp8),
                        p["stem_bn_g"], p["stem_bn_b"]))
    return jax.lax.reduce_window(
        h, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
        [(0, 0), (0, 0), (1, 1), (1, 1)])


def logits(p, cfg, images, fp8=False):
    """(N, 3, H, W) float32 images -> (N, classes) logits, training-mode
    batch norm over the whole of *images*."""
    h = jax.checkpoint(_stem, static_argnums=(2,))(p, images, fp8)
    unit = jax.checkpoint(_unit, static_argnums=(1, 3, 4, 5))
    for s, u, _, _, stride, projects in _units(cfg):
        h = unit(p, "s%du%d." % (s, u), h, stride, projects, fp8)
    h = jnp.mean(h, (2, 3))
    return dot(h, p["fc_w"].T, fp8) + p["fc_b"]


def loss_sum(p, cfg, images, labels, fp8=False):
    lg = logits(p, cfg, images, fp8)
    return jnp.sum(softmax_xent(lg, labels.astype(jnp.int32)))


# batch statistics couple the rows: the step takes the whole batch
ROWS_INDEPENDENT = False
