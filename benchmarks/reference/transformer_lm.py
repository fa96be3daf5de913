"""Plain float32 reference of the decoder-only LM the `transformer_lm`
family builds: token embedding + learned positions, pre-norm blocks
(LayerNorm, causal multi-head attention without biases, LayerNorm,
ReLU feed-forward with biases), final LayerNorm, untied head without
bias; mean token cross-entropy; sgd with momentum.

Straight `jax.numpy`, no kernels, no cache, no import of the program.
Departures from OPT as published are the program's and are listed in the
configuration file's `assumed`.  `fp8` is the control's lower precision
(see `common`).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import dot, layer_norm, softmax_xent


def param_table(cfg):
    """Ordered ``name -> (shape, init)``; init is ("normal", std),
    ("ones",) or ("zeros",).  The order is the program's parameter order."""
    v, d, f = cfg["vocab_size"], cfg["hidden_size"], cfg["ffn_dim"]
    std = cfg.get("init_std", 0.02)
    t = {"pos": ((1, cfg["max_position_embeddings"], d), ("normal", std)),
         "embed": ((v, d), ("normal", std))}
    for i in range(cfg["num_hidden_layers"]):
        p = "h%d." % i
        t[p + "ln1_g"] = ((d,), ("ones",))
        t[p + "ln1_b"] = ((d,), ("zeros",))
        for w in ("wq", "wk", "wv", "wo"):
            t[p + w] = ((d, d), ("normal", std))
        t[p + "ln2_g"] = ((d,), ("ones",))
        t[p + "ln2_b"] = ((d,), ("zeros",))
        t[p + "w1"] = ((f, d), ("normal", std))
        t[p + "b1"] = ((f,), ("zeros",))
        t[p + "w2"] = ((d, f), ("normal", std))
        t[p + "b2"] = ((d,), ("zeros",))
    t["lnf_g"] = ((d,), ("ones",))
    t["lnf_b"] = ((d,), ("zeros",))
    t["head"] = ((v, d), ("normal", std))
    return t


def _block(p, pre, h, heads, fp8):
    b, s, d = h.shape
    a = layer_norm(h, p[pre + "ln1_g"], p[pre + "ln1_b"])

    def split(x):
        return x.reshape(b, s, heads, d // heads).transpose(0, 2, 1, 3)

    q = split(dot(a, p[pre + "wq"].T, fp8))
    k = split(dot(a, p[pre + "wk"].T, fp8))
    v = split(dot(a, p[pre + "wv"].T, fp8))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d // heads)
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask, scores, -jnp.inf)
    att = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)
    att = att.transpose(0, 2, 1, 3).reshape(b, s, d)
    h = h + dot(att, p[pre + "wo"].T, fp8)
    m = layer_norm(h, p[pre + "ln2_g"], p[pre + "ln2_b"])
    m = jnp.maximum(0.0, dot(m, p[pre + "w1"].T, fp8) + p[pre + "b1"])
    return h + dot(m, p[pre + "w2"].T, fp8) + p[pre + "b2"]


def logits(p, cfg, tokens, fp8=False):
    """(B, S) int tokens -> (B, S, vocab) float32 logits."""
    s = tokens.shape[1]
    h = jnp.take(p["embed"], tokens, axis=0) + p["pos"][:, :s]
    block = jax.checkpoint(_block, static_argnums=(1, 3, 4))
    for i in range(cfg["num_hidden_layers"]):
        h = block(p, "h%d." % i, h, cfg["num_attention_heads"], fp8)
    h = layer_norm(h, p["lnf_g"], p["lnf_b"])
    return dot(h, p["head"].T, fp8)


def loss_sum(p, cfg, tokens, labels, fp8=False):
    """Sum over rows of each row's mean token cross-entropy, so that
    blocks of rows add up to batch * (the program's mean loss)."""
    lg = logits(p, cfg, tokens, fp8)
    return jnp.sum(jnp.mean(softmax_xent(lg, labels.astype(jnp.int32)), -1))


# rows of a batch do not interact: the step may run in blocks of rows
ROWS_INDEPENDENT = True
