"""Plain float32 reference of the decoder the `lfm2_moe` family builds
(LiquidAI LFM2-8B-A1B, `model_type` `lfm2_moe`), one chip's share of it.

Every layer ``l``: ``h = h + operator_l(rms(h)); h = h + ffn_l(rms(h))``
with ``rms(x) = x / sqrt(mean(x^2) + eps) * g``.

- ``conv`` operator: ``[B, C, X] = split3(x W_in)``; ``u = B * X``;
  ``c_t = sum_j w_j * u_(t-L+1+j)`` (depthwise, causal, zero before the
  sequence); ``y = (C * c) W_out``.
- ``full_attention`` operator: q, k, v projections to H, KV and KV heads;
  RMS norm over each q head and each k head; rotary positions
  (rotate-half); causal softmax attention, each key/value head serving
  H / KV query heads; output projection.  No biases.
- dense feed-forward (the leading `num_dense_layers`):
  ``W2(silu(W1 x) * W3 x)``.
- routed feed-forward: ``s = sigmoid(x W_r)`` over all the router's
  experts; the chosen are the top-k of ``s + b`` (`expert_bias`, fixed by
  the configuration); ``w_e = s_e / (sum of the chosen s + 1e-6)``; the
  result is the sum over the experts that are chosen AND held here
  (`num_experts` of them from `first_expert` on) of ``w_e *
  W2_e(silu(W1_e x) * W3_e x)``.  What the absent experts would add is
  left out.  No capacity, no dropped token, no auxiliary loss.
- final RMS norm; logits ``h E^T`` with ``E`` the embedding (tied);
  mean token cross-entropy.

Straight `jax.numpy`: no kernel, no sort, no grouped product, no import
of the program.  A held expert is applied to every token and masked by
the token's weight for it.  Attention is computed in blocks of query
rows under `jax.checkpoint` (at 8192 positions one row's scores are
8.6 GB).  `fp8` is the control's lower precision (`common`): it reaches
every contraction but the router's, which the configuration states as
float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import contraction, dot, softmax_xent

ATTENTION_QUERY_BLOCK = 1024


def expert_bias(cfg):
    """``b_e = s * (1 - 2 * ((7 e) mod E) / (E - 1))`` over the router's
    E experts, ``s`` the configuration's `expert_bias_scale`: fixed, not
    drawn from the seed, and uneven across every contiguous share."""
    n = cfg["num_routed_experts"]
    e = np.arange(n)
    return cfg.get("expert_bias_scale", 0.0) * (
        1.0 - 2.0 * ((7 * e) % n) / (n - 1))


def _head_dim(cfg):
    return cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]


def param_table(cfg):
    """Ordered ``name -> (shape, init)`` in the program's parameter
    order; `embed` is one leaf, used as the embedding and as the head."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    std = cfg.get("initializer_range", 0.02)
    hd = _head_dim(cfg)
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    normal, ones = ("normal", std), ("ones",)
    t = {"embed": ((v, d), normal)}
    for i, kind in enumerate(cfg["layer_types"]):
        p = "l%d." % i
        t[p + "operator_norm"] = ((d,), ones)
        if kind == "conv":
            t[p + "conv_in"] = ((3 * d, d), normal)
            t[p + "conv_w"] = ((d, cfg["conv_L_cache"]), normal)
            t[p + "conv_out"] = ((d, d), normal)
        elif kind == "full_attention":
            t[p + "wq"] = ((q, d), normal)
            t[p + "wk"] = ((kv, d), normal)
            t[p + "wv"] = ((kv, d), normal)
            t[p + "wo"] = ((d, q), normal)
            t[p + "q_norm"] = ((hd,), ones)
            t[p + "k_norm"] = ((hd,), ones)
        else:
            raise ValueError("unknown layer type %r" % kind)
        t[p + "ffn_norm"] = ((d,), ones)
        if i < cfg["num_dense_layers"]:
            f = cfg["intermediate_size"]
            t[p + "w1"] = ((f, d), normal)
            t[p + "w3"] = ((f, d), normal)
            t[p + "w2"] = ((d, f), normal)
        else:
            f, held = cfg["moe_intermediate_size"], cfg["num_experts"]
            t[p + "router"] = ((cfg["num_routed_experts"], d), normal)
            t[p + "expert_w1"] = ((held, d, f), normal)
            t[p + "expert_w3"] = ((held, d, f), normal)
            t[p + "expert_w2"] = ((held, f, d), normal)
    t["final_norm"] = ((d,), ones)
    return t


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _rotary(x, theta):
    """(B, heads, S, d), rotate-half, positions 0..S-1."""
    s, d = x.shape[-2], x.shape[-1]
    half = d // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.concatenate([np.cos(ang), np.cos(ang)], -1),
                      jnp.float32)
    sin = jnp.asarray(np.concatenate([np.sin(ang), np.sin(ang)], -1),
                      jnp.float32)
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def _attend(q, k, v, row0, fp8):
    """Query rows ``row0 ..`` of every head against all the keys up to
    their own position.  q ``(B, KV, G, R, d)``, k and v ``(B, KV, S,
    d)``: head ``(j, g)`` reads key/value head ``j``."""
    scores = contraction(
        lambda a, b: jnp.einsum("bjgqd,bjkd->bjgqk", a, b), q, k, fp8
    ) / math.sqrt(q.shape[-1])
    rows = row0 + jnp.arange(q.shape[3])
    mask = rows[:, None] >= jnp.arange(k.shape[2])[None, :]
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
    return contraction(
        lambda a, b: jnp.einsum("bjgqk,bjkd->bjgqd", a, b), probs, v, fp8)


def attention(p, pre, cfg, x, fp8):
    b, s, _ = x.shape
    heads, kv, hd = cfg["num_attention_heads"], \
        cfg["num_key_value_heads"], _head_dim(cfg)
    eps = cfg["norm_eps"]

    def split(y, n):
        return y.reshape(b, s, n, hd)

    q = rms(split(dot(x, p[pre + "wq"].T, fp8), heads), p[pre + "q_norm"],
            eps).transpose(0, 2, 1, 3)
    k = rms(split(dot(x, p[pre + "wk"].T, fp8), kv), p[pre + "k_norm"],
            eps).transpose(0, 2, 1, 3)
    v = split(dot(x, p[pre + "wv"].T, fp8), kv).transpose(0, 2, 1, 3)
    q = _rotary(q, cfg["rope_theta"]).reshape(b, kv, heads // kv, s, hd)
    k = _rotary(k, cfg["rope_theta"])
    blk = min(ATTENTION_QUERY_BLOCK, s)
    out = jnp.concatenate(
        [jax.checkpoint(functools.partial(_attend, row0=r, fp8=fp8))(
            q[:, :, :, r:r + blk], k[:, :, :r + blk], v[:, :, :r + blk])
         for r in range(0, s, blk)], axis=3)
    out = out.reshape(b, heads, s, hd).transpose(0, 2, 1, 3)
    return dot(out.reshape(b, s, heads * hd), p[pre + "wo"].T, fp8)


def short_conv(p, pre, cfg, x, fp8):
    d, taps = cfg["hidden_size"], cfg["conv_L_cache"]
    bcx = dot(x, p[pre + "conv_in"].T, fp8)
    b, c, xx = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    u = b * xx
    s = x.shape[1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    w = p[pre + "conv_w"]
    conv = sum(padded[:, j:j + s] * w[:, j] for j in range(taps))
    return dot(c * conv, p[pre + "conv_out"].T, fp8)


def gated(x, w1, w3, w2, fp8):
    return dot(jax.nn.silu(dot(x, w1, fp8)) * dot(x, w3, fp8), w2, fp8)


def route(cfg, x, router):
    """The chosen experts ``(.., k)`` of tokens *x* and their weights,
    in float32."""
    scores = jax.nn.sigmoid(jnp.matmul(x, router.T))
    _, chosen = jax.lax.top_k(
        scores + jnp.asarray(expert_bias(cfg), jnp.float32),
        cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, -1)
    if cfg.get("norm_topk_prob", True):
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-6)
    return chosen, weights * cfg.get("routed_scaling_factor", 1.0)


def routed(p, pre, cfg, x, fp8, first=None, held=None):
    """The part of the routed feed-forward that the experts ``first ..
    first + held - 1`` give (the configuration's own share by
    default; ``p[pre + "expert_w*"]`` hold exactly those)."""
    first = cfg.get("first_expert", 0) if first is None else first
    held = cfg["num_experts"] if held is None else held
    chosen, weights = route(cfg, x, p[pre + "router"])

    def one_expert(out, expert):
        e, w1, w3, w2 = expert
        w_e = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
        return out + w_e[..., None] * gated(x, w1, w3, w2, fp8), None

    # a loop over the held experts: a scan, so that the program holds
    # one expert's arithmetic and not `held` copies of it, checkpointed,
    # so that the backward pass keeps one expert's hidden states at a time
    out, _ = jax.lax.scan(
        jax.checkpoint(one_expert), jnp.zeros_like(x),
        (jnp.arange(held), p[pre + "expert_w1"], p[pre + "expert_w3"],
         p[pre + "expert_w2"]))
    return out


def _layer(p, h, i, cfg, fp8):
    pre, eps = "l%d." % i, cfg["norm_eps"]
    x = rms(h, p[pre + "operator_norm"], eps)
    if cfg["layer_types"][i] == "conv":
        h = h + short_conv(p, pre, cfg, x, fp8)
    else:
        h = h + attention(p, pre, cfg, x, fp8)
    x = rms(h, p[pre + "ffn_norm"], eps)
    if i < cfg["num_dense_layers"]:
        return h + gated(x, p[pre + "w1"].T, p[pre + "w3"].T,
                         p[pre + "w2"].T, fp8)
    return h + routed(p, pre, cfg, x, fp8)


def logits(p, cfg, tokens, fp8=False):
    """(B, S) int tokens -> (B, S, vocab held) float32 logits."""
    h = jnp.take(p["embed"], tokens, axis=0)
    for i in range(len(cfg["layer_types"])):
        h = jax.checkpoint(functools.partial(_layer, i=i, cfg=cfg, fp8=fp8)
                           )(p, h)
    h = rms(h, p["final_norm"], cfg["norm_eps"])
    return dot(h, p["embed"].T, fp8)


def loss_sum(p, cfg, tokens, labels, fp8=False):
    """Sum over rows of each row's mean token cross-entropy, so that
    blocks of rows add up to batch * (the program's mean loss)."""
    lg = logits(p, cfg, tokens, fp8)
    return jnp.sum(jnp.mean(softmax_xent(lg, labels.astype(jnp.int32)), -1))


def expert_counts(p, cfg, tokens):
    """Per routed layer, the tokens assigned to each of the router's
    experts at these parameters: ``(routed layers, E)`` int32, by the
    reference's own forward pass (what the program's counters count)."""
    h = jnp.take(p["embed"], tokens, axis=0)
    rows = []
    for i in range(len(cfg["layer_types"])):
        if i >= cfg["num_dense_layers"]:
            pre, eps = "l%d." % i, cfg["norm_eps"]
            x = rms(h, p[pre + "operator_norm"], eps)
            op = short_conv if cfg["layer_types"][i] == "conv" else attention
            mid = h + op(p, pre, cfg, x, False)
            chosen, _ = route(cfg, rms(mid, p[pre + "ffn_norm"], eps),
                              p[pre + "router"])
            rows.append(jnp.sum(
                chosen.reshape(-1, 1) == jnp.arange(cfg["num_routed_experts"]),
                axis=0, dtype=jnp.int32))
        h = _layer(p, h, i, cfg, False)
    return jnp.stack(rows)


# rows of a batch do not interact (dropless token-choice routing has no
# capacity that rows would compete for): the step may run in blocks of rows
ROWS_INDEPENDENT = True
