"""Plain float32 reference of the decoder the `sdar_moe` family builds
(JetLM's SDAR-30B-A3B-Chat, `model_type` `sdar_moe`), one chip's share of
it, and of the step that trains it: masked diffusion over blocks (BD3-LM,
arXiv:2503.09573, Algorithm 1 and section 3, linear schedule ``alpha_t = 1 -
t``; SDAR, arXiv:2510.06303, trains with it).  H query heads over KV
key/value heads of width hd, no bias anywhere.

A sequence of ``L`` clean ids ``x`` in blocks of ``B``; ``b(i) = i // B``.

- **Noise** (made with the batch, `benchmarks/models/sdar_moe.py`): ``t_k ~
  U[t_min, 1]`` a block; ``m_i ~ Bernoulli(t_b(i))``; the noised copy
  ``x~_i = MASK if m_i else x_i``; the weight ``w_i = m_i / t_b(i)``.
- **Input**: ids ``z = [x ; x~]``, ``2L`` positions, position ``p(j) = j
  mod L``; ``h = E[z]``.
- **Layer** ``l`` (all alike): ``h = h + attn_l(rms(h)); h = h +
  ffn_l(rms(h))`` with ``rms(x) = x / sqrt(mean(x^2) + eps) * g``.
  ``attn``: ``q = rope(rms_head(u Wq))`` (H x hd), ``k = rope(rms_head(u
  Wk))``, ``v = u Wv`` (KV x hd); query head ``i`` reads key/value head ``i
  // (H / KV)``; ``rope`` turns the pair ``(y[f], y[f + hd/2])`` by ``p(j) *
  theta^(-2f / hd)``; ``a_j = sum over {s : M[j, s]} softmax_s(q_j . k_s /
  sqrt(hd)) v_s``; the heads joined, ``Wo``.
  ``ffn``: ``r = softmax(u' Wr)`` over all `router_experts` outputs, in
  float32; ``T`` its top-k; ``c_e = r_e / sum_T r`` (`norm_topk_prob`); the
  result is the sum over the experts that are chosen AND held here
  (`num_experts` of them from `first_expert` on) of ``c_e W2_e(silu(W1_e u')
  * W3_e u')``.  What the absent experts would add is left out.  No bias, no
  scaling, no shared expert, no capacity, no dropped token, no auxiliary loss.
- **The mask.**  With ``n(j) = j >= L`` and ``b(j) = (j mod L) // B``:
  ``M[j, s] = (not n(s) and not n(j) and b(s) <= b(j))`` (clean sees clean,
  block-causally) ``or (not n(s) and n(j) and b(s) < b(j))`` (noised sees
  the clean copy of earlier blocks) ``or (n(s) and n(j) and b(s) == b(j))``
  (noised sees its own noised block, both ways).  Every query sees a key.
- **Head and objective**: ``logits_i = rms(h_{L+i}, gf) W_head``, ``i < L``,
  over the rows held (the clean half gets no logits: it is context only);
  ``loss = (1 / L) sum_i w_i * (-log softmax(logits_i)[x_i])``.  Prediction
  is of the token at the masked position itself (no shift).

Straight `jax.numpy`: no kernel, no sort but `lax.top_k`'s, no grouped
product, no import of the program.  Attention is computed in blocks of
`ATTENTION_QUERY_BLOCK` query rows under `jax.checkpoint`, one block at a
time, the mask built from its definition for those rows alone (at 16384
positions and 32 heads a block of 256 rows holds 0.54 GB of scores; no array
has two axes of ``2L``).  `fp8` is the control's lower precision (`common`):
it reaches every contraction but the router's.  *sight* is what the mask
lets a query see: ``"block_diffusion"`` (the model's), and two controls that
have to come out as not correct, ``"causal"`` (a causal mask over the ``2L``
positions) and ``"block_diagonal"`` (the second term taken away: a noised
query sees its own noised block and nothing of the clean copy).

Departures from the source, each under the configuration's `assumed`: the
block length, the noise law and ``t_min`` (the config has neither); the
``MASK`` id (the source's lies outside an eighth of the vocabulary); the
per-head q/k norm and half-split rotary pairs (the config has no key for
either); seeded weights; sgd with momentum for the source's optimizer.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import contraction, dot

ATTENTION_QUERY_BLOCK = 256
SIGHTS = ("block_diffusion", "causal", "block_diagonal")


def check_supported(cfg):
    """Raise for a configuration whose equations are not the ones above."""
    if cfg.get("mlp_only_layers") or cfg.get("decoder_sparse_step", 1) != 1:
        raise ValueError("every layer's feed-forward is routed")
    if cfg.get("use_sliding_window") or cfg.get("sliding_window"):
        raise ValueError("sliding windows are not built")
    if cfg.get("rope_scaling"):
        raise ValueError("scaled rotary positions are not built")
    if cfg.get("attention_bias"):
        raise ValueError("attention biases are not built")
    if cfg["num_attention_heads"] % cfg["num_key_value_heads"]:
        raise ValueError("query heads in whole groups a key/value head")
    train = cfg["train"]
    if train["sequence_length"] % train["diffusion_block"]:
        raise ValueError("a sequence is whole blocks")
    if not 0 <= train["mask_token_id"] < cfg["vocab_size"]:
        raise ValueError("the MASK id is a row of the vocabulary held here")


def param_table(cfg):
    """Ordered ``name -> (shape, init)`` in the program's parameter order:
    the embedding and the head first (the model's own leaves), then the
    layers."""
    check_supported(cfg)
    d, v, hd = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    std = cfg.get("initializer_range", 0.02)
    normal, ones = ("normal", std), ("ones",)
    # the per-head norms' scales may start above one (`assumed`, weights)
    head_scale = ("const", cfg.get("qk_norm_initializer", 1.0))
    f, held = cfg["moe_intermediate_size"], cfg["num_experts"]
    # the embedding may start at a scale of its own, and the ``MASK`` row
    # at another (`assumed`, weights): a standard deviation a row, which
    # `common.make_leaf` multiplies into the rows' normal draws
    rows = np.full((v, 1), cfg.get("embedding_initializer_range", std),
                   np.float32)
    if "mask_embedding_initializer_range" in cfg:
        rows[cfg["train"]["mask_token_id"]] = \
            cfg["mask_embedding_initializer_range"]
    t = {"embed": ((v, d), ("normal", rows))}
    if not cfg.get("tie_word_embeddings", False):
        t["head"] = ((v, d), normal)
    for i in range(cfg["num_hidden_layers"]):
        p = "l%d." % i
        t[p + "attn_norm"] = ((d,), ones)
        t[p + "wq"] = ((q, d), normal)
        t[p + "wk"] = ((kv, d), normal)
        t[p + "wv"] = ((kv, d), normal)
        t[p + "wo"] = ((d, q), normal)
        t[p + "q_norm"] = ((hd,), head_scale)
        t[p + "k_norm"] = ((hd,), head_scale)
        t[p + "ffn_norm"] = ((d,), ones)
        t[p + "router"] = ((cfg["router_experts"], d), normal)
        t[p + "expert_w1"] = ((held, d, f), normal)
        t[p + "expert_w3"] = ((held, d, f), normal)
        t[p + "expert_w2"] = ((held, f, d), normal)
    t["final_norm"] = ((d,), ones)
    return t


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def rope(x, positions, theta):
    """(B, heads, S, hd) with *positions* (S,): the pair ``(x[f], x[f +
    hd/2])`` turns by ``positions * theta^(-2f / hd)``."""
    half = x.shape[-1] // 2
    inv = 1.0 / (float(theta) ** (np.arange(half, dtype=np.float64) / half))
    ang = jnp.asarray(positions, jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)                   # (S, half)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def sees(q_pos, k_pos, half, block, sight="block_diffusion"):
    """``M[j, s]`` for query positions *q_pos* and key positions *k_pos*
    (they broadcast) of ``2 * half`` positions in blocks of *block*: the
    three terms above, or what a control puts in their place."""
    if sight not in SIGHTS:
        raise ValueError("sight %r is not one of %s" % (sight, SIGHTS))
    if sight == "causal":
        return k_pos <= q_pos
    qn, kn = q_pos >= half, k_pos >= half
    qb, kb = (q_pos % half) // block, (k_pos % half) // block
    seen = (~kn & ~qn & (kb <= qb)) | (kn & qn & (kb == qb))
    if sight == "block_diagonal":
        return seen
    return seen | (~kn & qn & (kb < qb))


def _attend(q, row0, k, v, half, block, sight, fp8):
    """Query rows ``row0 ..`` of the ``2 * half``: q (B, KV, G, R, hd)
    against k, v (B, KV, S, hd) -> (B, KV, G, R, hd)."""
    rows = row0 + jnp.arange(q.shape[3])
    seen = sees(rows[:, None], jnp.arange(k.shape[2])[None, :], half, block,
                sight)
    att = contraction(
        lambda a, b: jnp.einsum("bjgqd,bjkd->bjgqk", a, b), q, k, fp8
    ) / math.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(seen, att, -jnp.inf), -1)
    return contraction(
        lambda a, b: jnp.einsum("bjgqk,bjkd->bjgqd", a, b), probs, v, fp8)


def attention(p, pre, cfg, x, fp8=False, sight="block_diffusion"):
    """``attn(x)`` for x (B, 2L, d), a clean copy then a noised one."""
    b, s, _ = x.shape
    heads, kv, hd = cfg["num_attention_heads"], \
        cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    half, block = s // 2, cfg["train"]["diffusion_block"]
    positions = jnp.arange(s) % half

    def split(y, n):
        return y.reshape(b, s, n, hd)

    q = rms(split(dot(x, p[pre + "wq"].T, fp8), heads), p[pre + "q_norm"],
            eps).transpose(0, 2, 1, 3)
    k = rms(split(dot(x, p[pre + "wk"].T, fp8), kv), p[pre + "k_norm"],
            eps).transpose(0, 2, 1, 3)
    v = split(dot(x, p[pre + "wv"].T, fp8), kv).transpose(0, 2, 1, 3)
    q, k = rope(q, positions, theta), rope(k, positions, theta)

    blk = ATTENTION_QUERY_BLOCK if s % ATTENTION_QUERY_BLOCK == 0 else s
    n = s // blk
    rows = jax.checkpoint(functools.partial(
        _attend, k=k, v=v, half=half, block=block, sight=sight, fp8=fp8))
    out = jax.lax.map(
        lambda at: rows(*at),
        (q.reshape(b, kv, heads // kv, n, blk, hd).transpose(3, 0, 1, 2, 4, 5),
         jnp.arange(0, s, blk)))
    out = out.transpose(1, 2, 3, 0, 4, 5).reshape(b, heads, s, hd)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, heads * hd)
    return dot(out, p[pre + "wo"].T, fp8)


def gated(x, w1, w3, w2, fp8):
    return dot(jax.nn.silu(dot(x, w1, fp8)) * dot(x, w3, fp8), w2, fp8)


def route(cfg, x, router):
    """The chosen experts ``(.., k)`` of tokens *x* and their weights, in
    float32: a softmax over all the router's outputs, its top-k,
    renormalised."""
    gates = jax.nn.softmax(jnp.matmul(x, router.T), -1)
    weights, chosen = jax.lax.top_k(gates, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    return chosen, weights


def routed(p, pre, cfg, x, fp8=False, first=None, held=None):
    """The part of the routed feed-forward that the experts ``first ..
    first + held - 1`` give (the configuration's own share by default;
    ``p[pre + "expert_w*"]`` hold exactly those)."""
    first = cfg.get("first_expert", 0) if first is None else first
    held = cfg["num_experts"] if held is None else held
    chosen, weights = route(cfg, x, p[pre + "router"])

    def one_expert(out, expert):
        e, w1, w3, w2 = expert
        w_e = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
        return out + w_e[..., None] * gated(x, w1, w3, w2, fp8), None

    out, _ = jax.lax.scan(
        jax.checkpoint(one_expert), jnp.zeros_like(x),
        (jnp.arange(held), p[pre + "expert_w1"], p[pre + "expert_w3"],
         p[pre + "expert_w2"]))
    return out


def _layer(p, h, i, cfg, fp8, sight):
    pre, eps = "l%d." % i, cfg["rms_norm_eps"]
    h = h + attention(p, pre, cfg, rms(h, p[pre + "attn_norm"], eps), fp8,
                      sight)
    return h + routed(p, pre, cfg, rms(h, p[pre + "ffn_norm"], eps), fp8)


def logits(p, cfg, tokens, fp8=False, sight="block_diffusion"):
    """(B, 2L) int tokens, a clean copy then a noised one -> the noised
    half's (B, L, vocab held) float32 logits."""
    h = jnp.take(p["embed"], tokens, axis=0)
    for i in range(cfg["num_hidden_layers"]):
        h = jax.checkpoint(functools.partial(
            _layer, i=i, cfg=cfg, fp8=fp8, sight=sight))(p, h)
    h = rms(h[:, tokens.shape[1] // 2:], p["final_norm"],
            cfg["rms_norm_eps"])
    return dot(h, p.get("head", p["embed"]).T, fp8)


def loss_sum(p, cfg, tokens, labels, fp8=False, sight="block_diffusion"):
    """Sum over rows of ``(1 / L) sum_i w_i * -log softmax(logits_i)[x_i]``:
    *labels* (B, 2, L) float32 hold the clean ids and the weights."""
    ids, w = labels[:, 0].astype(jnp.int32), labels[:, 1]
    logp = jax.nn.log_softmax(logits(p, cfg, tokens, fp8, sight), -1)
    nll = -jnp.take_along_axis(logp, ids[..., None], -1)[..., 0]
    return jnp.sum(jnp.sum(w * nll, -1) / ids.shape[1])


def expert_counts(p, cfg, tokens):
    """Per layer, the positions assigned to each of the router's outputs at
    these parameters: ``(layers, E)`` int32, by the reference's own forward
    pass (what the program's counters count)."""
    h = jnp.take(p["embed"], tokens, axis=0)
    rows = []
    for i in range(cfg["num_hidden_layers"]):
        pre, eps = "l%d." % i, cfg["rms_norm_eps"]
        h = h + attention(p, pre, cfg, rms(h, p[pre + "attn_norm"], eps))
        x = rms(h, p[pre + "ffn_norm"], eps)
        chosen, _ = route(cfg, x, p[pre + "router"])
        rows.append(jnp.sum(
            chosen.reshape(-1, 1) == jnp.arange(cfg["router_experts"]),
            axis=0, dtype=jnp.int32))
        h = h + routed(p, pre, cfg, x)
    return jnp.stack(rows)


# rows of a batch do not interact: the step may run in blocks of rows
ROWS_INDEPENDENT = True
