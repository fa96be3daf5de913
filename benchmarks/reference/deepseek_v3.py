"""Plain float32 reference of the decoder the `deepseek_v3` family builds
(kakaocorp Kanana-2-30B-A3B, `model_type` `deepseek_v3`), one chip's share
of it.  H heads, T tokens, no bias anywhere.

Every layer ``l``: ``h = h + attn_l(rms(h)); h = h + ffn_l(rms(h))`` with
``rms(x) = x / sqrt(mean(x^2) + eps) * g``.

- ``attn`` (multi-head latent attention, the expanded form the source
  trains in): ``q = x Wq`` -> (T, H, nope + rope), split ``q_nope |
  q_rope`` (`q_lora_rank` is null: no query compression).  ``x Wkv_a`` ->
  (T, rank + rope), split ``c | k_rope``: ONE ``k_rope`` for all heads.
  ``rms(c, g_kv) Wkv_b`` -> (T, H, nope + v), split ``k_nope | v``.
  Rotary positions on ``q_rope`` and ``k_rope`` over the rope dims,
  INTERLEAVED (`rope_interleave`): the pair ``(x[2i], x[2i+1])`` turns by
  ``pos * theta^(-2i / rope)``.  The source de-interleaves each vector and
  then rotates halves; that lands the same fixed permutation on q and on
  k, so the scores, and everything after them, are the same: this file
  writes the pairs (`rotary_source_order` is the source's way, for the
  test that says so).  ``k_h = [k_nope_h | k_rope]``, ``q_h = [q_nope_h |
  q_rope_h]``, scores ``q_h . k_h / sqrt(nope + rope)``, causal softmax,
  times ``v_h`` (v wide), heads joined, ``Wo``.  The absorbed form is a
  decode matter and is not here.
- dense feed-forward (the leading `first_k_dense_replace` layers):
  ``W2(silu(W1 x) * W3 x)`` at `intermediate_size`.
- every other layer: ``shared(x) + routed(x)``.  ``shared`` is the same
  gated MLP at width `n_shared_experts` x `moe_intermediate_size` (the
  source builds its shared experts as one MLP of that width).  ``routed``:
  ``s = sigmoid(x W_r)`` over all `router_experts` outputs, in float32; the
  chosen are the top-k of ``s + b`` (`topk_method` `noaux_tc`; ``b`` the
  source's `e_score_correction_bias`, here `expert_bias`, fixed by the
  configuration; `n_group` = `topk_group` = 1: no group limit); ``w_e =
  s_e / (sum of the chosen s + 1e-20)`` (`norm_topk_prob`) times
  `routed_scaling_factor`; the result is the sum over the experts that are
  chosen AND held here (`n_routed_experts` of them from `first_expert` on)
  of ``w_e * W2_e(silu(W1_e x) * W3_e x)``.  What the absent experts would
  add is left out.  No capacity, no dropped token, no auxiliary loss.
  **Departure of the program, not of this file:** `_contrib_RoutedExperts`
  adds 1e-6 to that denominator where the source adds 1e-20 (a relative
  3e-7 on a sum near 3).
- final RMS norm; logits against a head matrix of its own
  (`tie_word_embeddings` false) over the rows of the vocabulary held here;
  mean token cross-entropy.

Straight `jax.numpy`: no kernel, no sort, no grouped product, no import
of the program.  A held expert is applied to every token and masked by
the token's weight for it.  Attention is computed in blocks of query rows
under `jax.checkpoint`, one block at a time (at 8192 positions and 32
heads one block's scores are 1.07 GB).  `fp8` is the control's lower precision (`common`):
it reaches every contraction but the router's, which the configuration
states as float32.
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
    E outputs, ``s`` the configuration's `expert_bias_scale`: fixed, not
    drawn from the seed, and uneven across every contiguous share."""
    n = cfg["router_experts"]
    e = np.arange(n)
    return cfg.get("expert_bias_scale", 0.0) * (
        1.0 - 2.0 * ((7 * e) % n) / (n - 1))


def check_supported(cfg):
    """Raise for a `deepseek_v3` configuration whose equations are not the
    ones above."""
    if cfg.get("q_lora_rank") is not None:
        raise ValueError("query compression (q_lora_rank) is not built")
    if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        raise ValueError("group-limited routing (n_group > 1) is not built")
    if cfg.get("rope_scaling") is not None:
        raise ValueError("scaled rotary positions (rope_scaling) are not "
                         "built")
    if not cfg.get("rope_interleave", True):
        raise ValueError("this family's rotary pairs are interleaved")
    if cfg.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("the router scores with a sigmoid")
    if cfg.get("moe_layer_freq", 1) != 1:
        raise ValueError("every layer after the dense ones is routed")


def param_table(cfg):
    """Ordered ``name -> (shape, init)`` in the program's parameter
    order: the embedding and the head first (the model's own leaves), then
    the layers."""
    check_supported(cfg)
    d, v, heads = cfg["hidden_size"], cfg["vocab_size"], \
        cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, vd = cfg["kv_lora_rank"], cfg["v_head_dim"]
    std = cfg.get("initializer_range", 0.02)
    normal, ones = ("normal", std), ("ones",)
    t = {"embed": ((v, d), normal)}
    if not cfg.get("tie_word_embeddings", False):
        t["head"] = ((v, d), normal)
    for i in range(cfg["num_hidden_layers"]):
        p = "l%d." % i
        t[p + "attn_norm"] = ((d,), ones)
        t[p + "wq"] = ((heads * (nope + rope), d), normal)
        t[p + "wkv_a"] = ((rank + rope, d), normal)
        t[p + "kv_norm"] = ((rank,), ones)
        t[p + "wkv_b"] = ((heads * (nope + vd), rank), normal)
        t[p + "wo"] = ((d, heads * vd), normal)
        t[p + "ffn_norm"] = ((d,), ones)
        if i < cfg["first_k_dense_replace"]:
            f = cfg["intermediate_size"]
            t[p + "w1"] = ((f, d), normal)
            t[p + "w3"] = ((f, d), normal)
            t[p + "w2"] = ((d, f), normal)
        else:
            f, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
            if cfg["n_shared_experts"]:
                s = cfg["n_shared_experts"] * f
                t[p + "shared_w1"] = ((s, d), normal)
                t[p + "shared_w3"] = ((s, d), normal)
                t[p + "shared_w2"] = ((d, s), normal)
            t[p + "router"] = ((cfg["router_experts"], d), normal)
            t[p + "expert_w1"] = ((held, d, f), normal)
            t[p + "expert_w3"] = ((held, d, f), normal)
            t[p + "expert_w2"] = ((held, f, d), normal)
    t["final_norm"] = ((d,), ones)
    return t


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _angles(seq, dim, theta):
    """``(seq, dim / 2)`` cos and sin of ``pos * theta^(-2i / dim)``."""
    inv = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    ang = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
    return jnp.asarray(np.cos(ang), jnp.float32), \
        jnp.asarray(np.sin(ang), jnp.float32)


def rotary(x, theta):
    """(.., S, dim), positions 0..S-1, interleaved: the pair ``(x[2i],
    x[2i+1])`` turns by the i-th angle."""
    cos, sin = _angles(x.shape[-2], x.shape[-1], theta)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     -1).reshape(x.shape)


def rotary_source_order(x, theta):
    """The source's way to the same scores: de-interleave (evens first,
    then odds), then rotate halves.  Equal to `rotary` followed by that
    same de-interleaving."""
    cos, sin = _angles(x.shape[-2], x.shape[-1], theta)
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * jnp.concatenate([cos, cos], -1) \
        + rot * jnp.concatenate([sin, sin], -1)


def _attend(q, row0, k, v, fp8):
    """Query rows ``row0 ..`` of every head against the keys up to their
    own position (all the keys are multiplied, the later ones masked).  q
    ``(B, H, R, nope + rope)``, k ``(B, H, S, nope + rope)``, v ``(B, H,
    S, v)``."""
    scores = contraction(
        lambda a, b: jnp.einsum("bhqd,bhkd->bhqk", a, b), q, k, fp8
    ) / math.sqrt(q.shape[-1])
    rows = row0 + jnp.arange(q.shape[2])
    mask = rows[:, None] >= jnp.arange(k.shape[2])[None, :]
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
    return contraction(
        lambda a, b: jnp.einsum("bhqk,bhkd->bhqd", a, b), probs, v, fp8)


def attention(p, pre, cfg, x, fp8):
    b, s, _ = x.shape
    heads, theta = cfg["num_attention_heads"], cfg["rope_theta"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, vd = cfg["kv_lora_rank"], cfg["v_head_dim"]

    q = dot(x, p[pre + "wq"].T, fp8).reshape(b, s, heads, nope + rope)
    q = q.transpose(0, 2, 1, 3)
    ckv = dot(x, p[pre + "wkv_a"].T, fp8)
    c, k_rope = ckv[..., :rank], ckv[..., rank:]
    kv = dot(rms(c, p[pre + "kv_norm"], cfg["rms_norm_eps"]),
             p[pre + "wkv_b"].T, fp8).reshape(b, s, heads, nope + vd)
    kv = kv.transpose(0, 2, 1, 3)
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], theta)], -1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(rotary(k_rope, theta)[:, None],
                          (b, heads, s, rope))], -1)
    v = kv[..., nope:]
    # blocks of query rows, one after another: a loop (`lax.map`) and not
    # eight unrolled copies a layer, which took the chip's host 150 s to
    # compile; each block's scores are computed again in the backward pass
    blk = ATTENTION_QUERY_BLOCK if s % ATTENTION_QUERY_BLOCK == 0 else s
    block = jax.checkpoint(functools.partial(_attend, k=k, v=v, fp8=fp8))
    out = jax.lax.map(
        lambda at: block(*at),
        (q.reshape(b, heads, s // blk, blk, nope + rope
                   ).transpose(2, 0, 1, 3, 4),
         jnp.arange(0, s, blk)))
    out = out.transpose(1, 2, 0, 3, 4).reshape(b, heads, s, vd)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, heads * vd)
    return dot(out, p[pre + "wo"].T, fp8)


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
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return chosen, weights * cfg.get("routed_scaling_factor", 1.0)


def routed(p, pre, cfg, x, fp8, first=None, held=None):
    """The part of the routed feed-forward that the experts ``first ..
    first + held - 1`` give (the configuration's own share by default;
    ``p[pre + "expert_w*"]`` hold exactly those)."""
    first = cfg.get("first_expert", 0) if first is None else first
    held = cfg["n_routed_experts"] if held is None else held
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


def shared(p, pre, cfg, x, fp8):
    """What every chip computes alike: the shared experts, one gated MLP;
    zero for a configuration without them."""
    if not cfg["n_shared_experts"]:
        return jnp.zeros_like(x)
    return gated(x, p[pre + "shared_w1"].T, p[pre + "shared_w3"].T,
                 p[pre + "shared_w2"].T, fp8)


def _layer(p, h, i, cfg, fp8):
    pre, eps = "l%d." % i, cfg["rms_norm_eps"]
    h = h + attention(p, pre, cfg, rms(h, p[pre + "attn_norm"], eps), fp8)
    x = rms(h, p[pre + "ffn_norm"], eps)
    if i < cfg["first_k_dense_replace"]:
        return h + gated(x, p[pre + "w1"].T, p[pre + "w3"].T,
                         p[pre + "w2"].T, fp8)
    return h + shared(p, pre, cfg, x, fp8) + routed(p, pre, cfg, x, fp8)


def logits(p, cfg, tokens, fp8=False):
    """(B, S) int tokens -> (B, S, vocab held) float32 logits."""
    h = jnp.take(p["embed"], tokens, axis=0)
    for i in range(cfg["num_hidden_layers"]):
        h = jax.checkpoint(functools.partial(_layer, i=i, cfg=cfg, fp8=fp8)
                           )(p, h)
    h = rms(h, p["final_norm"], cfg["rms_norm_eps"])
    return dot(h, p.get("head", p["embed"]).T, fp8)


def loss_sum(p, cfg, tokens, labels, fp8=False):
    """Sum over rows of each row's mean token cross-entropy, so that
    blocks of rows add up to batch * (the program's mean loss)."""
    lg = logits(p, cfg, tokens, fp8)
    return jnp.sum(jnp.mean(softmax_xent(lg, labels.astype(jnp.int32)), -1))


def expert_counts(p, cfg, tokens):
    """Per routed layer, the tokens assigned to each of the router's
    outputs at these parameters: ``(routed layers, E)`` int32, by the
    reference's own forward pass (what the program's counters count)."""
    h = jnp.take(p["embed"], tokens, axis=0)
    rows = []
    for i in range(cfg["num_hidden_layers"]):
        if i >= cfg["first_k_dense_replace"]:
            pre, eps = "l%d." % i, cfg["rms_norm_eps"]
            mid = h + attention(p, pre, cfg,
                                rms(h, p[pre + "attn_norm"], eps), False)
            chosen, _ = route(cfg, rms(mid, p[pre + "ffn_norm"], eps),
                              p[pre + "router"])
            rows.append(jnp.sum(
                chosen.reshape(-1, 1) == jnp.arange(cfg["router_experts"]),
                axis=0, dtype=jnp.int32))
        h = _layer(p, h, i, cfg, False)
    return jnp.stack(rows)


# rows of a batch do not interact (dropless token-choice routing has no
# capacity that rows would compete for): the step may run in blocks of rows
ROWS_INDEPENDENT = True
