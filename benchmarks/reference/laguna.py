"""Plain float32 reference of the decoder the `laguna` family builds
(poolside Laguna-S-2.1, `model_type` `laguna`), one chip's share of it.
T tokens, heads of `head_dim` d, `num_key_value_heads` key/value heads in
every layer, no bias anywhere.

Every layer ``l``: ``h = h + attn_l(rms(h)); h = h + ffn_l(rms(h))`` with
``rms(x) = x / sqrt(mean(x^2) + eps) * g``; then ``logits = rms(h) W_head``
over the rows of the vocabulary held here, and the mean next-token
cross-entropy.

- ``attn_l``, of kind `layer_types[l]` with ``H =
  num_attention_heads_per_layer[l]`` query heads: ``q = rms_head(u Wq,
  gq)`` (H heads), ``k = rms_head(u Wk, gk)``, ``v = u Wv``, ``g =
  sigmoid(u Wg)`` (H numbers a token: `gating` `per-head`).  Rotary
  positions ``0 .. T-1`` on q and k by `rope_parameters[kind]`: the first
  ``r = d * partial_rotary_factor`` dims of a head turn, the pair ``(x[i],
  x[i + r/2])`` by ``pos * inv_freq_i``, the others pass through;
  `rope_type` `default`: ``inv_freq_i = theta^(-2i/r)``; `yarn`: with
  ``e_i = theta^(-2i/r)``, ``n_i = e_i / factor``, ``c(x) = r ln(original /
  (2 pi x)) / (2 ln theta)``, ``low = max(floor(c(beta_fast)), 0)``, ``high
  = min(ceil(c(beta_slow)), r - 1)``, ``ramp_i = clip((i - low) / (high -
  low), 0, 1)``: ``inv_freq_i = n_i ramp_i + e_i (1 - ramp_i)``, and cos
  and sin are multiplied by `attention_factor` (as the public YaRN code
  applies it: the rotated dims' part of a score carries its square, the
  others' does not).  ``a_{t,h} = sum over visible s of softmax_s(q_{t,h} .
  k_{s,kv(h)} / sqrt(d)) v_{s,kv(h)}``, ``kv(h) = h // (H / kv heads)``;
  visible: ``s <= t`` (`full_attention`), ``t - sliding_window < s <= t``
  (`sliding_attention`: `sliding_window` keys, the query's own among
  them).  ``attn = concat_h(g_{t,h} a_{t,h}) Wo``.
- feed-forward, `mlp_layer_types[l]` `dense`: ``W2(silu(W1 x) * W3 x)`` at
  `intermediate_size`; `sparse`: ``shared(x) + routed(x)``, ``shared`` one
  gated MLP of `shared_expert_intermediate_size` (no gate on it);
  ``routed``: ``r = sigmoid(x W_r)`` over all `router_experts` outputs in
  float32, the chosen its `num_experts_per_tok` largest, ``c_e =
  moe_routed_scaling_factor * r_e / (sum of the chosen r + 1e-20)``
  (`norm_topk_prob`), the result the sum over the experts that are chosen
  AND held here (`num_experts` of them from `first_expert` on) of ``c_e
  W2_e(silu(W1_e x) * W3_e x)``.  What the absent experts would add is left
  out.  No bias on the choice, no capacity, no dropped token, no auxiliary
  loss.  **Departure of the program, not of this file:**
  `_contrib_RoutedExperts` adds 1e-6 to that denominator (a relative 3e-7
  on a sum near 5).

What the source's config does not say is the configuration file's
`assumed`: the sigmoid router, the per-head norms, where the gate reads
and what it multiplies.

Straight `jax.numpy`: no kernel, no sort, no grouped product, no import of
the program.  A held expert is applied to every token and masked by the
token's weight for it.  Attention is computed in blocks of query rows
under `jax.checkpoint`, one block at a time, every key multiplied and the
mask taken from its definition (a window layer's too: nothing is skipped
here).  `fp8` is the control's lower precision (`common`): it reaches
every contraction but the router's.  `sight` ``causal`` is the window's
control: every causal key visible in the sliding layers.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import contraction, dot, softmax_xent

ATTENTION_QUERY_BLOCK = 256
SIGHTS = ("window", "causal")
KINDS = ("full_attention", "sliding_attention")


def check_supported(cfg):
    """Raise for a `laguna` configuration whose equations are not the ones
    above."""
    layers = cfg["num_hidden_layers"]
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        if len(cfg[key]) != layers:
            raise ValueError("%s has %d entries for %d layers"
                             % (key, len(cfg[key]), layers))
    if set(cfg["layer_types"]) - set(KINDS):
        raise ValueError("layer kinds %r are not built"
                         % sorted(set(cfg["layer_types"]) - set(KINDS)))
    if set(cfg["mlp_layer_types"]) - {"dense", "sparse"}:
        raise ValueError("a feed-forward is dense or sparse")
    if any(h % cfg["num_key_value_heads"]
           for h in cfg["num_attention_heads_per_layer"]):
        raise ValueError("query heads in whole groups a key/value head")
    if cfg.get("gating") != "per-head" or set(
            cfg.get("gating_types", ["per_head"])) != {"per_head"}:
        raise ValueError("the output gate is one number a head")
    if cfg.get("attention_bias"):
        raise ValueError("attention biases are not built")
    if cfg.get("moe_router_logit_softcapping"):
        raise ValueError("the router's soft-cap is not built")
    if cfg.get("moe_apply_router_weight_on_input"):
        raise ValueError("router weights on the experts' input are not "
                         "built")
    if cfg.get("decoder_sparse_step", 1) != 1:
        raise ValueError("every layer outside mlp_only_layers is routed")
    if cfg.get("tie_word_embeddings"):
        raise ValueError("the head is a matrix of its own")


def param_table(cfg):
    """Ordered ``name -> (shape, init)`` in the program's parameter order:
    the embedding and the head first (the model's own leaves), then the
    layers."""
    check_supported(cfg)
    d, v, hd = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * hd
    std = cfg.get("initializer_range", 0.02)
    normal, ones = ("normal", std), ("ones",)
    t = {"embed": ((v, d), ("normal", cfg.get(
        "embedding_initializer_range", std))),
         "head": ((v, d), normal)}
    for i in range(cfg["num_hidden_layers"]):
        p = "l%d." % i
        heads = cfg["num_attention_heads_per_layer"][i]
        t[p + "attn_norm"] = ((d,), ones)
        t[p + "wq"] = ((heads * hd, d), normal)
        t[p + "wk"] = ((kv, d), normal)
        t[p + "wv"] = ((kv, d), normal)
        t[p + "wo"] = ((d, heads * hd), normal)
        t[p + "q_norm"] = ((hd,), ones)
        t[p + "k_norm"] = ((hd,), ones)
        t[p + "wg"] = ((heads, d), normal)
        t[p + "ffn_norm"] = ((d,), ones)
        if cfg["mlp_layer_types"][i] == "dense":
            f = cfg["intermediate_size"]
            t[p + "w1"] = ((f, d), normal)
            t[p + "w3"] = ((f, d), normal)
            t[p + "w2"] = ((d, f), normal)
        else:
            f, held = cfg["moe_intermediate_size"], cfg["num_experts"]
            s = cfg["shared_expert_intermediate_size"]
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


def inv_frequencies(parameters, head_dim):
    """``(rotary dims r, inv_freq (r / 2,) float64, table scale)`` of one
    layer kind's `rope_parameters`: the equations above."""
    theta = float(parameters["rope_theta"])
    r = int(head_dim * parameters.get("partial_rotary_factor", 1))
    i = np.arange(r // 2, dtype=np.float64)
    e = theta ** (-2.0 * i / r)
    if parameters.get("rope_type", "default") == "default":
        return r, e, 1.0
    if parameters["rope_type"] != "yarn":
        raise ValueError("rope_type %r is not built"
                         % (parameters["rope_type"],))
    original = parameters["original_max_position_embeddings"]

    def c(x):
        return r * math.log(original / (2.0 * math.pi * x)) \
            / (2.0 * math.log(theta))

    low = max(math.floor(c(parameters["beta_fast"])), 0)
    high = min(math.ceil(c(parameters["beta_slow"])), r - 1)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return r, e / parameters["factor"] * ramp + e * (1.0 - ramp), \
        float(parameters["attention_factor"])


def rope(x, parameters):
    """(B, heads, S, d) at positions ``0 .. S - 1``: the first ``r`` dims
    turned in pairs ``(x[i], x[i + r/2])``, the others as they are."""
    r, inv, scale = inv_frequencies(parameters, x.shape[-1])
    ang = np.arange(x.shape[-2], dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang) * scale, jnp.float32)
    sin = jnp.asarray(np.sin(ang) * scale, jnp.float32)
    a, b, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def sees(q_pos, k_pos, window):
    """Whether query position *q_pos* sees key position *k_pos* (they
    broadcast): causal, and with *window* the last *window* keys alone."""
    seen = k_pos <= q_pos
    return seen & (k_pos > q_pos - window) if window else seen


def _attend(q, row0, k, v, window, fp8):
    """Query rows ``row0 ..``: q (B, KV, G, R, d) against k, v (B, KV, S,
    d) -> (B, KV, G, R, d); every key multiplied, the mask from its
    definition."""
    rows = row0 + jnp.arange(q.shape[3])
    seen = sees(rows[:, None], jnp.arange(k.shape[2])[None, :], window)
    att = contraction(
        lambda a, b: jnp.einsum("bjgqd,bjkd->bjgqk", a, b), q, k, fp8
    ) / math.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(seen, att, -jnp.inf), -1)
    return contraction(
        lambda a, b: jnp.einsum("bjgqk,bjkd->bjgqd", a, b), probs, v, fp8)


def attention(p, i, cfg, x, fp8=False, sight="window"):
    """``attn_i(x)`` for x (B, S, hidden)."""
    if sight not in SIGHTS:
        raise ValueError("sight %r is not one of %s" % (sight, SIGHTS))
    pre, kind = "l%d." % i, cfg["layer_types"][i]
    b, s, _ = x.shape
    heads, kv, hd = cfg["num_attention_heads_per_layer"][i], \
        cfg["num_key_value_heads"], cfg["head_dim"]
    eps, turn = cfg["rms_norm_eps"], cfg["rope_parameters"][kind]
    window = cfg["sliding_window"] \
        if kind == "sliding_attention" and sight == "window" else None

    def split(y, n):
        return y.reshape(b, s, n, hd)

    q = rms(split(dot(x, p[pre + "wq"].T, fp8), heads), p[pre + "q_norm"],
            eps).transpose(0, 2, 1, 3)
    k = rms(split(dot(x, p[pre + "wk"].T, fp8), kv), p[pre + "k_norm"],
            eps).transpose(0, 2, 1, 3)
    v = split(dot(x, p[pre + "wv"].T, fp8), kv).transpose(0, 2, 1, 3)
    q, k = rope(q, turn), rope(k, turn)
    gate = jax.nn.sigmoid(dot(x, p[pre + "wg"].T, fp8))      # (B, S, H)

    blk = ATTENTION_QUERY_BLOCK if s % ATTENTION_QUERY_BLOCK == 0 else s
    n = s // blk
    rows = jax.checkpoint(functools.partial(
        _attend, k=k, v=v, window=window, fp8=fp8))
    out = jax.lax.map(
        lambda at: rows(*at),
        (q.reshape(b, kv, heads // kv, n, blk, hd).transpose(3, 0, 1, 2, 4, 5),
         jnp.arange(0, s, blk)))
    out = out.transpose(1, 2, 3, 0, 4, 5).reshape(b, heads, s, hd)
    out = out.transpose(0, 2, 1, 3) * gate[..., None]         # (B, S, H, d)
    return dot(out.reshape(b, s, heads * hd), p[pre + "wo"].T, fp8)


def gated(x, w1, w3, w2, fp8):
    return dot(jax.nn.silu(dot(x, w1, fp8)) * dot(x, w3, fp8), w2, fp8)


def route(cfg, x, router):
    """The chosen experts ``(.., k)`` of tokens *x* and their weights, in
    float32."""
    scores = jax.nn.sigmoid(jnp.matmul(x, router.T))
    weights, chosen = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return chosen, weights * cfg.get("moe_routed_scaling_factor", 1.0)


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

    # a scan, checkpointed: the program holds one expert's arithmetic and
    # the backward pass one expert's hidden states at a time
    out, _ = jax.lax.scan(
        jax.checkpoint(one_expert), jnp.zeros_like(x),
        (jnp.arange(held), p[pre + "expert_w1"], p[pre + "expert_w3"],
         p[pre + "expert_w2"]))
    return out


def shared(p, pre, cfg, x, fp8=False):
    """What every chip computes alike: the shared expert, one gated MLP."""
    return gated(x, p[pre + "shared_w1"].T, p[pre + "shared_w3"].T,
                 p[pre + "shared_w2"].T, fp8)


def feed_forward(p, i, cfg, x, fp8=False):
    pre = "l%d." % i
    if cfg["mlp_layer_types"][i] == "dense":
        return gated(x, p[pre + "w1"].T, p[pre + "w3"].T, p[pre + "w2"].T,
                     fp8)
    return shared(p, pre, cfg, x, fp8) + routed(p, pre, cfg, x, fp8)


def _layer(p, h, i, cfg, fp8, sight):
    pre, eps = "l%d." % i, cfg["rms_norm_eps"]
    h = h + attention(p, i, cfg, rms(h, p[pre + "attn_norm"], eps), fp8,
                      sight)
    return h + feed_forward(p, i, cfg, rms(h, p[pre + "ffn_norm"], eps),
                            fp8)


def logits(p, cfg, tokens, fp8=False, sight="window"):
    """(B, S) int tokens -> (B, S, vocab held) float32 logits."""
    h = jnp.take(p["embed"], tokens, axis=0)
    for i in range(cfg["num_hidden_layers"]):
        h = jax.checkpoint(functools.partial(
            _layer, i=i, cfg=cfg, fp8=fp8, sight=sight))(p, h)
    h = rms(h, p["final_norm"], cfg["rms_norm_eps"])
    return dot(h, p["head"].T, fp8)


def loss_sum(p, cfg, tokens, labels, fp8=False, sight="window"):
    """Sum over rows of each row's mean token cross-entropy, so that
    blocks of rows add up to batch * (the program's mean loss)."""
    lg = logits(p, cfg, tokens, fp8, sight)
    return jnp.sum(jnp.mean(softmax_xent(lg, labels.astype(jnp.int32)), -1))


def expert_counts(p, cfg, tokens):
    """Per routed layer, the tokens assigned to each of the router's
    outputs at these parameters: ``(routed layers, E)`` int32, by the
    reference's own forward pass (what the program's counters count)."""
    h = jnp.take(p["embed"], tokens, axis=0)
    rows = []
    for i in range(cfg["num_hidden_layers"]):
        if cfg["mlp_layer_types"][i] == "sparse":
            pre, eps = "l%d." % i, cfg["rms_norm_eps"]
            mid = h + attention(p, i, cfg,
                                rms(h, p[pre + "attn_norm"], eps))
            chosen, _ = route(cfg, rms(mid, p[pre + "ffn_norm"], eps),
                              p[pre + "router"])
            rows.append(jnp.sum(
                chosen.reshape(-1, 1) == jnp.arange(cfg["router_experts"]),
                axis=0, dtype=jnp.int32))
        h = _layer(p, h, i, cfg, False, "window")
    return jnp.stack(rows)


def visible_pairs(cfg, seq):
    """Per layer, the query-key pairs a head sees over *seq* positions,
    from the mask's definition summed a row at a time."""
    t = np.arange(seq, dtype=np.int64)
    return [int(np.minimum(t + 1, cfg["sliding_window"]).sum())
            if kind == "sliding_attention" else int((t + 1).sum())
            for kind in cfg["layer_types"]]


# rows of a batch do not interact (dropless token-choice routing has no
# capacity that rows would compete for): the step may run in blocks of rows
ROWS_INDEPENDENT = True
