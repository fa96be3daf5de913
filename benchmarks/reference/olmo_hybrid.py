"""Plain float32 reference of the decoder the `olmo_hybrid` family builds
(Ai2 Olmo-Hybrid-7B, `model_type` `olmo_hybrid`), one chip's share of it.
T tokens, hidden size d, no bias anywhere but the decay's.

``h = E[x]``; the layers; ``logits = rms(h, gf) W_head`` over the rows of the
vocabulary held here; the mean next-token cross-entropy.  ``rms(x, g) = x /
sqrt(mean(x^2) + eps) * g``; in every layer ``FFN(u) = W2 (silu(W1 u) * W3
u)`` at `intermediate_size`.

- kind `linear_attention` (pre-norm): ``h = h + GDN(rms(h, g1)); h = h +
  FFN(rms(h, g2))``.  ``GDN(u)`` with H = `linear_num_value_heads` heads, dk =
  `linear_key_head_dim`, dv = `linear_value_head_dim`, per head:
  ``[q~, k~, v~] = u [Wq, Wk, Wv]`` (the three are the row blocks of one
  stored matrix `wqkv`); each channel through its own causal convolution of
  `linear_conv_kernel_dim` taps along the sequence (no bias, zeros before
  position 0: four shifted products), then silu; ``q_t = q'_t /
  sqrt(sum(q'_t^2) + eps) / sqrt(dk)``, ``k_t = k'_t / sqrt(sum(k'_t^2) +
  eps)`` over the head's dk; ``b_t = sigmoid(u_t Wb)``, doubled with
  `linear_allow_neg_eigval`; ``g_t = -exp(A_log) softplus(u_t Wa +
  dt_bias)``, ``a_t = exp(g_t)``; the state ``S`` (dk x dv, ``S_{-1} = 0``):

      S_t = a_t S_{t-1} + b_t k_t (v_t - a_t S_{t-1}^T k_t)^T,  o_t = S_t^T q_t

  **token by token**: a `lax.scan` over the positions, the recurrence as
  written, no chunk algebra; ``y_t = rms(o_t, gn) * silu(z_t)`` with ``z = u
  Wz`` and ``gn`` dv-wide, one vector for all heads; ``GDN = concat_h(y)
  Wo``.
- kind `full_attention` (the norm on the output): ``h = h + rms(Attn(h),
  g1); h = h + rms(FFN(h), g2)``.  ``q = rms(h Wq, gq)``, ``k = rms(h Wk,
  gk)``, the norm over all the heads' numbers at once, ``v = h Wv``; no
  rotary positions (`rope_parameters.rope_theta` null); causal softmax
  attention by head at scale ``1 / sqrt(head width)``; ``Attn = concat_h(a)
  Wo``.

What the source's config does not say is the configuration file's `assumed`.

Straight `jax.numpy`: no kernel, no import of the program.  What is
computed again in the backward pass leaves the mathematics alone and keeps
the step's temporaries near 1 GB at 4096 tokens (beside them live four
float32 trees of 3.7 GB: the parameters, the momentum, and the last step's
gradient while the next one is made): each layer is checkpointed; the token
scan runs in blocks of `SCAN_BLOCK` positions and, inside a block, in blocks
of `SCAN_INNER` under `jax.checkpoint` (the boundary states kept, a block's
steps rebuilt); the convolution with its silu and norms runs a group of
`HEAD_GROUP` heads at a time, the feed-forward and the head with its loss
`ROW_BLOCK` rows at a time, attention in blocks of query rows, every key
multiplied and the mask taken from its definition, all under
`jax.checkpoint`.  `fp8` is the control's lower precision (`common`; one
scale a tensor as it is handed over, so a block of rows has its own): it
reaches
the projections, the feed-forwards, the attention's two contractions and
the head; the recurrence's state stays float32 as the configuration states
it.  `sight` is the rule's control (`benchmarks/control_delta.py`):
``no_erase`` leaves the erase term out (``S_t = a_t S_{t-1} + b_t k_t
v_t^T``), ``single_b`` does not double ``b``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import contraction, dot, softmax_xent

ATTENTION_QUERY_BLOCK = 256
SCAN_BLOCK = 64
SCAN_INNER = 8
HEAD_GROUP = 5
ROW_BLOCK = 512
SIGHTS = ("delta", "no_erase", "single_b")
KINDS = ("linear_attention", "full_attention")


def check_supported(cfg):
    """Raise for an `olmo_hybrid` configuration whose equations are not the
    ones above."""
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types has %d entries for %d layers" % (
            len(cfg["layer_types"]), cfg["num_hidden_layers"]))
    if set(cfg["layer_types"]) - set(KINDS):
        raise ValueError("layer kinds %r are not built"
                         % sorted(set(cfg["layer_types"]) - set(KINDS)))
    if cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
        raise ValueError("one state a head: as many key heads as value "
                         "heads")
    if cfg["hidden_size"] % cfg["num_attention_heads"] \
            or cfg["num_attention_heads"] % cfg["num_key_value_heads"]:
        raise ValueError("heads divide the width, key/value heads the heads")
    if cfg.get("attention_bias"):
        raise ValueError("attention biases are not built")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("the feed-forward's gate is silu")
    if (cfg.get("rope_parameters") or {}).get("rope_theta") is not None:
        raise ValueError("the full layers carry no rotary positions")
    if cfg.get("tie_word_embeddings"):
        raise ValueError("the head is a matrix of its own")


def gate_starts(cfg, layer):
    """``(A_log, dt_bias)`` of layer *layer*'s decay, one number a head, the
    public block's start: ``A`` uniform in (0, 16), ``dt`` log-uniform in
    (0.001, 0.1), ``dt_bias`` the inverse softplus of ``dt``.  The seeded
    leaves of `common` are normal or constant, so these are drawn once a
    layer from `gate_init_seed` and are the same for every run's seed."""
    heads = cfg["linear_num_value_heads"]
    rng = np.random.default_rng([int(cfg.get("gate_init_seed", 0)), layer])
    a = rng.uniform(0.0, 16.0, heads)
    dt = np.exp(rng.uniform(math.log(0.001), math.log(0.1), heads))
    return (np.log(a).astype(np.float32),
            (dt + np.log(-np.expm1(-dt))).astype(np.float32))


def widths(cfg):
    """``(heads, dk, dv, keys, values)`` of a linear layer."""
    heads, dk, dv = cfg["linear_num_value_heads"], \
        cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return heads, dk, dv, heads * dk, heads * dv


def param_table(cfg):
    """Ordered ``name -> (shape, init)`` in the program's parameter order:
    the embedding and the head first (the model's own leaves), then the
    layers."""
    check_supported(cfg)
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    heads, _, dv, keys, values = widths(cfg)
    hd = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    std = cfg.get("initializer_range", 0.02)
    normal, ones = ("normal", std), ("ones",)
    t = {"embed": ((v, d), ("normal", cfg.get(
        "embedding_initializer_range", std))),
         "head": ((v, d), normal)}
    for i, kind in enumerate(cfg["layer_types"]):
        p = "l%d." % i
        t[p + "attn_norm"] = ((d,), ones)
        if kind == "linear_attention":
            a_log, dt_bias = gate_starts(cfg, i)
            t[p + "wqkv"] = ((2 * keys + values, d), normal)
            t[p + "conv_w"] = (
                (2 * keys + values, cfg["linear_conv_kernel_dim"]),
                ("normal", cfg.get("conv_initializer_range", std)))
            t[p + "wa"] = ((heads, d), normal)
            t[p + "wb"] = ((heads, d), normal)
            t[p + "a_log"] = ((heads,), ("const", a_log))
            t[p + "dt_bias"] = ((heads,), ("const", dt_bias))
            t[p + "wz"] = ((values, d), normal)
            t[p + "gdn_norm"] = ((dv,), ones)
            t[p + "wo"] = ((d, values), normal)
        else:
            t[p + "wq"] = ((d, d), normal)
            t[p + "wk"] = ((kv, d), normal)
            t[p + "wv"] = ((kv, d), normal)
            t[p + "wo"] = ((d, d), normal)
            t[p + "q_norm"] = ((d,), ones)
            t[p + "k_norm"] = ((kv,), ones)
        t[p + "ffn_norm"] = ((d,), ones)
        t[p + "w1"] = ((f, d), normal)
        t[p + "w3"] = ((f, d), normal)
        t[p + "w2"] = ((d, f), normal)
    t["final_norm"] = ((d,), ones)
    return t


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def by_rows(fn, *xs):
    """``fn`` over arrays (B, S, ...) a block of `ROW_BLOCK` rows at a
    time, each block under `jax.checkpoint`: ``fn`` maps blocks (B, rows,
    ...) to (B, rows, ...)."""
    b, s = xs[0].shape[:2]
    blk = ROW_BLOCK if s % ROW_BLOCK == 0 else s
    out = jax.lax.map(
        jax.checkpoint(lambda at: fn(*at)),
        tuple(jnp.moveaxis(x.reshape((b, s // blk, blk) + x.shape[2:]), 1, 0)
              for x in xs))
    return jnp.moveaxis(out, 0, 1).reshape((b, s) + out.shape[3:])


def conv_silu(x, w):
    """(B, S, C) through each channel's causal taps ``w`` (C, L), as L
    shifted products, then silu."""
    taps, seq = w.shape[1], x.shape[1]
    padded = jnp.pad(x, [(0, 0), (taps - 1, 0), (0, 0)])
    return jax.nn.silu(sum(padded[:, j:j + seq] * w[:, j]
                           for j in range(taps)))


def _token(state, at, sight):
    """One position of the recurrence, for every row and head: state (B, H,
    dk, dv); q, k (B, H, dk), v (B, H, dv), g, b (B, H)."""
    q, k, v, g, b = at
    state = jnp.exp(g)[..., None, None] * state
    write = v if sight == "no_erase" \
        else v - jnp.einsum("bhde,bhd->bhe", state, k)
    state = state + (b[..., None] * k)[..., :, None] * write[..., None, :]
    return state, jnp.einsum("bhde,bhd->bhe", state, q)


def delta_rule(q, k, v, g, b, sight="delta"):
    """The recurrence over q, k (B, S, H, dk), v (B, S, H, dv), g, b (B, S,
    H), token by token -> (B, S, H, dv)."""
    bsz, seq, heads, dk = q.shape
    blk = SCAN_BLOCK if seq % SCAN_BLOCK == 0 else seq

    inner = SCAN_INNER if blk % SCAN_INNER == 0 else blk

    def few(state, ats):
        return jax.lax.scan(functools.partial(_token, sight=sight), state,
                            ats)

    def block(state, ats):
        return jax.lax.scan(jax.checkpoint(few), state, ats)

    def by_block(x):        # (B, S, ...) -> (S/blk, blk/inner, inner, B, ...)
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((seq // blk, blk // inner, inner) + x.shape[1:])

    _, out = jax.lax.scan(
        jax.checkpoint(block), jnp.zeros((bsz, heads, dk, v.shape[-1]),
                                         jnp.float32),
        tuple(by_block(x) for x in (q, k, v, g, b)))
    return jnp.moveaxis(out.reshape((seq,) + out.shape[3:]), 0, 1)


def linear_attention(p, i, cfg, u, fp8=False, sight="delta"):
    """``GDN(u)`` for u (B, S, hidden)."""
    if sight not in SIGHTS:
        raise ValueError("sight %r is not one of %s" % (sight, SIGHTS))
    pre, eps = "l%d." % i, cfg["rms_norm_eps"]
    bsz, seq, _ = u.shape
    heads, dk, dv, keys, _ = widths(cfg)
    def through_taps(lo, width, unit):
        """Rows ``lo .. lo + heads * width`` of `wqkv`: their product with
        u, its taps and silu, by head (B, S, H, width), `HEAD_GROUP` heads
        at a time; *unit*: each head L2-normalised."""
        group = HEAD_GROUP if heads % HEAD_GROUP == 0 else heads

        def some(at):
            w, taps = at             # (group * width, hidden), (the same, L)
            y = conv_silu(dot(u, w.T, fp8), taps).reshape(
                bsz, seq, group, width)
            return y * jax.lax.rsqrt(jnp.sum(jnp.square(y), -1,
                                             keepdims=True) + eps) \
                if unit else y

        hi = lo + heads * width
        out = jax.lax.map(jax.checkpoint(some), tuple(
            m[lo:hi].reshape(heads // group, group * width, -1)
            for m in (p[pre + "wqkv"], p[pre + "conv_w"])))
        return jnp.moveaxis(out, 0, 2).reshape(bsz, seq, heads, width)

    q = through_taps(0, dk, True) / math.sqrt(dk)
    k = through_taps(keys, dk, True)
    v = through_taps(2 * keys, dv, False)
    b = jax.nn.sigmoid(dot(u, p[pre + "wb"].T, fp8))
    if cfg.get("linear_allow_neg_eigval") and sight != "single_b":
        b = 2.0 * b
    g = -jnp.exp(p[pre + "a_log"]) * jax.nn.softplus(
        dot(u, p[pre + "wa"].T, fp8) + p[pre + "dt_bias"])
    o = jax.checkpoint(functools.partial(delta_rule, sight=sight))(
        q, k, v, g, b)

    def out(u, o):
        z = dot(u, p[pre + "wz"].T, fp8).reshape(o.shape)
        y = rms(o, p[pre + "gdn_norm"], eps) * jax.nn.silu(z)
        return dot(y.reshape(y.shape[:2] + (heads * dv,)), p[pre + "wo"].T,
                   fp8)

    return by_rows(out, u, o)


def _attend(q, row0, k, v, fp8):
    """Query rows ``row0 ..``: q (B, KV, G, R, d) against k, v (B, KV, S,
    d) -> (B, KV, G, R, d); every key multiplied, the causal mask from its
    definition."""
    rows = row0 + jnp.arange(q.shape[3])
    seen = jnp.arange(k.shape[2])[None, :] <= rows[:, None]
    att = contraction(
        lambda a, b: jnp.einsum("bjgqd,bjkd->bjgqk", a, b), q, k, fp8
    ) / math.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(seen, att, -jnp.inf), -1)
    return contraction(
        lambda a, b: jnp.einsum("bjgqk,bjkd->bjgqd", a, b), probs, v, fp8)


def attention(p, i, cfg, x, fp8=False):
    """``Attn(x)`` for x (B, S, hidden)."""
    pre, eps = "l%d." % i, cfg["rms_norm_eps"]
    b, s, d = x.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads

    def split(y, n):
        return y.reshape(b, s, n, hd).transpose(0, 2, 1, 3)

    q = split(rms(dot(x, p[pre + "wq"].T, fp8), p[pre + "q_norm"], eps),
              heads)
    k = split(rms(dot(x, p[pre + "wk"].T, fp8), p[pre + "k_norm"], eps), kv)
    v = split(dot(x, p[pre + "wv"].T, fp8), kv)
    blk = ATTENTION_QUERY_BLOCK if s % ATTENTION_QUERY_BLOCK == 0 else s
    n = s // blk
    rows = jax.checkpoint(functools.partial(_attend, k=k, v=v, fp8=fp8))
    out = jax.lax.map(
        lambda at: rows(*at),
        (q.reshape(b, kv, heads // kv, n, blk, hd).transpose(3, 0, 1, 2, 4, 5),
         jnp.arange(0, s, blk)))
    out = out.transpose(1, 2, 3, 0, 4, 5).reshape(b, heads, s, hd)
    return dot(out.transpose(0, 2, 1, 3).reshape(b, s, d), p[pre + "wo"].T,
               fp8)


def gated(x, w1, w3, w2, fp8):
    return dot(jax.nn.silu(dot(x, w1, fp8)) * dot(x, w3, fp8), w2, fp8)


def feed_forward(p, i, x, fp8=False):
    pre = "l%d." % i
    return by_rows(lambda rows: gated(rows, p[pre + "w1"].T,
                                      p[pre + "w3"].T, p[pre + "w2"].T, fp8),
                   x)


def _layer(p, h, i, cfg, fp8, sight):
    pre, eps = "l%d." % i, cfg["rms_norm_eps"]
    g1, g2 = p[pre + "attn_norm"], p[pre + "ffn_norm"]
    if cfg["layer_types"][i] == "linear_attention":
        h = h + linear_attention(p, i, cfg, rms(h, g1, eps), fp8, sight)
        return h + feed_forward(p, i, rms(h, g2, eps), fp8)
    h = h + rms(attention(p, i, cfg, h, fp8), g1, eps)
    return h + rms(feed_forward(p, i, h, fp8), g2, eps)


def hidden(p, cfg, tokens, fp8=False, sight="delta"):
    """(B, S) int tokens -> (B, S, hidden): the layers and the final norm."""
    h = jnp.take(p["embed"], tokens, axis=0)
    for i in range(cfg["num_hidden_layers"]):
        h = jax.checkpoint(functools.partial(
            _layer, i=i, cfg=cfg, fp8=fp8, sight=sight))(p, h)
    return rms(h, p["final_norm"], cfg["rms_norm_eps"])


def logits(p, cfg, tokens, fp8=False, sight="delta"):
    """(B, S) int tokens -> (B, S, vocab held) float32 logits."""
    return dot(hidden(p, cfg, tokens, fp8, sight), p["head"].T, fp8)


def loss_sum(p, cfg, tokens, labels, fp8=False, sight="delta"):
    """Sum over rows of each row's mean token cross-entropy, so that
    blocks of rows add up to batch * (the program's mean loss); the head's
    logits a block of positions at a time."""
    def some(h, labels):
        return softmax_xent(dot(h, p["head"].T, fp8), labels)

    return jnp.sum(jnp.mean(by_rows(
        some, hidden(p, cfg, tokens, fp8, sight), labels.astype(jnp.int32)),
        -1))


def forward_flops(cfg, seq):
    """FLOPs of one sequence's forward pass as this file computes it, 2 a
    multiply-add, counted from the parameter table: every matrix but the
    embedding (a lookup) and the taps once a token, the causal core over the
    pairs the mask leaves (``seq (seq + 1) / 2`` a head, two contractions),
    and the recurrence a token and head as `_token` writes it: the decay
    (dk dv), ``S^T k`` (2 dk dv), the rank-one write (2 dk dv) and ``S^T q``
    (2 dk dv)."""
    table = param_table(cfg)
    flops = sum(2 * seq * shape[0] * shape[1]
                for name, (shape, _) in table.items()
                if len(shape) == 2 and name != "embed"
                and not name.endswith("conv_w"))
    heads, dk, dv, _, _ = widths(cfg)
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    for kind in cfg["layer_types"]:
        if kind == "linear_attention":
            flops += seq * heads * 7 * dk * dv
        else:
            flops += cfg["num_attention_heads"] * (seq * (seq + 1) // 2) \
                * 4 * hd
    return flops


# rows of a batch do not interact: the step may run in blocks of rows
ROWS_INDEPENDENT = True
