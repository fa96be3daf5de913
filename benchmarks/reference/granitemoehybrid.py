"""Plain float32 reference of the decoder the `granitemoehybrid` family
builds (IBM Granite 4.0-H, `model_type` `granitemoehybrid`, the dense models:
no routed experts), one chip's share of it.  T tokens, hidden size d, no bias
anywhere but the convolution's.

``h = embedding_multiplier E[x]``; the layers; ``logits = rms(h, gf) E^T /
logits_scaling`` over the rows of the vocabulary held here (the head is the
embedding); the mean next-token cross-entropy.  ``rms(x, g) = x /
sqrt(mean(x^2) + eps) * g``.  Every layer, with ``m`` =
`residual_multiplier`: ``h = h + m mixer(rms(h, g1)); h = h + m FFN(rms(h,
g2))``, ``FFN(u) = W2 (silu(W1 u) * W3 u)`` at `shared_intermediate_size`.

- kind `mamba`: the Mamba-2 mixer (arXiv:2405.21060) with H = `mamba_n_heads`
  heads of P = `mamba_d_head`, a state of N = `mamba_d_state`, G =
  `mamba_n_groups` groups, I = H P.  ``[z | xBC | dt] = u W_in`` (I | I + 2 G
  N | H); every channel of xBC through its own causal convolution of
  `mamba_d_conv` taps along the sequence (zeros before position 0: shifted
  products) plus a bias, then silu; ``x, B, C = split(xBC)`` (H x P | G x N |
  G x N: the heads of a group share B and C); ``dt = softplus(dt + dt_bias)``
  (never clamped), ``A = -exp(A_log)``; per head the state ``S`` (P x N,
  ``S_{-1} = 0``):

      S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,  y_t = S_t C_t + D x_t

  **token by token**: a `lax.scan` over the positions, the recurrence as
  written, no chunk algebra; ``out = rms(y * silu(z), gn) W_out``: the gate
  in front of the norm, the norm over a group's whole width (all of I for
  one group).
- kind `attention`: ``q, k, v = u Wq, u Wk, u Wv`` as projected (no norm, no
  rotary positions: `position_embedding_type` `nope`); causal softmax
  attention by head at scale `attention_multiplier`, key/value heads shared by
  groups of query heads; ``out = concat_h(a) Wo``.

What the source's config does not say is the configuration file's `assumed`.

Straight `jax.numpy`: no kernel, no import of the program.  What is computed
again in the backward pass leaves the mathematics alone and keeps the step's
temporaries small beside the four float32 trees `common.follow_steps` holds:
each layer is checkpointed; the token scan runs in blocks of `SCAN_BLOCK`
positions and, inside a block, in blocks of `SCAN_INNER` under
`jax.checkpoint` (the boundary states kept, a block's steps rebuilt); the
feed-forward and the head with its loss `ROW_BLOCK` rows at a time, attention
in blocks of query rows, every key multiplied and the mask taken from its
definition.  `fp8` is the control's lower precision (`common`): it reaches
the projections, the feed-forwards, the attention's two contractions and the
head; the recurrence's state stays float32 as the configuration states it.
`sight` is the mechanism's control (`benchmarks/control_ssm.py`):
``no_decay`` never decays the state (``exp(dt A) = 1``), ``no_skip`` leaves
``D x`` out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .common import contraction, dot, softmax_xent
# the generic pieces of a dense layer, as the olmo_hybrid reference has them
from .olmo_hybrid import by_rows, gated, rms

ATTENTION_QUERY_BLOCK = 256
SCAN_BLOCK = 64
SCAN_INNER = 8
SIGHTS = ("ssm", "no_decay", "no_skip")
KINDS = ("mamba", "attention")


def check_supported(cfg):
    """Raise for a `granitemoehybrid` configuration whose equations are not
    the ones above."""
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types has %d entries for %d layers" % (
            len(cfg["layer_types"]), cfg["num_hidden_layers"]))
    if set(cfg["layer_types"]) - set(KINDS):
        raise ValueError("layer kinds %r are not built"
                         % sorted(set(cfg["layer_types"]) - set(KINDS)))
    if cfg.get("num_local_experts") or cfg.get("num_experts_per_tok"):
        raise ValueError("routed experts are not built: the dense models")
    if cfg["mamba_n_heads"] % cfg["mamba_n_groups"]:
        raise ValueError("groups divide the state-space heads")
    if cfg["mamba_n_heads"] * cfg["mamba_d_head"] \
            != cfg["mamba_expand"] * cfg["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head is mamba_expand x "
                         "hidden_size")
    if cfg["hidden_size"] % cfg["num_attention_heads"] \
            or cfg["num_attention_heads"] % cfg["num_key_value_heads"]:
        raise ValueError("heads divide the width, key/value heads the heads")
    if cfg.get("position_embedding_type") != "nope":
        raise ValueError("the attention layers carry no positions")
    if cfg.get("attention_bias") or cfg.get("mamba_proj_bias") \
            or not cfg.get("mamba_conv_bias"):
        raise ValueError("a bias on the convolution and on nothing else")
    if cfg.get("hidden_act", "silu") != "silu" \
            or cfg.get("normalization_function", "rmsnorm") != "rmsnorm":
        raise ValueError("silu and RMS norms")
    if not cfg.get("tie_word_embeddings"):
        raise ValueError("the head is the embedding")


def gate_starts(cfg, layer):
    """``(A_log, dt_bias)`` of layer *layer*'s mixer, one number a head, the
    public block's start: ``A`` uniform in (1, 16), ``dt`` log-uniform in
    (0.001, 0.1), ``dt_bias`` the inverse softplus of ``dt``.  The seeded
    leaves of `common` are normal or constant, so these are drawn once a
    layer from `gate_init_seed` and are the same for every run's seed."""
    heads = cfg["mamba_n_heads"]
    rng = np.random.default_rng([int(cfg.get("gate_init_seed", 0)), layer])
    a = rng.uniform(1.0, 16.0, heads)
    dt = np.exp(rng.uniform(np.log(0.001), np.log(0.1), heads))
    return (np.log(a).astype(np.float32),
            (dt + np.log(-np.expm1(-dt))).astype(np.float32))


def widths(cfg):
    """``(heads, head width, state, groups, inner width, convolved
    channels)`` of a mamba layer."""
    heads, p, n, g = cfg["mamba_n_heads"], cfg["mamba_d_head"], \
        cfg["mamba_d_state"], cfg["mamba_n_groups"]
    return heads, p, n, g, heads * p, heads * p + 2 * g * n


def param_table(cfg):
    """Ordered ``name -> (shape, init)`` in the program's parameter order:
    the embedding first (the model's own leaf), then the layers."""
    check_supported(cfg)
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], \
        cfg["shared_intermediate_size"]
    heads, _, _, _, inner, conv = widths(cfg)
    hd = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    std = cfg.get("initializer_range", 0.02)
    normal, ones = ("normal", std), ("ones",)
    t = {"embed": ((v, d), normal)}
    for i, kind in enumerate(cfg["layer_types"]):
        p = "l%d." % i
        t[p + "mixer_norm"] = ((d,), ones)
        if kind == "mamba":
            a_log, dt_bias = gate_starts(cfg, i)
            t[p + "w_in"] = ((inner + conv + heads, d), normal)
            t[p + "conv_w"] = ((conv, cfg["mamba_d_conv"]), (
                "normal", cfg.get("conv_initializer_range", std)))
            t[p + "conv_b"] = ((conv,), ("zeros",))
            t[p + "a_log"] = ((heads,), ("const", a_log))
            t[p + "dt_bias"] = ((heads,), ("const", dt_bias))
            t[p + "skip"] = ((heads,), ones)
            t[p + "ssm_norm"] = ((inner,), ones)
            t[p + "w_out"] = ((d, inner), normal)
        else:
            t[p + "wq"] = ((d, d), normal)
            t[p + "wk"] = ((kv, d), normal)
            t[p + "wv"] = ((kv, d), normal)
            t[p + "wo"] = ((d, d), normal)
        t[p + "ffn_norm"] = ((d,), ones)
        t[p + "w1"] = ((f, d), normal)
        t[p + "w3"] = ((f, d), normal)
        t[p + "w2"] = ((d, f), normal)
    t["final_norm"] = ((d,), ones)
    return t


def conv_bias_silu(x, w, bias):
    """(B, S, C) through each channel's causal taps ``w`` (C, L), as L
    shifted products, plus the channel's bias, then silu."""
    taps, seq = w.shape[1], x.shape[1]
    padded = jnp.pad(x, [(0, 0), (taps - 1, 0), (0, 0)])
    return jax.nn.silu(sum(padded[:, j:j + seq] * w[:, j]
                           for j in range(taps)) + bias)


def _token(state, at, a, d, sight):
    """One position of the recurrence, for every row and head: state (B, G,
    R, P, N); x (B, G, R, P), dt (B, G, R), b, c (B, G, N); a, d (G, R)."""
    x, dt, b, c = at
    if sight != "no_decay":
        state = jnp.exp(dt * a)[..., None, None] * state
    state = state + (dt[..., None] * x)[..., :, None] \
        * b[:, :, None, None, :]
    y = jnp.einsum("bgrpn,bgn->bgrp", state, c)
    return state, y if sight == "no_skip" else y + d[..., None] * x


def recurrence(x, dt, a, b, c, d, sight="ssm"):
    """The recurrence over x (B, S, H, P), dt (B, S, H), a, d (H,), b, c (B,
    S, G, N), token by token -> (B, S, H, P)."""
    bsz, seq, heads, p = x.shape
    groups, n = b.shape[2:]
    x = x.reshape(bsz, seq, groups, heads // groups, p)
    dt = dt.reshape(bsz, seq, groups, heads // groups)
    blk = SCAN_BLOCK if seq % SCAN_BLOCK == 0 else seq
    inner = SCAN_INNER if blk % SCAN_INNER == 0 else blk
    token = functools.partial(
        _token, a=a.reshape(groups, -1), d=d.reshape(groups, -1),
        sight=sight)

    def few(state, ats):
        return jax.lax.scan(token, state, ats)

    def block(state, ats):
        return jax.lax.scan(jax.checkpoint(few), state, ats)

    def by_block(v):        # (B, S, ...) -> (S/blk, blk/inner, inner, B, ...)
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape((seq // blk, blk // inner, inner) + v.shape[1:])

    _, out = jax.lax.scan(
        jax.checkpoint(block),
        jnp.zeros((bsz, groups, heads // groups, p, n), jnp.float32),
        tuple(by_block(v) for v in (x, dt, b, c)))
    return jnp.moveaxis(out.reshape((seq,) + out.shape[3:]), 0, 1).reshape(
        bsz, seq, heads, p)


def mamba(p, i, cfg, u, fp8=False, sight="ssm"):
    """The mixer for u (B, S, hidden)."""
    if sight not in SIGHTS:
        raise ValueError("sight %r is not one of %s" % (sight, SIGHTS))
    pre, eps = "l%d." % i, cfg["rms_norm_eps"]
    bsz, seq, _ = u.shape
    heads, width, n, groups, inner, conv = widths(cfg)
    zxd = dot(u, p[pre + "w_in"].T, fp8)
    z, xbc, dt = jnp.split(zxd, [inner, inner + conv], -1)
    xbc = conv_bias_silu(xbc, p[pre + "conv_w"], p[pre + "conv_b"])
    x, b, c = jnp.split(xbc, [inner, inner + groups * n], -1)
    y = jax.checkpoint(functools.partial(recurrence, sight=sight))(
        x.reshape(bsz, seq, heads, width),
        jax.nn.softplus(dt + p[pre + "dt_bias"]),
        -jnp.exp(p[pre + "a_log"]), b.reshape(bsz, seq, groups, n),
        c.reshape(bsz, seq, groups, n), p[pre + "skip"])
    y = (y.reshape(bsz, seq, inner) * jax.nn.silu(z)).reshape(
        bsz, seq, groups, inner // groups)
    y = rms(y, p[pre + "ssm_norm"].reshape(groups, -1), eps)
    return dot(y.reshape(bsz, seq, inner), p[pre + "w_out"].T, fp8)


def _attend(q, row0, k, v, scale, fp8):
    """Query rows ``row0 ..``: q (B, KV, G, R, d) against k, v (B, KV, S,
    d) -> (B, KV, G, R, d); every key multiplied, the causal mask from its
    definition."""
    rows = row0 + jnp.arange(q.shape[3])
    seen = jnp.arange(k.shape[2])[None, :] <= rows[:, None]
    att = scale * contraction(
        lambda a, b: jnp.einsum("bjgqd,bjkd->bjgqk", a, b), q, k, fp8)
    probs = jax.nn.softmax(jnp.where(seen, att, -jnp.inf), -1)
    return contraction(
        lambda a, b: jnp.einsum("bjgqk,bjkd->bjgqd", a, b), probs, v, fp8)


def attention(p, i, cfg, u, fp8=False):
    """The attention for u (B, S, hidden)."""
    pre = "l%d." % i
    b, s, d = u.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads

    def split(y, n):
        return y.reshape(b, s, n, hd).transpose(0, 2, 1, 3)

    q = split(dot(u, p[pre + "wq"].T, fp8), heads)
    k = split(dot(u, p[pre + "wk"].T, fp8), kv)
    v = split(dot(u, p[pre + "wv"].T, fp8), kv)
    blk = ATTENTION_QUERY_BLOCK if s % ATTENTION_QUERY_BLOCK == 0 else s
    n = s // blk
    rows = jax.checkpoint(functools.partial(
        _attend, k=k, v=v, scale=cfg["attention_multiplier"], fp8=fp8))
    out = jax.lax.map(
        lambda at: rows(*at),
        (q.reshape(b, kv, heads // kv, n, blk, hd).transpose(3, 0, 1, 2, 4, 5),
         jnp.arange(0, s, blk)))
    out = out.transpose(1, 2, 3, 0, 4, 5).reshape(b, heads, s, hd)
    return dot(out.transpose(0, 2, 1, 3).reshape(b, s, d), p[pre + "wo"].T,
               fp8)


def feed_forward(p, i, x, fp8=False):
    pre = "l%d." % i
    return by_rows(lambda rows: gated(rows, p[pre + "w1"].T,
                                      p[pre + "w3"].T, p[pre + "w2"].T, fp8),
                   x)


def _layer(p, h, i, cfg, fp8, sight):
    pre, eps, m = "l%d." % i, cfg["rms_norm_eps"], cfg["residual_multiplier"]
    u = rms(h, p[pre + "mixer_norm"], eps)
    h = h + m * (mamba(p, i, cfg, u, fp8, sight)
                 if cfg["layer_types"][i] == "mamba"
                 else attention(p, i, cfg, u, fp8))
    return h + m * feed_forward(p, i, rms(h, p[pre + "ffn_norm"], eps), fp8)


def hidden(p, cfg, tokens, fp8=False, sight="ssm"):
    """(B, S) int tokens -> (B, S, hidden): the layers and the final norm."""
    h = cfg["embedding_multiplier"] * jnp.take(p["embed"], tokens, axis=0)
    for i in range(cfg["num_hidden_layers"]):
        h = jax.checkpoint(functools.partial(
            _layer, i=i, cfg=cfg, fp8=fp8, sight=sight))(p, h)
    return rms(h, p["final_norm"], cfg["rms_norm_eps"])


def logits(p, cfg, tokens, fp8=False, sight="ssm"):
    """(B, S) int tokens -> (B, S, vocab held) float32 logits."""
    return dot(hidden(p, cfg, tokens, fp8, sight), p["embed"].T, fp8) \
        / cfg["logits_scaling"]


def loss_sum(p, cfg, tokens, labels, fp8=False, sight="ssm"):
    """Sum over rows of each row's mean token cross-entropy, so that
    blocks of rows add up to batch * (the program's mean loss); the head's
    logits a block of positions at a time."""
    def some(h, labels):
        return softmax_xent(dot(h, p["embed"].T, fp8)
                            / cfg["logits_scaling"], labels)

    return jnp.sum(jnp.mean(by_rows(
        some, hidden(p, cfg, tokens, fp8, sight), labels.astype(jnp.int32)),
        -1))


def forward_flops(cfg, seq):
    """FLOPs of one sequence's forward pass as this file computes it, 2 a
    multiply-add, counted from the parameter table: every matrix once a
    token (the embedding as the head; the taps not), the causal core over the
    pairs the mask leaves (``seq (seq + 1) / 2`` a head, two contractions),
    and the recurrence a token and head as `_token` writes it: the decay (P
    N), the rank-one write (2 P N) and the read (2 P N)."""
    table = param_table(cfg)
    flops = sum(2 * seq * shape[0] * shape[1]
                for name, (shape, _) in table.items()
                if len(shape) == 2 and not name.endswith("conv_w"))
    heads, p, n, _, _, _ = widths(cfg)
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    for kind in cfg["layer_types"]:
        if kind == "mamba":
            flops += seq * heads * 5 * p * n
        else:
            flops += cfg["num_attention_heads"] * (seq * (seq + 1) // 2) \
                * 4 * hd
    return flops


# rows of a batch do not interact: the step may run in blocks of rows
ROWS_INDEPENDENT = True
