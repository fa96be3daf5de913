"""Plain float32 reference of the decoder the `keye_vl2` family builds (the
language model of Kwai-Keye's Keye-VL-2.0-30B-A3B, `model_type` `KeyeVL2`),
one chip's share of it.  H query heads over KV key/value heads of width hd, J
indexer heads of width di over one indexer key, T tokens, no bias anywhere.

Every layer ``l``: ``h = h + attn_l(rms(h)); h = h + ffn_l(rms(h))`` with
``rms(x) = x / sqrt(mean(x^2) + eps) * g``.

- ``attn`` (grouped-query attention over the keys an indexer chose: learned
  sparse attention as DeepSeek-V3.2-Exp publishes it).  ``q = rope3(rms_head(x
  Wq))`` (H x hd), ``k = rope3(rms_head(x Wk))``, ``v = x Wv`` (KV x hd);
  query head ``i`` reads key/value head ``i // (H / KV)``.  ``rope3``: the
  pair ``(y[f], y[f + hd/2])`` turns by ``pos[a(f)] * theta^(-2f / hd)``,
  where the ``hd / 2`` frequencies are dealt to three position axes in
  chunks of `mrope_section` (``a(f)`` = 0 for the first 16, 1 for the next
  24, 2 for the last 24: the source's rotary type `default`); text positions
  have the three axes equal.
  The indexer reads ``xd = stop_gradient(x)``: ``qI = xd WqI`` (J x di),
  ``kI = xd WkI`` (di), ``w = (xd Ww) * J^-1/2 * di^-1/2`` (J); ``I[t, s] =
  sum_j w[t, j] * relu(qI[t, j] . kI[s])`` for ``s <= t``.
  Selection: ``S_t`` = the causal keys whose score is at least the row's
  ``topk``-th largest (`lax.top_k` finds that value; every causal key of a
  row with no more than ``topk`` of them).  Keys that tie with the
  ``topk``-th are all kept, so a row with ties holds more than ``topk``:
  the program's rule (it finds the value by counting), and this file's.
  The selection carries no gradient.
  ``o[t] = sum_{s in S_t} softmax_{s in S_t}(q[t] . k[s] / sqrt(hd)) v[s]``
  a head; the heads joined, ``Wo``.
  Alignment: ``p[t, s]`` = the H heads' probabilities on ``S_t``, averaged,
  under `stop_gradient`; ``L_I = mean_t KL(p[t, .] || softmax_{s in
  S_t}(I[t, .]))``.  The step's objective is the token cross-entropy plus
  `alignment_weight` times the mean of ``L_I`` over the layers; by the two
  `stop_gradient`s the cross-entropy moves everything but WqI, WkI, Ww, and
  ``L_I`` moves those three alone.
- feed-forward: ``g = softmax(x W_r)`` over all `router_experts` outputs, in
  float32; the chosen are the top-k of ``g``; ``w_e = g_e / (sum of the
  chosen g)`` (`norm_topk_prob`); the result is the sum over the experts that
  are chosen AND held here (`num_experts` of them from `first_expert` on) of
  ``w_e * W2_e(silu(W1_e x) * W3_e x)``.  What the absent experts would add
  is left out.  No bias, no scaling, no shared expert, no capacity, no
  dropped token, no auxiliary loss.
- final RMS norm; logits against a head matrix of its own over the rows of
  the vocabulary held here; mean token cross-entropy.

Straight `jax.numpy`: no kernel, no sort but `lax.top_k`'s, no grouped
product, no import of the program.  Attention is computed in blocks of
query rows under `jax.checkpoint`, one block at a time (at 16384 positions
and 32 heads a block of 256 rows holds 0.54 GB of scores).  `fp8` is the
control's lower precision (`common`): it reaches every contraction but the
router's.  ``select=False`` is the second control: every causal key is
visible (a dense decoder), the alignment term taken over all of them.

`loss_sum` hands back the cross-entropy as its VALUE (what the program's step
reports) with the whole objective's GRADIENT; `alignment_loss` gives the
``L_I`` of each layer.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import contraction, dot, softmax_xent

ATTENTION_QUERY_BLOCK = 256


def check_supported(cfg):
    """Raise for a configuration whose equations are not the ones above."""
    sa = cfg["sa_config"]
    if sa.get("indexer_num_kv_heads", 1) != 1:
        raise ValueError("the indexer has one key head")
    if cfg.get("mlp_only_layers") or cfg.get("decoder_sparse_step", 1) != 1:
        raise ValueError("every layer's feed-forward is routed")
    if cfg.get("use_sliding_window") or cfg.get("sliding_window"):
        raise ValueError("sliding windows are not built")
    scaling = cfg.get("rope_scaling") or {}
    if scaling.get("rope_type", "default") != "default":
        raise ValueError("scaled rotary positions are not built")
    half = cfg["head_dim"] // 2
    if sum(scaling.get("mrope_section", [half])) != half:
        raise ValueError("mrope_section has to cover the %d frequency pairs "
                         "of a head" % half)
    if cfg.get("attention_bias"):
        raise ValueError("attention biases are not built")
    if cfg["num_attention_heads"] % cfg["num_key_value_heads"]:
        raise ValueError("query heads in whole groups a key/value head")


def _sections(cfg):
    return tuple((cfg.get("rope_scaling") or {}).get(
        "mrope_section", [cfg["head_dim"] // 2]))


def param_table(cfg):
    """Ordered ``name -> (shape, init)`` in the program's parameter order:
    the embedding and the head first (the model's own leaves), then the
    layers."""
    check_supported(cfg)
    d, v, hd = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    sa = cfg["sa_config"]
    ih, iw = sa["indexer_num_heads"], sa["indexer_head_dim"]
    std = cfg.get("initializer_range", 0.02)
    normal, ones = ("normal", std), ("ones",)
    f, held = cfg["moe_intermediate_size"], cfg["num_experts"]
    # the embedding may start at a scale of its own (`assumed`, weights)
    t = {"embed": ((v, d), ("normal", cfg.get(
        "embedding_initializer_range", std)))}
    if not cfg.get("tie_word_embeddings", False):
        t["head"] = ((v, d), normal)
    for i in range(cfg["num_hidden_layers"]):
        p = "l%d." % i
        t[p + "attn_norm"] = ((d,), ones)
        t[p + "wq"] = ((q, d), normal)
        t[p + "wk"] = ((kv, d), normal)
        t[p + "wv"] = ((kv, d), normal)
        t[p + "wo"] = ((d, q), normal)
        t[p + "q_norm"] = ((hd,), ones)
        t[p + "k_norm"] = ((hd,), ones)
        t[p + "index_wq"] = ((ih * iw, d), normal)
        t[p + "index_wk"] = ((iw, d), normal)
        t[p + "index_ww"] = ((ih, d), normal)
        t[p + "ffn_norm"] = ((d,), ones)
        t[p + "router"] = ((cfg["router_experts"], d), normal)
        t[p + "expert_w1"] = ((held, d, f), normal)
        t[p + "expert_w3"] = ((held, d, f), normal)
        t[p + "expert_w2"] = ((held, f, d), normal)
    t["final_norm"] = ((d,), ones)
    return t


INDEXER_LEAVES = ("index_wq", "index_wk", "index_ww")


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def text_positions(batch, seq):
    """The three position axes of plain text: all ``0 .. seq - 1``."""
    return jnp.broadcast_to(jnp.arange(seq, dtype=jnp.float32),
                            (3, batch, seq))


def rope3(x, positions, theta, sections):
    """(B, heads, S, hd) with *positions* (3, B, S): the pair ``(x[f], x[f +
    hd/2])`` turns by ``positions[a(f)] * theta^(-2f / hd)``, the frequencies
    dealt to the axes in chunks of *sections*."""
    half = x.shape[-1] // 2
    inv = 1.0 / (float(theta) ** (np.arange(half, dtype=np.float64) / half))
    axis = np.repeat(np.arange(len(sections)), sections)        # a(f)
    pos = jnp.asarray(positions, jnp.float32)[axis]             # (half, B, S)
    ang = pos.transpose(1, 2, 0) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]  # (B, 1, S, half)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def index_scores(qi, ki, w, fp8=False):
    """``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``: qi (B, R, J, di),
    ki (B, S, di), w (B, R, J) -> (B, R, S)."""
    a = contraction(lambda x, y: jnp.einsum("brjd,bsd->brjs", x, y), qi, ki,
                    fp8)
    return jnp.sum(w[..., None] * jax.nn.relu(a), axis=2)


def selection(scores, row0, topk):
    """Which keys each of the rows ``row0 ..`` keeps, (B, R, S) bool: the
    causal keys that score at least the row's *topk*-th largest causal
    score, ties with it included; every causal key of a row that has no
    more than *topk*."""
    rows = row0 + jnp.arange(scores.shape[1])
    causal = rows[:, None] >= jnp.arange(scores.shape[2])[None, :]
    masked = jnp.where(causal, scores, -jnp.inf)
    k = min(int(topk), scores.shape[2])
    kth = jax.lax.top_k(masked, k)[0][..., k - 1:k]
    return (masked >= kth) & causal


def _attend(q, qi, w, row0, k, v, ki, topk, select, fp8):
    """Query rows ``row0 ..``: the attention output (B, KV, G, R, hd) and
    the rows' alignment divergences summed (B,).  q (B, KV, G, R, hd); qi
    (B, R, J, di); w (B, R, J); k, v (B, KV, S, hd); ki (B, S, di)."""
    scores = index_scores(qi, ki, w, fp8)
    if select:
        sel = selection(jax.lax.stop_gradient(scores), row0, topk)
    else:
        rows = row0 + jnp.arange(q.shape[3])
        sel = jnp.broadcast_to(
            rows[:, None] >= jnp.arange(k.shape[2])[None, :], scores.shape)
    att = contraction(
        lambda a, b: jnp.einsum("bjgqd,bjkd->bjgqk", a, b), q, k, fp8
    ) / math.sqrt(q.shape[-1])
    probs = jax.nn.softmax(
        jnp.where(sel[:, None, None], att, -jnp.inf), -1)
    out = contraction(
        lambda a, b: jnp.einsum("bjgqk,bjkd->bjgqd", a, b), probs, v, fp8)
    # the alignment term: the heads' mean distribution, held fixed, against
    # the indexer's own over the same keys
    target = jax.lax.stop_gradient(jnp.mean(probs, axis=(1, 2)))
    logp = jax.nn.log_softmax(jnp.where(sel, scores, -jnp.inf), -1)
    kl = jnp.where(target > 0,
                   target * (jnp.log(jnp.where(target > 0, target, 1.0))
                             - jnp.where(sel, logp, 0.0)), 0.0)
    return out, jnp.sum(kl, axis=(1, 2))


def attention(p, pre, cfg, x, positions, fp8, select=True):
    """``(attn(x), L_I of each row (B,))``."""
    b, s, _ = x.shape
    heads, kv, hd = cfg["num_attention_heads"], \
        cfg["num_key_value_heads"], cfg["head_dim"]
    sa, eps, theta = cfg["sa_config"], cfg["rms_norm_eps"], cfg["rope_theta"]
    ih, iw = sa["indexer_num_heads"], sa["indexer_head_dim"]

    def split(y, n):
        return y.reshape(b, s, n, hd)

    q = rms(split(dot(x, p[pre + "wq"].T, fp8), heads), p[pre + "q_norm"],
            eps).transpose(0, 2, 1, 3)
    k = rms(split(dot(x, p[pre + "wk"].T, fp8), kv), p[pre + "k_norm"],
            eps).transpose(0, 2, 1, 3)
    v = split(dot(x, p[pre + "wv"].T, fp8), kv).transpose(0, 2, 1, 3)
    q = rope3(q, positions, theta, _sections(cfg))
    k = rope3(k, positions, theta, _sections(cfg))
    xd = jax.lax.stop_gradient(x)
    qi = dot(xd, p[pre + "index_wq"].T, fp8).reshape(b, s, ih, iw)
    ki = dot(xd, p[pre + "index_wk"].T, fp8)
    w = dot(xd, p[pre + "index_ww"].T, fp8) * (ih ** -0.5 * iw ** -0.5)

    blk = ATTENTION_QUERY_BLOCK if s % ATTENTION_QUERY_BLOCK == 0 else s
    n = s // blk
    block = jax.checkpoint(functools.partial(
        _attend, k=k, v=v, ki=ki, topk=sa["topk"], select=select, fp8=fp8))
    out, kl = jax.lax.map(
        lambda at: block(*at),
        (q.reshape(b, kv, heads // kv, n, blk, hd).transpose(3, 0, 1, 2, 4, 5),
         qi.reshape(b, n, blk, ih, iw).transpose(1, 0, 2, 3, 4),
         w.reshape(b, n, blk, ih).transpose(1, 0, 2, 3),
         jnp.arange(0, s, blk)))
    out = out.transpose(1, 2, 3, 0, 4, 5).reshape(b, heads, s, hd)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, heads * hd)
    return dot(out, p[pre + "wo"].T, fp8), jnp.sum(kl, axis=0) / s


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


def routed(p, pre, cfg, x, fp8, first=None, held=None):
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


def _layer(p, h, positions, i, cfg, fp8, select):
    """``(h after layer i, the layer's L_I of each row)``."""
    pre, eps = "l%d." % i, cfg["rms_norm_eps"]
    att, kl = attention(p, pre, cfg, rms(h, p[pre + "attn_norm"], eps),
                        positions, fp8, select)
    h = h + att
    return h + routed(p, pre, cfg, rms(h, p[pre + "ffn_norm"], eps),
                      fp8), kl


def _forward(p, cfg, tokens, positions, fp8, select):
    """Logits (B, S, vocab held) and each layer's L_I of each row
    (layers, B)."""
    b, s = tokens.shape
    if positions is None:
        positions = text_positions(b, s)
    h = jnp.take(p["embed"], tokens, axis=0)
    kls = []
    for i in range(cfg["num_hidden_layers"]):
        h, kl = jax.checkpoint(functools.partial(
            _layer, i=i, cfg=cfg, fp8=fp8, select=select))(p, h, positions)
        kls.append(kl)
    h = rms(h, p["final_norm"], cfg["rms_norm_eps"])
    return dot(h, p.get("head", p["embed"]).T, fp8), jnp.stack(kls)


def logits(p, cfg, tokens, fp8=False, positions=None, select=True):
    """(B, S) int tokens -> (B, S, vocab held) float32 logits."""
    return _forward(p, cfg, tokens, positions, fp8, select)[0]


def alignment_loss(p, cfg, tokens, positions=None, select=True):
    """``L_I`` of each layer, the mean over the batch's rows: (layers,)."""
    return jnp.mean(_forward(p, cfg, tokens, positions, False, select)[1], -1)


def objective_sum(p, cfg, tokens, labels, fp8=False, positions=None,
                  select=True):
    """Sum over rows of each row's objective, and of its cross-entropy
    alone."""
    lg, kls = _forward(p, cfg, tokens, positions, fp8, select)
    xent = jnp.sum(jnp.mean(softmax_xent(lg, labels.astype(jnp.int32)), -1))
    term = cfg.get("alignment_weight", 1.0) * jnp.sum(jnp.mean(kls, axis=0))
    return xent + term, xent


def loss_sum(p, cfg, tokens, labels, fp8=False, select=True):
    """Sum over rows of each row's mean token cross-entropy as the VALUE
    (what the program's step reports; blocks of rows add up to batch * the
    program's mean loss), with the whole objective's GRADIENT: the alignment
    term is added and its own value taken off again."""
    total, xent = objective_sum(p, cfg, tokens, labels, fp8, select=select)
    return total - jax.lax.stop_gradient(total - xent)


def expert_counts(p, cfg, tokens):
    """Per layer, the tokens assigned to each of the router's outputs at
    these parameters: ``(layers, E)`` int32, by the reference's own forward
    pass (what the program's counters count)."""
    b, s = tokens.shape
    positions = text_positions(b, s)
    h = jnp.take(p["embed"], tokens, axis=0)
    rows = []
    for i in range(cfg["num_hidden_layers"]):
        pre, eps = "l%d." % i, cfg["rms_norm_eps"]
        h = h + attention(p, pre, cfg, rms(h, p[pre + "attn_norm"], eps),
                          positions, False)[0]
        x = rms(h, p[pre + "ffn_norm"], eps)
        chosen, _ = route(cfg, x, p[pre + "router"])
        rows.append(jnp.sum(
            chosen.reshape(-1, 1) == jnp.arange(cfg["router_experts"]),
            axis=0, dtype=jnp.int32))
        h = h + routed(p, pre, cfg, x, False)
    return jnp.stack(rows)


def selected_keys(p, pre, cfg, x):
    """The selection of one layer on its normed input *x* (B, S, d): (B, S,
    S) bool, for the tests that compare it key for key."""
    b, s, _ = x.shape
    sa = cfg["sa_config"]
    ih, iw = sa["indexer_num_heads"], sa["indexer_head_dim"]
    qi = dot(x, p[pre + "index_wq"].T).reshape(b, s, ih, iw)
    ki = dot(x, p[pre + "index_wk"].T)
    w = dot(x, p[pre + "index_ww"].T) * (ih ** -0.5 * iw ** -0.5)
    return selection(index_scores(qi, ki, w), 0, sa["topk"])


# rows of a batch do not interact: the step may run in blocks of rows
ROWS_INDEPENDENT = True
