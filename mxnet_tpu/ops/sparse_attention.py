"""Learned sparse attention's own parts: the indexer's scores, each query's
selection (the keys that score at least its row's k-th largest), and the
alignment term that trains the indexer against the attention it feeds.

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        s <= t
    S_t     = {s <= t : I[t, s] >= the topk-th largest of I[t, :t + 1]}
    L       = mean_t KL(mean_h P_h[t, .] || softmax_{S_t} I[t, .])

(DeepSeek-V3.2-Exp's indexer: J heads of width di over ONE key head.)  The
attention itself is `ops/attention.py` `selected_attention`: the flash
kernels with the selection as an operand.

No ``S x S`` array of scores, probabilities or masks is written whole:
`index_select` holds a block of score rows at a time and hands back the
selection one BIT a pair (`attention.pack_selection`: 33.5 MB at 16384
tokens, both ways round, for the forward kernel and for the backward),
which is what the backward reads: the forward's selection bit for bit.
The k-th largest is found by counting, not by sorting: the scores as
integers whose order is theirs, and the threshold built bit by bit from the
top (32 counts of "how many are at least this").  Ties with the k-th
largest are all kept, so a row with ties holds more than `topk` keys.

Which path runs where, decided by the platform the program is lowered for
and by what the code sees in its input: on the TPU (one device, or inside a
`shard_map` over every mesh axis) at a sequence in whole blocks, two Mosaic
kernels of this module, ``mx_dsa_select`` and ``mx_dsa_align``; on every
other platform and at every other shape the same arithmetic in `jax.numpy`,
a block of query rows at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._precision import matmul_precision
from .attention import (_LANES, _NEG_INF, _SEL_BITS, _lanes_to, _mxu_dot,
                        _NN, _NT, _pad_selection, _traced_inline,
                        mosaic_runs_here, pack_selection, unpack_selection)

__all__ = ["index_select", "alignment_term"]

_INT_MIN = -(1 << 31)
#: query rows a step holds scores for: of the `jax.numpy` bodies, and of
#: both kernels (a word of the selection is 32 of them; 8 words a block are
#: a whole sublane tile)
ROWS = 256
#: key columns a kernel works on at a time
SELECT_COLS = 512
ALIGN_COLS = 512
#: what the kernels ask Mosaic for (`vmem_limit_bytes`) over their blocks
#: and scratch, for the tiles' temporaries
_VMEM_SPARE = 16 << 20


def _sortable(x):
    """float32 as int32 whose signed order is the floats' (-0.0 has been
    made +0.0 before); its own inverse."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _unsortable(key):
    return jax.lax.bitcast_convert_type(
        key ^ ((key >> 31) & 0x7FFFFFFF), jnp.float32)


def _build_threshold(count_at_least, k, shape):
    """Per row the largest int32 ``T`` with ``count_at_least(T) >= k``, built
    bit by bit from the top in the biased (unsigned-order) pattern;
    ``INT_MIN + 1`` at the least, which every real key passes."""
    def step(i, biased):
        cand = biased | jnp.left_shift(jnp.int32(1), 31 - i)
        return jnp.where(count_at_least(cand ^ _INT_MIN) >= k, cand, biased)

    biased = jax.lax.fori_loop(0, 32, step, jnp.zeros(shape, jnp.int32))
    return jnp.maximum(biased ^ _INT_MIN, _INT_MIN + 1)


def _scores(qi, ki, w):
    """``I`` for a block of rows: qi (B, R, J, di), ki (B, S, di), w (B, R,
    J) float32 -> (B, R, S) float32."""
    a = jnp.einsum("brjd,bsd->brjs", qi, ki,
                   precision=matmul_precision(qi.dtype, ki.dtype),
                   preferred_element_type=jnp.float32)
    return jnp.sum(w[..., None] * jax.nn.relu(a), axis=2) + 0.0


def _blocks(seq):
    blk = ROWS if seq % ROWS == 0 else seq
    return blk, seq // blk


def _causal(row0, rows, seq):
    return jnp.arange(seq)[None, :] <= row0 + jnp.arange(rows)[:, None]


# ---------------------------------------------------------------------------
# The selection.
# ---------------------------------------------------------------------------

def _select_rows(qi, ki, w, topk):
    """`index_select` in `jax.numpy`, a block of query rows at a time."""
    b, s, heads = w.shape
    blk, n = _blocks(s)
    qi = qi.reshape(b, n, blk, heads, -1)

    def rows(at):
        qb, wb, row0 = at
        score = _scores(qb, ki, wb)
        causal = _causal(row0, blk, s)
        key = jnp.where(causal, _sortable(score), _INT_MIN)
        thr = _build_threshold(
            lambda t: jnp.sum(key >= t[..., None], -1, dtype=jnp.int32),
            min(topk, s), key.shape[:2])
        chosen = (key >= thr[..., None]) & causal
        lse = jax.nn.logsumexp(jnp.where(chosen, score, -jnp.inf), -1)
        return (pack_selection(chosen),
                pack_selection(chosen.transpose(0, 2, 1)), lse)

    sel_q, sel_k, lse = jax.lax.map(
        rows, (qi.transpose(1, 0, 2, 3, 4),
               w.reshape(b, n, blk, heads).transpose(1, 0, 2, 3),
               jnp.arange(0, s, blk)))
    groups = sel_q.shape[2]
    return (sel_q.transpose(1, 0, 2, 3).reshape(b, n * groups, s),
            sel_k.transpose(1, 2, 0, 3).reshape(b, sel_k.shape[2], s),
            lse.transpose(1, 0, 2).reshape(b, s))


def _fold(x):
    """(rows, n * 128) summed over its lane tiles: (rows, 128)."""
    return sum(x[:, i * _LANES:(i + 1) * _LANES]
               for i in range(x.shape[1] // _LANES))


def _pack_rows(chosen):
    """(rows, cols) float32 of 0 / 1 -> (rows / 32, cols) int32 words, bit
    r % 32 of word r // 32; in two float32 sums of 16 bits each (exact),
    which the sublane reduction takes."""
    rows, cols = chosen.shape
    g = chosen.reshape(rows // _SEL_BITS, _SEL_BITS, cols)
    weight = jnp.left_shift(1, jax.lax.broadcasted_iota(
        jnp.int32, (1, 16, cols), 1)).astype(jnp.float32)
    lo = jnp.sum(g[:, :16] * weight, axis=1).astype(jnp.int32)
    hi = jnp.sum(g[:, 16:] * weight, axis=1).astype(jnp.int32)
    return lo | (hi << 16)


@_traced_inline
def _select_kernel(qi_ref, ki_ref, w_ref, selq_ref, selk_ref, lse_ref,
                   key_ref, *, topk, heads, seq, cols):
    """One block of query rows: their scores against every causal key into
    *key_ref* as sortable integers, each row's threshold by counting, then
    the chosen keys' bits both ways round and the logsumexp of their
    scores."""
    rows = key_ref.shape[0]
    row0 = pl.program_id(1) * rows
    n_all = seq // cols
    n_vis = jnp.minimum(n_all, (row0 + rows + cols - 1) // cols)
    row = row0 + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)

    def at(c):
        return pl.ds(pl.multiple_of(c * cols, cols), cols)

    def score_chunk(c, carry):
        kc = ki_ref[0, at(c), :]
        acc = jnp.zeros((rows, cols), jnp.float32)
        for j in range(heads):
            a = _mxu_dot(qi_ref[0, j], kc, _NT)
            acc = acc + w_ref[0, :, j:j + 1] * jnp.maximum(a, 0.0)
        col = c * cols + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
        key_ref[:, at(c)] = jnp.where(col <= row, _sortable(acc + 0.0),
                                      _INT_MIN)
        return carry

    jax.lax.fori_loop(0, n_vis, score_chunk, 0)

    def count_at_least(t):
        def chunk(c, cnt):
            return cnt + _fold((key_ref[:, at(c)] >= _lanes_to(t, cols)
                                ).astype(jnp.int32))
        cnt = jax.lax.fori_loop(0, n_vis, chunk,
                                jnp.zeros((rows, _LANES), jnp.int32))
        # at most `seq` a row: exact in float32, whose lane sum Mosaic has
        return jnp.sum(cnt.astype(jnp.float32), axis=1, keepdims=True)

    thr = _build_threshold(count_at_least, min(topk, seq), (rows, _LANES))

    # the largest causal score is always chosen: the row's maximum
    def top_chunk(c, m):
        key = key_ref[:, at(c)]
        return jnp.maximum(m, jnp.where(
            key > _INT_MIN, _unsortable(key), -jnp.inf
        ).max(axis=1, keepdims=True))
    top = jax.lax.fori_loop(0, n_vis, top_chunk,
                            jnp.full((rows, _LANES), -jnp.inf))

    def pack_chunk(c, total):
        key = key_ref[:, at(c)]
        chosen = key >= _lanes_to(thr, cols)
        total = total + _fold(jnp.where(
            chosen, jnp.exp(_unsortable(key) - _lanes_to(top, cols)), 0.0))
        bits = chosen.astype(jnp.float32)
        selq_ref[0, :, at(c)] = _pack_rows(bits)
        selk_ref[0, pl.ds(pl.multiple_of(c * (cols // _SEL_BITS),
                                         cols // _SEL_BITS),
                          cols // _SEL_BITS), :] = _pack_rows(bits.T)
        return total

    total = jax.lax.fori_loop(0, n_vis, pack_chunk,
                              jnp.zeros((rows, _LANES), jnp.float32))

    def blank_chunk(c, carry):
        selq_ref[0, :, at(c)] = jnp.zeros((rows // _SEL_BITS, cols),
                                          jnp.int32)
        selk_ref[0, pl.ds(pl.multiple_of(c * (cols // _SEL_BITS),
                                         cols // _SEL_BITS),
                          cols // _SEL_BITS), :] = jnp.zeros(
            (cols // _SEL_BITS, rows), jnp.int32)
        return carry

    jax.lax.fori_loop(n_vis, n_all, blank_chunk, 0)
    lse = top + jnp.log(jnp.sum(total, axis=1, keepdims=True))
    lse_ref[0, :, :] = lse.T[:1]


def _select_vmem(seq, heads, width, itemsize):
    """What `mx_dsa_select` holds: the block's keys, the indexer's key head
    over the whole sequence, a block of indexer queries and the outputs'
    blocks, each block twice."""
    lanes = -(-width // _LANES) * _LANES
    return ROWS * seq * 4 + 2 * (
        seq * lanes * itemsize + heads * ROWS * lanes * itemsize
        + ROWS * _LANES * 4 + 2 * (ROWS // _SEL_BITS) * seq * 4 + 8 * ROWS * 4)


@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def _select_pallas(qi, ki, w, topk, interpret=False):
    b, s, heads = w.shape
    width = ki.shape[-1]
    # a head of the indexer's queries at a time: (B, J, S, di)
    qh = qi.reshape(b, s, heads, width).transpose(0, 2, 1, 3)
    groups = ROWS // _SEL_BITS
    sel_q, sel_k, lse = pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, heads=heads, seq=s,
                          cols=SELECT_COLS),
        grid=(b, s // ROWS),
        in_specs=[
            pl.BlockSpec((1, heads, ROWS, width), lambda i, r: (i, 0, r, 0)),
            pl.BlockSpec((1, s, width), lambda i, r: (i, 0, 0)),
            pl.BlockSpec((1, ROWS, heads), lambda i, r: (i, r, 0))],
        out_specs=[
            pl.BlockSpec((1, groups, s), lambda i, r: (i, r, 0)),
            pl.BlockSpec((1, s // _SEL_BITS, ROWS), lambda i, r: (i, 0, r)),
            pl.BlockSpec((1, 1, ROWS), lambda i, r: (i, 0, r))],
        out_shape=[
            jax.ShapeDtypeStruct((b, s // _SEL_BITS, s), jnp.int32),
            jax.ShapeDtypeStruct((b, s // _SEL_BITS, s), jnp.int32),
            jax.ShapeDtypeStruct((b, 1, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((ROWS, s), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_select_vmem(
                s, heads, width, qi.dtype.itemsize) + _VMEM_SPARE),
        interpret=interpret, name="mx_dsa_select",
    )(qh, ki, w)
    return sel_q, sel_k, lse.reshape(b, s)


def select_plan(seq, heads, width, dtype):
    """The form `index_select` runs a sequence in, for the `mx.dsa.plan`
    span: the kernel's rows on chip and what it asks Mosaic for, or the
    `jax.numpy` body's block."""
    if _kernels_tile(seq):
        return {"select": "kernel", "select_rows_on_chip": ROWS,
                "select_cols": SELECT_COLS,
                "select_vmem_limit_bytes": _select_vmem(
                    seq, heads, width, jnp.dtype(dtype).itemsize)
                + _VMEM_SPARE}
    return {"select": "xla", "select_rows_on_chip": None,
            "select_rows_a_block": _blocks(seq)[0],
            "select_vmem_limit_bytes": None}


def _kernels_tile(seq):
    """Whether the two kernels take a sequence: whole blocks of rows and
    of both kernels' columns, on one device."""
    return not (seq % ROWS or seq % SELECT_COLS or seq % ALIGN_COLS) \
        and mosaic_runs_here()


def index_select(qi, ki, w, topk, interpret=False):
    """Each query's chosen keys from the indexer's projections: qi ``(B, S,
    J * di)``, ki ``(B, S, di)``, w ``(B, S, J)`` float32.  Returns
    ``(sel_q, sel_k, lse)``: the selection as `selected_attention` takes it
    (`pack_selection` of the ``(B, S, S)`` mask and of its transpose) and
    the logsumexp of each row's chosen scores ``(B, S)``.  No gradient:
    the selection is a choice."""
    qi, ki, w = (jax.lax.stop_gradient(x) for x in (qi, ki, w))
    w = w.astype(jnp.float32)
    topk = int(topk)
    body = functools.partial(_select_rows, topk=topk)
    with jax.named_scope("mx.dsa.select"):
        if interpret:
            return _select_pallas(qi, ki, w, topk, True)
        if not _kernels_tile(w.shape[1]):
            return body(qi, ki, w)
        return jax.lax.platform_dependent(
            qi, ki, w, default=body,
            tpu=functools.partial(_select_pallas, topk=topk))


# ---------------------------------------------------------------------------
# The alignment term.
# ---------------------------------------------------------------------------

def _align_rows(qi, ki, w, q, k, lse, lse_i, sel_q, sm_scale):
    """The term in `jax.numpy`, a block of query rows at a time; JAX
    differentiates it.  q (B, H, S, d) against k (B, KV, S, d)."""
    b, s, heads = w.shape
    h, kv = q.shape[1], k.shape[1]
    blk, n = _blocks(s)
    groups = -(-blk // _SEL_BITS)

    @jax.checkpoint
    def rows(qib, wb, qb, lseb, lse_ib, words, row0, ki):
        score = _scores(qib, ki, wb)
        seen = unpack_selection(words, blk) & _causal(row0, blk, s)
        att = jnp.einsum("bjgqd,bjkd->bjgqk",
                         qb.reshape(b, kv, h // kv, blk, -1), k,
                         precision=matmul_precision(qb.dtype, k.dtype),
                         preferred_element_type=jnp.float32) * sm_scale
        att = att.reshape(b, h, blk, s)
        p = jnp.exp(jnp.where(seen[:, None], att, _NEG_INF)
                    - lseb[..., None])
        target = jnp.mean(p, axis=1)
        # the rows' own logsumexp, so that JAX's derivative carries the
        # softmax's part (the kept one, *lse_ib*, is the kernel's)
        logp = score - jax.nn.logsumexp(
            jnp.where(seen, score, -jnp.inf), -1, keepdims=True)
        return jnp.sum(jnp.where(
            seen & (target > 0),
            target * (jnp.log(jnp.where(target > 0, target, 1.0)) - logp),
            0.0))

    words = _pad_selection(sel_q, n * groups * _SEL_BITS, s)
    parts = jax.lax.map(
        lambda at: rows(*at, ki),
        (qi.reshape(b, n, blk, heads, -1).transpose(1, 0, 2, 3, 4),
         w.reshape(b, n, blk, heads).transpose(1, 0, 2, 3),
         q.reshape(b, h, n, blk, -1).transpose(2, 0, 1, 3, 4),
         lse.reshape(b, h, n, blk).transpose(2, 0, 1, 3),
         lse_i.reshape(b, n, blk).transpose(1, 0, 2),
         words.reshape(b, n, groups, s).transpose(1, 0, 2, 3),
         jnp.arange(0, s, blk)))
    return jnp.sum(parts) / (b * s)


def _align_body(qi, ki, w, q, k, lse, lse_i, sel_q, sm_scale):
    """``(L, dL/dqi, dL/dki, dL/dw)`` by the `jax.numpy` body."""
    loss, grads = jax.value_and_grad(_align_rows, argnums=(0, 1, 2))(
        qi, ki, w, q, k, lse, lse_i, sel_q, sm_scale)
    return (loss,) + grads


def _fold_rows(x):
    """(n * 8, cols) summed over its sublane tiles: (8, cols)."""
    return x.reshape(-1, 8, x.shape[1]).sum(axis=0)


@_traced_inline
def _align_kernel(row_ref, col_ref, qit_ref, ki_ref, kit_ref, w_ref, q_ref,
                  k_ref, lse_ref, lsei_ref, sel_ref, dqi_ref, dki_ref, dw_ref,
                  kl_ref, dqi_acc, dw_acc, kl_acc, kept_ref, *, sm_scale,
                  cols):
    """One tile of key columns by query rows, every head of it: the heads'
    mean probability from q, k and the kept logsumexps, the indexer's
    scores and their softmax from the kept logsumexp, the divergence's
    part, and ``dI = pi - p`` carried back to qI, kI and w.  A grid step is
    a tile at or under the diagonal, *row_ref* and *col_ref* saying which:
    a row of tiles after another.

    The tile is held keys down, queries along the lanes, as the flash
    backward holds its own: what belongs to a query (its logsumexps, its
    head weights) is a row laid down the tile, every product is a plain
    one or contracts both operands' last axis, and nothing is turned but
    the selection's bits, once a tile.  qI and kI arrive turned (width by
    tokens; kI as it is too), and their gradients leave so: ``g_j`` is the
    latched operand of both its products, the width's 64 rows stream
    against it, and the accumulators are whole lanes.  Each product is
    formed once: the indexer's ``relu(a_j)`` stay on chip in *kept_ref*
    (float32: `dw` sums the numbers the score was made of) for the
    gradient's loop.  kI's gradient accumulates in its output block, which
    spans the sequence; the rows' own in scratch over the row's tiles."""
    tile = pl.program_id(1)
    iq, ik = row_ref[tile], col_ref[tile]
    heads, _, rows = qit_ref.shape[1:]
    h, kv = q_ref.shape[1], k_ref.shape[1]
    shape = (cols, rows)

    @pl.when(tile == 0)
    def _zero_keys():
        dki_ref[...] = jnp.zeros(dki_ref.shape, jnp.float32)

    @pl.when(ik == 0)
    def _zero_rows():
        dqi_acc[...] = jnp.zeros(dqi_acc.shape, jnp.float32)
        dw_acc[...] = jnp.zeros(dw_acc.shape, jnp.float32)
        kl_acc[...] = jnp.zeros(kl_acc.shape, jnp.float32)

    col = ik * cols + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    row = iq * rows + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    # the bits are the forward's, a word 32 query rows: turned as float32,
    # which the XLU turns as it is
    seen = (unpack_selection(sel_ref[0]).astype(jnp.float32).T > 0) \
        & (col <= row)
    # the indexer's products first: their stores, and the score's sums
    # below, ride under the heads' products, which leave those slots idle
    ki = ki_ref[0]
    for j in range(heads):
        kept_ref[j] = jnp.maximum(_mxu_dot(ki, qit_ref[0, j], _NN), 0.0)
    # one select a pair, after the heads are summed: an unseen pair's terms
    # may overflow to inf, never to nan (none is negative)
    total = jnp.zeros(shape, jnp.float32)
    score = jnp.zeros(shape, jnp.float32)
    for j in range(heads):
        for i in range(j * h // heads, (j + 1) * h // heads):
            s = _mxu_dot(k_ref[0, i // (h // kv)], q_ref[0, i], _NT) \
                * sm_scale
            total = total + jnp.exp(s - lse_ref[0, i:i + 1, :])
        score = score + w_ref[0, j:j + 1, :] * kept_ref[j]
    target = jnp.where(seen, total, 0.0) * (1.0 / h)
    logp = score + 0.0 - lsei_ref[0]
    kl_acc[...] += _fold_rows(jnp.where(
        seen & (target > 0),
        target * (jnp.log(jnp.where(target > 0, target, 1.0)) - logp), 0.0))
    d_score = jnp.where(seen, jnp.exp(logp), 0.0) - target
    at = pl.ds(pl.multiple_of(ik * cols, cols), cols)
    for j in range(heads):
        kept = kept_ref[j]
        dw_acc[j] += _fold_rows(d_score * kept)
        g = jnp.where(kept > 0, d_score * w_ref[0, j:j + 1, :], 0.0
                      ).astype(ki.dtype)
        dqi_acc[j] += _mxu_dot(kit_ref[0], g, _NN)
        dki_ref[0, :, at] += _mxu_dot(qit_ref[0, j], g, _NT)

    @pl.when(ik == (iq * rows + rows - 1) // cols)
    def _finish():
        dqi_ref[0] = dqi_acc[...].astype(dqi_ref.dtype)
        for j in range(heads):
            dw_ref[0, j:j + 1, :] = jnp.sum(dw_acc[j], axis=0, keepdims=True)
        kl_ref[0] = jnp.sum(kl_acc[...], axis=0, keepdims=True)


def _align_kept_bytes(heads):
    """`mx_dsa_align`'s ``relu(a_j)`` of a tile, float32."""
    return heads * ALIGN_COLS * ROWS * 4


def _align_vmem(seq, heads, width, h, kv, d, itemsize):
    """What `mx_dsa_align` holds: its blocks (kI's gradient spans the
    sequence), each twice, the rows' accumulators and the kept
    ``relu(a_j)``."""
    lanes, wide = -(-width // _LANES) * _LANES, -(-d // _LANES) * _LANES
    blocks = (2 * heads * width * ROWS + ALIGN_COLS * (lanes + width)
              + h * ROWS * wide + kv * ALIGN_COLS * wide) * itemsize \
        + (2 * heads + h + 2 * 8) * ROWS * 4 \
        + (ROWS // _SEL_BITS) * ALIGN_COLS * 4 + seq * width * 4
    return 2 * blocks + (heads * (width + 8) + 8) * ROWS * 4 \
        + _align_kept_bytes(heads)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _align_pallas(qi, ki, w, q, k, lse, lse_i, sel_q, sm_scale,
                  interpret=False):
    b, s, heads = w.shape
    width, (h, kv, d) = ki.shape[-1], (q.shape[1], k.shape[1], q.shape[3])
    qit = qi.reshape(b, s, heads, width).transpose(0, 2, 3, 1)
    # the tiles at or under the diagonal, a row of them after another
    tiles = [(r, c) for r in range(s // ROWS)
             for c in range((r * ROWS + ROWS - 1) // ALIGN_COLS + 1)]
    row_of, col_of = (jnp.asarray(x, jnp.int32) for x in zip(*tiles))

    dqi, dki, dw, kl = pl.pallas_call(
        functools.partial(_align_kernel, sm_scale=sm_scale, cols=ALIGN_COLS),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, len(tiles)),
            in_specs=[
                pl.BlockSpec((1, heads, width, ROWS),
                             lambda i, t, row, col: (i, 0, 0, row[t])),
                pl.BlockSpec((1, ALIGN_COLS, width),
                             lambda i, t, row, col: (i, col[t], 0)),
                pl.BlockSpec((1, width, ALIGN_COLS),
                             lambda i, t, row, col: (i, 0, col[t])),
                pl.BlockSpec((1, heads, ROWS),
                             lambda i, t, row, col: (i, 0, row[t])),
                pl.BlockSpec((1, h, ROWS, d),
                             lambda i, t, row, col: (i, 0, row[t], 0)),
                pl.BlockSpec((1, kv, ALIGN_COLS, d),
                             lambda i, t, row, col: (i, 0, col[t], 0)),
                pl.BlockSpec((1, h, ROWS),
                             lambda i, t, row, col: (i, 0, row[t])),
                pl.BlockSpec((1, 1, ROWS),
                             lambda i, t, row, col: (i, 0, row[t])),
                pl.BlockSpec((1, ROWS // _SEL_BITS, ALIGN_COLS),
                             lambda i, t, row, col: (i, row[t], col[t]))],
            out_specs=[
                pl.BlockSpec((1, heads, width, ROWS),
                             lambda i, t, row, col: (i, 0, 0, row[t])),
                pl.BlockSpec((1, width, s), lambda i, t, row, col: (i, 0, 0)),
                pl.BlockSpec((1, heads, ROWS),
                             lambda i, t, row, col: (i, 0, row[t])),
                pl.BlockSpec((1, 1, ROWS),
                             lambda i, t, row, col: (i, 0, row[t]))],
            scratch_shapes=[
                pltpu.VMEM((heads, width, ROWS), jnp.float32),
                pltpu.VMEM((heads, 8, ROWS), jnp.float32),
                pltpu.VMEM((8, ROWS), jnp.float32),
                pltpu.VMEM((heads, ALIGN_COLS, ROWS), jnp.float32)]),
        out_shape=[
            jax.ShapeDtypeStruct((b, heads, width, s), qi.dtype),
            jax.ShapeDtypeStruct((b, width, s), jnp.float32),
            jax.ShapeDtypeStruct((b, heads, s), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, s), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_align_vmem(
                s, heads, width, h, kv, d, qi.dtype.itemsize)
            + _VMEM_SPARE),
        interpret=interpret, name="mx_dsa_align",
    )(row_of, col_of, qit, ki, ki.transpose(0, 2, 1), w.transpose(0, 2, 1),
      q, k, lse, lse_i[:, None], sel_q)
    scale = 1.0 / (b * s)
    return (jnp.sum(kl) * scale,
            (dqi.astype(jnp.float32) * scale).transpose(0, 3, 1, 2).reshape(
                qi.shape).astype(qi.dtype),
            (dki * scale).transpose(0, 2, 1).astype(ki.dtype),
            (dw * scale).transpose(0, 2, 1).astype(w.dtype))


def align_plan(seq, heads, width, h, kv, d, dtype):
    """The form `alignment_term` runs a sequence in, for the `mx.dsa.plan`
    span (`select_plan`'s sibling): the kernel's tile, the MXU products it
    issues a tile (the heads' scores, and the indexer's pre-activations and
    the two gradients they carry a head), what it keeps on chip between
    its loops and what it asks Mosaic for, or the `jax.numpy` body's
    block."""
    if _kernels_tile(seq):
        return {"align": "kernel", "align_rows_on_chip": ROWS,
                "align_cols": ALIGN_COLS,
                "align_products_a_tile": h + 3 * heads,
                "align_kept": "relu(a_j) of the tile, float32: %d bytes"
                % _align_kept_bytes(heads),
                "align_vmem_limit_bytes": _align_vmem(
                    seq, heads, width, h, kv, d, jnp.dtype(dtype).itemsize)
                + _VMEM_SPARE}
    return {"align": "xla", "align_rows_on_chip": None,
            "align_rows_a_block": _blocks(seq)[0],
            "align_vmem_limit_bytes": None}


def _align_value_and_grads(qi, ki, w, q, k, lse, lse_i, sel_q, sm_scale,
                           interpret):
    if interpret:
        return _align_pallas(qi, ki, w, q, k, lse, lse_i, sel_q, sm_scale,
                             True)
    body = functools.partial(_align_body, sm_scale=sm_scale)
    if not _kernels_tile(w.shape[1]):
        return body(qi, ki, w, q, k, lse, lse_i, sel_q)
    return jax.lax.platform_dependent(
        qi, ki, w, q, k, lse, lse_i, sel_q, default=body,
        tpu=functools.partial(_align_pallas, sm_scale=sm_scale))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _align(qi, ki, w, q, k, lse, lse_i, sel_q, sm_scale, interpret):
    return _align_value_and_grads(qi, ki, w, q, k, lse, lse_i, sel_q,
                                  sm_scale, interpret)[0]


def _align_fwd(qi, ki, w, q, k, lse, lse_i, sel_q, sm_scale, interpret):
    loss, *grads = _align_value_and_grads(qi, ki, w, q, k, lse, lse_i, sel_q,
                                          sm_scale, interpret)
    return loss, tuple(grads)


def _align_bwd(sm_scale, interpret, grads, g):
    # the term's value and its gradient leave one pass together: the
    # gradient does not wait for the cotangent, it is scaled by it
    return tuple((g * x.astype(jnp.float32)).astype(x.dtype)
                 for x in grads) + (None,) * 5


_align.defvjp(_align_fwd, _align_bwd)


def alignment_term(qi, ki, w, q, k, lse, lse_i, sel_q, sm_scale,
                   interpret=False):
    """``L = mean over rows and tokens of KL(p[t, .] || softmax_{S_t} I[t,
    .])``, ``p`` the mean over the heads of the attention's probabilities
    on the chosen keys: from the indexer's projections (as `index_select`
    takes them), the attention's q ``(B, H, S, d)`` and k ``(B, KV, S, d)``
    with the logsumexp `selected_attention` kept ``(B, H, S)``, the
    indexer's own (`index_select`'s), and the selection.  Its gradient
    reaches qi, ki and w alone: q, k and both logsumexps are held fixed."""
    q, k, lse, lse_i = (jax.lax.stop_gradient(x) for x in (q, k, lse, lse_i))
    with jax.named_scope("mx.dsa.align"):
        return _align(qi, ki, w.astype(jnp.float32), q, k, lse, lse_i, sel_q,
                      float(sm_scale), interpret)
