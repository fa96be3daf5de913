"""Scaled-dot-product attention: the long-context stance of this framework.

The reference predates Transformers — its only artifact is
``_contrib_div_sqrt_dim`` (reference: src/operator/contrib/transformer.cc:33)
and sequence scaling comes from bucketing + the fused RNN op (SURVEY §5.7).
On TPU the idiomatic equivalent is one attention op with a flash (blockwise,
online-softmax) kernel, plus a sequence-parallel ring variant over the ICI
mesh (``mxnet_tpu.parallel.sequence``).  This module provides:

- ``_chunked_attention``: lax.scan blockwise attention with online softmax —
  O(S * chunk) activation memory, differentiable through the scan, runs on
  every backend (the non-TPU dispatch target).
- ``flash_attention``: Pallas TPU kernels — MXU-tiled forward with online
  softmax in f32 scratch (saving the per-row logsumexp), and a custom VJP
  running the flash backward as one Pallas kernel (``_flash_bwd_kernel``)
  that recomputes p from the saved logsumexp once a score tile and feeds
  dv, dk and dq from it.  The scores stay transposed, (key rows, query
  columns): the K/V block is the resident side, dk and dv accumulate in
  block-sized scratch and both their dots are plain a @ b; dq, which
  accumulates over the other axis, lives in an f32 VMEM scratch that
  spans one head's whole sequence (f32 partials a K/V block in HBM where
  that would not fit).  Both kernels share one tile plan
  (``_flash_plan``): resident blocks of Q and K/V a grid step, score
  sub-tiles walked in the loops the call's mask description gives.
- ``_contrib_DotProductAttention`` / ``_contrib_div_sqrt_dim`` registered
  operators, so the op is reachable from mx.nd / mx.sym like any other.

Layout is (batch, heads, seq, head_dim) throughout.  Queries and keys
share one width ``d``; values (and so the output) may have another,
``d_v`` (a latent-attention block's 192-wide keys beside 128-wide
values): every path takes it from v's own shape, and nothing is padded
from the one width to the other.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ._precision import matmul_precision
from .registry import register_op

__all__ = ["flash_attention", "attention_reference", "BlockDiffusion",
           "Window"]

_NEG_INF = -1e30
# Inside a kernel the per-row softmax state (running max, denominator)
# rides lane-replicated as (rows, _LANES): 1-D VMEM scratch has no native
# layout.  In HBM the per-row residuals (logsumexp, delta) are compact
# rows of a (B*H, 1, seq_q) array, whose (1, 1, n) blocks Mosaic accepts.
_LANES = 128
# Score sub-tile (query rows, key columns) of both kernels, chosen by
# tools/flash_sweep.py on the v5e at the benchmark's LM cells' shapes
# (docs/PERF_NOTES.md "Flash attention kernel" has the tables): where a
# head's loops unroll whole, and where they stay loops.
_SUB_UNROLLED = (256, 256)
_SUB_LOOPED = (256, 512)
# What the plan lets a kernel's blocks, scratch and tile temporaries take
# of the 16 MiB of scoped VMEM that Mosaic grants on the v5e by default;
# the rest (`_VMEM_MOSAIC`) is Mosaic's own.
_VMEM_BUDGET = 12 << 20
_VMEM_MOSAIC = 4 << 20
# The backward's dq accumulator spans a head's sequence, beside that
# budget: the call asks Mosaic for what the plan counted
# (`vmem_limit_bytes`) out of the chip's 128 MiB.  A sequence whose
# accumulator would pass this much (49152 rows of bf16 at d = 128, where
# the call asks for 64 MiB: compiled for the v5e at that size, tier-1
# holds it) leaves dq as f32 partials a K/V block in HBM, and the heads
# then go through the kernel in groups whose partials stay under
# `_HBM_DQ`; one head's alone above it is refused.
_VMEM_DQ = 48 << 20
_HBM_DQ = 2 << 30
# Tiles a loop iteration holds (`_loop`), and the longest static loop
# that is unrolled whole.
_UNROLL = 4
_UNROLL_WHOLE = 8


# ---------------------------------------------------------------------------
# Mask descriptions.  Which query sees which key is one static object, a
# frozen dataclass that compares and hashes by value: it is a static
# argument of the two jitted wrappers and of `_flash`, so every layer of a
# model that gives the same description shares one traced kernel body.
# `_described` turns what a caller gives (`causal`, and a `Window` beside it
# or a `BlockDiffusion` in its place) into one of `Full`, `Causal`, `Window`,
# `BlockDiffusion`; everything below this section calls the description and
# never asks which one it holds.
#
# What an instance supplies (`Full` has every one; a kind overrides what it
# changes):
#   checked       whether it describes these sequences, and as what
#   visible       whether a pair is visible, from positions, by definition:
#                 `attention_reference`, `_chunked_attention` and the tests'
#                 brute force read this and nothing else
#   pairs         how many pairs are visible (`tiles_ideal`, the op's stat)
#   parts         the equal parts the sequence is padded in
#   k_runs/q_runs the forward's loops for a query sub-tile over a resident
#                 key block and the backward's for a key sub-tile over a
#                 resident query block, as runs ``(lo, hi, body)`` of tiles:
#                 *body* None where every pair of every tile is visible, else
#                 the terms `tile_mask` takes (`_PLAIN`: none of its own)
#   end_to_end    which of `_run`'s two ways the runs go (the programs
#                 differ, and only the chip can judge one for all: ROADMAP
#                 D16)
#   tile_mask     a masked tile's body, from iotas
#   k_block/q_block  the resident block an index map takes at a grid step:
#                 the step's own where it holds a visible pair, else the
#                 nearest that does, so that an empty step refetches nothing
#   plan_says/plan_counts  what `mx.flash.plan` says of it
#   stat/scope    the op's step stat and device scope
# What it never touches: the kernels, the wrappers, the plan, the block
# specs, the counts, the two `jax.numpy` bodies, the op.  A selection (the
# operand of `selected_attention`) is no description: it goes beside a
# `Causal` one, the runs are told once (`_Frame.selected`) that every
# visited tile then runs a body, and the bits are ANDed there.
# ---------------------------------------------------------------------------

# The loop bounds below run on Python ints (the plan's counts) and on the
# kernels' traced scalars alike; numerators are clamped at 0 first, so
# the division is the same truncating one on both.
def _imax(a, b):
    return max(a, b) if isinstance(a, int) and isinstance(b, int) \
        else jnp.maximum(a, b)


def _imin(a, b):
    return min(a, b) if isinstance(a, int) and isinstance(b, int) \
        else jnp.minimum(a, b)


def _idiv(a, b):
    return a // b if isinstance(a, int) else jax.lax.div(a, jnp.int32(b))


def _sel(which, a, b):
    """*a* where *which* is 1, *b* where it is 0 (ints or traced)."""
    return which * a + (1 - which) * b


def _below(a, b):
    """1 where *a* < *b*, else 0 (ints or traced)."""
    if isinstance(a, int) and isinstance(b, int):
        return int(a < b)
    return (a < b).astype(jnp.int32)


def _and(mask, other):
    return other if mask is None else mask & other


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


class _Frame(collections.namedtuple(
        "_Frame", "t seq_q seq_k pad_q pad_k selected")):
    """What a description's loops and bodies are asked about: one kernel's
    tiles *t* over *seq_q* queries and *seq_k* keys padded to *pad_q* and
    *pad_k*, sequence ends aligned (decode-style cross-length causal).
    With a selection operand (*selected*) every visited tile runs a body."""
    __slots__ = ()

    @property
    def off(self):
        return self.seq_k - self.seq_q

    @property
    def padded_k(self):
        return self.pad_k != self.seq_k


#: the terms of a mask body that takes none of its own
_PLAIN = ()


@dataclasses.dataclass(frozen=True)
class Full:
    """Every query sees every key: the rectangle, which only the padding
    crosses."""
    causal = False
    parts = 1
    end_to_end = False
    stat = scope = None

    def checked(self, causal, sq, sk):
        """This description as it stands over *sq* queries and *sk* keys;
        raises where it describes no such sequences."""
        return self

    def visible(self, q_pos, k_pos):
        """Whether query position *q_pos* (in the keys' positions: ends
        aligned) sees key position *k_pos*; the two broadcast."""
        return True

    def pairs(self, sq, sk):
        """Visible query-key pairs of a head."""
        return sq * sk

    def plan_says(self):
        return {}

    def plan_counts(self, t, sq, sk):
        """What a kernel's record in the span holds beside its visits."""
        return {}

    def _k_bounds(self, g, row0, k0, n):
        return n, n

    def _q_bounds(self, g, col0, q0, n):
        return 0, 0

    def _k_tiles(self, g, row0, k0, n):
        """``(n_full, n_vis)`` for the query sub-tile whose first row is
        *row0*, over the *n* key sub-tiles of the resident block that
        starts at column *k0*: tiles ``[0, n_vis)`` hold a visible score,
        and the first ``n_full`` of them nothing else (neither the diagonal
        nor the padding crosses them, and no selection is given)."""
        n_full, n_vis = self._k_bounds(g, row0, k0, n)
        n_real = _idiv(_imax(g.seq_k - k0, 0), g.t.sub_k)
        n_full = _imin(_imin(n_full, n_real), n_vis)
        return (0 if g.selected else n_full), n_vis

    def _q_tiles(self, g, col0, q0, n):
        """``(j_first, j_full)`` for the key sub-tile whose first column is
        *col0*, over the *n* query sub-tiles of the resident block that
        starts at row *q0*: tiles ``[j_first, n)`` hold a visible score, and
        from ``j_full`` on nothing else."""
        j_first, j_full = self._q_bounds(g, col0, q0, n)
        padded = col0 + g.t.sub_k > g.seq_k
        if isinstance(padded, bool):
            j_full = n if padded else j_full
        else:
            j_full = jnp.where(padded, n, j_full)
        return j_first, (n if g.selected else j_full)

    def _bodied(self, g, runs):
        """*runs* without those under a body where no tile can need one: no
        diagonal, no padding, no selection."""
        return runs if self.causal or g.padded_k or g.selected else tuple(
            run for run in runs if run[2] is None)

    def k_runs(self, g, row0, k0, n):
        n_full, n_vis = self._k_tiles(g, row0, k0, n)
        return self._bodied(g, ((0, n_full, None), (n_full, n_vis, _PLAIN)))

    def q_runs(self, g, col0, q0, n):
        j_first, j_full = self._q_tiles(g, col0, q0, n)
        return self._bodied(g, ((j_first, j_full, _PLAIN), (j_full, n, None)))

    def tile_mask(self, g, shape, q_axis, row0, col0, body):
        """Visibility of the score tile whose first query row is *row0* and
        first key column *col0* (query rows along *q_axis*) in a run under
        *body*: the padding's term and the definition's."""
        k_pos = col0 + _iota(shape, 1 - q_axis)
        seen = k_pos < g.seq_k if g.padded_k else None
        if not self.causal:
            return seen
        return _and(seen, self.visible(
            row0 + g.off + _iota(shape, q_axis), k_pos))

    def k_block(self, g, nkr, iq, ik):
        """The key-side block of forward grid step (*iq*, *ik*) of *nkr*."""
        return ik

    def q_block(self, g, nqr, ik, iq):
        """The query-side block of backward grid step (*ik*, *iq*)."""
        return iq


@dataclasses.dataclass(frozen=True)
class Causal(Full):
    """Query ``t`` sees the keys up to its own position, ``s <= t``."""
    causal = True

    def visible(self, q_pos, k_pos):
        return k_pos <= q_pos

    def pairs(self, sq, sk):
        off = sk - sq
        lo, hi = max(0, -off), sq            # rows that see a key
        return (hi - lo) * (lo + off + hi + off + 1) // 2 if hi > lo else 0

    def _k_bounds(self, g, row0, k0, n):
        t = g.t
        n_vis = _imin(n, _idiv(
            _imax(row0 + t.sub_q + g.off - k0, 0) + t.sub_k - 1, t.sub_k))
        return _idiv(_imax(row0 + g.off + 1 - k0, 0), t.sub_k), n_vis

    def _q_bounds(self, g, col0, q0, n):
        t = g.t
        return (_imin(n, _idiv(_imax(col0 - g.off - q0, 0), t.sub_q)),
                _imin(n, _idiv(
                    _imax(col0 + t.sub_k - 1 - g.off - q0, 0) + t.sub_q - 1,
                    t.sub_q)))

    def _last_k_block(self, g, nkr, iq):
        """The last resident key block a query block *iq* sees."""
        t = g.t
        return _imin(nkr - 1, _idiv(
            _imax(iq * t.res_q + t.res_q - 1 + g.off, 0), t.res_k))

    def _first_q_block(self, g, nqr, ik):
        """The first resident query block that sees key block *ik*."""
        return _imin(nqr - 1, _idiv(
            _imax(ik * g.t.res_k - g.off, 0), g.t.res_q))

    # a step above the diagonal is empty (its loops run no tile)
    def k_block(self, g, nkr, iq, ik):
        return jnp.minimum(ik, self._last_k_block(g, nkr, iq)) \
            if nkr > 1 else ik

    def q_block(self, g, nqr, ik, iq):
        return jnp.maximum(iq, self._first_q_block(g, nqr, ik)) \
            if nqr > 1 else iq


@dataclasses.dataclass(frozen=True)
class Window(Causal):
    """A causal window, given WITH ``causal``: query ``t`` sees the *keys*
    keys that end at its own position, ``t - keys < s <= t``.  A row of
    ``keys`` or more positions sees ``keys`` pairs, an earlier one all it
    has: ``keys * S - keys * (keys - 1) / 2`` pairs over ``S`` positions.

    A query's keys are bounded from below as well: the forward's loop over
    key sub-tiles starts where the window does and the backward's loop over
    query sub-tiles ends where the last query that sees the key tile
    stands, so a tile with no visible pair is not visited on either side.
    The runs: the tiles the window's lower edge crosses, those every pair
    of which is visible, those the diagonal (or the padding) crosses.  A
    window narrower than a tile's two edges together leaves no tile whole:
    one masked loop."""
    keys: int
    end_to_end = True
    stat = "swa_visible_pairs"

    def checked(self, causal, sq, sk):
        if not causal or self.keys < 1:
            raise ValueError(
                "a window bounds a causal query's keys from below: pass "
                "causal=True and at least one key (got causal=%r, %d keys)"
                % (bool(causal), self.keys))
        # a window that leaves every causal key visible is no window
        return Causal() if self.keys >= sk else self

    def visible(self, q_pos, k_pos):
        return super().visible(q_pos, k_pos) & (k_pos > q_pos - self.keys)

    def pairs(self, sq, sk):
        pos = np.arange(sq, dtype=np.int64) + (sk - sq)
        return int(np.maximum(np.minimum(pos, sk - 1) - np.maximum(
            pos - self.keys + 1, 0) + 1, 0).sum())

    def plan_says(self):
        return {"mask": "window", "window": self.keys}

    def plan_counts(self, t, sq, sk):
        """`tiles_needed`: the tiles of the real sequence that hold a
        visible pair (a correct schedule visits those and no other), from
        the window's definition a row of tiles at a time."""
        off, needed = sk - sq, 0
        for r0 in range(0, sq, t.sub_q):
            lo = max(0, r0 + off - self.keys + 1)
            hi = min(min(r0 + t.sub_q, sq) - 1 + off, sk - 1)
            if hi >= lo:
                needed += hi // t.sub_k - lo // t.sub_k + 1
        return {"tiles_needed": needed}

    def k_runs(self, g, row0, k0, n):
        t, w = g.t, self.keys
        n_full, n_vis = self._k_tiles(g, row0, k0, n)
        lo = _imin(n_vis, _idiv(
            _imax(row0 + g.off - w + 1 - k0, 0), t.sub_k))
        if w < t.sub_q + t.sub_k - 1:
            return ((lo, n_vis, _PLAIN),)
        a = _imin(n_vis, _imax(lo, _idiv(
            _imax(row0 + t.sub_q + g.off - w - k0, 0) + t.sub_k - 1,
            t.sub_k)))
        b = _imax(n_full, a)
        return ((lo, a, _PLAIN), (a, b, None), (b, n_vis, _PLAIN))

    def q_runs(self, g, col0, q0, n):
        t, w = g.t, self.keys
        j_first, j_full = self._q_tiles(g, col0, q0, n)
        # the last query that sees the tile's last key stands w - 1 after it
        j_end = _imax(j_first, _imin(n, _idiv(
            _imax(col0 + t.sub_k + w - 2 - g.off - q0 + t.sub_q, 0),
            t.sub_q)))
        if w < t.sub_q + t.sub_k - 1:
            return ((j_first, j_end, _PLAIN),)
        a = _imin(j_full, j_end)
        b = _imax(a, _imin(j_end, _idiv(
            _imax(col0 + w - g.off - q0, 0), t.sub_q)))
        return ((j_first, a, _PLAIN), (a, b, None), (b, j_end, _PLAIN))

    # a step below the window takes the first block the query block sees, a
    # step past the last row that sees the key block the last that does
    def k_block(self, g, nkr, iq, ik):
        first = _idiv(_imax(iq * g.t.res_q + g.off - self.keys + 1, 0),
                      g.t.res_k)
        return jnp.clip(ik, first, self._last_k_block(g, nkr, iq))

    def q_block(self, g, nqr, ik, iq):
        t = g.t
        return jnp.clip(iq, self._first_q_block(g, nqr, ik), _imin(
            nqr - 1, _idiv(_imax(
                ik * t.res_k + t.res_k + self.keys - 2 - g.off, 0), t.res_q)))


# Under a `BlockDiffusion` both kernels see the sequence as two halves, each
# padded to whole resident blocks: `_Halves` holds the block length, a
# half's real length and its padded lengths along the queries and along the
# keys.  Positions below are LOCAL to their half.  A real query never sees a
# padded key, so the padding needs no term of its own; a padded query sees
# what the last real one does.
_Halves = collections.namedtuple("_Halves", "block real pad_q pad_k")
#: a tile's mask body: the tile's keys are clean ones (*noised* 0), visible
#: below the query's block start plus *extra* (the block length for a clean
#: query, 0 for a noised one), or noised ones (*noised* 1), visible inside
#: the query's block.  Ints, or traced where one loop runs tiles of several
#: kinds (`_run`)
_BdMask = collections.namedtuple("_BdMask", "noised extra")


def _bd_start(x, block):
    """First position of the *block*-long block that holds position *x*."""
    if block & (block - 1) == 0:
        return x & -block
    return _idiv(x, block) * block


def _bd_blocks(x0, n, hv):
    """Block starts of the first and of the last real position among the
    *n* from *x0* on (of the last real one of the half where all *n* are
    padding)."""
    return (_bd_start(_imin(x0, hv.real - 1), hv.block),
            _bd_start(_imin(x0 + n, hv.real) - 1, hv.block))


@dataclasses.dataclass(frozen=True)
class BlockDiffusion(Full):
    """The block-diffusion mask, given in ``causal``'s place: the sequence
    is two copies of *half* positions, a clean one then a noised one, in
    blocks of *block*.  With ``n(j) = j >= half`` and ``b(j) = (j mod half)
    // block`` query ``j`` sees key ``s`` where both are clean and ``b(s) <=
    b(j)``, or the query is noised and the key clean and ``b(s) < b(j)``, or
    both are noised and ``b(s) == b(j)`` (BD3-LM, arXiv:2503.09573, section
    5).  Every query sees a key; ``half * (half + block)`` pairs are
    visible."""
    block: int
    half: int
    parts = 2
    end_to_end = True
    stat = "bd_visible_pairs"
    scope = "mx.bd.attention"

    def checked(self, causal, sq, sk):
        if causal:
            raise ValueError("a block-diffusion mask is not causal: pass one "
                             "or the other")
        if sq != sk or sq != 2 * self.half or self.block < 1 \
                or self.half % self.block:
            raise ValueError(
                "a block-diffusion mask of two halves of %d in blocks of %d "
                "does not describe %d queries and %d keys"
                % (self.half, self.block, sq, sk))
        return self

    def visible(self, q_pos, k_pos):
        qn, kn = q_pos >= self.half, k_pos >= self.half
        qb = (q_pos - qn * self.half) // self.block
        kb = (k_pos - kn * self.half) // self.block
        return (~kn & ~qn & (kb <= qb)) | (~kn & qn & (kb < qb)) \
            | (kn & qn & (kb == qb))

    def pairs(self, sq, sk):
        return self.half * (self.half + self.block)

    def plan_says(self):
        return {"mask": "block_diffusion", "block": self.block,
                "half": self.half}

    def _halves(self, g):
        return _Halves(self.block, self.half, g.pad_q // 2, g.pad_k // 2)

    def k_runs(self, g, row0, k0, n):
        """The clean keys every row of the tile sees, those a block
        boundary crosses, and (a noised tile) the noised keys of its own
        blocks."""
        t, hv = g.t, self._halves(g)
        qh = _idiv(row0, hv.pad_q)          # 0: clean queries, 1: noised ones
        first, last = _bd_blocks(row0 - qh * hv.pad_q, t.sub_q, hv)
        live = _below(row0 - qh * hv.pad_q, hv.real)    # 0: a tile of padding
        extra = hv.block * (1 - qh)
        base, half = _idiv(k0, t.sub_k), hv.pad_k // t.sub_k

        def local(x):
            return _imin(_imax(x - base, 0), n)

        def up(x):
            return _idiv(x + t.sub_k - 1, t.sub_k)

        full = live * local(_idiv(first + extra, t.sub_k))
        vis = live * local(up(last + extra))
        lo = local(half + _idiv(first, t.sub_k))
        hi = _sel(qh * live, local(half + up(last + hv.block)), lo)
        return ((0, full, None), (full, vis, _BdMask(0, extra)),
                (lo, hi, _BdMask(1, 0)))

    def q_runs(self, g, col0, q0, n):
        """Clean keys: the clean rows from the keys' first block on and the
        noised rows from the block after, each masked until the keys' last
        block is passed; noised keys: the noised rows of their own
        blocks."""
        t, hv = g.t, self._halves(g)
        kh = _idiv(col0, hv.pad_k)          # 0: clean keys, 1: noised ones
        first, last = _bd_blocks(col0 - kh * hv.pad_k, t.sub_k, hv)
        live = _below(col0 - kh * hv.pad_k, hv.real)    # 0: a tile of padding
        base, half = _idiv(q0, t.sub_q), hv.pad_q // t.sub_q
        rows = -(-hv.real // t.sub_q)   # a half's tiles that hold a real row

        def local(x):
            return _imin(_imax(x - base, 0), n)

        def up(x):
            return _imin(_idiv(x + t.sub_q - 1, t.sub_q), rows)

        clean, noised = (1 - kh) * live, kh * live
        a_lo, a_full = _idiv(first, t.sub_q), up(last)
        # (the keys' first block may be the half's last: no noised row then)
        b_lo = _sel(_below(first + hv.block, hv.real),
                    _idiv(first + hv.block, t.sub_q), rows)
        b_full = _imax(up(last + hv.block), b_lo)
        # on noised keys the first four are empty, on clean keys the last
        return ((local(_sel(clean, a_lo, rows)),
                 local(_sel(clean, a_full, rows)), _BdMask(0, hv.block)),
                (local(_sel(clean, a_full, rows)), local(rows), None),
                (local(half + _sel(clean, b_lo, rows)),
                 local(half + _sel(clean, b_full, rows)), _BdMask(0, 0)),
                (local(half + _sel(clean, b_full, rows)), local(half + rows),
                 None),
                (local(half + _sel(noised, a_lo, rows)),
                 local(half + _sel(noised, b_full, rows)), _BdMask(1, 0)))

    def tile_mask(self, g, shape, q_axis, row0, col0, body):
        """From iotas, no operand: *row0* and *col0* are whole-sequence
        positions, the tile's keys of the half and with the bound that
        *body* (a `_BdMask`'s terms) names."""
        hv, (noised, extra) = self._halves(g), body
        q_loc = row0 - _idiv(row0, hv.pad_q) * hv.pad_q + _iota(shape, q_axis)
        k_loc = col0 - noised * hv.pad_k + _iota(shape, 1 - q_axis)
        start = _bd_start(q_loc, hv.block)
        # clean keys: below the start plus `extra`; noised keys: from the
        # start on, one block long
        return (k_loc >= noised * start) \
            & (k_loc < start + extra + noised * hv.block)

    def k_block(self, g, nkr, iq, ik):
        """A step past the query block's last clean block takes the nearest
        noised block it visits (a clean query block's: its last clean block
        again)."""
        t, hv = g.t, self._halves(g)
        qh = _idiv(iq * t.res_q, hv.pad_q)
        first, last = _bd_blocks(iq * t.res_q - qh * hv.pad_q, t.res_q, hv)
        c_last = _idiv(_imax(last + hv.block * (1 - qh) - 1, 0), t.res_k)
        half = hv.pad_k // t.res_k
        n_first = _sel(qh, half + _idiv(first, t.res_k), c_last)
        n_last = _sel(qh, half + _idiv(last + hv.block - 1, t.res_k), c_last)
        return jnp.where(ik <= c_last, ik, jnp.clip(ik, n_first, n_last))

    def q_block(self, g, nqr, ik, iq):
        """A step outside the rows that see the key block takes the nearest
        inside: from the first clean one on, and from the first to the last
        noised one."""
        t, hv = g.t, self._halves(g)
        kh = _idiv(ik * t.res_k, hv.pad_k)
        first, last = _bd_blocks(ik * t.res_k - kh * hv.pad_k, t.res_k, hv)
        half = hv.pad_q // t.res_q
        a_lo = _idiv(first, t.res_q)
        b_lo = _imin(_idiv(first + hv.block, t.res_q), half - 1)
        a_first = _sel(kh, half, a_lo)
        n_first = half + _sel(kh, a_lo, b_lo)
        n_last = half + _sel(
            kh, _idiv(last + hv.block - 1, t.res_q), half - 1)
        return jnp.where((iq < nqr // 2) & (a_first < nqr // 2),
                         jnp.maximum(iq, a_first),
                         jnp.clip(iq, n_first, n_last))


def _described(causal, mask, sq, sk):
    """The description that `causal` and *mask* (None, a `Window` beside
    `causal`, a `BlockDiffusion` in its place, or what this function
    returned) make of *sq* queries over *sk* keys."""
    if mask is None:
        mask = Causal() if causal else Full()
    return mask.checked(causal, sq, sk)


def attention_reference(q, k, v, causal=False, sm_scale=None, mask=None):
    """O(S^2)-memory einsum attention — the numeric oracle for tests:
    the softmax over the pairs that `causal` and *mask* (`_described`) call
    visible.

    Degenerate-row convention (shared by all paths in this module): a
    causal query row that can see NO keys (seq_q > seq_k under the
    aligned-ends convention) outputs zeros and contributes zero
    gradient — softmax over an empty visible set is undefined, and both
    the uniform-average and NaN alternatives leak masked content or
    poison training."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   precision=matmul_precision(q.dtype, k.dtype)) \
        * sm_scale
    qlen, klen = s.shape[-2:]
    seen = jnp.broadcast_to(_described(causal, mask, qlen, klen).visible(
        jnp.arange(qlen)[:, None] + (klen - qlen),   # sequence ends aligned
        jnp.arange(klen)[None, :]), (qlen, klen))
    p = jax.nn.softmax(jnp.where(seen, s, _NEG_INF), axis=-1)
    p = p * seen.any(-1)[:, None]  # zero fully-masked rows
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
                      precision=matmul_precision(q.dtype, v.dtype)
                      ).astype(q.dtype)


# ---------------------------------------------------------------------------
# Chunked (blockwise) attention: scan over K/V chunks with online softmax.
# ---------------------------------------------------------------------------

def _online_softmax_update(o, m, l, s, vb):
    """One blockwise online-softmax accumulation step over masked scores
    *s* against value block *vb*; shared by the chunked scan here and the
    ring-attention scan (parallel/sequence.py) so the two paths cannot
    drift numerically.

    p is cast to vb's storage dtype for the MXU dot (full bf16 rate;
    f32 inputs are untouched) while the o/m/l state stays f32 via
    preferred_element_type — the same convention as the Pallas kernel."""
    m_new = jnp.maximum(m, s.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l = l * alpha + p.sum(axis=-1)
    o = o * alpha[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(vb.dtype), vb,
        precision=matmul_precision(vb.dtype, vb.dtype),
        preferred_element_type=jnp.float32)
    return o, m_new, l


def _finalize_softmax(o, m, l):
    """Final division of an online-softmax accumulation, applying the
    degenerate-row convention: rows whose running max *m* never rose
    above the _NEG_INF sentinel saw no visible key and output zeros
    (with zero gradient — l_safe keeps the untaken 0/0 branch out of
    the vjp, where 0 * nan would poison it).  Shared by the chunked and
    ring paths; the flash kernel encodes the same rule in-kernel."""
    degenerate = m <= _NEG_INF * 0.5
    l_safe = jnp.where(degenerate, 1.0, l)
    return jnp.where(degenerate[..., None], 0.0, o / l_safe[..., None])

def _chunked_attention(q, k, v, causal=False, sm_scale=None, chunk=512,
                       mask=None):
    """Blockwise attention with online softmax over K chunks, under the
    description `causal` and *mask* make (`_described`).

    Memory is O(S_q * chunk) instead of O(S_q * S_k); the scan body is
    rematerialized on backward (jax.checkpoint), which is exactly the
    flash-attention recompute strategy expressed at the XLA level.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    b, h, sq, d = q.shape
    sk, d_v = k.shape[2], v.shape[3]
    mask = _described(causal, mask, sq, sk)
    chunk = min(chunk, sk)
    nchunk = -(-sk // chunk)
    pad = nchunk * chunk - sk
    if pad:
        kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    else:
        kp, vp = k, v
    kc = kp.reshape(b, h, nchunk, chunk, d).transpose(2, 0, 1, 3, 4)
    vc = vp.reshape(b, h, nchunk, chunk, d_v).transpose(2, 0, 1, 3, 4)
    q_pos = jnp.arange(sq) + (sk - sq)  # align ends for causal cross-length

    @jax.checkpoint
    def body(carry, xs):
        o, m, l = carry
        ci, kb, vb = xs
        # storage-dtype operands, f32 accumulation: bf16 runs at the
        # full MXU rate (a pre-cast to f32 would halve it)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kb,
                       precision=matmul_precision(q.dtype, kb.dtype),
                       preferred_element_type=jnp.float32) * sm_scale
        k_pos = ci * chunk + jnp.arange(chunk)
        valid = (k_pos < sk)[None, :] & mask.visible(
            q_pos[:, None], k_pos[None, :])
        s = jnp.where(valid[None, None], s, _NEG_INF)
        o, m, l = _online_softmax_update(o, m, l, s, vb)
        return (o, m, l), None

    o0 = jnp.zeros((b, h, sq, d_v), jnp.float32)
    m0 = jnp.full((b, h, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    (o, m, l), _ = jax.lax.scan(
        body, (o0, m0, l0), (jnp.arange(nchunk), kc, vc))
    return _finalize_softmax(o, m, l).astype(q.dtype)


# ---------------------------------------------------------------------------
# The tile plan of the two Pallas calls.
# ---------------------------------------------------------------------------

#: one kernel's tiles: a grid step keeps `res_q` query rows and `res_k`
#: key rows resident in VMEM and walks score tiles of `sub_q` x `sub_k`
#: inside them, in loops whose bounds come from the causal limit
_Tiles = collections.namedtuple("_Tiles", "res_q res_k sub_q sub_k")
#: what `_flash_plan` hands the wrappers: the head dim as the kernels see
#: it, the padded sequence lengths of the forward and of the backward,
#: each kernel's tiles, the values' head dim as the kernels see it
#: (`d_block` again where v is as wide as q and k), and where the
#: backward accumulates dq (`vmem` or `hbm`)
_Plan = collections.namedtuple(
    "_Plan",
    "d_block sq_fwd sk_fwd sq_bwd sk_bwd fwd bwd dv_block dq_accumulator")
_KERNELS = ("fwd", "bwd")


def _round_up(n, m):
    return -(-n // m) * m


def _d_block(d):
    """The head dim as the kernels see it.  A block whose last dimension
    equals the array's is legal for Mosaic, so a multiple of 64 crosses
    HBM at its own width; anything else is padded to the 128-lane tile."""
    return d if d % 64 == 0 else _round_up(d, _LANES)


def _side_bytes(kernel, d_block, dv_block, itemsize):
    """VMEM bytes that one resident query row and one resident key row
    cost *kernel*: its double-buffered blocks, its f32 accumulators and
    its row statistics (a `(1, n)` f32 block occupies 8 sublanes).  The
    lane dimension is padded to 128 in VMEM whatever the array's width.
    q, k, dq and dk are *d_block* wide; v, o, dO and dv *dv_block*."""
    wide, wide_v = _round_up(d_block, _LANES), _round_up(dv_block, _LANES)
    blk, blk_v = 2 * wide * itemsize, 2 * wide_v * itemsize  # two buffers
    acc, acc_v = wide * 4, wide_v * 4
    row = 2 * 8 * 4                     # one statistics row's two buffers
    return {
        # q, o, acc, m, l, lse out        k, v
        "fwd": (blk + blk_v + acc_v + 2 * _LANES * 4 + row, blk + blk_v),
        # q, do, lse, delta               k, v, dk, dv, two accumulators
        "bwd": (blk + blk_v + 2 * row,
                2 * blk + 2 * blk_v + acc + acc_v),
    }[kernel]


def _dq_bytes(dq_accumulator, rows, d_block, itemsize):
    """VMEM bytes of the backward's dq side, which its resident blocks do
    not hold.  `vmem`: the f32 accumulator and dq's double-buffered
    output block, both over the *rows* of a head's whole (padded)
    sequence.  `hbm`: the f32 partial's double-buffered block over the
    *rows* of a resident query block."""
    wide = _round_up(d_block, _LANES)
    if dq_accumulator == "vmem":
        return rows * (wide * 4 + 2 * wide * itemsize)
    return rows * 2 * wide * 4


def _tile_bytes(sub_q, sub_k):
    """VMEM for one tile's f32 score-sized temporaries (s, p, dp, ds, the
    mask's iotas, the hoisted statistics)."""
    return 6 * sub_q * sub_k * 4


def _vmem_bytes(kernel, plan, itemsize):
    """The VMEM *kernel* asks for with *plan*'s tiles, by the plan's
    model."""
    t = getattr(plan, kernel)
    per_q, per_k = _side_bytes(kernel, plan.d_block, plan.dv_block, itemsize)
    need = per_q * t.res_q + per_k * t.res_k + _tile_bytes(t.sub_q, t.sub_k)
    if kernel == "bwd":
        vmem = plan.dq_accumulator == "vmem"
        need += _dq_bytes(plan.dq_accumulator,
                          plan.sq_bwd if vmem else t.res_q, plan.d_block,
                          itemsize)
    return need


def _vmem_limit(plan, itemsize):
    """What the backward call asks Mosaic for (`vmem_limit_bytes`): the
    plan's count and Mosaic's own share, never under the default.  That
    share holds what Mosaic makes of a tile's operands and of the three
    products before they are added, so it grows with a row's bytes:
    `_VMEM_MOSAIC` up to 256 lanes of bf16 (512 bytes), and as many
    times that as a wider row holds 512 bytes."""
    row = _round_up(max(plan.d_block, plan.dv_block), _LANES) * itemsize
    return max(_VMEM_BUDGET, _vmem_bytes("bwd", plan, itemsize)) \
        + _VMEM_MOSAIC * max(1, row // 512)


#: queries (forward) or keys (backward) a word of the selection operand
#: holds, one bit each
_SEL_BITS = 32


def _selection_bytes(t):
    """VMEM bytes of a kernel's selection block, held twice, and of the
    two more score-sized temporaries a tile takes to unpack it."""
    return 2 * (t.res_q * t.res_k // _SEL_BITS) * 4 \
        + 2 * t.sub_q * t.sub_k * 4


def _selected_vmem_limit(kernel, plan, itemsize):
    """What a call with a selection operand asks Mosaic for: the plan's
    count (the backward's limit as it stands), the selection's block and
    temporaries, and for the forward Mosaic's own share."""
    extra = _selection_bytes(getattr(plan, kernel))
    if kernel == "bwd":
        return _vmem_limit(plan, itemsize) + extra
    return max(_VMEM_BUDGET, _vmem_bytes("fwd", plan, itemsize)) + extra \
        + _VMEM_MOSAIC


def pack_selection(mask):
    """A ``(batch, rows, cols)`` bool mask as the selection operand the
    kernels read: ``(batch, ceil(rows / 32), cols)`` int32, bit ``r % 32``
    of word ``[r // 32, c]`` saying whether ``mask[r, c]``.  One bit a
    pair: a sequence of 16384 takes 33.5 MB where the mask itself would
    take 268."""
    b, r, c = mask.shape
    m = jnp.pad(mask, ((0, 0), (0, -r % _SEL_BITS), (0, 0)))
    m = m.reshape(b, -1, _SEL_BITS, c).astype(jnp.uint32)
    words = jnp.sum(m << jnp.arange(_SEL_BITS, dtype=jnp.uint32)[:, None],
                    axis=2, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.int32)


def unpack_selection(words, rows=None):
    """`pack_selection`'s inverse on ``(..., groups, cols)`` words: a bool
    ``(..., groups * 32, cols)`` (cut to *rows*).  The same arithmetic
    inside the kernels and outside them."""
    groups, cols = words.shape[-2:]
    lead = words.shape[:-2]
    spread = jnp.broadcast_to(
        words[..., None, :], lead + (groups, _SEL_BITS, cols)
    ).reshape(lead + (groups * _SEL_BITS, cols))
    bit = jax.lax.broadcasted_iota(jnp.int32, spread.shape,
                                   spread.ndim - 2) & (_SEL_BITS - 1)
    mask = ((spread >> bit) & 1) != 0
    return mask if rows is None else mask[..., :rows, :]


def _resident(n_sub, bytes_per_sub, budget):
    """How many sub-tiles a resident block holds: the largest divisor of
    *n_sub* that fits *budget*, at least one."""
    fit = max(1, budget // bytes_per_sub)
    return max(c for c in range(1, n_sub + 1)
               if n_sub % c == 0 and c <= fit)


def _kernel_tiles(kernel, sub, sq, sk, d_block, dv_block, itemsize, res_q,
                  res_k, halves=1):
    """``(sq_padded, sk_padded, _Tiles)`` of *kernel* with sub-tile *sub*
    cut to the sequence: the resident blocks are the largest
    `_VMEM_BUDGET` holds, the streamed side first (K/V for the forward,
    Q/dO for the backward).  A sequence of *halves* equal parts (a
    block-diffusion mask's two copies) is padded a part at a time and a
    resident block lies inside one part, so that no tile crosses from one
    into the next."""
    sub_q, sub_k = sub
    sq_p = _round_up(sq // halves, res_q or sub_q)
    sk_p = _round_up(sk // halves, res_k or sub_k)
    nq, nk = sq_p // sub_q, sk_p // sub_k
    sq_p, sk_p = halves * sq_p, halves * sk_p
    per_q, per_k = _side_bytes(kernel, d_block, dv_block, itemsize)
    per_q, per_k = per_q * sub_q, per_k * sub_k
    budget = _VMEM_BUDGET - _tile_bytes(sub_q, sub_k)
    if kernel == "bwd":
        cq = _resident(nq, per_q, budget // 2)
        ck = _resident(nk, per_k, budget - cq * per_q)
    else:
        ck = _resident(nk, per_k, budget // 2)
        cq = _resident(nq, per_q, budget - ck * per_k)
    return sq_p, sk_p, _Tiles(res_q or cq * sub_q, res_k or ck * sub_k,
                              sub_q, sub_k)


def _unrolls_whole(t, sq_p, sk_p):
    """Whether a kernel with tiles *t* is one grid step a head whose
    loops `_loop` unrolls whole: every bound and slice static."""
    return (t.res_q, t.res_k) == (sq_p, sk_p) and \
        max(sq_p // t.sub_q, sk_p // t.sub_k) <= _UNROLL_WHOLE


def _flash_plan(sq, sk, d, dtype, blk_q=None, blk_k=None, res_q=None,
                res_k=None, d_v=None, halves=1):
    """Tiles of the two kernels from what the call can see: the
    lengths, the head dim of q and k, that of v (*d_v*; *d* where not
    given) and the dtype.  One algorithm with different
    parameters at different shapes.  Where a head fits VMEM and its
    tiles are few enough to unroll whole (S = 2048 at d = 64), the
    sub-tile is `_SUB_UNROLLED`, the one closest to the causal triangle;
    where the loops stay loops it is `_SUB_LOOPED`, wider along the
    keys: fewer, larger iterations (both from the sweep; the backward's
    query edge has to stay at 256).
    `causal` is not an input: the same tiles serve both, the loops'
    bounds differ.  Under a block-diffusion mask the sequence is *halves*
    = 2 copies: the tiles are cut to one copy and never cross into the
    other (`_kernel_tiles`), so a head is never one grid step and the
    sub-tile is the looped one.  The backward keeps dq's accumulator in VMEM where a
    head's whole sequence of it stays under `_VMEM_DQ`, else in HBM.
    *blk_q*, *blk_k* (sub-tile edges) and *res_q*, *res_k* (resident
    rows; multiples of the sub-tile that divide the padded length)
    override, for the tests and the sweep."""
    itemsize = jnp.dtype(dtype).itemsize
    d_block = _d_block(d)
    dv_block = d_block if d_v is None else _d_block(d_v)

    def cut(sub):
        return (min(blk_q or sub[0],
                    _round_up(sq // halves, 1 if blk_q else _LANES)),
                min(blk_k or sub[1],
                    _round_up(sk // halves, 1 if blk_k else _LANES)))

    found = {}
    for kernel in _KERNELS:
        found[kernel] = _kernel_tiles(
            kernel, cut(_SUB_UNROLLED), sq, sk, d_block, dv_block, itemsize,
            res_q, res_k, halves)
        if not _unrolls_whole(found[kernel][2], *found[kernel][:2]):
            found[kernel] = _kernel_tiles(
                kernel, cut(_SUB_LOOPED), sq, sk, d_block, dv_block,
                itemsize, res_q, res_k, halves)
    (sq_f, sk_f, fwd), (sq_b, sk_b, bwd) = found["fwd"], found["bwd"]
    dq_accumulator = "vmem" if _dq_bytes(
        "vmem", sq_b, d_block, itemsize) <= _VMEM_DQ else "hbm"
    return _Plan(d_block, sq_f, sk_f, sq_b, sk_b, fwd, bwd, dv_block,
                 dq_accumulator)


def _tile_counts(kernel, plan, sq, sk, causal, mask=None, selected=False):
    """What the schedule of *kernel* visits, for one head: score tiles
    computed, those of them computed under a mask body, and the visible
    scores in tiles (`tiles_ideal`), with what the description adds
    (`plan_counts`).  The kernels' own runs, on ints."""
    mask = _described(causal, mask, sq, sk)
    t = getattr(plan, kernel)
    g = _Frame(t, sq, sk, *((plan.sq_fwd, plan.sk_fwd) if kernel == "fwd"
                            else (plan.sq_bwd, plan.sk_bwd)), selected)
    nqs, nks = t.res_q // t.sub_q, t.res_k // t.sub_k
    visited = masked = 0
    for q0 in range(0, g.pad_q, t.res_q):
        for k0 in range(0, g.pad_k, t.res_k):
            if kernel == "bwd":
                runs = [run for j in range(nks) for run in mask.q_runs(
                    g, k0 + j * t.sub_k, q0, nqs)]
            else:
                runs = [run for j in range(nqs) for run in mask.k_runs(
                    g, q0 + j * t.sub_q, k0, nks)]
            visited += sum(max(hi - lo, 0) for lo, hi, _ in runs)
            masked += sum(max(hi - lo, 0) for lo, hi, body in runs
                          if body is not None)
    return dict(
        tiles_visited=visited, tiles_masked=masked,
        **mask.plan_counts(t, sq, sk),
        tiles_ideal=round(mask.pairs(sq, sk) / (t.sub_q * t.sub_k), 3))


def _plan_args(plan, sq, sk, d, dtype, causal, d_v=None, mask=None,
               selected=False):
    """The plan as the `mx.flash.plan` span carries it: static per shape,
    so recorded where the call is traced, not where it runs.  With a
    selection operand (*selected*) the span says so: every visited tile is
    then computed under a mask body, and the forward asks Mosaic for its
    own `vmem_limit_bytes` too."""
    mask = _described(causal, mask, sq, sk)
    rec = {"sq": sq, "sk": sk, "d": d, "dtype": jnp.dtype(dtype).name,
           "causal": bool(causal), "d_block": plan.d_block,
           "d_v": d if d_v is None else d_v, "dv_block": plan.dv_block,
           **mask.plan_says()}
    itemsize = jnp.dtype(dtype).itemsize
    for kernel in _KERNELS:
        t = getattr(plan, kernel)
        rec[kernel] = dict(
            _tile_counts(kernel, plan, sq, sk, causal, mask, selected),
            resident=[t.res_q, t.res_k], sub_tile=[t.sub_q, t.sub_k],
            vmem_bytes=_vmem_bytes(kernel, plan, itemsize))
    rec["bwd"].update(dq_accumulator=plan.dq_accumulator,
                      vmem_limit_bytes=_vmem_limit(plan, itemsize))
    if selected:
        rec["selection"] = "bits"
        for kernel in _KERNELS:
            rec[kernel].update(
                selection_block_bytes=_selection_bytes(getattr(plan, kernel)),
                vmem_limit_bytes=_selected_vmem_limit(kernel, plan, itemsize))
    return rec


def _record_plan(q, k, v, causal, selected=False, mask=None):
    """One `mx.flash.plan` span each time the op is traced.  At trace
    time on purpose: the plan is a fact of the compiled program, not of
    a step.  It carries what the description says of itself
    (`plan_says`, `plan_counts`)."""
    from .. import profiler
    sq, sk, d, d_v = q.shape[2], k.shape[2], q.shape[3], v.shape[3]
    with profiler.scope(  # graftlint: disable=JG003
            "mx.flash.plan", "flash") as span:
        plan = _flash_plan(sq, sk, d, q.dtype, d_v=d_v, halves=_described(
            causal, mask, sq, sk).parts)
        span.args = _plan_args(plan, sq, sk, d, q.dtype, causal, d_v, mask,
                               selected)


# ---------------------------------------------------------------------------
# Pallas flash kernels.  Shared conventions: operands enter the MXU in
# their storage dtype and accumulate f32; the softmax state is f32; a
# grid step holds resident blocks of Q and of K/V and loops over score
# sub-tiles in the runs the mask description gives, so a tile with no
# visible pair (above the diagonal) costs neither a grid step, a DMA nor a
# branch, and only the tiles the mask or the padding crosses pay for it.
# ---------------------------------------------------------------------------

def _mxu_dot(a, b, contract):
    """In-kernel MXU dot, f32 accumulation, at the framework's precision
    policy: bf16 operands take the full-rate path, f32 operands HIGHEST —
    Mosaic's default would multiply them as bf16 (the f32 op then
    disagrees with the CPU at 1e-2; consistency sweep, PR 21)."""
    return jax.lax.dot_general(
        a, b, (contract, ((), ())),
        precision=matmul_precision(a.dtype, b.dtype),
        preferred_element_type=jnp.float32)


_NT = ((1,), (1,))      # a @ b.T: contract both operands' last dim
_NN = ((1,), (0,))      # a @ b


def _lanes_to(x, n):
    """Widen (or cut) lane-replicated row state (rows, _LANES) to
    (rows, n)."""
    if n <= _LANES:
        return x[:, :n]
    reps, rem = divmod(n, _LANES)
    if rem:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], n))
    return jnp.tile(x, (1, reps))


def _sub(j, size, count):
    """Rows of sub-tile *j* of *count* in a resident block."""
    if count == 1:
        return slice(0, size)
    return pl.ds(pl.multiple_of(j * size, size), size)


def _grid_pos(axis, n):
    """This step's index along grid *axis* of static size *n*; the int 0
    where the axis is a single step, so that what follows from it (the
    loops' bounds, the slices) is static."""
    return 0 if n == 1 else pl.program_id(axis)


def _loop(lo, hi, body):
    """Run ``body(j)`` for j in [lo, hi), for its effects on refs.
    Tiles in one basic block let the scheduler run one tile's MXU work
    under another's VPU work (a `fori_loop` iteration is a block of its
    own: at 256x256 tiles the forward takes 1.6 times as long that way,
    tools/flash_sweep.py).  So a short static loop is unrolled whole, and
    any other runs `_UNROLL` tiles an iteration, the rest one by one."""
    if isinstance(lo, int) and isinstance(hi, int) and \
            hi - lo <= _UNROLL_WHOLE:
        for j in range(lo, hi):
            body(j)
        return
    chunks = _idiv(hi - lo, _UNROLL)

    def chunk(i, carry):
        for u in range(_UNROLL):
            body(lo + i * _UNROLL + u)
        return carry

    def one(j, carry):
        body(j)
        return carry

    jax.lax.fori_loop(0, chunks, chunk, 0)
    jax.lax.fori_loop(lo + chunks * _UNROLL, hi, one, 0)


def _run(runs, tile, end_to_end):
    """Run *tile* over each ``(lo, hi, body)`` of *runs* (a description's
    `k_runs` or `q_runs`), the way the description says: a loop a run, in
    order; or (*end_to_end*) in two loops, the tiles that need no mask body,
    then those that do, each loop over its runs laid end to end with the
    tile's index and the body's terms chosen by where the count stands.  A
    loop a run is five copies of the backward's tile body in each of ten
    loops a key sub-tile under a block-diffusion mask, 71085 bundles where
    the causal kernel has 29182, and a tile then took 3.6 times as long on
    the chip (PERF.md section 6, PR 39)."""
    if not end_to_end:
        for lo, hi, body in runs:
            _loop(lo, hi, functools.partial(tile, masked=body))
        return
    for masked in (False, True):
        group = [(lo, _imax(hi, lo), m) for lo, hi, m in runs
                 if (m is not None) == masked]
        if not group:
            continue

        def body(j, group=group):
            lo, end, m = group[0]
            at, end = lo + j, end - lo      # `end`: tiles up to here
            for lo2, hi2, m2 in group[1:]:
                later = _below(end - 1, j)
                at = _sel(later, lo2 + j - end, at)
                if m:
                    m = tuple(_sel(later, b, a) for a, b in zip(m, m2))
                end = end + hi2 - lo2
            tile(at, masked=m)

        _loop(0, sum(hi - lo for lo, hi, _ in group), body)


def _traced_inline(kernel):
    """Trace *kernel*'s body with `jax.disable_jit`: every `jnp` function
    and array operator is itself a jitted function, and an unrolled body
    calls some two thousand of them; traced as nested jits they take
    several seconds of set-up on the chip's host, inline a fraction."""
    @functools.wraps(kernel)
    def wrapped(*refs, **params):
        with jax.disable_jit():
            return kernel(*refs, **params)
    return wrapped


@_traced_inline
def _flash_fwd_kernel(q_ref, k_ref, v_ref, *rest, mask, g, grid, sm_scale):
    # *mask* is the call's description and *g* its `_Frame`: the loops'
    # runs and the mask bodies are the description's
    # with a selection (`g.selected`) a selection block follows v: (1,
    # res_q / 32, res_k) words, bit r % 32 of word r // 32 saying whether
    # query row r of the block sees the key; every visited tile is then a
    # masked one
    sel_ref = rest[0] if g.selected else None
    o_ref, *maybe_lse_and_scratch = rest[1:] if g.selected else rest
    if len(maybe_lse_and_scratch) == 4:
        lse_ref, acc_ref, m_ref, l_ref = maybe_lse_and_scratch
    else:  # inference path: no logsumexp output allocated
        lse_ref = None
        acc_ref, m_ref, l_ref = maybe_lse_and_scratch
    t, nkr = g.t, grid[1]
    iq, ik = _grid_pos(1, grid[0]), _grid_pos(2, nkr)
    nqs, nks = t.res_q // t.sub_q, t.res_k // t.sub_k
    d = acc_ref.shape[1]                # the values' width, and the output's

    # a query tile's first and last steps sit in its own loop body, not
    # at the kernel's ends: unrolled, they run under other tiles' dots
    def q_tile(jq):
        qs = _sub(jq, t.sub_q, nqs)
        row0 = iq * t.res_q + jq * t.sub_q
        q = q_ref[0, qs, :]

        @pl.when(ik == 0)
        def _init():
            acc_ref[qs, :] = jnp.zeros((t.sub_q, d), jnp.float32)
            m_ref[qs, :] = jnp.full((t.sub_q, _LANES), _NEG_INF)
            l_ref[qs, :] = jnp.zeros((t.sub_q, _LANES), jnp.float32)

        def tile(jk, masked):
            ks = _sub(jk, t.sub_k, nks)
            k = k_ref[0, ks, :]
            v = v_ref[0, ks, :]
            s = _mxu_dot(q, k, _NT) * sm_scale      # (sub_q, sub_k)
            if masked is not None:
                col0 = ik * t.res_k + jk * t.sub_k
                seen = mask.tile_mask(g, s.shape, 0, row0, col0, masked)
                if sel_ref is not None:
                    seen = _and(seen, unpack_selection(sel_ref[
                        0, _sub(jq, t.sub_q // _SEL_BITS, nqs), ks]))
                s = jnp.where(seen, s, _NEG_INF)
            m_prev = m_ref[qs, :]                   # (sub_q, _LANES)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - _lanes_to(m_new, t.sub_k))
            l_ref[qs, :] = l_ref[qs, :] * alpha + p.sum(axis=-1,
                                                        keepdims=True)
            m_ref[qs, :] = m_new
            # p in v's dtype for the second MXU dot (flash convention:
            # the f32 online-softmax state carries the precision; p's
            # entries are probabilities in [0,1] where bf16 relative
            # error is ~2^-8)
            acc_ref[qs, :] = acc_ref[qs, :] * _lanes_to(alpha, d) + \
                _mxu_dot(p.astype(v.dtype), v, _NN)

        _run(mask.k_runs(g, row0, ik * t.res_k, nks), tile, mask.end_to_end)

        @pl.when(ik == nkr - 1)
        def _finish():
            # rows whose running max never rose above the sentinel saw no
            # visible key (causal with seq_q > seq_k): emit zeros, and a
            # +1e30 lse so the backward's recomputed p = exp(s - lse)
            # underflows to 0 for them — zero output, zero gradient, same
            # convention as attention_reference/_chunked_attention
            m = m_ref[qs, :]
            degenerate = m <= _NEG_INF * 0.5
            l_safe = jnp.where(degenerate, 1.0, l_ref[qs, :])
            # widen the f32 state, then compare: Mosaic does not tile or
            # reshape i1 vectors
            o_ref[0, qs, :] = jnp.where(
                _lanes_to(m, d) <= _NEG_INF * 0.5, 0.0,
                acc_ref[qs, :] / _lanes_to(l_safe, d)).astype(o_ref.dtype)
            if lse_ref is not None:
                # logsumexp residual for the flash backward, compact: the
                # lane-replicated column state leaves as part of a row
                lse = jnp.where(degenerate, -_NEG_INF, m + jnp.log(l_safe))
                lse_ref[0, :, qs] = lse.T[:1]

    _loop(0, nqs, q_tile)


def _pad_bh(x, s_to, d_to, halves=1):
    """(b, h, s, d) as (b*h, s_to, d_to), zero padded (each of *halves*
    equal parts of the sequence to its own share of *s_to*); no copy where
    the shape already fits."""
    b, h, s, d = x.shape
    if s_to != s or d_to != d:
        x = jnp.pad(x.reshape(b, h, halves, s // halves, d), (
            (0, 0), (0, 0), (0, 0), (0, (s_to - s) // halves),
            (0, d_to - d)))
    return x.reshape(b * h, s_to, d_to)


def _pad_rows(x, s_to, halves=1):
    """A (bh, 1, s) row statistic zero padded to *s_to*, a part at a
    time."""
    bh, _, s = x.shape
    if s_to == s:
        return x
    return jnp.pad(x.reshape(bh, 1, halves, s // halves), (
        (0, 0), (0, 0), (0, 0), (0, (s_to - s) // halves))
    ).reshape(bh, 1, s_to)


def _pad_selection(sel, rows, cols):
    """A selection operand zero padded to *rows* packed rows and *cols*
    columns: a padded row sees nothing and a padded column is seen by
    nobody."""
    groups = rows // _SEL_BITS
    if sel.shape[1:] == (groups, cols):
        return sel
    return jnp.pad(sel, ((0, 0), (0, groups - sel.shape[1]),
                         (0, cols - sel.shape[2])))


def _unpad_bh(x, b, h, s, d, halves=1):
    """(b*h, s_padded, d_padded) back to (b, h, s, d), each of *halves*
    parts cut to its own rows; no copy where nothing was padded."""
    s_p, d_p = x.shape[1:]
    if (s_p, d_p) == (s, d):
        return x.reshape(b, h, s, d)
    x = x.reshape(b, h, halves, s_p // halves, d_p)
    return x[:, :, :, :s // halves, :d].reshape(b, h, s, d)


def _unpad_rows(x, s, halves=1):
    """`_pad_rows` undone."""
    bh, _, s_p = x.shape
    if s_p == s:
        return x
    return x.reshape(bh, 1, halves, s_p // halves)[
        ..., :s // halves].reshape(bh, 1, s)


def _block_specs(t, d_block, dv_block, q_index, k_index):
    """BlockSpecs of a resident query-side block and a key-side block at
    q's and k's width (q, dq; k, dk), of the same two at v's width (o, dO;
    v, dv), and of a query-side statistics row."""
    def block(rows, width, index):
        return pl.BlockSpec((1, rows, width),
                            lambda *g: (g[0], index(*g), 0))

    return (block(t.res_q, d_block, q_index),
            block(t.res_k, d_block, k_index),
            block(t.res_q, dv_block, q_index),
            block(t.res_k, dv_block, k_index),
            pl.BlockSpec((1, 1, t.res_q),
                         lambda *g: (g[0], 0, q_index(*g))))


# The wrappers are jitted for the trace cache alone: an unrolled kernel
# body takes 0.4 to 0.7 s to trace and as long again to lower, and a
# model calls the same kernel once a layer.  Through the cache the body
# is traced once per shape and emitted as one function that every layer
# calls (XLA inlines it; each call site keeps its own op_name).
_STATIC = ("causal", "sm_scale", "blk_q", "blk_k", "interpret", "res_q",
           "res_k", "mask")


@functools.partial(jax.jit, static_argnames=_STATIC + ("with_lse",))
def _flash_fwd_pallas(q, k, v, causal, sm_scale, blk_q=None, blk_k=None,
                      interpret=False, with_lse=False, res_q=None,
                      res_k=None, sel=None, mask=None):
    """Flash forward: grid (B*H, resident q blocks, resident k blocks),
    f32 accumulators in VMEM scratch.  ``with_lse`` also returns the
    per-row logsumexp residual (the flash backward's recompute anchor)
    as ``(B*H, 1, seq_q)``.  *sel* is a selection operand
    (`pack_selection` of a ``(B, seq_q, seq_k)`` mask, one for all the
    heads of a batch row): a query then sees a key only where its bit is
    set, besides what `causal` and *mask* describe (`_described`) and the
    padding."""
    b, h, sq, d = q.shape
    sk, d_v = k.shape[2], v.shape[3]
    mask = _described(causal, mask, sq, sk)
    halves = mask.parts
    plan = _flash_plan(sq, sk, d, q.dtype, blk_q, blk_k, res_q, res_k, d_v,
                       halves)
    t, dp, dvp = plan.fwd, plan.d_block, plan.dv_block
    sq_p, sk_p = plan.sq_fwd, plan.sk_fwd
    g = _Frame(t, sq, sk, sq_p, sk_p, sel is not None)
    qp = _pad_bh(q, sq_p, dp, halves)
    kp = _pad_bh(k, sk_p, dp, halves)
    vp = _pad_bh(v, sk_p, dvp, halves)
    bh = b * h
    nqr, nkr = sq_p // t.res_q, sk_p // t.res_k

    def k_index(bh_, iq, ik):
        return mask.k_block(g, nkr, iq, ik)

    q_spec, k_spec, o_spec, v_spec, row_spec = _block_specs(
        t, dp, dvp, lambda bh_, iq, ik: iq, k_index)
    kernel = functools.partial(_flash_fwd_kernel, mask=mask, g=g,
                               grid=(nqr, nkr), sm_scale=sm_scale)
    in_specs, operands, params = [q_spec, k_spec, v_spec], (qp, kp, vp), {}
    if sel is not None:
        in_specs.append(pl.BlockSpec(
            (1, t.res_q // _SEL_BITS, t.res_k),
            lambda bh_, iq, ik: (bh_ // h, iq, k_index(bh_, iq, ik))))
        operands += (_pad_selection(sel, sq_p, sk_p),)
        params["vmem_limit_bytes"] = _selected_vmem_limit(
            "fwd", plan, q.dtype.itemsize)
    out_specs = [o_spec]
    out_shape = [jax.ShapeDtypeStruct((bh, sq_p, dvp), q.dtype)]
    if with_lse:  # training: also emit the logsumexp residual
        out_specs.append(row_spec)
        out_shape.append(jax.ShapeDtypeStruct((bh, 1, sq_p), jnp.float32))
    with jax.named_scope("mx.flash.fwd"):
        res = pl.pallas_call(
            kernel,
            grid=(bh, nqr, nkr),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((t.res_q, dvp), jnp.float32),
                pltpu.VMEM((t.res_q, _LANES), jnp.float32),
                pltpu.VMEM((t.res_q, _LANES), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                **params),
            interpret=interpret,
            name="mx_flash_fwd",
        )(*operands)
    out = _unpad_bh(res[0], b, h, sq, d_v, halves)
    if with_lse:
        return out, _unpad_rows(res[1], sq, halves)
    return out


# ---------------------------------------------------------------------------
# Pallas flash backward kernel (the flash-attention backward: recompute p
# from the saved logsumexp, accumulate dq / dk / dv tile by tile; delta_i
# = rowsum(dO_i * O_i) precomputed at the XLA level).  lse and delta enter
# compact, as rows of a (B*H, 1, seq_q) array.
#
# One kernel forms s, the mask, p, dP and ds once a visible score tile
# and feeds three accumulating dots from them (two kernels would each
# recompute the scores' half: 9 dots, two passes of exp and of the f32
# chain, for 7 and one).  The scores stay transposed, (sub_k, sub_q):
# the K/V block is the resident side and Q/dO stream past it, the
# statistics broadcast along sublanes as the rows they are, and dv += p
# dO and dk += ds q are plain a @ b.  dq accumulates over the other axis,
# so no block-sized scratch can hold it: its f32 accumulator spans the
# head's whole sequence in VMEM (`dq_accumulator` `vmem`), zeroed block
# by block in the steps of the head's first K/V block and written out in
# those of its last; dq's output block spans the sequence too, its index
# constant over both inner grid axes.  Its dot is the one that needs a
# score tile turned, ds^T k: XLU work that rides under the dots.  Where
# a sequence is too long for that (`_VMEM_DQ`) each K/V block's share of
# dq leaves as an f32 partial that XLA sums (`hbm`), a group of heads at
# a time so that the partials in HBM stay bounded (`_HBM_DQ`).
# ---------------------------------------------------------------------------

def _wide(x, width):
    """A (rows, d) operand of the backward kernel, zero-extended to the
    accumulators' 128-lane width inside VMEM.  At d = 64 the dots that
    contract over d or write d columns then run on whole lane tiles
    (the pair this kernel replaced: dk/dv 9% and dq 6% faster at S =
    2048 than on 64-wide operands, tools/flash_sweep.py; the forward
    measured flat and is left narrow); HBM holds the 64 columns only.  Each operand is widened to
    its own accumulator's lanes: q and k to dq's and dk's, v and dO to
    dv's (at 192 the second lane tile's zero half is written out, the
    lanes VMEM pads the block to anyway)."""
    d = x.shape[1]
    return x if d == width else jnp.pad(x, ((0, 0), (0, width - d)))


@_traced_inline
def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      *rest, mask, g, grid, sm_scale):
    """K/V block resident, Q/dO sub-tiles over the runs the description
    *mask* gives of the `_Frame` *g* (causal: from the first visible row
    on); *dq_acc* spans the head's sequence, and without it this step's
    share of dq accumulates in its f32 output block.  With a selection
    (`g.selected`) a selection block follows delta, the scores' way round:
    (1, res_k / 32, res_q) words, bit c % 32 of word c // 32 saying whether
    the query sees key row c of the block."""
    sel_ref = rest[0] if g.selected else None
    dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *maybe_dq_acc = \
        rest[1:] if g.selected else rest
    dq_acc = maybe_dq_acc[0] if maybe_dq_acc else None
    t, (nqr, nkr) = g.t, grid
    ik, iq = _grid_pos(1, nkr), _grid_pos(2, nqr)
    nqs, nks = t.res_q // t.sub_q, t.res_k // t.sub_k
    d, wide = dk_ref.shape[2], dk_acc.shape[1]
    d_v, wide_v = dv_ref.shape[2], dv_acc.shape[1]

    if dq_acc is None:
        dq_ref[0, 0] = jnp.zeros(dq_ref.shape[2:], jnp.float32)

        def dq_add(jq, x):
            dq_ref[0, 0, _sub(jq, t.sub_q, nqs), :] += x[:, :d]
    else:
        # this query block's rows of the head's accumulator
        rows = _sub(iq, t.res_q, nqr)

        @pl.when(ik == 0)
        def _init_dq():
            dq_acc[rows, :] = jnp.zeros((t.res_q, wide), jnp.float32)

        def dq_add(jq, x):
            dq_acc[_sub(iq * nqs + jq, t.sub_q, nqr * nqs), :] += x

    def k_tile(jk):
        ks = _sub(jk, t.sub_k, nks)
        col0 = ik * t.res_k + jk * t.sub_k
        k = _wide(k_ref[0, ks, :], wide)
        v = _wide(v_ref[0, ks, :], wide_v)

        @pl.when(iq == 0)
        def _init():
            dk_acc[ks, :] = jnp.zeros((t.sub_k, wide), jnp.float32)
            dv_acc[ks, :] = jnp.zeros((t.sub_k, wide_v), jnp.float32)

        def tile(jq, masked):
            qs = _sub(jq, t.sub_q, nqs)
            q = _wide(q_ref[0, qs, :], wide)
            do = _wide(do_ref[0, qs, :], wide_v)
            s = _mxu_dot(k, q, _NT) * sm_scale      # (sub_k, sub_q)
            if masked is not None:
                row0 = iq * t.res_q + jq * t.sub_q
                seen = mask.tile_mask(g, s.shape, 1, row0, col0, masked)
                if sel_ref is not None:
                    seen = _and(seen, unpack_selection(sel_ref[
                        0, _sub(jk, t.sub_k // _SEL_BITS, nks), qs]))
                s = jnp.where(seen, s, _NEG_INF)
            p = jnp.exp(s - lse_ref[0, :, qs])
            # dv += p dO (the tile is p^T as the forward knew it) — p cast
            # to the storage dtype for a full-rate MXU dot; accumulators
            # stay f32
            dv_acc[ks, :] += _mxu_dot(p.astype(do.dtype), do, _NN)
            # ds = p * (dO v^T - delta) * scale;  dk += ds q;  dq += ds^T k
            ds = p * (_mxu_dot(v, do, _NT) - delta_ref[0, :, qs]) * sm_scale
            dk_acc[ks, :] += _mxu_dot(ds.astype(q.dtype), q, _NN)
            # ds is turned in f32 and cast after: the XLU transposes
            # 32-bit tiles as they are, a bf16 tile it has to unpack (what
            # Mosaic makes of a dot that contracts ds over its first axis:
            # 8% slower at S = 2048, tools/flash_sweep.py)
            dq_add(jq, _mxu_dot(ds.T.astype(k.dtype), k, _NN))

        _run(mask.q_runs(g, col0, iq * t.res_q, nqs), tile, mask.end_to_end)

        @pl.when(iq == nqr - 1)
        def _finish():
            dk_ref[0, ks, :] = dk_acc[ks, :d].astype(dk_ref.dtype)
            dv_ref[0, ks, :] = dv_acc[ks, :d_v].astype(dv_ref.dtype)

    _loop(0, nks, k_tile)

    if dq_acc is not None:
        @pl.when(ik == nkr - 1)
        def _finish_dq():
            dq_ref[0, rows, :] = dq_acc[rows, :d].astype(dq_ref.dtype)


def _dq_head_groups(bh, partial_bytes, sq):
    """How many of the *bh* heads go through the backward call together
    where dq leaves as partials of *partial_bytes* a head: the largest
    divisor of *bh* whose partials `_HBM_DQ` holds."""
    if partial_bytes > _HBM_DQ:
        raise ValueError(
            "flash attention backward: a query sequence of %d is too long: "
            "dq's f32 partials of one head would take %.1f GiB of HBM "
            "(limit %.1f); split the sequence (parallel.sequence)"
            % (sq, partial_bytes / 2 ** 30, _HBM_DQ / 2 ** 30))
    return max(g for g in range(1, bh + 1)
               if bh % g == 0 and g * partial_bytes <= _HBM_DQ)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _flash_bwd_pallas(q, k, v, out, lse, dout, causal, sm_scale,
                      blk_q=None, blk_k=None, interpret=False, res_q=None,
                      res_k=None, sel=None, mask=None):
    """dq, dk, dv from the forward's output and its ``(B*H, 1, seq_q)``
    logsumexp: grid (B*H, resident k blocks, resident q blocks), K/V
    resident and Q/dO streamed.  *sel* is the forward's selection the
    scores' way round here: `pack_selection` of the ``(B, seq_k, seq_q)``
    transposed mask."""
    b, h, sq, d = q.shape
    sk, d_v = k.shape[2], v.shape[3]
    mask = _described(causal, mask, sq, sk)
    halves = mask.parts
    plan = _flash_plan(sq, sk, d, q.dtype, blk_q, blk_k, res_q, res_k, d_v,
                       halves)
    t, dp, dvp = plan.bwd, plan.d_block, plan.dv_block
    sq_p, sk_p = plan.sq_bwd, plan.sk_bwd
    g = _Frame(t, sq, sk, sq_p, sk_p, sel is not None)
    # the accumulators' lanes
    wide, wide_v = _round_up(dp, _LANES), _round_up(dvp, _LANES)
    qp = _pad_bh(q, sq_p, dp, halves)
    kp = _pad_bh(k, sk_p, dp, halves)
    vp = _pad_bh(v, sk_p, dvp, halves)
    dop = _pad_bh(dout, sq_p, dvp, halves)
    bh = b * h
    # delta_i = rowsum(dO_i * O_i), a row like lse; both zero on padded
    # rows, where dO is zero too and p = exp(0 - 0) multiplies nothing
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(bh, 1, sq)
    lse, delta = _pad_rows(lse, sq_p, halves), _pad_rows(delta, sq_p, halves)
    nqr, nkr = sq_p // t.res_q, sk_p // t.res_k

    def q_index(bh_, ik, iq):
        return mask.q_block(g, nqr, ik, iq)

    q_spec, k_spec, do_spec, v_spec, row_spec = _block_specs(
        t, dp, dvp, q_index, lambda bh_, ik, iq: ik)
    scratch = [pltpu.VMEM((t.res_k, wide), jnp.float32),
               pltpu.VMEM((t.res_k, wide_v), jnp.float32)]
    in_vmem = plan.dq_accumulator == "vmem"
    if in_vmem:
        # the whole head's dq: fetched never, written back once a head
        heads = bh
        dq_spec = pl.BlockSpec((1, sq_p, dp), lambda bh_, ik, iq: (bh_, 0, 0))
        dq_shape = jax.ShapeDtypeStruct((heads, sq_p, dp), q.dtype)
        scratch.append(pltpu.VMEM((sq_p, wide), jnp.float32))
    else:
        # every step writes its block, the empty ones zeros
        heads = _dq_head_groups(bh, nkr * sq_p * dp * 4, sq)
        dq_spec = pl.BlockSpec((1, 1, t.res_q, dp),
                               lambda bh_, ik, iq: (bh_, ik, iq, 0))
        dq_shape = jax.ShapeDtypeStruct((heads, nkr, sq_p, dp), jnp.float32)

    kernel = functools.partial(_flash_bwd_kernel, mask=mask, g=g,
                               grid=(nqr, nkr), sm_scale=sm_scale)
    in_specs = [q_spec, k_spec, v_spec, do_spec, row_spec, row_spec]
    limit = _vmem_limit(plan, q.dtype.itemsize)
    if sel is not None:
        if heads != bh:
            raise ValueError(
                "flash attention backward: a selection operand with dq's "
                "partials in HBM (a query sequence of %d) is not built" % sq)
        in_specs.append(pl.BlockSpec(
            (1, t.res_k // _SEL_BITS, t.res_q),
            lambda bh_, ik, iq: (bh_ // h, ik, q_index(bh_, ik, iq))))
        limit = _selected_vmem_limit("bwd", plan, q.dtype.itemsize)

    def call(operands):
        """The kernel over *heads* heads (axis 0 of every operand)."""
        with jax.named_scope("mx.flash.bwd"):
            dq, dk, dv = pl.pallas_call(
                kernel,
                grid=(heads, nkr, nqr),
                in_specs=in_specs,
                out_specs=[dq_spec, k_spec, v_spec],
                out_shape=[dq_shape,
                           jax.ShapeDtypeStruct((heads, sk_p, dp), k.dtype),
                           jax.ShapeDtypeStruct((heads, sk_p, dvp),
                                                v.dtype)],
                scratch_shapes=scratch,
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("parallel", "arbitrary",
                                         "arbitrary"),
                    vmem_limit_bytes=limit),
                interpret=interpret,
                name="mx_flash_bwd",
            )(*operands)
        if not in_vmem:
            dq = dq.sum(axis=1).astype(q.dtype)
        return dq, dk, dv

    operands = (qp, kp, vp, dop, lse, delta)
    if sel is not None:
        operands += (_pad_selection(sel, sk_p, sq_p),)
    if heads == bh:
        dq, dk, dv = call(operands)
    else:
        # one group's partials at a time: the loop's buffer is reused
        dq, dk, dv = (
            x.reshape((bh,) + x.shape[2:]) for x in jax.lax.map(call, tuple(
                x.reshape((bh // heads, heads) + x.shape[1:])
                for x in operands)))
    return (_unpad_bh(dq, b, h, sq, d, halves),
            _unpad_bh(dk, b, h, sk, d, halves),
            _unpad_bh(dv, b, h, sk, d_v, halves))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, sm_scale, interpret, mask=None):
    _record_plan(q, k, v, causal, mask=mask)
    return _flash_fwd_pallas(q, k, v, causal, sm_scale, interpret=interpret,
                             mask=mask)


def _flash_vjp_fwd(q, k, v, causal, sm_scale, interpret, mask):
    out, lse = _flash_fwd_pallas(q, k, v, causal, sm_scale,
                                 interpret=interpret, with_lse=True,
                                 mask=mask)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, sm_scale, interpret, mask, res, g):
    q, k, v, out, lse = res
    return _flash_bwd_pallas(q, k, v, out, lse, g, causal, sm_scale,
                             interpret=interpret, mask=mask)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)



def flash_attention(q, k, v, causal=False, sm_scale=None, interpret=False,
                    mask=None):
    """Blockwise (flash) attention, (B, H, S, D) layout.

    Pallas MXU kernel on TPU; chunked-scan XLA path elsewhere.  Both have
    O(S * block) activation memory; grads flow through either.  *mask* is
    a static description: of a mask that is not causal, a `BlockDiffusion`
    ``(block, half)``, or beside ``causal=True`` a `Window` ``(keys,)``
    that bounds a query's keys from below as well.  Either way the kernels'
    loops visit the tiles that hold a visible pair and no other, and no
    mask is an operand.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    mask = _described(causal, mask, q.shape[2], k.shape[2])
    if interpret:
        dt = jnp.result_type(q.dtype, k.dtype, v.dtype)
        return _flash(q.astype(dt), k.astype(dt), v.astype(dt),
                      causal, float(sm_scale), True, mask).astype(q.dtype)

    def _tpu(q, k, v):
        # the kernels' MXU dots need one operand dtype (f32 q against a
        # bf16 KV cache would raise); promote once here so the uniform
        # bf16 fast path is untouched.  platform_dependent traces BOTH
        # branches on every platform, so the promotion stays inside
        dt = jnp.result_type(q.dtype, k.dtype, v.dtype)

        def local(q, k, v):
            return _flash(q.astype(dt), k.astype(dt), v.astype(dt),
                          causal, float(sm_scale), False,
                          mask).astype(q.dtype)

        from ..parallel.mesh import current_mesh
        mesh = current_mesh()
        manual = jax.sharding.get_abstract_mesh().manual_axes
        if mesh is None or mesh.size == 1 or \
                set(manual) == set(mesh.axis_names):
            # one device, or already per shard (a pipeline stage)
            return local(q, k, v)
        # XLA does not partition a Mosaic kernel ("wrap the call in a
        # shard_map"): run it per shard.  Attention is independent over
        # batch and heads, so dp splits the batch and tp the heads; any
        # other mesh axis computes replicated.
        spec = P("dp" if "dp" in mesh.shape else None,
                 "tp" if "tp" in mesh.shape else None)
        return jax.shard_map(local, mesh=mesh, in_specs=(spec,) * 3,
                             out_specs=spec, check_vma=False)(q, k, v)

    def _other(q, k, v):
        return _chunked_attention(q, k, v, causal, sm_scale,
                                  mask=mask).astype(q.dtype)

    # decided at LOWERING time per platform, not by which devices this
    # process happens to see: only the target platform's branch is
    # lowered, so the Mosaic kernels are the program on a TPU and never
    # reach a CPU compile
    return jax.lax.platform_dependent(q, k, v, tpu=_tpu, default=_other)


# ---------------------------------------------------------------------------
# Causal attention over the keys a selection names (learned sparse
# attention: `ops/sparse_attention.py` makes the selection).  The same two
# kernels with a selection operand, one bit a query-key pair, shared by the
# heads of a batch row; the blockwise `jax.numpy` body on other platforms.
# ---------------------------------------------------------------------------

#: query rows a step of the `jax.numpy` body holds scores for
_SELECTED_BLOCK = 256


def _selected_rows(q, k, v, sel_q, sm_scale):
    """The `jax.numpy` body: blocks of query rows, one after another, each
    against every key under its rows' bits and the diagonal; JAX
    differentiates it, computing a block's scores again.  ``(out, lse (B,
    H, S))``."""
    b, h, s, d = q.shape
    blk = _SELECTED_BLOCK if s % _SELECTED_BLOCK == 0 else s
    n, groups = s // blk, -(-blk // _SEL_BITS)
    cols = jnp.arange(s)

    @jax.checkpoint
    def rows(qb, words, row0):
        att = jnp.einsum("bhqd,bhkd->bhqk", qb, k,
                         precision=matmul_precision(qb.dtype, k.dtype),
                         preferred_element_type=jnp.float32) * sm_scale
        seen = unpack_selection(words, blk) \
            & (cols[None, :] <= row0 + jnp.arange(blk)[:, None])
        att = jnp.where(seen[:, None], att, _NEG_INF)
        m = att.max(-1)
        p = jnp.exp(att - m[..., None])
        l = p.sum(-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                       precision=matmul_precision(v.dtype, v.dtype),
                       preferred_element_type=jnp.float32)
        return (o / l[..., None]).astype(q.dtype), m + jnp.log(l)

    words = _pad_selection(sel_q, n * groups * _SEL_BITS, s)
    out, lse = jax.lax.map(
        lambda at: rows(*at),
        (q.reshape(b, h, n, blk, d).transpose(2, 0, 1, 3, 4),
         words.reshape(b, n, groups, s).transpose(1, 0, 2, 3),
         jnp.arange(0, s, blk)))
    return (out.transpose(1, 2, 0, 3, 4).reshape(b, h, s, v.shape[3]),
            lse.transpose(1, 2, 0, 3).reshape(b, h, s))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash_selected(q, k, v, sel_q, sel_k, sm_scale, interpret):
    _record_plan(q, k, v, True, selected=True)
    out, lse = _flash_fwd_pallas(q, k, v, True, sm_scale,
                                 interpret=interpret, with_lse=True,
                                 sel=sel_q)
    return out, lse.reshape(q.shape[:3])


def _flash_selected_fwd(q, k, v, sel_q, sel_k, sm_scale, interpret):
    out, lse = _flash_selected(q, k, v, sel_q, sel_k, sm_scale, interpret)
    return (out, lse), (q, k, v, out, lse, sel_k)


def _flash_selected_bwd(sm_scale, interpret, res, g):
    # the logsumexp leaves for the alignment term, which holds it fixed:
    # its cotangent is not read
    q, k, v, out, lse, sel_k = res
    b, h, s, _ = q.shape
    return _flash_bwd_pallas(q, k, v, out, lse.reshape(b * h, 1, s), g[0],
                             True, sm_scale, interpret=interpret,
                             sel=sel_k) + (None, None)


_flash_selected.defvjp(_flash_selected_fwd, _flash_selected_bwd)


def mosaic_runs_here():
    """Whether a Mosaic call traced here reaches one device: no mesh, a
    mesh of one, or inside a `shard_map` over every mesh axis.  (XLA does
    not partition a Mosaic kernel.)"""
    from ..parallel.mesh import current_mesh
    mesh = current_mesh()
    return mesh is None or mesh.size == 1 or set(
        jax.sharding.get_abstract_mesh().manual_axes) == set(mesh.axis_names)


def selected_attention(q, k, v, sel_q, sel_k, sm_scale=None,
                       interpret=False):
    """Causal attention in which query ``t`` of a batch row sees key ``s``
    only where the selection says so, (B, H, S, D) layout, equal head
    counts: ``(out, lse)`` with ``lse`` (B, H, S) the logsumexp of each
    row's visible scores (float32, carrying no gradient).

    *sel_q* is `pack_selection` of the ``(B, S, S)`` mask (queries by
    keys) and *sel_k* of its transpose, one selection for all the heads of
    a batch row; a query has to see at least one key.  The flash kernels
    with the selection as an operand where the program is lowered for the
    TPU (one device, or inside a `shard_map` over every mesh axis), the
    blockwise `jax.numpy` body elsewhere."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    dt = jnp.result_type(q.dtype, k.dtype, v.dtype)
    q, k, v = q.astype(dt), k.astype(dt), v.astype(dt)
    if interpret:
        return _flash_selected(q, k, v, sel_q, sel_k, float(sm_scale), True)

    def _tpu(q, k, v, sel_q, sel_k):
        if not mosaic_runs_here():
            # no cell spans chips with a selection
            return _selected_rows(q, k, v, sel_q, float(sm_scale))
        return _flash_selected(q, k, v, sel_q, sel_k, float(sm_scale),
                               False)

    def _other(q, k, v, sel_q, sel_k):
        return _selected_rows(q, k, v, sel_q, float(sm_scale))

    return jax.lax.platform_dependent(q, k, v, sel_q, sel_k, tpu=_tpu,
                                      default=_other)


# ---------------------------------------------------------------------------
# Operator registrations.
# ---------------------------------------------------------------------------

@register_op("_contrib_DotProductAttention",
             input_names=("query", "key", "value"))
def _dot_product_attention(query, key, value, causal=False, sm_scale=None,
                           mask=None, mask_block=1, window=0):
    """Fused scaled-dot-product attention (TPU-native; no reference
    counterpart — the reference predates Transformers, SURVEY §5.7).
    *mask* ``"block_diffusion"`` with *mask_block* puts the sequence under
    the block-diffusion mask (`ops/attention.py` `BlockDiffusion`: a clean
    copy then a noised copy of half the sequence each), under device scope
    ``mx.bd.attention``.  *window* > 0 with ``causal`` bounds a query's
    keys to that many, its own the last (`Window`).  The pairs either
    leaves visible go out as step stat ``bd_visible_pairs`` or
    ``swa_visible_pairs``."""
    if mask == "block_diffusion":
        mask = BlockDiffusion(int(mask_block), query.shape[2] // 2)
    elif mask is not None:
        raise ValueError("mask %r is not built (block_diffusion is)"
                         % (mask,))
    elif window:
        mask = Window(int(window))
    scope = contextlib.nullcontext()
    if mask is not None:
        from .. import profiler
        # at trace time on purpose (as the routed op's counts); a float: a
        # batch's pairs pass 2^31
        profiler.emit_step_stat(  # graftlint: disable=JG003
            mask.stat, jnp.float32(query.shape[0] * mask.pairs(
                query.shape[2], key.shape[2])))
        if mask.scope:
            scope = jax.named_scope(mask.scope)
    with scope:
        return flash_attention(query, key, value, causal=bool(causal),
                               sm_scale=sm_scale, mask=mask)
