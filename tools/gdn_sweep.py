"""Gated delta rule sweep on the real chip: numbers and device time of the
two Mosaic kernels of `_contrib_GatedDeltaRule` (`mx_gdn_fwd`, `mx_gdn_bwd`
in ops/delta_rule.py) at the benchmark cell's shape, (1, 3072, 30, 96 | 192)
in bf16, and at 4096 positions.

One command: first the kernels against the recurrence itself, token by
token in float64 on the host (`benchmarks/gdn_counts.py`), at the cell's
widths over three chunks of two heads with float32 inputs, in the two
regimes that strain the in-chunk solve (``b`` near 2, keys nearly alike)
and the usual one: the largest difference of the output over its largest
entry, which `tests/test_olmo_hybrid.py` holds under 2e-5 on the CPU.  Then,
a sequence at a time, the `jax.numpy` path (`_forward`, `_backward`) as XLA
compiles it, and the kernels over the solve's block, each checked against the `jax.numpy` path's result on the same chip and
timed, with the pair's share of the recurrence's floor (`gdn_counts`' FLOPs
and bytes at the chip's peaks: what `gdn_scan_roofline_pct` reads in the
cell).  `GDN_TILES` in ops/delta_rule.py, and the table in PERF.md section 6
(PR 49), come from it.

    python tools/gdn_sweep.py [--default-only] [--seq N ...]

Timing is `tools/shortconv_sweep.py`'s: the device's busy time a call under
the profiler.  Needs the chip to itself: one process per chip.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the cell's batch, heads, key and value widths; the sequences
BATCH, HEADS, DK, DV, CHUNK = 1, 30, 96, 192, 64
SEQS = (3072, 4096)

#: rows of the solve's diagonal blocks
SOLVES = (8, 16, 32, 64)


def inputs(seq, heads, dtype, regime="usual", seed=0):
    """q, k normalised as the block hands them, v, the decay's logarithm
    and the write strength: ``(B, S, H, .)``."""
    import numpy as np
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    shape = (BATCH, seq, heads)
    q = unit(rng.normal(size=shape + (DK,))) / np.sqrt(DK)
    k = unit(rng.normal(size=shape + (DK,)))
    v = rng.normal(size=shape + (DV,))
    g = -rng.uniform(0.0, 0.3, shape)
    b = rng.uniform(0.0, 2.0, shape)
    if regime == "b-near-2":
        b, g = rng.uniform(1.9, 2.0, shape), -rng.uniform(0.0, 0.05, shape)
    elif regime == "keys-nearly-alike":
        k = unit(k[:, :1] + 0.05 * rng.normal(size=k.shape))
        g = -rng.uniform(0.0, 0.02, shape)
    import jax.numpy as jnp
    return tuple(jnp.asarray(x, t) for x, t in zip(
        (q, k, v, g, b), (dtype, dtype, dtype, jnp.float32, dtype)))


def floor_ms(seq, kind):
    """The recurrence's least time forward and backward at the peaks of
    device *kind* (`benchmarks/peaks.json`), in ms."""
    import json
    from benchmarks import gdn_counts
    with open(os.path.join(sys.path[0], "benchmarks", "peaks.json")) as f:
        peaks = json.load(f)[kind]
    return 1e3 * max(
        gdn_counts.rule_flops(BATCH, seq, HEADS, DK, DV)
        / peaks["bf16_flops_per_s"],
        gdn_counts.rule_bytes(BATCH, seq, HEADS, DK, DV)
        / peaks["hbm_bytes_per_s"])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--default-only", action="store_true",
                    help="GDN_TILES as they stand and no other tiling")
    ap.add_argument("--seq", type=int, nargs="*", default=list(SEQS))
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks import gdn_counts
    from mxnet_tpu.ops import delta_rule
    from tools.shortconv_sweep import device_ms, gap

    dev = jax.devices()[0]
    print("gdn_sweep: platform=%s kind=%r" % (dev.platform, dev.device_kind),
          flush=True)
    if dev.platform != "tpu":
        sys.exit("gdn_sweep: no TPU: a device time comes only from the chip")
    tiles = delta_rule.GDN_TILES

    def pair(solve):
        kw = dict(chunk=CHUNK, solve=solve)
        return (jax.jit(lambda *a: delta_rule._gdn_fwd_pallas(*a, **kw)),
                jax.jit(lambda *a: delta_rule._gdn_bwd_pallas(*a, **kw)))

    # the kernels against the recurrence in float64, on the chip's own
    # float32 products
    for regime in ("usual", "b-near-2", "keys-nearly-alike"):
        at = inputs(3 * CHUNK, 2, jnp.float32, regime)
        want, _ = gdn_counts.recurrence(*(np.asarray(x) for x in at))
        scale = np.abs(want).max()
        got = {"kernel": pair(tiles["solve"])[0](*at)[0],
               "jax.numpy": jax.jit(lambda *a: delta_rule._forward(
                   *a, CHUNK))(*at)[0]}
        print("against the recurrence, %-17s %s" % (regime, "  ".join(
            "%s %.3g" % (name, np.abs(np.asarray(o, np.float64) - want).max()
                         / scale) for name, o in got.items())), flush=True)

    for seq in args.seq:
        at = inputs(seq, HEADS, jnp.bfloat16)
        dout = jnp.asarray(np.random.default_rng(1).normal(
            size=at[2].shape), jnp.bfloat16)
        body = jax.jit(lambda *a: delta_rule._forward(*a, CHUNK))
        body_bwd = jax.jit(lambda *a: delta_rule._backward(*a, CHUNK))
        want, starts = body(*at)
        want_grads = body_bwd(*at, starts, dout)
        least = floor_ms(seq, dev.device_kind)
        ms = (device_ms(body, *at), device_ms(body_bwd, *at, starts, dout))
        print("seq %d jax.numpy        fwd %7.3f ms  bwd %7.3f ms  %5.2f%% "
              "of the floor's %.3f ms" % ((seq,) + ms + (
                  100 * least / sum(ms), least)), flush=True)
        for solve in [tiles["solve"]] if args.default_only else SOLVES:
            try:
                fwd, bwd = pair(solve)
                out, kept = fwd(*at)
                grads = bwd(*at, kept, dout)
                gaps = "o rel %.2g  states rel %.2g  " % (
                    gap(out, want)[1], gap(kept, starts)[1]) + " ".join(
                        "d%s %.2g" % (n, gap(mine, theirs)[1])
                        for n, mine, theirs in zip("qkvgb", grads,
                                                   want_grads))
                ms = (device_ms(fwd, *at), device_ms(bwd, *at, kept, dout))
            except Exception as e:      # a tiling Mosaic refuses
                print("seq %d solve %2d  refused: %s" % (
                    seq, solve, str(e).splitlines()[0][:120]), flush=True)
                continue
            print("seq %d solve %2d           fwd %7.3f ms  bwd %7.3f ms  "
                  "%5.2f%% of the floor  %s" % (
                      seq, solve, ms[0], ms[1], 100 * least / sum(ms), gaps),
                  flush=True)
    print("gdn_sweep: done", flush=True)


if __name__ == "__main__":
    main()
