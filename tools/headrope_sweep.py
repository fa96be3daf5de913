"""Per-head norm + rotary + head-major layout sweep on the real chip:
numbers and device time of `_head_norm_rotary` (ops/lm_blocks.py) at its
two callers' shapes, each q's 32 heads and k's 4 of 128 over 16384
positions: the sparse-attention cell's (``--caller keye``: a text's
positions, three-axis sections, tables that are constants of the program)
and the block-diffusion cell's (``--caller sdar``: `GroupedQueryAttention`
through `_contrib_HeadNormRotary`, the positions of a clean and a noised
copy as an operand, so the tables are computed on the device; their build
is timed beside the kernels).

One command: the two Mosaic kernels (`mx_headrope_fwd`, `mx_headrope_bwd`)
at several tilings, each checked against `_headrope_body` (today's `_rotary`
over `_rms_norm`, to the bit) and its derivative on the same chip, and each
timed; then that body itself, forward and backward alone, as XLA compiles
it.  `HEADROPE_TILES` in ops/lm_blocks.py, and the table in PERF.md section
6 (PR 34), come from it.

    python tools/headrope_sweep.py [--default-only] [--caller keye|sdar]

Timing is `tools/shortconv_sweep.py`'s: the device's busy time a call
under the profiler.  Needs the chip to itself: one process per chip.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: both cells' ``(batch, seq)``, head width and eps
BATCH, SEQ, D, EPS = 1, 16384, 128, 1e-6

#: a caller's rotary base and sections, and whether its positions are an
#: operand (then ``j mod seq / 2``: `_contrib_BlockDiffusionPositions`)
CALLERS = {"keye": (1e7, (16, 24, 24), False), "sdar": (1e6, (), True)}

#: (rows of a grid step, heads of a grid step)
TILINGS = ((256, 8), (512, 1), (1024, 1), (2048, 1), (256, 4), (512, 4),
           (1024, 4), (128, 8), (512, 8), (128, 32), (256, 32), (128, 16),
           (256, 16))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--default-only", action="store_true",
                    help="HEADROPE_TILES as they stand and no other tiling")
    ap.add_argument("--caller", choices=sorted(CALLERS), default="keye")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import lm_blocks
    from tools.shortconv_sweep import device_ms, gap

    dev = jax.devices()[0]
    print("headrope_sweep: platform=%s kind=%r" % (
        dev.platform, dev.device_kind), flush=True)
    if dev.platform != "tpu":
        sys.exit("headrope_sweep: no TPU: a device time comes only from "
                 "the chip")
    bf = jnp.bfloat16
    theta, sections, as_operand = CALLERS[args.caller]
    if as_operand:
        ids = jnp.zeros((BATCH, SEQ), jnp.int32)
        positions = lm_blocks._block_diffusion_positions(ids)
        tables = jax.jit(lambda pos: lm_blocks._rotary_tables(
            SEQ, D, theta, pos, sections))
        cos, sin = tables(positions)
        print("caller %s: the tables from the operand %.3f ms, once a step"
              % (args.caller, device_ms(tables, positions)), flush=True)
    else:
        cos, sin = lm_blocks._rotary_tables(SEQ, D, theta, None, sections)

    tiles = lm_blocks.HEADROPE_TILES
    for heads in (32, 4):
        ks = jax.random.split(jax.random.PRNGKey(heads), 3)
        y = jax.random.normal(ks[0], (BATCH, SEQ, heads * D), bf)
        gamma = (1 + 0.1 * jax.random.normal(ks[1], (D,))).astype(bf)
        dout = jax.random.normal(ks[2], (BATCH, heads, SEQ, D), bf)
        size = y.size * 2
        moved = {"fwd": 2 * size, "bwd": 3 * size}
        body = jax.jit(lambda y, g: lm_blocks._headrope_body(
            y, g, cos, sin, heads, EPS))
        body_bwd = jax.jit(lambda y, g, do: lm_blocks._headrope_body_backward(
            y, g, cos, sin, do, heads, EPS))
        want = body(y, gamma)
        want_dy, want_dg = body_bwd(y, gamma, dout)
        print("heads %2d body   fwd %.3f ms   bwd alone %.3f ms" % (
            heads, device_ms(body, y, gamma),
            device_ms(body_bwd, y, gamma, dout)), flush=True)
        default = {k: (tiles[k], lm_blocks._headrope_plan(y, heads)[0][
            "heads"]) for k in ("fwd", "bwd")}
        for kernel in ("fwd", "bwd"):
            for rows, at_once in ([default[kernel]] if args.default_only
                                  else TILINGS):
                if SEQ % rows or heads % at_once:
                    continue
                kw = dict(heads=heads, eps=EPS, rows=rows, at_once=at_once)
                try:
                    if kernel == "fwd":
                        fn = jax.jit(
                            lambda y, g: lm_blocks._headrope_fwd_pallas(
                                y, g, cos, sin, **kw))
                        a = (y, gamma)
                        gaps = "out max %.3g rel %.3g" % gap(fn(*a), want)
                    else:
                        fn = jax.jit(
                            lambda y, g, do: lm_blocks._headrope_bwd_pallas(
                                y, g, cos, sin, do, **kw))
                        a = (y, gamma, dout)
                        got = fn(*a)
                        gaps = "dy max %.3g rel %.3g  dgamma max %.3g " \
                            "rel %.3g" % (gap(got[0], want_dy)
                                          + gap(got[1], want_dg))
                    ms = device_ms(fn, *a)
                except Exception as e:      # a tiling Mosaic refuses
                    print("heads %2d %s rows %4d at once %2d  refused: %s" % (
                        heads, kernel, rows, at_once,
                        str(e).splitlines()[0][:120]), flush=True)
                    continue
                print("heads %2d %s rows %4d at once %2d  %.3f ms  %5.1f%% "
                      "of 819 GB/s  %s" % (
                          heads, kernel, rows, at_once, ms,
                          100 * moved[kernel] / (ms * 1e-3) / 819e9, gaps),
                      flush=True)
    print("headrope_sweep: done", flush=True)


if __name__ == "__main__":
    main()
