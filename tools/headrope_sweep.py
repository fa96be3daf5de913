"""Per-head norm + rotary + head-major layout sweep on the real chip:
numbers and device time of `_head_norm_rotary` (ops/lm_blocks.py) at its
callers' shapes.  Two at q's 32 heads and k's 4 of 128 over 16384
positions: the sparse-attention cell's (``--caller keye``: a text's
positions, three-axis sections, tables that are constants of the program)
and the block-diffusion cell's (``--caller sdar``: `GroupedQueryAttention`
through `_contrib_HeadNormRotary`, the positions of a clean and a noised
copy as an operand, so the tables are computed on the device; their build
is timed beside the kernels).  One at 48 and 8 heads of 128 over 4096:
the window-and-full cell's full layers (``--caller laguna --rotary-dim
64``: half a head at the published YaRN frequencies, so two rolls and
three tables; without ``--rotary-dim`` the same shape turned whole, the
two-table kernels beside them).

One command: the two Mosaic kernels (`mx_headrope_fwd`, `mx_headrope_bwd`)
at several tilings, each checked against `_headrope_body` (today's `_rotary`
over `_rms_norm`, to the bit) and its derivative on the same chip, and each
timed; then that body itself, forward and backward alone, as XLA compiles
it.  `HEADROPE_TILES` in ops/lm_blocks.py, and the table in PERF.md section
6 (PR 34), come from it.

    python tools/headrope_sweep.py [--default-only]
        [--caller keye|sdar|laguna] [--rotary-dim N]

Timing is `tools/shortconv_sweep.py`'s: the device's busy time a call
under the profiler.  Needs the chip to itself: one process per chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: every cell's batch, head width and eps
BATCH, D, EPS = 1, 128, 1e-6

#: a caller's rotary base and sections, whether its positions are an
#: operand (then ``j mod seq / 2``: `_contrib_BlockDiffusionPositions`),
#: its sequence and q's and k's heads
CALLERS = {"keye": (1e7, (16, 24, 24), False, 16384, (32, 4)),
           "sdar": (1e6, (), True, 16384, (32, 4)),
           "laguna": (5e5, (), False, 4096, (48, 8))}

#: what ``--rotary-dim`` under the head's width turns by: the published
#: ``rope_parameters["full_attention"]`` of this configuration (YaRN), the
#: part of a head as the option gives it
YARN_OF = "benchmarks/configs/laguna-s-2.1-ep32share.json"

#: (rows of a grid step, heads of a grid step)
TILINGS = ((256, 8), (512, 1), (1024, 1), (2048, 1), (256, 4), (512, 4),
           (1024, 4), (128, 8), (512, 8), (128, 32), (256, 32), (128, 16),
           (256, 16), (256, 24), (128, 24), (256, 12), (512, 12))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--default-only", action="store_true",
                    help="HEADROPE_TILES as they stand and no other tiling")
    ap.add_argument("--caller", choices=sorted(CALLERS), default="keye")
    ap.add_argument("--rotary-dim", type=int, default=D,
                    help="lanes of a head that turn, at YARN_OF's "
                         "frequencies where fewer than all %d" % D)
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
    theta, sections, as_operand, seq, head_counts = CALLERS[args.caller]
    rotary_dim = args.rotary_dim
    if rotary_dim < D:
        if as_operand:
            sys.exit("headrope_sweep: given frequencies turn by a text's "
                     "positions")
        with open(os.path.join(sys.path[0], YARN_OF)) as f:
            yarn = json.load(f)["rope_parameters"]["full_attention"]
        turn = lm_blocks.rope_frequencies(
            dict(yarn, partial_rotary_factor=rotary_dim / D), D)
        tables = lm_blocks._rotary_tables(
            seq, D, theta, None, (),
            (rotary_dim, turn["inv_freq"], turn["table_scale"]))
        print("caller %s: %d of %d lanes turn, %d tables" % (
            args.caller, rotary_dim, D, len(tables)), flush=True)
    elif as_operand:
        ids = jnp.zeros((BATCH, seq), jnp.int32)
        positions = lm_blocks._block_diffusion_positions(ids)
        build = jax.jit(lambda pos: lm_blocks._rotary_tables(
            seq, D, theta, pos, sections))
        tables = build(positions)
        print("caller %s: the tables from the operand %.3f ms, once a step"
              % (args.caller, device_ms(build, positions)), flush=True)
    else:
        tables = lm_blocks._rotary_tables(seq, D, theta, None, sections)

    tiles = lm_blocks.HEADROPE_TILES
    for heads in head_counts:
        ks = jax.random.split(jax.random.PRNGKey(heads), 3)
        y = jax.random.normal(ks[0], (BATCH, seq, heads * D), bf)
        gamma = (1 + 0.1 * jax.random.normal(ks[1], (D,))).astype(bf)
        dout = jax.random.normal(ks[2], (BATCH, heads, seq, D), bf)
        size = y.size * 2
        moved = {"fwd": 2 * size, "bwd": 3 * size}
        body = jax.jit(lambda y, g: lm_blocks._headrope_body(
            y, g, tables, heads, EPS, rotary_dim))
        body_bwd = jax.jit(lambda y, g, do: lm_blocks._headrope_body_backward(
            y, g, tables, do, heads, EPS, rotary_dim))
        want = body(y, gamma)
        want_dy, want_dg = body_bwd(y, gamma, dout)
        print("heads %2d body   fwd %.3f ms   bwd alone %.3f ms" % (
            heads, device_ms(body, y, gamma),
            device_ms(body_bwd, y, gamma, dout)), flush=True)
        default = {k: (tiles[k], lm_blocks._headrope_plan(
            y, heads, rotary_dim=rotary_dim)[0]["heads"])
            for k in ("fwd", "bwd")}
        for kernel in ("fwd", "bwd"):
            for rows, at_once in ([default[kernel]] if args.default_only
                                  else TILINGS):
                if seq % rows or heads % at_once:
                    continue
                kw = dict(heads=heads, eps=EPS, rows=rows, at_once=at_once,
                          rotary_dim=rotary_dim)
                try:
                    if kernel == "fwd":
                        fn = jax.jit(
                            lambda y, g: lm_blocks._headrope_fwd_pallas(
                                y, g, tables, **kw))
                        a = (y, gamma)
                        gaps = "out max %.3g rel %.3g" % gap(fn(*a), want)
                    else:
                        fn = jax.jit(
                            lambda y, g, do: lm_blocks._headrope_bwd_pallas(
                                y, g, tables, do, **kw))
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
