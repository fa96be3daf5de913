"""Short convolution sweep on the real chip: numbers and device time of the
two Mosaic kernels of `_contrib_ShortConvHeads` (`mx_gdnconv_fwd`,
`mx_gdnconv_bwd` in ops/delta_rule.py) alone at the benchmark cell's shape,
(1, 3072, 11520) in bf16 with 30 heads of 96 | 192 and 4 taps, and at 4096
positions.

One command: first the pair against the body on float32 inputs (two rows of
256 positions, tiles of 128), where the chip's own reciprocal, lane sums and
rolls show; then, a sequence at a time, the `jax.numpy` body
(`_conv_heads_body` and JAX's derivative of it behind `_again`'s barrier) as
XLA compiles it, then the pair over the row tiles, the pieces inside them
and the channels of a block, each checked against the body's result on the
same chip and timed, with the share of the HBM's peak that the pass's bytes
are (forward: the input read and q, k, v written; backward: the input and
the three gradients read, one gradient written).
`GDNCONV_TILES` in ops/delta_rule.py, and the table in docs/PERF_NOTES.md
and PERF.md section 6 (PR 51), come from it.

    python tools/gdnconv_sweep.py [--default-only] [--seq N ...]

Timing is `tools/shortconv_sweep.py`'s: the device's busy time a call under
the profiler.  Needs the chip to itself: one process per chip.
"""

from __future__ import annotations

import argparse
import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the cell's batch, heads, key and value widths, taps; the sequences
BATCH, HEADS, DK, DV, TAPS, EPS = 1, 30, 96, 192, 4, 1e-6
SEQS = (3072, 4096)

#: (rows of a grid step, channels of a block, rows of a piece).  A head's
#: sums as a three-part bf16 product on the MXU were swept beside the masked
#: lane sums by PR 50's builder (0.633 and 1.011 ms against 0.467 and 0.701)
#: and left the module
FWD = ((1024, 384, 128), (1024, 384, 64), (1024, 384, 32), (1024, 384, 256),
       (512, 384, 128), (3072, 384, 128), (512, 1152, 128))
BWD = ((1024, 384, 64), (1024, 384, 32), (1024, 384, 128), (512, 384, 64),
       (3072, 384, 64), (512, 1152, 64))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--default-only", action="store_true",
                    help="GDNCONV_TILES as they stand and no other tiling")
    ap.add_argument("--seq", type=int, nargs="*", default=list(SEQS))
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.ops import delta_rule
    from tools.shortconv_sweep import device_ms, gap

    dev = jax.devices()[0]
    print("gdnconv_sweep: platform=%s kind=%r" % (
        dev.platform, dev.device_kind), flush=True)
    if dev.platform != "tpu":
        sys.exit("gdnconv_sweep: no TPU: a device time comes only from the "
                 "chip")
    width, bf = HEADS * (2 * DK + DV), jnp.bfloat16
    at = dict(heads=HEADS, dk=DK, eps=EPS)
    body = jax.jit(functools.partial(delta_rule._conv_heads_body, **at))
    body_bwd = jax.jit(functools.partial(
        delta_rule._conv_heads_body_backward, **at))

    # the kernels against the body on float32 inputs, on the chip's own EUP
    # (the sigmoid's reciprocal) and XLU (the lane sums, the rolls)
    rng = np.random.default_rng(0)
    data = jnp.asarray(rng.normal(size=(2, 256, width)), jnp.float32)
    taps = jnp.asarray(rng.normal(size=(width, TAPS)) * 0.3, jnp.float32)
    want = body(data, taps)
    dout = tuple(jnp.asarray(rng.normal(size=o.shape), jnp.float32)
                 for o in want)
    want += body_bwd(data, taps, dout)
    tiles = dict(at, rows=128, channels=384, piece=64)
    got = delta_rule._gdnconv_fwd_pallas(data, taps, **tiles) \
        + delta_rule._gdnconv_bwd_pallas(data, taps, dout, **tiles)
    print("float32 against the body  %s" % "  ".join(
        "%s rel %.2g" % (n, gap(mine, theirs)[1]) for n, mine, theirs
        in zip(("q", "k", "v", "d data", "d taps"), got, want)), flush=True)

    for seq in args.seq:
        rng = np.random.default_rng(seq)
        data = jnp.asarray(rng.normal(size=(BATCH, seq, width)), bf)
        taps = jnp.asarray(rng.normal(size=(width, TAPS)) * 0.3, bf)
        want = body(data, taps)
        dout = tuple(jnp.asarray(rng.normal(size=o.shape), bf) for o in want)
        want_grads = body_bwd(data, taps, dout)
        moved = {"fwd": 2 * data.size * 2, "bwd": 3 * data.size * 2}
        plan, why = delta_rule._gdnconv_plan(data, taps, HEADS, DK)
        print("seq %d plan %s" % (seq, plan or why), flush=True)
        print("seq %d jax.numpy                  fwd %7.3f ms  bwd %7.3f ms"
              % (seq, device_ms(body, data, taps),
                 device_ms(body_bwd, data, taps, dout)), flush=True)
        if args.default_only and plan is None:
            continue
        default = plan and {k: (plan[k]["rows"], plan["channels"],
                                plan[k]["piece"]) for k in ("fwd", "bwd")}
        for kernel, sweep in (("fwd", FWD), ("bwd", BWD)):
            for rows, channels, piece in (
                    [default[kernel]] if args.default_only else sweep):
                if seq % rows:
                    continue
                tiles = dict(at, rows=rows, channels=channels, piece=piece)
                try:
                    if kernel == "fwd":
                        fn = jax.jit(functools.partial(
                            delta_rule._gdnconv_fwd_pallas, **tiles))
                        a, names, theirs = (data, taps), "qkv", want
                    else:
                        fn = jax.jit(functools.partial(
                            delta_rule._gdnconv_bwd_pallas, **tiles))
                        a, names, theirs = (data, taps, dout), (
                            "d data", "d taps"), want_grads
                    gaps = "  ".join("%s rel %.2g" % (n, gap(mine, t)[1])
                                     for n, mine, t in zip(names, fn(*a),
                                                           theirs))
                    ms = device_ms(fn, *a)
                except Exception as e:      # a tiling Mosaic refuses
                    print("seq %d %s rows %4d channels %4d piece %3d  "
                          "refused: %s" % (
                              seq, kernel, rows, channels, piece,
                              str(e).splitlines()[0][:120]), flush=True)
                    continue
                print("seq %d %s rows %4d channels %4d piece %3d  %7.3f ms  "
                      "%5.1f%% of 819 GB/s  %s" % (
                          seq, kernel, rows, channels, piece, ms,
                          100 * moved[kernel] / (ms * 1e-3) / 819e9, gaps),
                      flush=True)
    print("gdnconv_sweep: done", flush=True)


if __name__ == "__main__":
    main()
