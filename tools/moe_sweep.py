"""Routed-expert layer sweep on the real chip: numbers and time of the
grouped products' paths at the benchmark cell's shape.

One command: `_contrib_RoutedExperts` (ops/lm_blocks.py) forward, and
forward + backward, through XLA's ragged dot and through the installed
JAX's megablox kernels at several tilings; each checked against the ragged
dot's result, each timed alone.  `GROUPED_PATH` and `GROUPED_TILES` in
ops/lm_blocks.py, and the table in PERF.md section 6 (PR 26), come from it.
Then the pair buffer (PERF.md section 6, PR 27): the op as shipped, whose
buffer is bounded (`BUFFER_FACTOR`), against the worst-case body alone
with the same kernels, under the cell's bias and under one that sends
every choice of every token to the held experts, so that the shipped op
overflows its rows and takes the worst-case branch of its `cond`, which is
XLA's ragged dot.  At the bounded buffer the output and the three weight
gradients agree with the worst-case body to the bit (a gap of 0.0: the
kernels' sums are the same) and dx, which also carries the router's path,
to 6e-6 of its norm (XLA tiles a float32 row reduction by the buffer's
shape); the overflowing call agrees as the ragged dot does with the
kernels, to some 0.5% of a norm.

    python tools/moe_sweep.py [--tokens 16384] [--iters 5]
                              [--default-only | --buffer-only]

Timing: each call is jitted, run once to compile, then *iters* times to a
`block_until_ready`; the best and the median are printed.  Needs the chip
to itself: one process per chip.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: (row tile, cap of the k and n tiles) of the megablox kernels; (512,
#: 2048) and (1024, 1024) ask for more VMEM than a v5e kernel may use
TILINGS = ((512, 1024), (256, 1024), (512, 512), (128, 128))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=16384)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--expert-hidden", type=int, default=1792)
    ap.add_argument("--held", type=int, default=8)
    ap.add_argument("--router", type=int, default=32)
    ap.add_argument("--top-k", type=int, default=4)
    ap.add_argument("--bias-scale", type=float, default=0.1)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--default-only", action="store_true",
                    help="the ragged dot and GROUPED_TILES as they stand")
    ap.add_argument("--buffer-only", action="store_true",
                    help="the pair buffer's four rows and no tiling")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.ops import lm_blocks
    from mxnet_tpu.ops.registry import get_op

    dev = jax.devices()[0]
    print("moe_sweep: platform=%s kind=%r" % (dev.platform, dev.device_kind),
          flush=True)
    n, d, f, held = args.tokens, args.hidden, args.expert_hidden, args.held
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    bf = jnp.bfloat16
    x = jax.random.normal(ks[0], (n, d), bf)
    router = (0.02 * jax.random.normal(ks[1], (args.router, d))).astype(bf)
    w1 = (0.02 * jax.random.normal(ks[2], (held, d, f))).astype(bf)
    w3 = (0.02 * jax.random.normal(ks[3], (held, d, f))).astype(bf)
    w2 = (0.02 * jax.random.normal(ks[4], (held, f, d))).astype(bf)
    dout = jax.random.normal(ks[5], (n, d), bf)
    # the benchmark configuration's fixed, uneven bias
    from benchmarks.reference.lfm2_moe import expert_bias
    bias = tuple(float(b) for b in expert_bias({
        "num_routed_experts": args.router,
        "expert_bias_scale": args.bias_scale}))
    fn = get_op("_contrib_RoutedExperts").fn

    def make(bias=bias):
        def fwd(x, w1, w3, w2):
            return fn(x, router, w1, w3, w2, expert_bias=bias,
                      num_experts_per_tok=args.top_k)

        def both(x, w1, w3, w2):
            out, vjp = jax.vjp(fwd, x, w1, w3, w2)
            return (out,) + vjp(dout)
        return jax.jit(fwd), jax.jit(both)

    def timed(call):
        out = jax.block_until_ready(call(x, w1, w3, w2))
        times = []
        for _ in range(args.iters):
            t0 = time.perf_counter()
            jax.block_until_ready(call(x, w1, w3, w2))
            times.append(time.perf_counter() - t0)
        return out, 1e3 * min(times), 1e3 * statistics.median(times)

    def gap(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))

    def routed_counts(bias):
        """``(the router's load, pairs held, rows run at, overflowed)``."""
        chosen, _ = lm_blocks._route(x, router, bias, args.top_k, True, 1.0)
        counts = np.asarray(lm_blocks.routed_expert_counts(
            chosen, args.router, 0, held,
            lm_blocks._buffer_rows(n, args.top_k, held, args.router)))
        return (counts[:args.router], int(counts[args.router]),
                int(counts[-2]), bool(counts[-1]))

    load, pairs, ran_at, _ = routed_counts(bias)
    print("moe_sweep: %d tokens, %d pairs on the %d held experts (%.3f a "
          "token), buffer %d rows of %d at worst; fullest expert %.2f x the "
          "mean" % (n, pairs, held, pairs / n, ran_at, n * args.top_k,
                    load.max() / load.mean()), flush=True)
    flops = 3 * 2 * pairs * d * f
    rows = []
    base = None
    tilings = [lm_blocks.GROUPED_TILES[:2]] if args.default_only \
        else TILINGS
    variants = [] if args.buffer_only else \
        [("ragged", None)] + [("megablox", t) for t in tilings]
    shipped = lm_blocks.GROUPED_PATH, lm_blocks.GROUPED_TILES
    for path, tiles in variants:
        lm_blocks.GROUPED_PATH = path
        if tiles is not None:
            lm_blocks.GROUPED_TILES = (tiles[0], tiles[1], tiles[1])
        try:
            fwd, both = make()
            out, f_best, f_med = timed(fwd)
            outs, b_best, b_med = timed(both)
        except Exception as e:          # a tiling the compiler refuses
            print("moe_sweep: %s %s failed: %s: %s" % (
                path, tiles, type(e).__name__, str(e)[:300]), flush=True)
            continue
        if base is None:
            base = outs
        row = {"path": path, "tiles": tiles,
               "fwd_ms": round(f_best, 3), "fwd_median_ms": round(f_med, 3),
               "fwd_bwd_ms": round(b_best, 3),
               "fwd_bwd_median_ms": round(b_med, 3),
               "useful_tflops_fwd": round(flops / f_best / 1e9, 1),
               "useful_tflops_fwd_bwd": round(3 * flops / b_best / 1e9, 1),
               "gap_to_ragged": [round(gap(a, b), 5)
                                 for a, b in zip(outs, base)]}
        rows.append(row)
        print("moe_sweep: " + json.dumps(row), flush=True)

    # the pair buffer: the shipped op against the worst-case body alone
    # (a factor at which `_buffer_rows` is the worst case: one path)
    lm_blocks.GROUPED_PATH, lm_blocks.GROUPED_TILES = shipped
    factor = lm_blocks.BUFFER_FACTOR
    every = tuple(100.0 if e < args.top_k else 0.0
                  for e in range(args.router))
    buffers = []
    for name, b in (("cell", bias), ("all-held", every)):
        _, b_pairs, b_ran_at, b_over = routed_counts(b)
        outs = {}
        for body, value in (("worst-case", args.router / held),
                            ("shipped", factor)):
            lm_blocks.BUFFER_FACTOR = value
            fwd, both = make(b)
            _, f_best, f_med = timed(fwd)
            outs[body], b_best, b_med = timed(both)
            row = {"bias": name, "pairs": b_pairs, "body": body,
                   "buffer_rows": b_ran_at if body == "shipped"
                   else n * args.top_k,
                   "took_worst_case_branch": body == "shipped" and b_over,
                   "fwd_ms": round(f_best, 3),
                   "fwd_median_ms": round(f_med, 3),
                   "fwd_bwd_ms": round(b_best, 3),
                   "fwd_bwd_median_ms": round(b_med, 3)}
            if body == "shipped":
                # 0.0 is equal to the bit: out, dx, dw1, dw3, dw2
                row["gap_to_worst_case"] = [
                    gap(a, w) for a, w in zip(outs["shipped"],
                                              outs["worst-case"])]
            buffers.append(row)
            print("moe_sweep: buffer " + json.dumps(row), flush=True)
    lm_blocks.BUFFER_FACTOR = factor
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "moe_sweep.json"), "w") as fh:
        json.dump({"tokens": n, "pairs": pairs, "rows": rows,
                   "buffers": buffers}, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
