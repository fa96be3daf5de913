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

With ``--combine`` it times the combine alone instead (PERF.md section 6,
PR 46), both directions, at the five routed cells' (tokens, top-k, width,
pair buffer) under random even routing: `_sum_pairs`' gather a choice
against `_combine_rows`' one pass over the rows in token order (with and
without `_token_order`'s integer work, which a layer does once for both
directions) at several (token tile, chunk rows), every row of the buffer
past the last pair set to NaN, each result checked against `_sum_pairs`'
in float32.
`COMBINE_TILES` and `COMBINE_RATIO` in ops/lm_blocks.py, and the table in
docs/PERF_NOTES.md, come from it.

    python tools/moe_sweep.py [--tokens 16384] [--iters 5]
                              [--default-only | --buffer-only | --combine]

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


#: the routed cells' (tokens a step, experts a token, width, experts held,
#: router's experts): SDAR's is Keye's too; then LFM2's with a third and a
#: half of the experts held, between its ratio and the worst case's 1, to
#: place `COMBINE_RATIO`
COMBINE_CELLS = {"laguna": (4096, 10, 3072, 8, 256),
                 "sdar": (16384, 8, 2048, 16, 128),
                 "kanana": (8192, 6, 2048, 16, 128),
                 "lfm2": (16384, 4, 2048, 8, 32),
                 "a-third-held": (16384, 4, 2048, 8, 24),
                 "a-half-held": (16384, 4, 2048, 8, 16)}
#: (token tile, chunk rows) of `_combine_rows`
COMBINE_TILINGS = ((128, 128), (256, 128), (256, 256))


def traced(calls, iters):
    """``{label: (result, device ms a call, [[operation family, ms a
    call], ... the six longest])}`` for *calls* ``{label: (fn, args)}``,
    each compiled and run once, then *iters* times under one profiler
    trace."""
    import tempfile

    import jax
    from benchmarks import trace

    fns = {k: jax.jit(f) for k, (f, _) in calls.items()}
    outs = {k: jax.block_until_ready(fns[k](*calls[k][1]))
            for k in calls}
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for k, (_, a) in calls.items():
            with jax.profiler.TraceAnnotation(trace.SPAN_PREFIX + k):
                for _ in range(iters):
                    last = fns[k](*a)
                jax.block_until_ready(last)
        jax.profiler.stop_trace()
        events = trace.load_events(trace.find_xplane(tmp))
    ops = [e for e in events if trace.DEVICE_PLANE.match(e["plane"])
           and not trace._WRAPPERS.match(trace.op_family(e["name"]))]
    found = {}
    for e in events:
        if not e["name"].startswith(trace.SPAN_PREFIX):
            continue
        lo, hi = e["start_ns"], e["start_ns"] + e["dur_ns"]
        by = {}
        for op in ops:
            if lo <= op["start_ns"] < hi:
                fam = trace.op_family(op["name"])
                by[fam] = by.get(fam, 0) + op["dur_ns"]
        k = 1e-6 / iters
        found[e["name"][len(trace.SPAN_PREFIX):]] = (
            round(sum(by.values()) * k, 4),
            [[f, round(v * k, 4)] for f, v in sorted(
                by.items(), key=lambda kv: -kv[1])[:6]])
    return {k: (outs[k],) + found[k] for k in calls}


def combine_sweep(args):
    """The combine alone at `COMBINE_CELLS`: one JSON row a cell, direction
    and body, device ms a call from a profiler trace of *iters* calls (the
    operations that ran between a call group's first dispatch and its last
    result; a host clock around calls of under a millisecond reads the
    dispatch), and the operations that took most of it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.ops import lm_blocks as lb

    def off(got, want, scale):
        """The largest error over what a rounding of the result's dtype
        and a float32 rounding of a token's summed magnitudes allow (1 or
        less is equal but for the addends' order), NaN counted as 1e9."""
        got = np.asarray(got.astype(jnp.float32))
        bound = 2.0 ** -8 * np.abs(want) + 2.0 ** -21 * scale + 1e-30
        return float(np.nan_to_num(np.abs(got - want) / bound,
                                   nan=1e9).max())

    dev = jax.devices()[0]
    print("moe_sweep: platform=%s kind=%r" % (dev.platform, dev.device_kind),
          flush=True)
    bf, rows_out = jnp.bfloat16, []
    for cell, (tokens, top_k, d, held, router) in COMBINE_CELLS.items():
        rng = np.random.default_rng(0)
        chosen = jnp.asarray(rng.random((tokens, router)).argsort(
            1)[:, :top_k], jnp.int32)
        order, inverse, sizes = lb._sort_pairs(chosen, 0, held)
        n = lb._buffer_rows(tokens, top_k, held, router)
        pairs = int(jnp.sum(sizes))
        assert pairs <= n
        past = jnp.arange(n)[:, None] >= pairs

        def buffer(seed):
            return jnp.where(past, jnp.nan, jax.random.normal(
                jax.random.PRNGKey(seed), (n, d), jnp.float32)).astype(bf)

        y, by_w1, by_w3 = buffer(1), buffer(2), buffer(3)
        weights = jax.random.uniform(jax.random.PRNGKey(4), (tokens, top_k),
                                     jnp.float32, 0.05, 1.0)
        head = {"cell": cell, "tokens": tokens, "top_k": top_k, "d": d,
                "buffer_rows": n, "pairs": pairs,
                "gathered_per_buffer_row": round(tokens * top_k / n, 2)}

        def summed(by_w1, by_w3):
            return (by_w1.astype(jnp.float32) + by_w3.astype(jnp.float32)
                    ).astype(bf)

        def places():
            return lb._places(inverse, sizes, top_k)

        def old_fwd(y, weights):
            return lb._sum_pairs(y, *places(), weights).astype(bf)

        def old_bwd(by_w1, by_w3):
            return lb._sum_pairs(summed(by_w1, by_w3), *places()).astype(bf)

        # float32 references, and each token's summed magnitudes
        want = {"fwd": jax.jit(lambda: (
            lb._sum_pairs(y, *places(), weights),
            lb._sum_pairs(jnp.abs(y), *places(), weights)))(),
            "bwd": jax.jit(lambda: (
                lb._sum_pairs(summed(by_w1, by_w3), *places()),
                lb._sum_pairs(jnp.abs(summed(by_w1, by_w3)), *places())))()}
        want = {k: [np.asarray(a) for a in v] for k, v in want.items()}
        calls = {"fwd sum_pairs": (old_fwd, (y, weights)),
                 "bwd sum_pairs": (old_bwd, (by_w1, by_w3))}
        reads = {}
        for tile, chunk in COMBINE_TILINGS:
            if tokens % tile or n % chunk:
                continue
            tiles = (tile, chunk)
            # a rehearsal off the chip runs the kernel interpreted
            kw = dict(tokens=tokens, tiles=tiles,
                      interpret=dev.platform != "tpu")

            def plan(order, inverse, sizes, tiles=tiles):
                return lb._token_order(order, inverse, sizes, top_k, n,
                                       tiles)

            def new_fwd(run, y, weights, kw=kw):
                return lb._combine_rows(y, run, weights, **kw)

            def new_bwd(run, by_w1, by_w3, kw=kw):
                return lb._combine_rows(summed(by_w1, by_w3), run, **kw)

            def layer(order, inverse, sizes, y, weights, by_w1, by_w3,
                      plan=plan, new_fwd=new_fwd, new_bwd=new_bwd):
                # what a layer pays: the integer work once, a pass each way
                run, _ = plan(order, inverse, sizes)
                return new_fwd(run, y, weights), new_bwd(run, by_w1, by_w3)

            run, read = jax.jit(plan)(order, inverse, sizes)
            name = "combine_rows %dx%d" % (tile, chunk)
            reads[name] = round(int(read) / pairs, 3)
            calls.update({
                "order " + name: (plan, (order, inverse, sizes)),
                "fwd " + name: (new_fwd, (run, y, weights)),
                "bwd " + name: (new_bwd, (run, by_w1, by_w3)),
                "order+fwd+bwd " + name: (layer, (
                    order, inverse, sizes, y, weights, by_w1, by_w3))})
        for label, (got, ms, ops) in traced(calls, args.iters).items():
            way, body = label.split(" ", 1)
            row = dict(head, way=way, body=body, device_ms=ms, ops=ops)
            if body in reads:
                row["rows_read_per_pair"] = reads[body]
                if way in want:
                    row["off"] = round(off(got, *want[way]), 3)
            rows_out.append(row)
            print("moe_sweep: combine " + json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "moe_combine_sweep.json"),
              "w") as fh:
        json.dump(rows_out, fh, indent=1)
    return 0


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
    ap.add_argument("--combine", action="store_true",
                    help="the combine alone at the five routed cells' "
                    "shapes, and nothing else")
    args = ap.parse_args(argv)
    if args.combine:
        return combine_sweep(args)

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
