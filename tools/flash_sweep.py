"""Flash-attention kernel sweep: numerics + time, fwd AND bwd, on the
real chip.

One command: numeric checks of the Pallas kernels against the einsum
oracle (forward and all three gradients; causal and not, seq_q == seq_k
and not), then a timing sweep of resident block x sub-tile for each of
the two kernels (the backward also with dq's accumulator in either
place, and the two places' dq set beside each other), each timed alone, with its throughput by useful work and by the
MXU passes it executes, and the plan's tile counts.  The table in
docs/PERF_NOTES.md "Flash attention kernel" and the values of
`ops/attention.py` `_SUB_*` come from it.

    python tools/flash_sweep.py                  # checks + both shapes
    python tools/flash_sweep.py --shape 2,32,2048,64 --default-only
    python tools/flash_sweep.py --shape 1,32,8192,192,128 --kernel bwd

Timing discipline: iterations are chained through a data dependency
inside one jit (scan), timed to a host readback.  Needs the chip to
itself: one process per chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the shapes swept, (b, h, s, d) or (b, h, s, d, d_v): the benchmark's
#: LM cell, and a long wide one
SHAPES = ((2, 32, 2048, 64), (4, 16, 4096, 128))
#: (query rows, key columns) of a score sub-tile
SUB_TILES = ((256, 256), (256, 512), (512, 256), (512, 512), (1024, 1024))


def numeric_check(shapes=(1, 2, 256, 64), seq_k=None, d_v=None):
    """Flash (compiled, on-device) vs oracle: fwd + dq/dk/dv, causal and
    not; *seq_k* sets a key length of its own (ends aligned), *d_v* a
    width of the values' own."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import (attention_reference,
                                         flash_attention)
    b, h, s, d = shapes
    sk = seq_k or s
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, h, sk, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, h, sk, d_v or d), jnp.bfloat16)

    for causal in (False, True):
        def loss_f(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=causal)
                           .astype(jnp.float32) ** 2)

        def loss_r(q, k, v):
            return jnp.sum(attention_reference(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), causal=causal) ** 2)

        out_f = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=causal))(q, k, v)
        out_r = attention_reference(q.astype(jnp.float32),
                                    k.astype(jnp.float32),
                                    v.astype(jnp.float32), causal=causal)
        fwd_err = float(jnp.max(jnp.abs(out_f.astype(jnp.float32) -
                                        out_r)))
        gf = jax.jit(jax.grad(loss_f, argnums=(0, 1, 2)))(q, k, v)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        errs = [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b)))
                for a, b in zip(gf, gr)]
        scale = float(jnp.max(jnp.abs(out_r))) + 1e-6
        gscales = [float(jnp.max(jnp.abs(g))) + 1e-6 for g in gr]
        print(json.dumps({"check": "numerics",
                          "shape": [b, h, s, sk, d, d_v or d],
                          "causal": causal,
                          "fwd_maxerr": fwd_err,
                          "grad_maxerr": errs,
                          "out_scale": scale,
                          "grad_scales": gscales}), flush=True)
        # explicit raises: chip_smoke.py gates on this, also under -O
        if not fwd_err < 0.12 * scale:
            raise AssertionError("forward mismatch (%g vs scale %g)"
                                 % (fwd_err, scale))
        for which, e, gs in zip("dq dk dv".split(), errs, gscales):
            if not e < 0.15 * gs:
                raise AssertionError("%s mismatch (%g vs scale %g)"
                                     % (which, e, gs))


def _time_scan(fn, args, iters):
    """``(ms, kernel_ms)`` of one call of *fn*.  Chained timing: scan fn
    iters times inside ONE dispatch (the first argument carries the
    dependency, the others are constants), timed to a host readback:
    kernels and whatever the wrappers add around them.  Then one traced
    dispatch, for the Mosaic kernels' own device time (the `XLA Ops`
    events named `mx_flash_*`)."""
    import jax
    import jax.numpy as jnp

    def chained(*args):
        def body(c, _):
            out = fn(*((c,) + args[1:]))
            # feed a scaled output back as q to chain the iterations (one
            # column of it: the output may be narrower than q)
            return (c * 0 + out[..., :1]).astype(args[0].dtype), None
        c, _ = jax.lax.scan(body, args[0], None, length=iters)
        return jnp.sum(c.astype(jnp.float32))

    j = jax.jit(chained)
    float(j(*args))  # compile + warm
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        float(j(*args))
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1e3, _kernel_ms(lambda: float(j(*args))) / iters


def _kernel_ms(run):
    """Device milliseconds under the `mx_flash_*` kernels while *run*
    runs, from a profiler trace of it."""
    import glob
    import tempfile

    import jax
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        try:
            run()
        finally:
            jax.profiler.stop_trace()
        path = sorted(glob.glob(os.path.join(
            tmp, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        ns = 0
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/device:TPU:0"):
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ns += sum(ev.duration_ns for ev in line.events
                              if "mx_flash_" in ev.name.split(" = ")[0])
    return ns * 1e-6


def _kernels(A, causal, scale, tiles):
    """The two calls, each alone.  The backward's three results are all
    used, so that nothing around the kernel (the sum of dq's partials)
    is dropped."""
    def fwd(q, k, v, out, lse, dout):
        return A._flash_fwd_pallas(q, k, v, causal, scale, with_lse=True,
                                   **tiles)[0]

    def bwd(q, k, v, out, lse, dout):
        dq, dk, dv = A._flash_bwd_pallas(q, k, v, out, lse, dout, causal,
                                         scale, **tiles)
        return dq + dk + dv[..., :1]

    return {"fwd": fwd, "bwd": bwd}


def _work(A, name, row, bh, s, d, d_v, causal):
    """``(useful, executed)`` FLOPs of one call.  Useful: the visible
    scores (the causal triangle) through the forward's two dots, or the
    backward's five (the recomputed scores, dP, dv, dk, dq), each at its
    own width.  Executed: the tiles the plan visits, whole, through the
    128-lane MXU passes the kernel makes of them (the backward's operands
    are widened to whole lane tiles; 192 takes two passes, 64 one)."""
    scores = bh * s * s * (0.5 if causal else 1.0)
    wide = -(-A._d_block(d) // 128)
    wide_v = -(-A._d_block(d_v) // 128)
    if name == "fwd":
        dots, passes = d + d_v, wide + wide_v
    else:
        dots, passes = 3 * d + 2 * d_v, 3 * wide + 2 * wide_v
    tile = row["sub_tile"][0] * row["sub_tile"][1]
    return 2.0 * scores * dots, \
        2.0 * bh * row["tiles_visited"] * tile * 128 * passes


def sweep(shape, causal=True, iters=8, default_only=False,
          kernels=("fwd", "bwd"), out=None, residents=True,
          sub_tiles=SUB_TILES):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention as A

    b, h, s, d = shape[:4]
    d_v = shape[4] if len(shape) > 4 else d
    scale = 1.0 / d ** 0.5
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q, k = (jax.random.normal(kk, (b, h, s, d), jnp.bfloat16)
            for kk in ks[:2])
    v, dout = (jax.random.normal(kk, (b, h, s, d_v), jnp.bfloat16)
               for kk in ks[2:])
    o, lse = jax.jit(lambda q, k, v: A._flash_fwd_pallas(
        q, k, v, causal, scale, with_lse=True))(q, k, v)
    args = (q, k, v, o, lse, dout)

    variants = [{}]
    if not default_only:
        for sub_q, sub_k in sub_tiles:
            if sub_q > s or sub_k > s:
                continue
            variants.append({"blk_q": sub_q, "blk_k": sub_k})
            if not residents:
                continue
            # the same sub-tile through the grid: one tile a grid step
            variants.append({"blk_q": sub_q, "blk_k": sub_k,
                             "res_q": sub_q, "res_k": sub_k})
            if max(sub_q, sub_k) <= s // 2:
                variants.append({"blk_q": sub_q, "blk_k": sub_k,
                                 "res_q": s // 2, "res_k": s // 2})
    rows = []
    vmem_dq = A._VMEM_DQ
    for tiles in variants:
        for name in kernels:
            # the backward with dq's accumulator where the plan puts it,
            # then (at the plan's own resident blocks) in the other
            # place: the plan as a `_VMEM_DQ` that holds every sequence,
            # or none, makes it (no argument chooses)
            places = (None, "vmem", "hbm") \
                if name == "bwd" and "res_q" not in tiles else (None,)
            for place in places:
                A._VMEM_DQ = {None: vmem_dq, "vmem": 1 << 40, "hbm": 0}[place]
                A._flash_bwd_pallas.clear_cache()
                plan = A._flash_plan(s, s, d, q.dtype, d_v=d_v, **tiles)
                if place is None:
                    own_place = plan.dq_accumulator
                elif place == own_place:
                    continue            # the plan's own: timed already
                row = {"metric": "flash_" + name, "shape": list(shape),
                       "causal": causal, "tiles": tiles}
                row.update(A._plan_args(plan, s, s, d, q.dtype, causal,
                                        d_v)[name])
                try:
                    ms, kernel_ms = _time_scan(
                        _kernels(A, causal, scale, tiles)[name], args, iters)
                except Exception as e:  # a tile VMEM refuses: say so, go on
                    row["error"] = str(e).strip().splitlines()[0][:200]
                else:
                    useful, executed = _work(A, name, row, b * h, s, d, d_v,
                                             causal)
                    row["ms"], row["kernel_ms"] = ms, kernel_ms
                    row["useful_tflops"] = useful / kernel_ms / 1e9
                    row["executed_tflops"] = executed / kernel_ms / 1e9
                    if name == "bwd" and len(places) > 1:
                        # the other place's dq beside the plan's own
                        dq = jax.jit(lambda *a: A._flash_bwd_pallas(
                            *a, causal, scale, **tiles)[0])(*args).astype(
                                jnp.float32)
                        if place is None:
                            dq_own = dq
                        else:
                            row["dq_maxdiff_from_plans"] = float(
                                jnp.max(jnp.abs(dq - dq_own)))
                            row["dq_scale"] = float(jnp.max(jnp.abs(dq_own)))
                rows.append(row)
                print(json.dumps(row), flush=True)
                if out is not None:
                    out.write(json.dumps(row) + "\n")
                    out.flush()
    A._VMEM_DQ = vmem_dq
    A._flash_bwd_pallas.clear_cache()

    print(table(rows), flush=True)
    if not default_only and d_v == d:
        def chunked(q, k, v, *_):
            return A._chunked_attention(q, k, v, causal=causal)

        ms, _ = _time_scan(chunked, args, iters)
        row = {"metric": "chunked_xla_fwd", "shape": list(shape),
               "ms": ms, "useful_tflops": 4.0 * b * h * s * s * d
               * (0.5 if causal else 1.0) / ms / 1e9}
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def table(rows):
    """The sweep's rows as the markdown table of docs/PERF_NOTES.md."""
    out = ["| kernel | sub-tile q x k | resident q, k | dq accumulates in | "
           "tiles visited (masked) / ideal | kernel ms | with wrappers ms | "
           "TFLOP/s useful | executed |",
           "| --- | --- | --- | --- | --- | --- | --- | --- | --- |"]
    for r in sorted((r for r in rows if "kernel_ms" in r),
                    key=lambda r: (r["metric"], r["kernel_ms"])):
        out.append("| %s%s | %dx%d | %d, %d | %s | %d (%d) / %.1f | %.3f | "
                   "%.3f | %.1f | %.1f |" % (
                       r["metric"][6:], "" if r["tiles"] else " (plan)",
                       *r["sub_tile"], *r["resident"],
                       r.get("dq_accumulator", ""), r["tiles_visited"],
                       r["tiles_masked"], r["tiles_ideal"], r["kernel_ms"],
                       r["ms"], r["useful_tflops"], r["executed_tflops"]))
    return "\n".join(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-sweep", action="store_true")
    ap.add_argument("--skip-checks", action="store_true")
    ap.add_argument("--default-only", action="store_true",
                    help="time the plan's own tiles only")
    ap.add_argument("--sub-tiles-only", action="store_true",
                    help="each sub-tile with the plan's resident blocks "
                    "only")
    ap.add_argument("--sub", action="append",
                    help="QxK sub-tile to sweep (repeatable; default: "
                    "%s)" % " ".join("%dx%d" % t for t in SUB_TILES))
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--shape", action="append",
                    help="b,h,s,d or b,h,s,d,d_v (repeatable; default: %s)"
                    % " and ".join(",".join(map(str, s)) for s in SHAPES))
    ap.add_argument("--kernel", action="append", choices=("fwd", "bwd"),
                    help="the kernel to time (repeatable; default: both)")
    ap.add_argument("--out", default="chiprun_out/flash_sweep.jsonl")
    args = ap.parse_args()
    if not args.skip_checks:
        numeric_check()
        numeric_check((2, 4, 2048, 64))
        numeric_check((1, 2, 384, 64), seq_k=1000)   # decode-style
        numeric_check((1, 2, 1000, 128), seq_k=384)  # degenerate rows
        numeric_check((1, 2, 8192, 192), d_v=128)    # looped, two widths
    if not args.skip_sweep:
        shapes = [tuple(int(x) for x in s.split(","))
                  for s in args.shape] if args.shape else SHAPES
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "a") as out:
            for shape in shapes:
                sweep(shape, iters=args.iters,
                      default_only=args.default_only, out=out,
                      kernels=args.kernel or ("fwd", "bwd"),
                      residents=not args.sub_tiles_only,
                      sub_tiles=[tuple(int(x) for x in t.split("x"))
                                 for t in args.sub] if args.sub
                      else SUB_TILES)
    return 0


if __name__ == "__main__":
    sys.exit(main())
