"""Gated short convolution sweep on the real chip: numbers and device time
of the operator's middle (`_gate` in ops/lm_blocks.py) at the benchmark
cell's shape.

One command: the two Mosaic kernels (`mx_shortconv_fwd`, `mx_shortconv_bwd`)
at several tilings, each checked against the `jax.numpy` body's result and
its derivative on the same chip, and each timed; then the body itself,
forward and forward + backward, as XLA compiles it; then the whole operator
(`_contrib_GatedShortConv`, projections included), value and all four
gradients, as shipped against the same with the body in the kernels' place.
`SHORTCONV_TILES` in ops/lm_blocks.py, and the table in PERF.md section 6
(PR 29), come from it.

    python tools/shortconv_sweep.py [--default-only]

Timing: each call is jitted and run once to compile, then `ITERS` times
under the profiler; the time is the device's busy time a call (the union
of its `XLA Ops` events, `benchmarks/trace.py`), so the host's dispatch is
not in it.  Needs the chip to itself: one process per chip.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the cell's ``(batch, seq, d)`` and taps, and the calls under one trace
SHAPE, TAPS, ITERS = (2, 8192, 2048), 3, 5

#: (rows of a grid step, channels of a chunk); 256 rows and more ask the
#: backward kernel for more VMEM than a v5e kernel is given, 512 the forward
FWD = ((256, 512), (256, 256), (256, 1024), (256, 2048), (128, 512),
       (128, 2048), (64, 512))
BWD = ((128, 512), (128, 256), (128, 1024), (128, 2048), (64, 512),
       (64, 2048), (32, 512))


def device_ms(fn, *a, iters=ITERS):
    """Device busy time of one call of jitted *fn*, in ms (shared with
    `tools/headrope_sweep.py`)."""
    import jax
    from benchmarks import trace
    jax.block_until_ready(fn(*a))
    where = tempfile.mkdtemp(prefix="sweep")
    try:
        with jax.profiler.trace(where):
            for _ in range(iters):
                jax.block_until_ready(fn(*a))
        events = [e for e in trace.load_events(trace.find_xplane(where))
                  if e["line"] == trace.OPS_LINE]
    finally:
        shutil.rmtree(where, ignore_errors=True)
    busy = trace.total(trace.union(
        [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events]))
    return busy / iters / 1e6


def gap(a, b):
    """``(largest difference, relative difference of the norms)``."""
    import jax.numpy as jnp
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b))), float(
        jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--default-only", action="store_true",
                    help="SHORTCONV_TILES as they stand and no other tiling")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import lm_blocks
    from mxnet_tpu.ops.registry import get_op

    dev = jax.devices()[0]
    print("shortconv_sweep: platform=%s kind=%r" % (
        dev.platform, dev.device_kind), flush=True)
    if dev.platform != "tpu":
        sys.exit("shortconv_sweep: no TPU: a device time comes only from "
                 "the chip")
    (n, s, d), taps = SHAPE, TAPS
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    bf = jnp.bfloat16
    data = jax.random.normal(ks[0], (n, s, d), bf)
    w_in = (0.02 * jax.random.normal(ks[1], (3 * d, d))).astype(bf)
    w_conv = (0.5 * jax.random.normal(ks[2], (d, taps))).astype(bf)
    w_out = (0.02 * jax.random.normal(ks[3], (d, d))).astype(bf)
    bcx = jax.random.normal(ks[4], (n, s, 3 * d), bf)
    dgated = jax.random.normal(ks[5], (n, s, d), bf)
    item = 2
    moved = {"fwd": 4 * n * s * d * item, "bwd": 7 * n * s * d * item}

    body = jax.jit(lm_blocks._gate_body)
    body_bwd = jax.jit(lm_blocks._body_backward)
    want = body(bcx, w_conv)
    want_dbcx, want_dw = body_bwd(bcx, w_conv, dgated)
    print("body      fwd %.3f ms   bwd alone %.3f ms" % (
        device_ms(body, bcx, w_conv),
        device_ms(body_bwd, bcx, w_conv, dgated)), flush=True)

    tiles = lm_blocks.SHORTCONV_TILES
    default = {"fwd": (tiles["fwd"], tiles["channels"]),
               "bwd": (tiles["bwd"], tiles["channels"])}
    for kernel, sweep in (("fwd", FWD), ("bwd", BWD)):
        for rows, channels in ([default[kernel]] if args.default_only
                               else sweep):
            if s % rows or d % channels:
                continue
            try:
                if kernel == "fwd":
                    fn = jax.jit(lambda b, w: lm_blocks._shortconv_fwd_pallas(
                        b, w, rows=rows, channels=channels))
                    a = (bcx, w_conv)
                    gaps = "gated max %.3g rel %.3g" % gap(fn(*a), want)
                else:
                    fn = jax.jit(
                        lambda b, w, g: lm_blocks._shortconv_bwd_pallas(
                            b, w, g, rows=rows, channels=channels))
                    a = (bcx, w_conv, dgated)
                    got = fn(*a)
                    gaps = "dbcx max %.3g rel %.3g  dw max %.3g rel %.3g" % (
                        gap(got[0], want_dbcx) + gap(got[1], want_dw))
                ms = device_ms(fn, *a)
            except Exception as e:      # a tiling Mosaic refuses
                print("%s rows %4d channels %4d  refused: %s" % (
                    kernel, rows, channels, str(e).splitlines()[0][:120]),
                    flush=True)
                continue
            print("%s rows %4d channels %4d  %.3f ms  %5.1f%% of 819 GB/s  %s"
                  % (kernel, rows, channels, ms,
                     100 * moved[kernel] / (ms * 1e-3) / 819e9, gaps),
                  flush=True)

    # the operator, projections included
    op = get_op("_contrib_GatedShortConv").fn

    def with_body(x, wi, wc, wo):
        return lm_blocks._dot(lm_blocks._gate_body(lm_blocks._dot(x, wi), wc),
                              wo)

    def loss(f):
        return lambda *a: jnp.sum(f(*a).astype(jnp.float32)
                                  * dgated.astype(jnp.float32))

    a = (data, w_in, w_conv, w_out)
    grads = {}
    for name, f in (("shipped", op), ("body", with_body)):
        fwd = jax.jit(f)
        both = jax.jit(jax.value_and_grad(loss(f), argnums=(0, 1, 2, 3)))
        grads[name] = both(*a)[1]
        print("op %-8s fwd %.3f ms   fwd + bwd %.3f ms" % (
            name, device_ms(fwd, *a), device_ms(both, *a)), flush=True)
    for name, g, r in zip(("d data", "d in_weight", "d conv_weight",
                           "d out_weight"), grads["shipped"], grads["body"]):
        print("op %-14s max %.3g rel %.3g" % ((name,) + gap(g, r)),
              flush=True)
    print("shortconv_sweep: done", flush=True)


if __name__ == "__main__":
    main()
