"""Flash-attention kernel sweep: numerics + TF/s, fwd AND bwd, on the
real chip.

One command: per-config numeric checks of the Pallas kernels against
the einsum oracle (forward and all three gradients), then a block-size
timing sweep with useful-FLOP throughput for forward, backward, and the
chunked-XLA baseline.

    python tools/flash_sweep.py

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

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def numeric_check(shapes=(1, 2, 256, 64)):
    """Flash (compiled, on-device) vs oracle: fwd + dq/dk/dv."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import (attention_reference,
                                         flash_attention)
    b, h, s, d = shapes
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, h, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, h, s, d), jnp.bfloat16)

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
        print(json.dumps({"check": "numerics", "causal": causal,
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
    """Chained timing: scan fn iters times inside ONE dispatch."""
    import jax
    import jax.numpy as jnp

    def chained(*args):
        def body(c, _):
            out = fn(*((c,) + args[1:]))
            # feed a scaled output back as q to chain the iterations
            return (c * 0 + out).astype(args[0].dtype), None
        c, _ = jax.lax.scan(body, args[0], None, length=iters)
        return jnp.sum(c.astype(jnp.float32))

    j = jax.jit(chained)
    float(j(*args))  # compile + warm
    t0 = time.perf_counter()
    float(j(*args))
    return (time.perf_counter() - t0) / iters


def sweep(b=4, h=16, s=4096, d=128, causal=True, iters=8):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention as A

    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, h, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, h, s, d), jnp.bfloat16)
    # useful flops: 2 dots of 2*s*s*d per head, halved by causal masking
    flops = 4.0 * b * h * s * s * d * (0.5 if causal else 1.0)
    results = []
    # 2048 does not compile on v5e: its tiles ask for 25.8 MiB of the
    # 16 MiB scoped VMEM (CHANGES.md PR 21)
    for blk in (256, 512, 1024):
        def fwd(q, k, v):
            return A._flash_fwd_pallas(q, k, v, causal,
                                       1.0 / (d ** 0.5),
                                       blk_q=blk, blk_k=blk)

        dt = _time_scan(fwd, (q, k, v), iters)
        row = {"metric": "flash_fwd", "blk": blk, "ms": dt * 1e3,
               "tflops": flops / dt / 1e12}
        results.append(row)
        print(json.dumps(row), flush=True)

        def bwd(q, k, v):
            out, lse = A._flash_fwd_pallas(
                q, k, v, causal, 1.0 / (d ** 0.5), blk_q=blk,
                blk_k=blk, with_lse=True)
            dout = jnp.ones_like(out)
            dq, dk, dv = A._flash_bwd_pallas(
                q, k, v, out, lse, dout, causal, 1.0 / (d ** 0.5),
                blk_q=blk, blk_k=blk)
            # consume dk/dv too: returning dq alone would let XLA
            # dead-code-eliminate the whole dkdv kernel and inflate
            # the reported throughput
            return dq + (jnp.sum(dk.astype(jnp.float32)) +
                         jnp.sum(dv.astype(jnp.float32))
                         ).astype(dq.dtype)

        dt = _time_scan(bwd, (q, k, v), iters)
        # bwd ~ 2.5x fwd flops (recompute + 4 grad dots over 2 fwd dots)
        row = {"metric": "flash_fwd_plus_bwd", "blk": blk,
               "ms": dt * 1e3, "tflops": 3.5 * flops / dt / 1e12}
        results.append(row)
        print(json.dumps(row), flush=True)

    def chunked(q, k, v):
        return A._chunked_attention(q, k, v, causal=causal)

    dt = _time_scan(chunked, (q, k, v), iters)
    row = {"metric": "chunked_xla_fwd", "ms": dt * 1e3,
           "tflops": flops / dt / 1e12}
    results.append(row)
    print(json.dumps(row), flush=True)
    best = max(r["tflops"] for r in results if r["metric"] == "flash_fwd")
    print(json.dumps({"metric": "flash_fwd_best_tflops", "value": best}))
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-sweep", action="store_true")
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--seq", type=int, default=4096)
    args = ap.parse_args()
    numeric_check()
    if not args.skip_sweep:
        sweep(s=args.seq, iters=args.iters)
    return 0


if __name__ == "__main__":
    sys.exit(main())
