#!/usr/bin/env python3
"""The two flash kernels under the block-diffusion mask, alone, on the chip:

    python tools/bd_flash_sweep.py [--half 8192] [--block 4] [--heads 32] \
        [--d 128] [--iters 8] [--against FILE ...]

Checks the kernels (forward and all three gradients) against the
`jax.numpy` body on the device at a small shape, then times each kernel at
the cell's shape (a clean and a noised copy of *half* positions in blocks of
*block*) beside the causal kernels over the same ``2 * half`` positions:
device milliseconds a call from a profiler trace (`tools/flash_sweep.py`
`_time_scan`), the tiles the plan visits, and the share of the MXU's peak
that the VISIBLE pairs' work is of that time (`benchmarks/bd_counts.py`).
*--against* times another version of `ops/attention.py` beside this tree's
(a parent's, a candidate's): the file is loaded under the package's name, so
its relative imports resolve.  About two minutes on one chip.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def load(path):
    """`ops/attention.py` from *path* as a module of its own; the operator
    it would register is the tree's already, so its registration is
    skipped."""
    from mxnet_tpu.ops import registry
    spec = importlib.util.spec_from_file_location(
        "mxnet_tpu.ops._attention_%d" % abs(hash(path)), path)
    module = importlib.util.module_from_spec(spec)
    register, registry.register_op = registry.register_op, \
        lambda *a, **kw: (lambda fn: fn)
    try:
        spec.loader.exec_module(module)
    finally:
        registry.register_op = register
    return module


def check(A):
    """Largest error of the kernels against the body, values and
    gradients, at 2 x 1000 positions in blocks of 8 (ragged tiles)."""
    import jax
    import jax.numpy as jnp
    mask = A.BlockDiffusion(8, 1000)
    key = jax.random.PRNGKey(0)
    q, k, v, g = (jax.random.normal(jax.random.fold_in(key, i),
                                    (1, 4, 2000, 128), jnp.float32)
                  for i in range(4))

    def both(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out,) + vjp(g)

    got = both(lambda q, k, v: A._flash(q, k, v, False, 128 ** -0.5, False,
                                        mask))
    want = both(lambda q, k, v: A._chunked_attention(
        q, k, v, False, 128 ** -0.5, 512, mask))
    return [float(jnp.max(jnp.abs(a - b))) for a, b in zip(got, want)]


def times(A, half, block, heads, d, iters, masks):
    import jax
    import jax.numpy as jnp
    from benchmarks import bd_counts
    from flash_sweep import _time_scan
    s, scale = 2 * half, d ** -0.5
    key = jax.random.PRNGKey(1)
    q, k, v, do = (jax.random.normal(jax.random.fold_in(key, i),
                                     (1, heads, s, d), jnp.bfloat16)
                   for i in range(4))
    rows = []
    for name in masks:
        mask = A.BlockDiffusion(block, half) if name == "block_diffusion" \
            else None
        kw = {"mask": mask} if mask is not None else {}
        causal = mask is None
        out, lse = jax.jit(lambda q, k, v: A._flash_fwd_pallas(
            q, k, v, causal, scale, with_lse=True, **kw))(q, k, v)
        plan = A._flash_plan(s, s, d, q.dtype,
                             **({"halves": 2} if mask is not None else {}))
        calls = {
            "fwd": lambda q, k, v, out, lse, do: A._flash_fwd_pallas(
                q, k, v, causal, scale, **kw),
            "bwd": lambda q, k, v, out, lse, do: A._flash_bwd_pallas(
                q, k, v, out, lse, do, causal, scale, **kw)[0]}
        pairs = bd_counts.visible_pairs(half, block) if mask is not None \
            else bd_counts.causal_pairs(s)
        for kernel, fn in calls.items():
            ms, kernel_ms = _time_scan(fn, (q, k, v, out, lse, do), iters)
            counts = A._tile_counts(kernel, plan, s, s, causal, mask)
            flops = 2 * heads * pairs * 2 * d * (1 if kernel == "fwd" else 2)
            rows.append({"mask": name, "kernel": kernel, "ms": ms,
                         "kernel_ms": kernel_ms, **counts,
                         "useful_tflops": flops / kernel_ms / 1e9,
                         "mxu_peak_pct": 100 * flops / 197e12
                         / (kernel_ms * 1e-3)})
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--half", type=int, default=8192)
    ap.add_argument("--block", type=int, default=4)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--against", action="append", default=[])
    ap.add_argument("--out", default="chiprun_out/bd_flash_sweep.jsonl")
    args = ap.parse_args()
    import jax
    from mxnet_tpu.ops import attention
    d = jax.devices()[0]
    print("bd_flash_sweep: platform=%s kind=%r" % (d.platform, d.device_kind),
          flush=True)
    if d.platform != "tpu":
        print("bd_flash_sweep: the kernels run on a TPU -- nothing was timed",
              file=sys.stderr)
        return 1
    print("bd_flash_sweep: kernels against the body o dq dk dv %s"
          % " ".join("%.2e" % e for e in check(attention)), flush=True)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    versions = [("tree", attention, ("block_diffusion", "causal"))]
    for path in args.against:
        other = load(path)
        versions.append((path, other, ("block_diffusion",) if hasattr(
            other, "BlockDiffusion") else ("causal",)))
    with open(args.out, "a") as f:
        for version, A, masks in versions:
            for row in times(A, args.half, args.block, args.heads, args.d,
                             args.iters, masks):
                row["version"] = version
                f.write(json.dumps(row) + "\n")
                print("bd_flash_sweep: %-12s %-16s %s  %8.3f ms a call "
                      "(%8.3f in the kernel)  %4d tiles, %3d masked  "
                      "%5.1f%% of the MXU's peak"
                      % (version[-12:], row["mask"], row["kernel"],
                         row["ms"], row["kernel_ms"], row["tiles_visited"],
                         row["tiles_masked"], row["mxu_peak_pct"]),
                      flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
