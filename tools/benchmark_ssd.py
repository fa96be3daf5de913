#!/usr/bin/env python
"""SSD-300 (VGG16-reduced) inference throughput — the mirror of the
reference's `example/ssd/benchmark_score.py` (detection headline).

The full graph — backbone, multi-scale heads, 8732 anchors, box decode
+ NMS (`MultiBoxDetection`) — is ONE XLA program timed with the shared
scanned-forward discipline.

    python tools/benchmark_ssd.py [--batches 1 32] [--classes 20]

Needs the chip to itself: one process per chip.  On CPU
(JAX_PLATFORMS=cpu) shrinks shapes for a plumbing smoke.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))
sys.path.insert(0, os.path.join(os.path.dirname(_HERE), "examples"))


def timed_ssd(batch, image, classes, iters, scan_n, warmup=1,
              dtype="bfloat16"):
    import jax.numpy as jnp
    from mxnet_tpu.executor import _build_eval
    import bench
    from ssd_model import build_ssd300_infer

    net = build_ssd300_infer(num_classes=classes)
    arg_shapes, _, _ = net.infer_shape(data0=(batch, 3, image, image))
    shapes = dict(zip(net.list_arguments(), arg_shapes))
    rng = np.random.RandomState(0)
    cdt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    params = {n: jnp.asarray(
        rng.randn(*s).astype(np.float32) * 0.05).astype(cdt)
        for n, s in shapes.items() if n != "data0"}
    xd = jnp.asarray(rng.randn(batch, 3, image, image)
                     .astype(np.float32)).astype(cdt)
    eval_fn = _build_eval(net, False)
    dt, n, _ = bench.timed_scan_forward(eval_fn, params, {}, xd, {},
                                        scan_n, iters, warmup)
    return batch * n / dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", nargs="*", type=int, default=[1, 32])
    ap.add_argument("--classes", type=int, default=20)
    ap.add_argument("--image", type=int, default=300)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--dtype", default="bfloat16",
                    choices=["bfloat16", "float32"])
    args = ap.parse_args()

    import mxnet_tpu  # noqa: F401  (re-pins jax platform from env)
    import jax
    on_tpu = jax.devices()[0].platform == "tpu"
    if not on_tpu:
        # image must stay 300: smaller inputs collapse the last
        # feature scales (3x3 valid convs) to zero size
        args.batches, args.iters = [1], 4

    for batch in args.batches:
        try:
            img_s = timed_ssd(batch, args.image, args.classes,
                              args.iters, scan_n=5 if on_tpu else 2,
                              dtype=args.dtype)
            print(json.dumps({
                "metric": "ssd300_vgg16_infer", "batch": batch,
                "image": args.image, "classes": args.classes,
                "dtype": args.dtype, "img_s": round(img_s, 2),
                "device": "tpu" if on_tpu else "cpu",
            }), flush=True)
        except Exception as e:
            print(json.dumps({"batch": batch,
                              "error": repr(e)[:300]}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
