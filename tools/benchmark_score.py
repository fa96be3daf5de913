#!/usr/bin/env python
"""Inference throughput sweep across the model zoo — the TPU mirror of
the reference's `example/image-classification/benchmark_score.py`
(the harness behind every inference table in docs/faq/perf.md:42-175).

One JSON line per (model, batch) with img/s, using bench.py's timing
discipline: batches scanned inside one dispatch, completion forced by a
host readback.

    python tools/benchmark_score.py \
        [--models resnet50_v1 vgg16 ...] [--batches 1 32 128] [--image 224]

Needs the chip to itself: one process per chip.  On CPU
(JAX_PLATFORMS=cpu) shrinks shapes for a plumbing smoke.
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

# the reference sweeps these six families (docs/faq/perf.md tables)
DEFAULT_MODELS = [
    "alexnet", "vgg16", "inception_v3", "resnet50_v1", "resnet152_v1",
    "mobilenet1_0",
]


def _model_image(model, image):
    # inception's canonical input is 299², but only when measuring at
    # full scale — a tiny-shape plumbing smoke stays tiny
    return 299 if model.startswith("inception") and image >= 224 else image


def _trace_and_split(model, batch, image):
    """Build + materialize a zoo model, trace it to a symbol on
    var('data0'), and split its parameters into (arg, aux) NDArray
    dicts.  Shared by the fp and int8 paths."""
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu import nd
    import mxnet_tpu.symbol as sym_mod

    net = vision.get_model(model, classes=1000)
    net.initialize()
    net.hybridize()  # one dispatch to materialize, not one per op
    rng = np.random.RandomState(0)
    size = _model_image(model, image)
    x = nd.array(rng.randn(batch, 3, size, size).astype(np.float32))
    net(x)  # materialize params

    out_sym = net(sym_mod.var("data0"))
    if not isinstance(out_sym, sym_mod.Symbol):
        out_sym = out_sym[0]
    arg_names = set(out_sym.list_arguments())
    aux_names = set(out_sym.list_auxiliary_states())
    arg_params, aux_params = {}, {}
    for p in net.collect_params().values():
        if p.name in arg_names:
            arg_params[p.name] = p.data()
        elif p.name in aux_names:
            aux_params[p.name] = p.data()
    return out_sym, arg_params, aux_params, x


def timed_infer(model, batch, image, iters=40, scan_n=10, warmup=2,
                dtype="bfloat16"):
    import jax.numpy as jnp
    from mxnet_tpu.executor import _build_eval
    import bench

    out_sym, arg_params, aux_params, x = _trace_and_split(
        model, batch, image)
    eval_fn = _build_eval(out_sym, False)
    cdt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    params = {k: v._data.astype(cdt) for k, v in arg_params.items()}
    aux = {k: v._data for k, v in aux_params.items()}
    xd = x._data.astype(cdt)

    dt, n, _ = bench.timed_scan_forward(eval_fn, params, aux, xd, {},
                                        scan_n, iters, warmup)
    return batch * n / dt


def timed_infer_int8(model, batch, image, iters=40, scan_n=10,
                     warmup=2):
    """INT8 inference via the quantization graph rewrite
    (contrib.quantization.quantize_model, naive calibration on a
    synthetic batch) — the reference's quantization benchmark path
    (benchmark/python/quantization)."""
    from mxnet_tpu.contrib.quantization import quantize_model
    from mxnet_tpu.io import NDArrayIter
    from mxnet_tpu.executor import _build_eval
    import bench

    out_sym, arg_params, aux_params, x = _trace_and_split(
        model, batch, image)
    calib = NDArrayIter(np.asarray(x.asnumpy()), None,
                        batch_size=batch)
    qsym, qargs, qaux = quantize_model(
        out_sym, arg_params, aux_params, data_names=("data0",),
        calib_mode="naive", calib_data=calib,
        num_calib_examples=batch)

    eval_fn = _build_eval(qsym, False)
    params = {k: v._data for k, v in qargs.items()}
    aux = {k: v._data for k, v in qaux.items()}
    dt, n, _ = bench.timed_scan_forward(eval_fn, params, aux, x._data,
                                        {}, scan_n, iters, warmup)
    return batch * n / dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", nargs="*", default=DEFAULT_MODELS)
    ap.add_argument("--batches", nargs="*", type=int,
                    default=[1, 32, 128])
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--dtype", default="bfloat16",
                    choices=["bfloat16", "float32", "int8"])
    ap.add_argument("--iters", type=int, default=40)
    args = ap.parse_args()

    import jax
    on_tpu = jax.devices()[0].platform == "tpu"
    if not on_tpu:
        # plumbing smoke only: small shapes (64 is the smallest every
        # default family accepts — alexnet's 11x11/s4 stack collapses
        # below that), tiny batches
        args.image, args.batches = 64, [2]
        args.iters = 4

    for model in args.models:
        for batch in args.batches:
            try:
                if args.dtype == "int8":
                    img_s = timed_infer_int8(model, batch, args.image,
                                             iters=args.iters)
                else:
                    img_s = timed_infer(model, batch, args.image,
                                        iters=args.iters,
                                        dtype=args.dtype)
                print(json.dumps({
                    "model": model, "batch": batch,
                    "dtype": args.dtype,
                    "image": _model_image(model, args.image),
                    "img_s": round(img_s, 2),
                    "device": ("tpu" if on_tpu else "cpu"),
                }), flush=True)
            except Exception as e:
                print(json.dumps({"model": model, "batch": batch,
                                  "error": repr(e)[:200]}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
