#!/usr/bin/env python
"""Train an ImageNet-class CNN (ResNet-50 default).

Reference: ``example/image-classification/train_imagenet.py`` (data via
ImageRecordIter, symbols from the model zoo, common/fit.py loop; its
``--benchmark 1`` mode trains on synthetic data, which is also the
default here when no .rec files are given).

Two trainer paths:
  --trainer module    symbolic Module.fit (reference flow; kvstore=local/
                      dist_sync/dist_async)
  --trainer parallel  one pjit-compiled sharded train step over the
                      device mesh (kvstore='tpu' north-star path:
                      bf16 compute + f32 masters + LARS)
"""

from __future__ import annotations

import argparse
import logging
import os
import sys
import time

import numpy as np

_here = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _here)
sys.path.insert(0, os.path.dirname(_here))
import common  # noqa: E402


def build_symbol(args):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    net = vision.get_model(args.network, classes=args.num_classes)
    net.initialize(mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                  magnitude=2))
    x = mx.nd.zeros((2, 3, args.image_shape, args.image_shape))
    net(x)  # materialize deferred shapes
    data = mx.sym.var("data")
    out = net(data)
    sym = mx.sym.SoftmaxOutput(data=out, name="softmax")
    params = {p.name: p for p in net.collect_params().values()}
    arg_names = [a for a in sym.list_arguments() if a != "data" and
                 a != "softmax_label"]
    aux_names = sym.list_auxiliary_states()
    arg_params = {n: params[n].data() for n in arg_names}
    aux_params = {n: params[n].data() for n in aux_names}
    return net, sym, arg_params, aux_params


def get_iters(args, kv):
    import mxnet_tpu as mx
    rank = kv.rank if kv is not None else 0
    nworker = kv.num_workers if kv is not None else 1
    shape = (3, args.image_shape, args.image_shape)
    if args.data_train and os.path.exists(args.data_train):
        train = mx.io.ImageRecordIter(
            path_imgrec=args.data_train, data_shape=shape,
            batch_size=args.batch_size, shuffle=True,
            rand_crop=True, rand_mirror=True,
            num_parts=nworker, part_index=rank,
            preprocess_threads=args.data_nthreads)
        val = None
        if args.data_val and os.path.exists(args.data_val):
            val = mx.io.ImageRecordIter(
                path_imgrec=args.data_val, data_shape=shape,
                batch_size=args.batch_size, shuffle=False,
                preprocess_threads=args.data_nthreads)
        return train, val
    # synthetic benchmark mode (reference --benchmark 1)
    rng = np.random.RandomState(42 + rank)
    n = args.num_examples
    x = rng.uniform(-1, 1, (n,) + shape).astype(np.float32)
    y = rng.randint(0, args.num_classes, (n,)).astype(np.float32)
    train = mx.io.NDArrayIter(data=x, label=y,
                              batch_size=args.batch_size, shuffle=False,
                              label_name="softmax_label")
    return train, None


def fit_parallel(args):
    """kvstore='tpu' path: whole train step as one pjit program."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.data_parallel import ParallelTrainer

    net = vision.get_model(args.network, classes=args.num_classes)
    net.initialize()
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = make_mesh()
    trainer = ParallelTrainer(
        net, loss, optimizer=args.optimizer,
        optimizer_params={"learning_rate": args.lr,
                          "momentum": args.mom, "wd": args.wd,
                          "eta": 0.001},
        mesh=mesh, multi_precision=args.dtype == "bfloat16",
        shard_params=args.zero1, remat=args.remat or None)
    train, _ = get_iters(args, None)
    logging.info("parallel trainer: mesh=%s dtype=%s", mesh, args.dtype)
    step = 0
    tic = time.time()
    for epoch in range(args.num_epochs):
        train.reset()
        for batch in train:
            l = trainer.fit_batch(batch.data[0], batch.label[0])
            step += 1
            if step % args.disp_batches == 0:
                l = float(np.asarray(l))  # waits for the device
                dt = time.time() - tic
                logging.info(
                    "Epoch[%d] Batch [%d] Speed: %.2f samples/sec "
                    "loss=%.4f", epoch, step,
                    args.disp_batches * args.batch_size / dt, l)
                tic = time.time()
    return trainer


def main():
    parser = argparse.ArgumentParser(description="train imagenet")
    common.add_fit_args(parser)
    # after add_fit_args: its explicit defaults would win otherwise
    parser.set_defaults(network="resnet50_v1", num_epochs=1,
                        batch_size=128, lr=0.1, disp_batches=10,
                        optimizer="sgd")
    parser.add_argument("--trainer", default="module",
                        choices=["module", "parallel"])
    parser.add_argument("--num-classes", type=int, default=1000)
    parser.add_argument("--num-examples", type=int, default=1280)
    parser.add_argument("--image-shape", type=int, default=224)
    parser.add_argument("--data-train", type=str, default=None,
                        help="train .rec path (synthetic data if absent)")
    parser.add_argument("--data-val", type=str, default=None)
    parser.add_argument("--data-nthreads", type=int, default=4)
    parser.add_argument("--dtype", type=str, default="float32",
                        choices=["float32", "bfloat16"])
    parser.add_argument("--zero1", action="store_true",
                        help="ZeRO-1 shard params/optimizer over dp")
    parser.add_argument("--remat", default="",
                        choices=["", "dots", "full"],
                        help="rematerialization policy for the step")
    args = parser.parse_args()

    logging.basicConfig(level=logging.INFO)
    if args.trainer == "parallel":
        fit_parallel(args)
        return 0

    _, sym, arg_params, aux_params = build_symbol(args)
    common.fit(args, sym, get_iters,
               arg_params=arg_params, aux_params=aux_params,
               allow_missing=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
