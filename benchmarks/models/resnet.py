"""The `resnet` family: the zoo's ResNet v1 at a configuration file's
sizes, its loss, its seeded batches and its FLOPs."""

from __future__ import annotations

import numpy as np

from .. import flops
from ..reference import resnet as reference  # noqa: F401  (the harness reads family.reference)
from .common import rng_for


def build(cfg):
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import vision

    net = vision.get_model(cfg["zoo_name"], classes=cfg["classes"])
    return net, gluon.loss.SoftmaxCrossEntropyLoss()


def batches(cfg, seed, count, rows):
    """*count* distinct batches of *rows* float32 NCHW images and float32
    class labels, as `examples/train_imagenet.py --benchmark` feeds."""
    size = cfg["image_size"]
    out = []
    for i in range(count):
        rng = rng_for(seed, i)
        out.append((rng.standard_normal((rows, 3, size, size),
                                        dtype=np.float32),
                    rng.integers(0, cfg["classes"], (rows,))
                    .astype(np.float32)))
    return out


def flops_per_sample(cfg):
    return flops.resnet_train_flops(cfg)


def sample_shapes(cfg, rows):
    size = cfg["image_size"]
    return ((rows, 3, size, size), np.float32), ((rows,), np.float32)
