"""The `transformer_lm` family: the repo's `TransformerLM` at a
configuration file's sizes, its loss, its seeded batches and its FLOPs."""

from __future__ import annotations

import numpy as np

from .. import flops
from ..reference import transformer_lm as reference  # noqa: F401  (the harness reads family.reference)
from .common import rng_for


def build(cfg):
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo.transformer import get_transformer_lm

    if cfg["ffn_dim"] % cfg["hidden_size"]:
        raise ValueError("TransformerLM takes a whole mlp_ratio")
    net = get_transformer_lm(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        heads=cfg["num_attention_heads"], layers=cfg["num_hidden_layers"],
        max_seq=cfg["max_position_embeddings"],
        mlp_ratio=cfg["ffn_dim"] // cfg["hidden_size"])
    return net, gluon.loss.SoftmaxCrossEntropyLoss()


def batches(cfg, seed, count, rows):
    """*count* distinct batches of *rows* sequences: int32 token ids (the
    bf16 input cast would round float ids) and float32 labels, as
    `tools/benchmark_lm.py` feeds them."""
    seq, vocab = cfg["train"]["sequence_length"], cfg["vocab_size"]
    out = []
    for i in range(count):
        rng = rng_for(seed, i)
        out.append((rng.integers(0, vocab, (rows, seq), dtype=np.int32),
                    rng.integers(0, vocab, (rows, seq)).astype(np.float32)))
    return out


def flops_per_sample(cfg):
    return flops.transformer_lm_train_flops(
        cfg["vocab_size"], cfg["hidden_size"], cfg["ffn_dim"],
        cfg["num_hidden_layers"], cfg["train"]["sequence_length"])


def sample_shapes(cfg, rows):
    seq = cfg["train"]["sequence_length"]
    return ((rows, seq), np.int32), ((rows, seq), np.float32)
