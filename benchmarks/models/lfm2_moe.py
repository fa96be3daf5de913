"""The `lfm2_moe` family: the model zoo's layered decoder
(`gluon/model_zoo/decoder.py`) at a configuration file's sizes: one chip's
share of LiquidAI's LFM2 mixture-of-experts models.  Its loss, its seeded
batches and its FLOPs."""

from __future__ import annotations

import numpy as np

from ..reference import lfm2_moe as reference  # noqa: F401  (the harness reads family.reference)
from .common import rng_for


def build(cfg):
    # first, so that a program without the decoder fails here and at once
    from mxnet_tpu.gluon.model_zoo.decoder import get_decoder_lm
    from mxnet_tpu import gluon

    net = get_decoder_lm(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        layer_types=cfg["layer_types"],
        num_dense_layers=cfg["num_dense_layers"],
        dense_hidden=cfg["intermediate_size"],
        expert_hidden=cfg["moe_intermediate_size"],
        num_experts=cfg["num_routed_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        experts_held=cfg["num_experts"],
        first_expert=cfg.get("first_expert", 0),
        expert_bias=[float(b) for b in reference.expert_bias(cfg)],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg.get("head_dim"),
        rope_theta=cfg["rope_theta"], conv_kernel=cfg["conv_L_cache"],
        eps=cfg["norm_eps"])
    return net, gluon.loss.SoftmaxCrossEntropyLoss()


def batches(cfg, seed, count, rows):
    """*count* distinct batches of *rows* packed sequences: int32 token
    ids and float32 labels, uniform over the rows of the vocabulary that
    are held here."""
    seq, vocab = cfg["train"]["sequence_length"], cfg["vocab_size"]
    out = []
    for i in range(count):
        rng = rng_for(seed, i)
        out.append((rng.integers(0, vocab, (rows, seq), dtype=np.int32),
                    rng.integers(0, vocab, (rows, seq)).astype(np.float32)))
    return out


def forward_macs_per_token(cfg):
    """Multiply-adds a token of the forward pass on this chip: the
    projections and the convolution's two, the feed-forwards with the
    EXPECTED local expert assignments a token (experts per token times
    the share of the router's experts held here), the tied head over the
    rows held, and causal attention's two contractions at half the
    square."""
    d, seq = cfg["hidden_size"], cfg["train"]["sequence_length"]
    hd = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    local = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["num_routed_experts"]
    macs = cfg["vocab_size"] * d
    for i, kind in enumerate(cfg["layer_types"]):
        if kind == "conv":
            macs += 3 * d * d + d * d
        else:
            macs += 2 * d * q + 2 * d * kv + 2 * (seq / 2) * q
        if i < cfg["num_dense_layers"]:
            macs += 3 * d * cfg["intermediate_size"]
        else:
            macs += d * cfg["num_routed_experts"] \
                + local * 3 * d * cfg["moe_intermediate_size"]
    return macs


def routed_layers_and_experts_held(cfg):
    """How many of the cell's layers are routed, and how many experts of
    each this chip holds: what a reader of the routed layers' work asks
    its family, whose keys it does not know."""
    return len(cfg["layer_types"]) - cfg["num_dense_layers"], \
        cfg["num_experts"]


def flops_per_sample(cfg):
    """Training FLOPs of one sequence: 2 a multiply-add, backward twice
    the forward; normalisations, activations, the softmax, the depthwise
    taps and the routing's sort and gathers are not counted."""
    return 3 * 2 * forward_macs_per_token(cfg) \
        * cfg["train"]["sequence_length"]


def sample_shapes(cfg, rows):
    seq = cfg["train"]["sequence_length"]
    return ((rows, seq), np.int32), ((rows, seq), np.float32)
