"""The `sdar_moe` family: the model zoo's layered decoder
(`gluon/model_zoo/decoder.py`) at a configuration file's sizes, every layer
grouped-query attention under the block-diffusion mask (the
`block_diffusion_attention` kind) and one chip's share of softmax-routed
experts, an untied head over the noised half: one chip's share of JetLM's
SDAR-30B-A3B-Chat, trained by masked diffusion over blocks.  Its loss, its
seeded batches (which carry the noise) and its FLOPs."""

from __future__ import annotations

import numpy as np

from .. import bd_counts
# the harness reads family.reference
from ..reference import sdar_moe as reference  # noqa: F401
from .common import rng_for


def _decoder():
    """The model zoo's decoder, or a RuntimeError where it lacks the kind:
    asked for by `batches` (the first thing the loop asks a family for) and
    by `build`, so that such a program fails at once, before the seeded
    weights are made and before anything is compiled."""
    from mxnet_tpu.gluon.model_zoo import decoder
    if "block_diffusion_attention" not in decoder.OPERATOR_KINDS:
        raise RuntimeError(
            "this program's decoder has no block_diffusion_attention layer "
            "kind (it has %s): the sdar_moe family cannot be built"
            % (decoder.OPERATOR_KINDS,))
    return decoder


def build(cfg):
    decoder = _decoder()
    reference.check_supported(cfg)
    net = decoder.get_decoder_lm(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        layer_types=["block_diffusion_attention"] * cfg["num_hidden_layers"],
        num_dense_layers=0, dense_hidden=cfg["intermediate_size"],
        expert_hidden=cfg["moe_intermediate_size"],
        num_experts=cfg["router_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        experts_held=cfg["num_experts"],
        first_expert=cfg.get("first_expert", 0),
        norm_topk_prob=cfg["norm_topk_prob"], scoring_func="softmax",
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=cfg["rope_theta"], eps=cfg["rms_norm_eps"],
        tied_head=cfg["tie_word_embeddings"],
        diffusion_block=cfg["train"]["diffusion_block"])
    return net, decoder.BlockDiffusionLoss()


def noised(rng, cfg, rows):
    """One batch: ``x`` int32 ``(rows, 2L)``, the clean ids then the noised
    ids, and ``y`` float32 ``(rows, 2, L)``, the clean ids and the weights.
    The clean ids are uniform over the rows of the vocabulary held here,
    less the ``MASK`` row; a noise level ``t ~ U[t_min, 1]`` a block; each
    position masked with probability its block's ``t``, and then weighted
    ``1 / t``."""
    train = cfg["train"]
    seq, block = train["sequence_length"], train["diffusion_block"]
    mask_id = train["mask_token_id"]
    ids = rng.integers(0, cfg["vocab_size"] - 1, (rows, seq), dtype=np.int32)
    ids += ids >= mask_id
    t = np.repeat(rng.uniform(train["t_min"], 1.0, (rows, seq // block)),
                  block, axis=1)
    masked = rng.random((rows, seq)) < t
    x = np.concatenate([ids, np.where(masked, np.int32(mask_id), ids)], 1)
    y = np.stack([ids.astype(np.float32),
                  (masked / t).astype(np.float32)], 1)
    return x, y


def batches(cfg, seed, count, rows):
    """*count* distinct batches of *rows* sequences, each with its noise."""
    _decoder()
    return [noised(rng_for(seed, i), cfg, rows) for i in range(count)]


def sample_shapes(cfg, rows):
    seq = cfg["train"]["sequence_length"]
    return ((rows, 2 * seq), np.int32), ((rows, 2, seq), np.float32)


def forward_flops(cfg):
    """FLOPs of one sequence's forward pass on this chip, useful work (the
    algorithm's): the projections, the router and the EXPECTED local expert
    assignments a position (experts per token times the share of the
    router's outputs held here) over the ``2L`` positions, the attention
    core over the VISIBLE pairs (`bd_counts`: ``L^2 + L B`` a head), and the
    head over the ``L`` rows of the noised half."""
    d, seq = cfg["hidden_size"], cfg["train"]["sequence_length"]
    hd, heads, kv = cfg["head_dim"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    local = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_experts"]
    per_position = 2 * d * heads * hd + 2 * d * kv * hd \
        + d * cfg["router_experts"] \
        + local * 3 * d * cfg["moe_intermediate_size"]
    layer = 2 * (2 * seq) * per_position + bd_counts.core_flops(
        1, heads, seq, cfg["train"]["diffusion_block"], hd, hd, False)
    return cfg["num_hidden_layers"] * layer \
        + 2 * seq * cfg["vocab_size"] * d


def routed_layers_and_experts_held(cfg):
    """How many of the cell's layers are routed, and how many experts of
    each this chip holds (the source's `num_experts` counts what is held
    here; the router's width is `router_experts`)."""
    return cfg["num_hidden_layers"], cfg["num_experts"]


def flops_per_sample(cfg):
    """Training FLOPs of one sequence (its two copies): backward twice the
    forward; normalisations, activations, the softmaxes, rotary positions
    and the routing's sort and gathers are not counted, nor the pairs a
    kernel computes in a tile the mask crosses and drops."""
    return 3 * forward_flops(cfg)
