"""The `deepseek_v3` family: the model zoo's layered decoder
(`gluon/model_zoo/decoder.py`) at a configuration file's sizes, every layer
a latent attention block (MLA), the leading layers with a dense gated MLP
and the others with shared experts beside one chip's share of the routed
ones, an untied head: one chip's share of kakaocorp's Kanana-2-30B-A3B.
Its loss and its FLOPs; its seeded batches are `lfm2_moe`'s."""

from __future__ import annotations

from ..reference import deepseek_v3 as reference  # noqa: F401  (the harness reads family.reference)
# the same seeded batches of packed token ids over the rows held
from .lfm2_moe import batches, sample_shapes  # noqa: F401


def build(cfg):
    # first, so that a program whose decoder lacks the latent block, the
    # shared experts or the untied head fails here and at once
    from mxnet_tpu.gluon.model_zoo import decoder
    if "latent_attention" not in decoder.OPERATOR_KINDS:
        raise RuntimeError(
            "this program's decoder has no latent_attention layer kind "
            "(it has %s): the deepseek_v3 family cannot be built"
            % (decoder.OPERATOR_KINDS,))
    from mxnet_tpu import gluon

    reference.check_supported(cfg)
    net = decoder.get_decoder_lm(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        layer_types=["latent_attention"] * cfg["num_hidden_layers"],
        num_dense_layers=cfg["first_k_dense_replace"],
        dense_hidden=cfg["intermediate_size"],
        expert_hidden=cfg["moe_intermediate_size"],
        shared_hidden=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        num_experts=cfg["router_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        experts_held=cfg["n_routed_experts"],
        first_expert=cfg.get("first_expert", 0),
        expert_bias=[float(b) for b in reference.expert_bias(cfg)],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        heads=cfg["num_attention_heads"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=cfg["rope_theta"],
        rope_interleave=cfg["rope_interleave"], eps=cfg["rms_norm_eps"],
        tied_head=cfg["tie_word_embeddings"])
    return net, gluon.loss.SoftmaxCrossEntropyLoss()


def forward_macs_per_token(cfg):
    """Multiply-adds a token of the forward pass on this chip: the latent
    block's four projections and causal attention's two contractions at
    half the square (scores over nope + rope, values over v), the
    feed-forwards with the shared experts whole and the EXPECTED local
    expert assignments a token (experts per token times the share of the
    router's outputs held here), the router, and the head over the rows
    held."""
    d, seq, heads = cfg["hidden_size"], cfg["train"]["sequence_length"], \
        cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, vd = cfg["kv_lora_rank"], cfg["v_head_dim"]
    attention = d * heads * (nope + rope) + d * (rank + rope) \
        + rank * heads * (nope + vd) + heads * vd * d \
        + (seq / 2) * heads * (nope + rope + vd)
    local = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["router_experts"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    macs = cfg["vocab_size"] * d
    for i in range(cfg["num_hidden_layers"]):
        macs += attention
        if i < cfg["first_k_dense_replace"]:
            macs += 3 * d * cfg["intermediate_size"]
        else:
            macs += d * cfg["router_experts"] \
                + (cfg["n_shared_experts"] + local) * expert
    return macs


def routed_layers_and_experts_held(cfg):
    """How many of the cell's layers are routed, and how many experts of
    each this chip holds (the source's `n_routed_experts` counts what is
    held here; the router's width is `router_experts`)."""
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"], \
        cfg["n_routed_experts"]


def flops_per_sample(cfg):
    """Training FLOPs of one sequence: 2 a multiply-add, backward twice
    the forward; normalisations, activations, the softmax, rotary
    positions and the routing's sort and gathers are not counted."""
    return 3 * 2 * forward_macs_per_token(cfg) \
        * cfg["train"]["sequence_length"]
