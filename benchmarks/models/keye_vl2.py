"""The `keye_vl2` family: the model zoo's layered decoder
(`gluon/model_zoo/decoder.py`) at a configuration file's sizes, every layer
grouped-query attention over the keys a learned indexer chose (the
`sparse_attention` kind, trained by its alignment term) and one chip's share
of softmax-routed experts, an untied head: one chip's share of the language
model of Kwai-Keye's Keye-VL-2.0-30B-A3B.  Its loss and its FLOPs; its seeded
batches are `lfm2_moe`'s."""

from __future__ import annotations

from .. import dsa_counts
# the harness reads family.reference
from ..reference import keye_vl2 as reference  # noqa: F401
# the same seeded batches of packed token ids over the rows held
from .lfm2_moe import batches, sample_shapes  # noqa: F401


def build(cfg):
    # first, so that a program whose decoder lacks the sparse attention kind
    # fails here and at once, before anything is compiled
    from mxnet_tpu.gluon.model_zoo import decoder
    if "sparse_attention" not in decoder.OPERATOR_KINDS:
        raise RuntimeError(
            "this program's decoder has no sparse_attention layer kind "
            "(it has %s): the keye_vl2 family cannot be built"
            % (decoder.OPERATOR_KINDS,))
    from mxnet_tpu import gluon

    reference.check_supported(cfg)
    sa = cfg["sa_config"]
    net = decoder.get_decoder_lm(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        layer_types=["sparse_attention"] * cfg["num_hidden_layers"],
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
        index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
        mrope_section=tuple(cfg["rope_scaling"]["mrope_section"]),
        alignment_weight=cfg.get("alignment_weight", 1.0))
    # the net returns (logits, alignment term): the term joins the objective
    return net, decoder.AlignedLoss(gluon.loss.SoftmaxCrossEntropyLoss())


def forward_flops(cfg):
    """FLOPs of one sequence's forward pass on this chip, useful work (the
    algorithm's, `dsa_counts`): the projections and the indexer's three,
    the attention core over the SELECTED pairs, the indexer's scores over
    the VISIBLE pairs, the router, the EXPECTED local expert assignments a
    token (experts per token times the share of the router's outputs held
    here), and the head over the rows held."""
    d, seq = cfg["hidden_size"], cfg["train"]["sequence_length"]
    hd, heads, kv = cfg["head_dim"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    sa = cfg["sa_config"]
    ih, iw = sa["indexer_num_heads"], sa["indexer_head_dim"]
    local = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_experts"]
    per_token = 2 * d * heads * hd + 2 * d * kv * hd \
        + d * (ih * iw + iw + ih) + d * cfg["router_experts"] \
        + local * 3 * d * cfg["moe_intermediate_size"]
    layer = 2 * seq * per_token \
        + dsa_counts.core_flops(1, heads, seq, sa["topk"], hd, hd, False) \
        + dsa_counts.index_flops(1, ih, iw, seq, False)
    return cfg["num_hidden_layers"] * layer \
        + 2 * seq * cfg["vocab_size"] * d


def routed_layers_and_experts_held(cfg):
    """How many of the cell's layers are routed, and how many experts of
    each this chip holds (the source's `num_experts` counts what is held
    here; the router's width is `router_experts`)."""
    return cfg["num_hidden_layers"], cfg["num_experts"]


def flops_per_sample(cfg):
    """Training FLOPs of one sequence: backward twice the forward (the
    indexer's too: its backward exists through the alignment term);
    normalisations, activations, the softmaxes, rotary positions, the
    counting that finds the k-th largest and the routing's sort and gathers
    are not counted, nor the pairs a masked kernel computes and drops."""
    return 3 * forward_flops(cfg)
