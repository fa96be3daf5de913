"""The `laguna` family: the model zoo's layered decoder
(`gluon/model_zoo/decoder.py`) at a configuration file's sizes:
grouped-query attention whose layers are of two kinds mixed by
`layer_types`, `sliding_attention` (a causal window of `sliding_window`
keys) and `full_attention`, each with its own count of query heads
(`num_attention_heads_per_layer`) and its own rotary settings
(`rope_parameters`), a per-head sigmoid gate on the attention's output, a
dense gated MLP in the `dense` layers and a shared expert beside one chip's
share of sigmoid-routed experts in the `sparse` ones, an untied head: one
chip's share of poolside's Laguna-S-2.1.  Its loss and its FLOPs; its
seeded batches are `lfm2_moe`'s."""

from __future__ import annotations

from .. import swa_counts
from ..reference import laguna as reference  # noqa: F401  (the harness reads family.reference)
# the same seeded batches of packed token ids over the rows held
from .lfm2_moe import batches as _token_batches, sample_shapes  # noqa: F401


def _decoder():
    """The model zoo's decoder, or a RuntimeError where it lacks the kind:
    asked for by `batches` (the first thing the loop asks a family for) and
    by `build`, so that such a program fails at once, before the seeded
    weights are made and before anything is compiled."""
    from mxnet_tpu.gluon.model_zoo import decoder
    if "sliding_attention" not in decoder.OPERATOR_KINDS:
        raise RuntimeError(
            "this program's decoder has no sliding_attention layer kind "
            "(it has %s): the laguna family cannot be built"
            % (decoder.OPERATOR_KINDS,))
    return decoder


def batches(cfg, seed, count, rows):
    _decoder()
    return _token_batches(cfg, seed, count, rows)


def build(cfg):
    decoder = _decoder()
    from mxnet_tpu import gluon

    reference.check_supported(cfg)
    dense = [kind == "dense" for kind in cfg["mlp_layer_types"]]
    if dense != sorted(dense, reverse=True):
        raise ValueError("the dense feed-forwards lead the routed ones")
    net = decoder.get_decoder_lm(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        layer_types=cfg["layer_types"], num_dense_layers=sum(dense),
        dense_hidden=cfg["intermediate_size"],
        expert_hidden=cfg["moe_intermediate_size"],
        shared_hidden=cfg["shared_expert_intermediate_size"],
        num_experts=cfg["router_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        experts_held=cfg["num_experts"],
        first_expert=cfg.get("first_expert", 0),
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["moe_routed_scaling_factor"],
        heads=cfg["num_attention_heads_per_layer"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_parameters=cfg["rope_parameters"],
        sliding_window=cfg["sliding_window"], attention_gate=True,
        eps=cfg["rms_norm_eps"], tied_head=cfg["tie_word_embeddings"])
    return net, gluon.loss.SoftmaxCrossEntropyLoss()


def forward_flops(cfg):
    """FLOPs of one sequence's forward pass on this chip, useful work (the
    algorithm's): each layer's projections (q, k, v, the gate, the output),
    its attention core over the pairs its OWN mask leaves visible
    (`swa_counts`: the window's in a sliding layer, the triangle in a full
    one), the dense MLP or the router, the shared expert whole and the
    EXPECTED local expert assignments a token (experts per token times the
    share of the router's outputs held here), and the head over the rows
    held."""
    d, seq, hd = cfg["hidden_size"], cfg["train"]["sequence_length"], \
        cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * hd
    local = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_experts"]
    flops = 2 * seq * cfg["vocab_size"] * d
    for kind, heads, mlp in zip(cfg["layer_types"],
                                cfg["num_attention_heads_per_layer"],
                                cfg["mlp_layer_types"]):
        per_token = 2 * d * heads * hd + 2 * d * kv + d * heads
        if mlp == "dense":
            per_token += 3 * d * cfg["intermediate_size"]
        else:
            per_token += d * cfg["router_experts"] \
                + 3 * d * cfg["shared_expert_intermediate_size"] \
                + local * 3 * d * cfg["moe_intermediate_size"]
        window = cfg["sliding_window"] if kind == "sliding_attention" \
            else seq
        flops += 2 * seq * per_token + swa_counts.core_flops(
            1, heads, seq, window, hd, hd, False)
    return flops


def routed_layers_and_experts_held(cfg):
    """How many of the cell's layers are routed, and how many experts of
    each this chip holds (the source's `num_experts` counts what is held
    here; the router's width is `router_experts`)."""
    return cfg["mlp_layer_types"].count("sparse"), cfg["num_experts"]


def flops_per_sample(cfg):
    """Training FLOPs of one sequence: backward twice the forward;
    normalisations, activations, the softmax, the gate's sigmoid and
    multiply, rotary positions and the routing's sort and gathers are not
    counted, nor the pairs a kernel computes in a tile the window's edge or
    the diagonal crosses and drops."""
    return 3 * forward_flops(cfg)
