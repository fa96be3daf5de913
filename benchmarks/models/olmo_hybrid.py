"""The `olmo_hybrid` family: the model zoo's layered decoder
(`gluon/model_zoo/decoder.py`) at a configuration file's sizes: layers of
two kinds mixed by `layer_types`, `linear_attention` (the gated delta rule:
a float32 state a head carried along the sequence, behind a short causal
convolution; pre-normed) and `full_attention` (grouped-query attention with
one norm over the whole width of q and of k, no rotary positions, the
layer's norms on the outputs), a dense gated MLP in every layer, an untied
head: one chip's share of Ai2's Olmo-Hybrid-7B.  Its loss and its FLOPs; its
seeded batches are `lfm2_moe`'s."""

from __future__ import annotations

from .. import gdn_counts, swa_counts
from ..reference import olmo_hybrid as reference  # noqa: F401  (the harness reads family.reference)
# the same seeded batches of packed token ids over the rows held
from .lfm2_moe import batches as _token_batches, sample_shapes  # noqa: F401


def _decoder():
    """The model zoo's decoder, or a RuntimeError where it lacks the kind:
    asked for by `batches` (the first thing the loop asks a family for) and
    by `build`, so that such a program fails at once, before the seeded
    weights are made and before anything is compiled."""
    from mxnet_tpu.gluon.model_zoo import decoder
    if "linear_attention" not in decoder.OPERATOR_KINDS:
        raise RuntimeError(
            "this program's decoder has no linear_attention layer kind "
            "(it has %s): the olmo_hybrid family cannot be built"
            % (decoder.OPERATOR_KINDS,))
    return decoder


def batches(cfg, seed, count, rows):
    _decoder()
    return _token_batches(cfg, seed, count, rows)


def build(cfg):
    decoder = _decoder()
    from mxnet_tpu import gluon

    reference.check_supported(cfg)
    layers = cfg["num_hidden_layers"]
    net = decoder.get_decoder_lm(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        layer_types=cfg["layer_types"], num_dense_layers=layers,
        dense_hidden=cfg["intermediate_size"], expert_hidden=0,
        num_experts=0, num_experts_per_tok=0,
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        rope_parameters={"full_attention": cfg["rope_parameters"]},
        qk_norm={"full_attention": "width"},
        norm_place={"full_attention": "output"},
        linear={key[len("linear_"):]: cfg[key] for key in (
            "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim", "linear_allow_neg_eigval")},
        eps=cfg["rms_norm_eps"], tied_head=cfg["tie_word_embeddings"])
    return net, gluon.loss.SoftmaxCrossEntropyLoss()


def linear_layers(cfg):
    return cfg["layer_types"].count("linear_attention")


def forward_flops(cfg):
    """FLOPs of one sequence's forward pass on this chip, useful work (the
    algorithm's): the head over the rows held; in every layer the
    feed-forward's three products; in a linear layer the six projections,
    the output product and the rule by `gdn_counts` (the recurrence as it is
    stated: ``7 dk dv`` a token and head, not the chunk algebra that computes
    it); in a full layer the four products and the causal core over its
    visible pairs."""
    d, seq = cfg["hidden_size"], cfg["train"]["sequence_length"]
    heads, dk, dv = cfg["linear_num_value_heads"], \
        cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    full_heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // full_heads
    flops = 2 * seq * cfg["vocab_size"] * d
    for kind in cfg["layer_types"]:
        per_token = 3 * d * cfg["intermediate_size"]
        if kind == "linear_attention":
            # q, k, v, z, the two gates, the output product
            per_token += d * (2 * heads * dk + 2 * heads * dv + 2 * heads) \
                + heads * dv * d
            flops += gdn_counts.rule_flops(1, seq, heads, dk, dv, False)
        else:
            per_token += 2 * d * full_heads * hd + 2 * d * kv * hd
            flops += swa_counts.core_flops(1, full_heads, seq, seq, hd, hd,
                                           False)
        flops += 2 * seq * per_token
    return flops


def flops_per_sample(cfg):
    """Training FLOPs of one sequence: backward twice the forward; the
    convolution's taps, normalisations, activations, the softmax and the
    gates' arithmetic are not counted, nor what the chunkwise form of the
    rule multiplies beyond the recurrence."""
    return 3 * forward_flops(cfg)
