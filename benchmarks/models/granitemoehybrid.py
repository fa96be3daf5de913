"""The `granitemoehybrid` family: the model zoo's layered decoder
(`gluon/model_zoo/decoder.py`) at a configuration file's sizes: layers of two
kinds mixed by `layer_types`, `mamba` (a Mamba-2 state-space mixer: a float32
state a head carried along the sequence under a scalar decay, behind a short
causal convolution with a bias, the gate in front of its norm) and
`attention` (grouped-query attention with no positions, no q/k norm and the
published scale), a dense gated MLP in every layer, the residual, embedding
and logit multipliers, a tied head: one chip's share of IBM's Granite 4.0-H
dense models.  Its loss and its FLOPs; its seeded batches are `lfm2_moe`'s."""

from __future__ import annotations

from .. import ssm_counts, swa_counts
from ..reference import granitemoehybrid as reference  # noqa: F401  (the harness reads family.reference)
# the same seeded batches of packed token ids over the rows held
from .lfm2_moe import batches as _token_batches, sample_shapes  # noqa: F401

#: the published words for the layer kinds, as the decoder names them
DECODER_KINDS = {"mamba": "mamba", "attention": "full_attention"}


def _decoder():
    """The model zoo's decoder, or a RuntimeError where it lacks the kind:
    asked for by `batches` (the first thing the loop asks a family for) and
    by `build`, so that such a program fails at once, before the seeded
    weights are made and before anything is compiled."""
    from mxnet_tpu.gluon.model_zoo import decoder
    if "mamba" not in decoder.OPERATOR_KINDS:
        raise RuntimeError(
            "this program's decoder has no mamba layer kind (it has %s): "
            "the granitemoehybrid family cannot be built"
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
        layer_types=[DECODER_KINDS[kind] for kind in cfg["layer_types"]],
        num_dense_layers=layers,
        dense_hidden=cfg["shared_intermediate_size"], expert_hidden=0,
        num_experts=0, num_experts_per_tok=0,
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        rope_parameters={"full_attention": {"rope_theta": None}},
        qk_norm={"full_attention": None},
        attention_scale=cfg["attention_multiplier"],
        state_space={key[len("mamba_"):]: cfg[key] for key in (
            "mamba_n_heads", "mamba_d_head", "mamba_d_state",
            "mamba_n_groups", "mamba_d_conv", "mamba_chunk_size")},
        residual_multiplier=cfg["residual_multiplier"],
        embedding_multiplier=cfg["embedding_multiplier"],
        logits_scaling=cfg["logits_scaling"],
        eps=cfg["rms_norm_eps"], tied_head=cfg["tie_word_embeddings"])
    return net, gluon.loss.SoftmaxCrossEntropyLoss()


def forward_flops(cfg):
    """FLOPs of one sequence's forward pass on this chip, useful work (the
    algorithm's): the head over the rows held; in every layer the
    feed-forward's three products; in a mamba layer the in- and
    out-projections and the recurrence by `ssm_counts` (as it is stated: ``5
    P N`` a token and head, not the chunk algebra that computes it); in an
    attention layer the four products and the causal core over its visible
    pairs."""
    d, seq = cfg["hidden_size"], cfg["train"]["sequence_length"]
    heads, p, n, groups = cfg["mamba_n_heads"], cfg["mamba_d_head"], \
        cfg["mamba_d_state"], cfg["mamba_n_groups"]
    inner = heads * p
    full_heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // full_heads
    flops = 2 * seq * cfg["vocab_size"] * d
    for kind in cfg["layer_types"]:
        per_token = 3 * d * cfg["shared_intermediate_size"]
        if kind == "mamba":
            per_token += d * (2 * inner + 2 * groups * n + heads) + inner * d
            flops += ssm_counts.scan_flops(1, seq, heads, p, n, False)
        else:
            per_token += 2 * d * full_heads * hd + 2 * d * kv * hd
            flops += swa_counts.core_flops(1, full_heads, seq, seq, hd, hd,
                                           False)
        flops += 2 * seq * per_token
    return flops


def flops_per_sample(cfg):
    """Training FLOPs of one sequence: backward twice the forward; the
    convolution's taps, normalisations, activations, the softmax and the
    skip are not counted, nor what the chunkwise form of the recurrence
    multiplies beyond it."""
    return 3 * forward_flops(cfg)
