"""A decoder-only language model built from a list of layer kinds: each
layer has an operator (``"conv"``: a gated short causal convolution, or
``"full_attention"``: grouped-query attention with per-head RMS norm and
rotary positions) and a feed-forward (dense gated MLP, or dropless top-k
routed experts), pre-normed with RMS norm and added to the residual:

    h = h + operator(rms(h));  h = h + feed_forward(rms(h))

then a final RMS norm and a head tied to the embedding.  This is the
shape of LiquidAI's LFM2 mixture-of-experts models (``model_type``
``lfm2_moe``), whose published ``config.json`` keys the arguments follow;
`benchmarks/models/lfm2_moe.py` builds one from such a file.

The routed layers hold ONE CHIP'S SHARE of their experts
(`gluon.contrib.nn.RoutedExperts`): ``experts_held`` of ``num_experts``
from ``first_expert`` on, with the router over all of them; likewise
``vocab`` may be the rows of the vocabulary held here.

Usage::

    net = get_decoder_lm(vocab=1024, dim=256, layer_types=["conv",
                         "full_attention"], num_dense_layers=1, ...)
    logits = net(tokens)         # (B, S) int -> (B, S, vocab)
"""

from __future__ import annotations

from ..block import HybridBlock
from .. import nn
from ..contrib.nn import (GatedMLP, GatedShortConv, GroupedQueryAttention,
                          RoutedExperts)

__all__ = ["DecoderLayer", "DecoderLM", "get_decoder_lm", "OPERATOR_KINDS"]

OPERATOR_KINDS = ("conv", "full_attention")


class DecoderLayer(HybridBlock):
    """One pre-norm layer: *operator* is built by kind, *feed_forward*
    is handed in."""

    def __init__(self, dim, kind, feed_forward, heads, kv_heads, head_dim,
                 rope_theta, conv_kernel, eps, init, **kwargs):
        super().__init__(**kwargs)
        if kind not in OPERATOR_KINDS:
            raise ValueError("layer kind %r is not one of %s"
                             % (kind, OPERATOR_KINDS))
        with self.name_scope():
            self.operator_norm = nn.RMSNorm(dim, eps,
                                            prefix="operator_norm_")
            if kind == "conv":
                self.operator = GatedShortConv(
                    dim, conv_kernel, weight_initializer=init,
                    prefix="conv_")
            else:
                self.operator = GroupedQueryAttention(
                    dim, heads, kv_heads, head_dim, rope_theta, eps,
                    weight_initializer=init, prefix="attn_")
            self.ffn_norm = nn.RMSNorm(dim, eps, prefix="ffn_norm_")
            self.feed_forward = feed_forward()

    def hybrid_forward(self, F, x):
        x = x + self.operator(self.operator_norm(x))
        return x + self.feed_forward(self.ffn_norm(x))


class DecoderLM(HybridBlock):
    """Token embedding, the layers of *layer_types* (the first
    *num_dense_layers* with a dense gated MLP of width *dense_hidden*,
    the others with routed experts of width *expert_hidden*), final RMS
    norm, and logits against the embedding itself (a tied head)."""

    def __init__(self, vocab, dim, layer_types, num_dense_layers,
                 dense_hidden, expert_hidden, num_experts,
                 num_experts_per_tok, experts_held=None, first_expert=0,
                 expert_bias=None, norm_topk_prob=True,
                 routed_scaling_factor=1.0, heads=8, kv_heads=None,
                 head_dim=None, rope_theta=10000.0, conv_kernel=3,
                 eps=1e-5, weight_initializer="normal", **kwargs):
        super().__init__(**kwargs)
        self._vocab, self._dim = vocab, dim
        init = weight_initializer

        def dense():
            return GatedMLP(dim, dense_hidden, weight_initializer=init,
                            prefix="mlp_")

        def routed():
            return RoutedExperts(
                dim, expert_hidden, num_experts, num_experts_per_tok,
                experts_held, first_expert, expert_bias, norm_topk_prob,
                routed_scaling_factor, weight_initializer=init,
                prefix="moe_")

        with self.name_scope():
            self.embed_weight = self.params.get(
                "embed_weight", shape=(vocab, dim), init=init)
            self.layers = []
            for i, kind in enumerate(layer_types):
                layer = DecoderLayer(
                    dim, kind, dense if i < num_dense_layers else routed,
                    heads, kv_heads or heads, head_dim, rope_theta,
                    conv_kernel, eps, init, prefix="l%d_" % i)
                setattr(self, "l%d" % i, layer)
                self.layers.append(layer)
            self.final_norm = nn.RMSNorm(dim, eps, prefix="final_norm_")

    def hybrid_forward(self, F, x, embed_weight):
        h = F.Embedding(x, embed_weight, input_dim=self._vocab,
                        output_dim=self._dim)
        for layer in self.layers:
            h = layer(h)
        return F.FullyConnected(self.final_norm(h), embed_weight,
                                no_bias=True, flatten=False,
                                num_hidden=self._vocab)


def get_decoder_lm(**kwargs):
    return DecoderLM(**kwargs)
