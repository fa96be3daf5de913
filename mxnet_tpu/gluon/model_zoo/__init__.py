"""Model zoo (reference capability: python/mxnet/gluon/model_zoo/).

Beside the vision zoo (imported here) the zoo carries language models as
modules of their own, imported where they are used:

- `model_zoo.transformer`: `TransformerLM`, GPT-2-style blocks (learned
  positions, LayerNorm, ReLU MLP) over the flash-attention op;
- `model_zoo.decoder`: `DecoderLM`, built from a list of layer kinds
  (gated short convolution or grouped-query attention with rotary
  positions; dense gated MLP or dropless top-k routed experts, one
  chip's share of them), RMS norm, a head tied to the embedding;
- `model_zoo.lm`: the recurrent language models.
"""

from . import vision

__all__ = ["vision"]
