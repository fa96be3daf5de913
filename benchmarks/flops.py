"""Operation counts from shapes: the only place a FLOP count lives.

Model FLOPs are what the forward and backward passes of the algorithm
need (2 per multiply-add, backward = 2 x forward for every contraction);
recomputation (`remat`) and the optimizer's elementwise work are not
counted, nor are normalisations, activations and the softmax.
"""

from __future__ import annotations


def transformer_lm_forward_flops(vocab, dim, ffn, layers, seq, causal=True):
    """Forward FLOPs of ONE sequence of *seq* tokens: the projections, the
    feed-forward, the head, and attention's two contractions, of which a
    causal kernel needs half (the scores above the diagonal are never
    used)."""
    per_token = 2 * (layers * (4 * dim * dim + 2 * dim * ffn) + vocab * dim)
    attention = layers * 2 * 2 * seq * seq * dim
    if causal:
        attention //= 2
    return seq * per_token + attention


def transformer_lm_train_flops(vocab, dim, ffn, layers, seq, causal=True):
    return 3 * transformer_lm_forward_flops(vocab, dim, ffn, layers, seq,
                                            causal)


def _conv_out(size, k, stride, pad):
    return (size + 2 * pad - k) // stride + 1


def resnet_forward_flops(cfg):
    """Forward FLOPs of ONE image through the bottleneck ResNet v1 that
    `reference/resnet.py` writes out: every convolution and the head."""
    size = _conv_out(cfg["image_size"], 7, 2, 3)
    macs = size * size * cfg["stem_channels"] * 3 * 49
    size = _conv_out(size, 3, 2, 1)
    in_ch = cfg["stem_channels"]
    for s, (n, width) in enumerate(zip(cfg["units"], cfg["stage_channels"])):
        for u in range(n):
            stride = 2 if (s > 0 and u == 0) else 1
            out = size // stride
            inner = width // 4
            macs += out * out * (inner * in_ch + inner * inner * 9
                                 + width * inner)
            if stride != 1 or in_ch != width:
                macs += out * out * width * in_ch
            size, in_ch = out, width
    macs += cfg["classes"] * in_ch
    return 2 * macs


def resnet_train_flops(cfg):
    return 3 * resnet_forward_flops(cfg)
