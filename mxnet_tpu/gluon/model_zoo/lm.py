"""LSTM language model — the reference's own LM headline shape
(example/rnn PTB models: Embedding + fused-RNN LSTM stack + head; the
fused op is `lax.scan` here, ops/rnn.py).  Driven by the trainer
tests (tests/test_parallel_modes.py)."""

from __future__ import annotations

from ..block import HybridBlock
from .. import nn, rnn

__all__ = ["LSTMLM", "get_lstm_lm"]


class LSTMLM(HybridBlock):
    def __init__(self, vocab, dim, layers, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.embed = nn.Embedding(vocab, dim)
            self.lstm = rnn.LSTM(dim, num_layers=layers, layout="NTC")
            self.head = nn.Dense(vocab, use_bias=False, flatten=False)

    def hybrid_forward(self, F, x):
        return self.head(self.lstm(self.embed(x)))


def get_lstm_lm(vocab=10000, dim=650, layers=2, **kwargs):
    """Defaults: the reference PTB 'medium' config (2x650)."""
    return LSTMLM(vocab, dim, layers, **kwargs)
