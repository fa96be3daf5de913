"""Contrib layers (reference: gluon/contrib/nn/basic_layers.py)."""

from .basic_layers import (Concurrent, GatedMLP, GatedShortConv,  # noqa
                           GroupedQueryAttention, HybridConcurrent, Identity,
                           MoEFFN, MultiHeadAttention, RoutedExperts,
                           SparseEmbedding, SyncBatchNorm)
