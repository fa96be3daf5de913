"""Contrib layers (reference: gluon/contrib/nn/basic_layers.py)."""

from .basic_layers import (Concurrent, GatedDeltaNet, GatedMLP,  # noqa
                           GatedShortConv, GroupedQueryAttention, HybridConcurrent, Identity,
                           LatentAttention, MoEFFN, MultiHeadAttention,
                           RoutedExperts, SharedExperts, SparseAttention,
                           SparseEmbedding, StateSpaceMixer, SyncBatchNorm)
