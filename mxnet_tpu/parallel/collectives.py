"""Collective helpers.

Replaces the reference's comm layer (src/kvstore/comm.h Reduce/Broadcast,
kvstore_nccl.h ncclReduce/ncclBcast): on TPU collectives are XLA ops
(psum/all_gather/reduce_scatter/ppermute) emitted inside shard_map/pjit and
scheduled by the compiler onto ICI.  These wrappers exist so framework code
and user code share one vocabulary; inside a shard_map they are the raw
jax.lax collectives.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

__all__ = ["allreduce", "allgather", "reduce_scatter", "broadcast",
           "psum", "pmean", "ppermute_ring", "axis_size"]

# in-shard_map primitives (axis_name bound by caller)
psum = jax.lax.psum
pmean = jax.lax.pmean


def axis_size(axis_name):
    """Static size of a mapped axis.  ``jax.lax.axis_size`` only exists
    in newer jax releases; ``psum(1, axis)`` is the classic idiom and
    constant-folds to a Python int, so callers can use the result in
    Python control flow either way."""
    fn = getattr(jax.lax, "axis_size", None)
    if fn is not None:
        return fn(axis_name)
    return jax.lax.psum(1, axis_name)


def ppermute_ring(x, axis_name, shift=1):
    """Rotate shards around the ring (ring-attention building block)."""
    n = axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name, perm)


@functools.lru_cache(maxsize=None)
def _allreduce_fn(mesh, axis):
    @jax.jit
    def f(x):
        # x: (n, ...) sharded over axis on dim0 -> replicated sum over dim0
        def shard_fn(s):
            return jax.lax.psum(jnp.sum(s, axis=0), axis)
        return shard_map(shard_fn, mesh=mesh, in_specs=P(axis),
                         out_specs=P(), check_vma=False)(x)
    return f


def allreduce(stacked, mesh, axis="dp"):
    """Sum a leading-axis-sharded stack over *axis*; returns the
    replicated sum (shape = stacked.shape[1:]).  Host-callable."""
    return _allreduce_fn(mesh, axis)(stacked)


@functools.lru_cache(maxsize=None)
def _reduce_scatter_fn(mesh, axis):
    @jax.jit
    def f(x):
        # x: (n, m) sharded over axis -> (m,) sharded: device i holds the
        # i-th m/n block of the sum (ZeRO gradient layout)
        def shard_fn(s):
            return jax.lax.psum_scatter(s[0], axis, scatter_dimension=0,
                                        tiled=True)
        return shard_map(shard_fn, mesh=mesh, in_specs=P(axis),
                         out_specs=P(axis))(x)
    return f


def reduce_scatter(stacked, mesh, axis="dp"):
    return _reduce_scatter_fn(mesh, axis)(stacked)


@functools.lru_cache(maxsize=None)
def _allgather_fn(mesh, axis):
    @jax.jit
    def f(x):
        return shard_map(
            lambda s: jax.lax.all_gather(s, axis, axis=0, tiled=True),
            mesh=mesh, in_specs=P(axis), out_specs=P(),
            check_vma=False)(x)
    return f


def allgather(shards, mesh, axis="dp"):
    return _allgather_fn(mesh, axis)(shards)


def broadcast(x, mesh):
    """Replicate a host/single-device array across the mesh."""
    from jax.sharding import NamedSharding
    return jax.device_put(x, NamedSharding(mesh, P()))
