"""Sequence/context parallelism: ring attention over the ICI mesh.

The reference has no sequence parallelism (SURVEY §5.7 — bucketing and the
fused RNN op were its only sequence-scaling tools).  The TPU-native stance:
shard the sequence dimension over a mesh axis and run *ring attention* —
each device keeps its Q shard resident and rotates K/V shards around the
ring with ``ppermute`` while accumulating blockwise online-softmax partials,
so attention over a sequence of length S costs O(S/n) memory per chip and
the K/V transfers ride the ICI ring concurrently with compute.

``ring_attention_shard`` is the per-shard function (use inside shard_map /
pjit with a bound axis name); ``sequence_parallel_attention`` is the
host-level wrapper that builds the shard_map over a mesh axis.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from ..ops.attention import (_NEG_INF, _finalize_softmax,
                             _online_softmax_update)

__all__ = ["ring_attention_shard", "sequence_parallel_attention"]


def ring_attention_shard(q, k, v, axis_name, causal=False, sm_scale=None):
    """Ring attention on one sequence shard; call inside shard_map.

    q, k, v: (B, H, S_local, D) — this device's contiguous slice of the
    sequence (device i holds positions [i*S_local, (i+1)*S_local)).
    Returns the (B, H, S_local, D) attention output for the local queries
    over the FULL global sequence.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    from .collectives import axis_size
    n = axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    b, h, s_loc, d = q.shape
    s_loc_k = k.shape[2]
    # storage-dtype q for the score dot (bf16 at full MXU rate); the
    # online-softmax state stays f32 via preferred_element_type
    # global positions, sequence ends aligned (same convention as
    # ops.attention when seq_q != seq_k)
    q_pos = me * s_loc + jnp.arange(s_loc) + (s_loc_k - s_loc) * n
    # receive from the right, send to the left: after step t this device
    # holds the K/V shard that originated at (me + t) % n
    perm = [(i, (i - 1) % n) for i in range(n)]

    def body(carry, t):
        o, m, l, kb, vb = carry
        src = (me + t) % n
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kb,
                       preferred_element_type=jnp.float32) * sm_scale
        if causal:
            k_pos = src * s_loc_k + jnp.arange(s_loc_k)
            mask = k_pos[None, :] <= q_pos[:, None]
            s = jnp.where(mask[None, None], s, _NEG_INF)
        o, m, l = _online_softmax_update(o, m, l, s, vb)
        # rotate K/V one hop around the ring (overlaps with next compute
        # under XLA's async collective scheduling)
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        return (o, m, l, kb, vb), None

    o0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    m0 = jnp.full((b, h, s_loc), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc), jnp.float32)
    (o, m, l, _, _), _ = jax.lax.scan(
        jax.checkpoint(body), (o0, m0, l0, k, v), jnp.arange(n))
    return _finalize_softmax(o, m, l).astype(q.dtype)


def sequence_parallel_attention(q, k, v, mesh, axis="sp", causal=False,
                                sm_scale=None):
    """Host-level ring attention: (B, H, S, D) arrays sharded (or to be
    sharded) on the sequence dim over mesh axis *axis*."""
    from jax.sharding import NamedSharding
    spec = P(None, None, axis, None)
    sh = NamedSharding(mesh, spec)
    # inputs may be committed to a single device (e.g. outputs of an
    # earlier jitted op) — place them onto the mesh first; remember the
    # original placement so imperative callers get the result back where
    # the rest of their ops run (inside pjit this wrapper isn't used —
    # ring_attention_shard composes directly)
    orig_dev = None
    if not isinstance(q, jax.core.Tracer) and hasattr(q, "devices"):
        try:
            devs = list(q.devices())
        except (AttributeError, TypeError, RuntimeError, ValueError):
            # abstract/uncommitted values have no devices; anything
            # else must propagate rather than silently lose the
            # caller's placement
            devs = []
        if len(devs) == 1:
            orig_dev = devs[0]
    q, k, v = (jax.device_put(a, sh) for a in (q, k, v))

    def fn(qs, ks, vs):
        return ring_attention_shard(qs, ks, vs, axis, causal=causal,
                                    sm_scale=sm_scale)

    out = shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                    out_specs=spec, check_vma=False)(q, k, v)
    if orig_dev is not None:
        out = jax.device_put(out, orig_dev)
    return out
