"""Expert parallelism: top-1 MoE dispatch over a mesh axis.

Absent from the reference (SURVEY §2.3 lists DP + manual model
parallelism only); the TPU-native pattern is an ``ep`` mesh axis holding
one expert per device, with `all_to_all` shuffling token capacity
buffers device->expert and back — the Switch-Transformer dispatch
expressed as XLA collectives over ICI.

``moe_apply`` is differentiable; overflow beyond per-expert capacity is
dropped (standard top-1 capacity semantics) and the combine weights
carry the router probability so the gate learns.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

__all__ = ["moe_apply"]


def moe_apply(expert_fn, expert_params, x, gate_w, axis_name="ep",
              mesh=None, capacity_factor=1.0):
    """Top-1 routed mixture of experts.

    expert_fn(params_e, x) -> y : one expert's computation ((tokens, D)
        in and out).
    expert_params : pytree, leaves with leading dim E (expert e's
        weights live on device e of *axis_name*).
    x : (B, D) tokens, sharded over *axis_name* on dim 0.
    gate_w : (D, E) router weights (replicated).
    Returns (B, D) with each token processed by its chosen expert,
    scaled by the router probability (zeros for dropped tokens).
    """

    def shard_fn(params, xs, gw):
        from ..ops.nn import top1_route
        params = jax.tree.map(lambda a: a[0], params)
        from .collectives import axis_size
        e = axis_size(axis_name)
        nloc, d = xs.shape
        cap = max(1, int(capacity_factor * nloc / e))
        _, gate, expert_idx, slot, keep = top1_route(xs, gw, cap)
        # dispatch buffer: (E, cap, D) of this device's tokens, plus a
        # filled-slot mask that travels with it
        disp = jnp.zeros((e, cap, d), xs.dtype)
        disp = disp.at[expert_idx, jnp.clip(slot, 0, cap - 1)].add(
            xs * keep[:, None])
        filled = jnp.zeros((e, cap), xs.dtype)
        filled = filled.at[expert_idx, jnp.clip(slot, 0, cap - 1)].add(
            keep.astype(xs.dtype))
        # all_to_all: dim0 (expert) scatters, gathers peer dim ->
        # (E_peers, cap, D) buffers destined for MY expert
        recv = jax.lax.all_to_all(disp, axis_name, split_axis=0,
                                  concat_axis=0, tiled=True)
        rmask = jax.lax.all_to_all(filled[..., None], axis_name,
                                   split_axis=0, concat_axis=0,
                                   tiled=True)
        rmask = rmask.reshape(e * cap, 1)
        # double-where: padding slots must not evaluate expert_fn on
        # zeros (NaN Jacobians of normalization-style experts would
        # poison the gradient) and must come back as exact zeros
        flat = recv.reshape(e * cap, d)
        safe = jnp.where(rmask > 0, flat, jnp.ones_like(flat))
        out = jnp.where(rmask > 0, expert_fn(params, safe), 0.0)
        out = out.reshape(e, cap, d)
        back = jax.lax.all_to_all(out, axis_name, split_axis=0,
                                  concat_axis=0, tiled=True)
        # un-dispatch: token i reads (expert_idx[i], slot[i])
        y = back[expert_idx, jnp.clip(slot, 0, cap - 1)]
        return y * (gate * keep)[:, None]

    if mesh is not None:
        param_specs = jax.tree.map(lambda _: P(axis_name), expert_params)
        return shard_map(shard_fn, mesh=mesh,
                         in_specs=(param_specs, P(axis_name), P()),
                         out_specs=P(axis_name), check_vma=False)(
            expert_params, x, gate_w)
    return shard_fn(expert_params, x, gate_w)
