"""Pipeline parallelism over a mesh axis (GPipe-style loop skew).

The reference's only inter-device model splitting is manual `group2ctx`
placement (SURVEY §2.3); the TPU-native generalisation is a pipeline
axis: stage i's weights live on device i of the ``pp`` axis, microbatches
stream through with `ppermute` passing activations stage-to-stage, and
the whole schedule is one `lax.scan` inside `shard_map` — XLA overlaps
the per-tick compute with the neighbor transfer.

``pipeline_apply`` is differentiable (scan + ppermute have VJPs), so a
training step can `jax.grad` straight through the pipeline.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from .collectives import ppermute_ring

__all__ = ["pipeline_apply"]


def pipeline_apply(stage_fn, stage_params, x_micro, axis_name="pp",
                   mesh=None, x_spec=None):
    """Run S pipeline stages over microbatches.

    stage_fn(params_i, x) -> y : one stage's computation (same shape in
        and out across stages, the usual transformer-block case).
    stage_params : pytree whose leaves have leading dim S — leaf i is
        stage i's weights (sharded over *axis_name*).
    x_micro : (M, B, ...) microbatched input (replicated, or laid out
        per *x_spec* — e.g. P(None, "dp") composes the pipeline with a
        data-parallel batch axis; outputs keep the same layout).
    Returns (M, B, ...) outputs of the final stage.

    Schedule: T = M + S - 1 ticks of [receive from left neighbor ->
    compute my stage -> emit right] with the classic skew: stage s works
    on microbatch t - s at tick t; devices idle in the ramp-up/down
    bubble compute zeros (masked out of the result).
    """

    def shard_fn(params, xm):
        # params leaves arrive with leading dim 1 (this stage's slice)
        params = jax.tree.map(lambda a: a[0], params)
        s = jax.lax.axis_index(axis_name)
        from .collectives import axis_size
        n_stage = axis_size(axis_name)
        m = xm.shape[0]
        ticks = m + n_stage - 1
        out_shape = xm.shape[1:]

        def tick(carry, t):
            prev_out, outputs = carry
            # activation entering this stage this tick
            recv = ppermute_ring(prev_out, axis_name)
            mb_idx = jnp.clip(t, 0, m - 1)
            first = jnp.where(t < m, xm[mb_idx],
                              jnp.zeros(out_shape, xm.dtype))
            inp = jnp.where(s == 0, first, recv)
            # bubble ticks (stage s idle: t - s outside [0, m)) must not
            # evaluate stage_fn on garbage — a fn whose Jacobian is
            # non-finite at zeros (normalization layers) would leak NaN
            # into the scan transpose.  Double-where: feed a safe dummy
            # input on bubble ticks and zero the result.
            working = (t - s >= 0) & (t - s < m)
            safe_inp = jnp.where(working, inp,
                                 jnp.ones(out_shape, xm.dtype))
            out = jnp.where(working, stage_fn(params, safe_inp), 0.0)
            # last stage collects microbatch t - (S-1) at tick t
            coll_idx = t - (n_stage - 1)
            valid = (s == n_stage - 1) & (coll_idx >= 0)
            outputs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, out, jnp.maximum(coll_idx, 0), 0),
                lambda o: o, outputs)
            return (out, outputs), None

        init_out = jnp.zeros(out_shape, xm.dtype)
        outputs0 = jnp.zeros((m,) + out_shape, xm.dtype)
        (_, outputs), _ = jax.lax.scan(tick, (init_out, outputs0),
                                       jnp.arange(ticks))
        # every device carries the buffer; only the last stage filled it —
        # broadcast it back so the result is replicated
        outputs = jax.lax.psum(
            jnp.where(s == n_stage - 1, outputs, 0.0), axis_name)
        return outputs

    if mesh is not None:
        param_specs = jax.tree.map(lambda _: P(axis_name), stage_params)
        xs = P() if x_spec is None else x_spec
        return shard_map(shard_fn, mesh=mesh,
                         in_specs=(param_specs, xs),
                         out_specs=xs, check_vma=False)(
            stage_params, x_micro)
    return shard_fn(stage_params, x_micro)
