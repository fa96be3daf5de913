"""In-graph data-parallel training — the ``kvstore='tpu'`` execution path.

The reference's data parallelism is host-orchestrated: per-GPU executors,
then KVStore push/pull moves gradients through NCCL/ps-lite
(SURVEY.md §2.3).  The TPU-native equivalent inverts this: the WHOLE
training step — forward, backward, gradient all-reduce, fused optimizer
update — is one pjit-compiled SPMD program over a `jax.sharding.Mesh`.
Parameters/optimizer state are replicated (or dp-sharded, ZeRO-style, with
``shard_params=True``); the batch is sharded over the ``dp`` axis; XLA's
SPMD partitioner inserts the psum over ICI where the gradients meet the
replicated parameters.  Buffer donation makes updates in-place in HBM.

Supports every fused update op in ops/optimizer_ops.py, bf16
multi-precision training (bf16 compute weights + f32 master copies via
the mp_sgd ops' scheme — reference optimizer_op.cc mp_sgd), and
LARS/LBSGD layer-wise adaptive rates (reference optimizer.py:678) — the
ResNet-50 north-star configuration.

This is what the benchmark's training cells (`benchmarks/run.py`) and
`__graft_entry__.dryrun_multichip` run, and what Gluon's Trainer uses
when constructed with ``kvstore='tpu'``.
"""

from __future__ import annotations

import collections
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import make_mesh, use_mesh
from .. import profiler as _prof
from ..ndarray import NDArray
from ..observability import metrics as _obs_metrics

__all__ = ["ParallelTrainer"]

# module-level instrument ref (hot path: consulted per fit_batch) —
# same registry instrument the ndarray/executor placement paths bump
_DEVICE_PUT_ELIDED = _obs_metrics.counter(
    "device_put_elided_total",
    "host->device transfers skipped because the array was already "
    "committed to its target device/sharding (device-resident input)")


# optimizer name -> (update op, number of zero-init states).
# State layout convention of the fused ops: fn(weight, grad, *states,
# **hyper) -> (new_weight, *new_states).
_OPT_OPS = {
    "sgd": ("sgd_update", 0),
    "sgd_mom": ("sgd_mom_update", 1),
    "nag": ("nag_mom_update", 1),
    "adam": ("adam_update", 2),
    "rmsprop": ("rmsprop_update", 1),
    "rmspropalex": ("rmspropalex_update", 3),
    "ftrl": ("ftrl_update", 2),
    "ftml": ("ftml_update", 3),
    "signum": ("signum_update", 1),
    "signsgd": ("signsgd_update", 0),
    "adadelta": ("adadelta_update", 2),
    "adamax": ("adamax_update", 2),
    "nadam": ("nadam_update", 2),
}

# LARS-family: layer-wise trust ratio scaling wrapped around momentum sgd
_LARS_NAMES = ("lars", "lbsgd")


class ParallelTrainer:
    """Compile a Gluon HybridBlock + loss + optimizer into one sharded
    train step.

    Parameters
    ----------
    net : HybridBlock (traced symbolically, like hybridize)
    loss : gluon loss HybridBlock
    optimizer : any name in ops/optimizer_ops.py ('sgd', 'adam',
        'rmsprop', ...) or 'lars'/'lbsgd'; momentum>0 upgrades sgd to
        the momentum kernel
    mesh : jax Mesh (default: all devices on one 'dp' axis)
    shard_params : ZeRO-1-style dp-sharding of params + optimizer state
    multi_precision : train with bf16 compute weights + f32 master
        copies (bf16 batches, f32 loss/update math)
    grad_clip : optional global-norm clip
    """

    def __init__(self, net, loss, optimizer="sgd", optimizer_params=None,
                 mesh=None, shard_params=False, grad_clip=None,
                 multi_precision=False, remat=None, coalesce_small=None,
                 param_specs=None):
        self.net = net
        self.loss = loss
        self.mesh = mesh or make_mesh()
        self.opt_name = optimizer
        self.opt_params = dict(optimizer_params or {})
        self.shard_params = shard_params
        from .multihost import is_multihost_mesh
        self._multihost = is_multihost_mesh(self.mesh)
        if shard_params and self._multihost:
            raise NotImplementedError(
                "shard_params (ZeRO) over a multi-host mesh needs "
                "host-local shard feeding; use replicated params")
        if self._multihost:
            # the host-local batch contract assumes processes partition
            # the mesh ALONG dp: every device's owning process must be
            # a function of its dp coordinate alone (frozen-state
            # scaling and host_local_to_global both build on it)
            import numpy as _onp
            names = list(self.mesh.axis_names)
            if "dp" not in names:
                raise NotImplementedError(
                    "a multi-host mesh needs a 'dp' axis spanning the "
                    "processes (got axes %s)" % names)
            dp_axis = names.index("dp")
            owner_of_dp = {}
            for idx, dev in _onp.ndenumerate(self.mesh.devices):
                prev = owner_of_dp.setdefault(idx[dp_axis],
                                              dev.process_index)
                if prev != dev.process_index:
                    raise NotImplementedError(
                        "multi-host meshes must span processes along "
                        "the dp axis only (dp index %d maps to "
                        "processes %d and %d)"
                        % (idx[dp_axis], prev, dev.process_index))
        self.grad_clip = grad_clip
        self.multi_precision = multi_precision
        # coalesce_small: apply the optimizer (and the LARS trust-ratio
        # norms) to all SMALL parameters — BN scales/biases and the like
        # — as one fused flat-buffer computation instead of hundreds of
        # tiny per-tensor kernels.  A ResNet-50 LARS step otherwise pays
        # ~2 norm reductions + an update kernel for each of ~110 tiny
        # tensors, pure kernel-launch overhead on TPU.  Default: on for
        # the LARS family with the (mp_)sgd kernels (the north-star
        # config); only supported for those kernels and for replicated
        # (non-ZeRO) parameter layouts.
        self.coalesce_small = coalesce_small
        # param_specs: tensor parallelism at the trainer level — a dict
        # mapping a parameter-name regex to the PartitionSpec its
        # weight (and optimizer state) lives at, e.g. a megatron MLP:
        #   {r"fc1.*weight": P("tp", None),   # column-parallel
        #    r"fc2.*weight": P(None, "tp")}   # row-parallel
        # First match wins; unmatched params follow the replicated /
        # ZeRO-dp default.  XLA's SPMD partitioner closes the tp
        # collectives inside the compiled step.
        self.param_specs = dict(param_specs or {})
        # rematerialization policy for the fwd activations kept for
        # backward: None (XLA decides), 'full' (recompute everything —
        # min HBM), 'dots' (save matmul/conv outputs only, recompute the
        # cheap elementwise chains — the usual sweet spot), or any
        # jax.checkpoint policy callable
        self.remat = remat
        self._step_fn = None
        self._step_called = False
        self._eval_fn = None
        self._params = None          # name -> jax array (device, sharded)
        self._opt_state = None
        self._aux = None
        self._graph = None
        self._num_update = 0
        # counts the step hands out, not yet folded into counters
        self._stats_pending = collections.deque()

    # -- tracing -----------------------------------------------------------
    def _trace(self, x, y):
        from .. import symbol as sym_mod
        from ..executor import _build_eval
        data = sym_mod.var("data0")
        label = sym_mod.var("label0")
        out = self.net(data)
        loss_sym = self.loss(out, label)
        self._graph = loss_sym
        self._eval = _build_eval(loss_sym, True)
        self._eval_infer = _build_eval(loss_sym, False)
        out_syms = out if isinstance(out, sym_mod.Symbol) else out[0]
        self._fwd_eval = _build_eval(out_syms, False)
        args = loss_sym.list_arguments()
        self.param_names = [a for a in args if a not in ("data0", "label0")]
        self.aux_names = loss_sym.list_auxiliary_states()

    def _resolve_opt(self):
        from ..ops.registry import get_op
        name = self.opt_name
        self._lars = name in _LARS_NAMES
        if self._lars:
            name = "sgd"
        if name == "sgd" and self.opt_params.get("momentum", 0):
            name = "sgd_mom"
        if name not in _OPT_OPS:
            raise ValueError(
                "optimizer %r not supported by ParallelTrainer; one of %s"
                % (self.opt_name, sorted(_OPT_OPS) + list(_LARS_NAMES)))
        base_op, n_states = _OPT_OPS[name]
        self._opt_base = name
        if self.multi_precision:
            if name not in ("sgd", "sgd_mom"):
                raise ValueError(
                    "multi_precision needs the mp_sgd update kernels; "
                    "use optimizer='sgd'/'lars'/'lbsgd' (got %r)"
                    % self.opt_name)
            base_op = "mp_" + base_op
        self._opt_op = get_op(base_op)
        self._opt_n_states = n_states

    def _gather_state(self, data_shape=None, label_shape=None):
        params = {p.name: p for p in self.net.collect_params().values()}
        repl = NamedSharding(self.mesh, P())
        self._resolve_opt()
        # graph arguments with no backing Parameter (e.g. the fused RNN
        # op's auto-created begin-state vars) are zero-filled constant
        # inputs, exactly like simple_bind's unbound-arg semantics —
        # they get no optimizer state and pass through the step frozen
        self._frozen = frozenset(
            n for n in self.param_names if n not in params)
        frozen_arrays = {}
        if self._frozen:
            frozen_arrays = self._infer_frozen(data_shape, label_shape)
            self._frozen_built_for = (tuple(data_shape or ()),
                                      tuple(label_shape or ()))
        self._params = {}
        self._opt_state = {}
        for n in self.param_names:
            if n in self._frozen:
                self._params[n] = self._put(frozen_arrays[n], P())
                self._opt_state[n] = ()
                continue
            arr, states = self._state_for_array(params[n].data()._data)
            self._params[n] = self._put(arr, self._spec_for(arr, n))
            self._opt_state[n] = tuple(
                self._put(s, self._spec_for(s, n)) for s in states)
        self._aux = {n: self._put(params[n].data()._data, P())
                     for n in self.aux_names}

    def _state_for_array(self, arr):
        """(stored array, fresh optimizer states) for one parameter,
        honoring multi_precision (bf16 compute + f32 master copy)."""
        if self.multi_precision:
            master = arr.astype(jnp.float32)
            arr = arr.astype(jnp.bfloat16)
            # f32 states + trailing f32 master copy (mp op signature:
            # ..., mom, weight32)
            states = [jnp.zeros_like(master)
                      for _ in range(self._opt_n_states)]
            states.append(master)
        else:
            # states match the stored weight dtype so fused updates
            # neither promote nor retrace
            states = [jnp.zeros_like(arr)
                      for _ in range(self._opt_n_states)]
        return arr, states

    def _infer_frozen(self, data_shape, label_shape):
        """Zero arrays for the frozen (non-Parameter) graph args at the
        shapes inference yields for this batch geometry."""
        params = {p.name: p for p in self.net.collect_params().values()}
        cdtype = jnp.bfloat16 if self.multi_precision else None

        def _global(shape):
            # callers pass HOST-LOCAL batch shapes; the compiled step
            # sees the global batch (rows concatenated across hosts)
            if shape is None or not self._multihost:
                return shape
            shape = tuple(shape)
            import jax as _jax
            return (shape[0] * _jax.process_count(),) + shape[1:]

        shapes = {}
        data_shape = _global(data_shape)
        label_shape = _global(label_shape)
        if data_shape is not None:
            shapes["data0"] = tuple(data_shape)
        if label_shape is not None:
            shapes["label0"] = tuple(label_shape)
        # every materialized Parameter shape is a known — only the
        # frozen args are left for inference to solve
        for pname, p in params.items():
            shp = getattr(p, "shape", None)
            if pname in self.param_names and shp and \
                    all(int(s) > 0 for s in shp):
                shapes[pname] = tuple(int(s) for s in shp)
        arg_shapes, _, _ = self._graph.infer_shape(**shapes)
        inferred = dict(zip(self._graph.list_arguments(), arg_shapes))
        return {n: jnp.zeros(inferred[n], cdtype or jnp.float32)
                for n in self._frozen}

    def _refresh_frozen(self, x_shape, y_shape=None):
        """Frozen begin-states are shaped by the batch geometry; a new
        batch size means new zeros (the step retraces anyway).  With no
        label (predict), the label shape is derived from the stored one
        at the new batch size."""
        if not self._frozen:
            return
        if y_shape is None:
            tail = self._frozen_built_for[1][1:]
            y_shape = (tuple(x_shape)[0],) + tuple(tail)
        key = (tuple(x_shape), tuple(y_shape))
        if key == self._frozen_built_for:
            return
        for n, z in self._infer_frozen(x_shape, y_shape).items():
            self._params[n] = self._put(z, P())
        self._frozen_built_for = key

    def _put(self, arr, spec):
        """Place an array at (mesh, spec).  On a mesh spanning several
        processes, device_put cannot move bytes across hosts — instead
        every process contributes its local copy/shard
        (multihost_utils), which is the SPMD contract: replicated
        values must already be identical on every host (same init
        seed), sharded values must be the host-local rows."""
        if self._mesh_is_multihost():
            from .multihost import host_local_to_global
            return host_local_to_global(jnp.asarray(arr), self.mesh,
                                        spec)
        return jax.device_put(arr, NamedSharding(self.mesh, spec))

    def _spec_for(self, arr, name=None):
        if name is not None:
            for pat, spec in self.param_specs.items():
                if re.search(pat, name):
                    return spec
        ndp = self.mesh.shape.get("dp", 1)
        if self.shard_params and arr.ndim >= 1 and \
                arr.shape[0] % ndp == 0 and arr.shape[0] >= ndp:
            return P("dp")
        return P()

    def _shard_for(self, arr, name=None):
        return NamedSharding(self.mesh, self._spec_for(arr, name))

    # -- compiled step -----------------------------------------------------
    def _build_step(self):
        eval_fn = self._eval
        opt_op = self._opt_op
        opt_hp = {k: v for k, v in self.opt_params.items()
                  if k in opt_op.param_names and k not in ("lr", "t")}
        grad_clip = self.grad_clip
        lars = self._lars
        lars_eta = float(self.opt_params.get("eta", 0.001))
        lars_eps = float(self.opt_params.get("epsilon", 1e-9))
        wd = float(self.opt_params.get("wd", 0.0))
        mp = self.multi_precision

        # -- coalesced small-parameter apply (see __init__ docstring) --
        import numpy as onp
        coalesce = self.coalesce_small
        if coalesce is None:
            coalesce = lars
        supported = (not self.shard_params
                     and self._opt_base in ("sgd", "sgd_mom"))
        if self.coalesce_small and not supported:
            raise ValueError(
                "coalesce_small=True requires an (mp_)sgd[_mom] optimizer "
                "and shard_params=False (got optimizer base %r, "
                "shard_params=%r); drop the flag to use the per-tensor "
                "apply path" % (self._opt_base, self.shard_params))
        coalesce = coalesce and supported
        small = []
        if coalesce:
            _SMALL_MAX = 8192
            small = [n for n in self.param_names
                     if n not in self._frozen
                     and self._params[n].size <= _SMALL_MAX
                     and not any(re.search(p, n)
                                 for p in self.param_specs)]
            coalesce = len(small) >= 2
        if coalesce:
            small_set = frozenset(small)
            c_shapes = [self._params[n].shape for n in small]
            c_sizes = onp.array([max(1, int(onp.prod(s)))
                                 for s in c_shapes])
            # pad each tensor to the 128-lane boundary so the chunked
            # row sums below never mix two parameters in one chunk
            c_psz = ((c_sizes + 127) // 128) * 128
            c_offs = onp.concatenate(([0], onp.cumsum(c_psz)))[:-1]
            c_total = int(c_psz.sum())
            # chunk -> parameter one-hot selector: per-parameter squared
            # sums become ONE (n_small, n_chunks) f32 matmul over the
            # chunk partials instead of n_small tiny reductions
            c_seg = onp.repeat(onp.arange(len(small)), c_psz // 128)
            c_sel = onp.zeros((len(small), c_total // 128), onp.float32)
            c_sel[c_seg, onp.arange(c_total // 128)] = 1.0
            c_sel = jnp.asarray(c_sel)
            c_mom = float(self.opt_params.get("momentum", 0.0))
            c_rescale = float(self.opt_params.get("rescale_grad", 1.0))
            c_clip = float(self.opt_params.get("clip_gradient", -1.0))
            c_has_mom = self._opt_base == "sgd_mom"

            def _apply_small(params, grads, opt_state, lr):
                def flat(pieces):
                    return jnp.concatenate([
                        jnp.pad(p.reshape(-1).astype(jnp.float32),
                                (0, int(ps - sz)))
                        for p, sz, ps in zip(pieces, c_sizes, c_psz)])
                w32f = flat([opt_state[n][-1] if mp else params[n]
                             for n in small])
                gf = flat([grads[n] for n in small])
                if lars:
                    # the per-tensor path computes these norms with
                    # jnp.sum (f32 regardless of matmul precision), so
                    # this contraction is pinned to HIGHEST outright —
                    # not via matmul_precision(), whose env override
                    # would silently de-sync the two paths
                    prec = jax.lax.Precision.HIGHEST
                    wsq = jnp.matmul(
                        c_sel, jnp.sum(w32f.reshape(-1, 128) ** 2, axis=1),
                        precision=prec)
                    gsq = jnp.matmul(
                        c_sel, jnp.sum(gf.reshape(-1, 128) ** 2, axis=1),
                        precision=prec)
                    wnorm = jnp.sqrt(wsq)
                    gnorm = jnp.sqrt(gsq)
                    trust = jnp.where(
                        (wnorm > 0) & (gnorm > 0),
                        lars_eta * wnorm / (gnorm + wd * wnorm +
                                            lars_eps),
                        1.0)
                    lr_elem = jnp.repeat(lr * trust, c_psz,
                                         total_repeat_length=c_total)
                else:
                    lr_elem = lr
                # exact (mp_)sgd[_mom] update math on the flat buffer
                # (ops/optimizer_ops.py _rescale_clip order: rescale ->
                # clip -> + wd*w32)
                g = gf * c_rescale
                if c_clip >= 0:
                    g = jnp.clip(g, -c_clip, c_clip)
                g = g + wd * w32f
                if c_has_mom:
                    momf = flat([opt_state[n][0] for n in small])
                    momf = c_mom * momf - lr_elem * g
                    w32f = w32f + momf
                else:
                    w32f = w32f - lr_elem * g
                out_p, out_s = {}, {}
                for i, n in enumerate(small):
                    o, sz = int(c_offs[i]), int(c_sizes[i])
                    w32n = w32f[o:o + sz].reshape(c_shapes[i])
                    out_p[n] = w32n.astype(params[n].dtype)
                    st = []
                    if c_has_mom:
                        st.append(momf[o:o + sz].reshape(c_shapes[i]))
                    if mp:
                        st.append(w32n)
                    out_s[n] = tuple(st)
                return out_p, out_s
        else:
            small_set = frozenset()
            _apply_small = None

        frozen = self._frozen
        remat = self.remat
        if remat is not None:
            policy = None
            if remat == "dots":
                policy = jax.checkpoint_policies \
                    .dots_with_no_batch_dims_saveable
            elif callable(remat):
                policy = remat
            elif remat != "full":
                raise ValueError("remat must be None, 'full', 'dots' or "
                                 "a jax.checkpoint policy")

        def train_step(params, opt_state, aux, x, y, key, lr, t):
            # trace-time only — the compile counter for the sharded step
            # (cached executions bump nothing; see profiler.py counters)
            _prof.bump_counter(  # graftlint: disable=JG003
                "parallel_step_compiles")  # trace-time-only on purpose

            def loss_of(p):
                amap = dict(p)
                amap["data0"] = x
                amap["label0"] = y
                # what the ops count inside the step (routed tokens an
                # expert) leaves beside the loss: profiler "statistics
                # that leave a compiled step"
                with _prof.collect_step_stats() as stats:
                    outs, auxu = eval_fn(amap, aux, key)
                stats = {k: jnp.stack(v) for k, v in stats.items()}
                return jnp.mean(outs[0].astype(jnp.float32)), (auxu, stats)

            if remat is not None:
                loss_of = jax.checkpoint(loss_of, policy=policy)
            # device scopes (docs/observability.md "Spans"): forward and
            # backward under mx.loss, then mx.grad_clip and mx.optimizer
            with jax.named_scope("mx.loss"):
                (loss_val, (auxu, stats)), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(params)
            if grad_clip is not None:
                with jax.named_scope("mx.grad_clip"):
                    gnorm = jnp.sqrt(sum(
                        jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for n, g in grads.items() if n not in frozen))
                    scale = jnp.minimum(1.0, grad_clip / (gnorm + 1e-8))
                    grads = {
                        k: (g.astype(jnp.float32) * scale).astype(g.dtype)
                        for k, g in grads.items()}
            with jax.named_scope("mx.optimizer"):
                new_params, new_state = apply_updates(
                    params, grads, opt_state, lr, t)
            new_aux = dict(aux)
            new_aux.update(auxu)
            return new_params, new_state, new_aux, loss_val, stats

        def apply_updates(params, grads, opt_state, lr, t):
            new_params = {}
            new_state = {}
            hp = dict(opt_hp)
            if "t" in opt_op.param_names:
                hp["t"] = t
            for n, w in params.items():
                if n in frozen:
                    # zero-filled non-Parameter graph inputs (RNN
                    # begin-states): never updated
                    new_params[n] = w
                    new_state[n] = ()
                    continue
                if n in small_set:
                    continue
                g = grads[n]
                lr_n = lr
                if lars:
                    # layer-wise trust ratio (reference LBSGD:678):
                    # lr_layer = lr * eta * ||w|| / (||g|| + wd*||w||)
                    w32 = opt_state[n][-1] if mp else \
                        w.astype(jnp.float32)
                    wnorm = jnp.sqrt(jnp.sum(jnp.square(w32)))
                    gnorm = jnp.sqrt(jnp.sum(
                        jnp.square(g.astype(jnp.float32))))
                    trust = jnp.where(
                        (wnorm > 0) & (gnorm > 0),
                        lars_eta * wnorm / (gnorm + wd * wnorm +
                                            lars_eps),
                        1.0)
                    lr_n = lr * trust
                out = opt_op.fn(w, g, *opt_state[n], lr=lr_n, **hp)
                if not isinstance(out, tuple):
                    out = (out,)
                new_params[n] = out[0]
                new_state[n] = tuple(out[1:])
            if _apply_small is not None:
                sp, ss = _apply_small(params, grads, opt_state, lr)
                new_params.update(sp)
                new_state.update(ss)
            return new_params, new_state

        mesh = self.mesh

        def in_mesh(fn, program):
            # the steps trace under the trainer's mesh so ops that XLA
            # cannot partition by itself (the Mosaic attention kernels)
            # can shard_map over it.  *program* names the compiled module
            # (jit_<program> in XProf and in the scope map's op_names)
            def traced(*args):
                with use_mesh(mesh):
                    return fn(*args)
            traced.__name__ = traced.__qualname__ = program
            return traced

        repl = NamedSharding(self.mesh, P())
        batch_sh = NamedSharding(self.mesh, P("dp"))
        # frozen args always live replicated, whatever param_specs says
        param_sh = {n: self._shard_for(self._params[n],
                                       None if n in self._frozen else n)
                    for n in self._params}
        state_sh = {n: tuple(self._shard_for(
                        s, None if n in self._frozen else n)
                             for s in self._opt_state[n])
                    for n in self._opt_state}
        aux_sh = {n: repl for n in self._aux}
        self._step_fn = jax.jit(
            in_mesh(train_step, "parallel_step"),
            in_shardings=(param_sh, state_sh, aux_sh,
                          batch_sh, batch_sh, repl, None, None),
            # pin outputs to the input layout so the params/state returned
            # by step N are valid inputs for step N+1 (otherwise XLA's
            # sharding propagation may choose a different layout)
            out_shardings=(param_sh, state_sh, aux_sh, repl, repl),
            donate_argnums=(0, 1, 2))

        eval_infer = self._eval_infer
        fwd_eval = self._fwd_eval

        def eval_step(params, aux, x, y, key):
            amap = dict(params)
            amap["data0"] = x
            amap["label0"] = y
            outs, _ = eval_infer(amap, aux, key)
            return jnp.mean(outs[0].astype(jnp.float32))

        def predict_step(params, aux, x, key):
            amap = dict(params)
            amap["data0"] = x
            outs, _ = fwd_eval(amap, aux, key)
            return outs[0]

        self._eval_fn = jax.jit(
            in_mesh(eval_step, "parallel_eval"),
            in_shardings=(param_sh, aux_sh, batch_sh, batch_sh, repl))
        self._predict_fn = jax.jit(
            in_mesh(predict_step, "parallel_predict"),
            in_shardings=(param_sh, aux_sh, batch_sh, repl),
            out_shardings=batch_sh)
        self._key = jax.random.PRNGKey(0)

    def _ensure_built(self, x, y):
        if self._step_fn is None:
            # one row settles the deferred parameter shapes; the whole
            # global batch would land on the default device first
            self.net._ensure_params(NDArray(x[:1]))
            with _prof.scope("mx.trainer.trace", "setup"):
                self._trace(x, y)
            with _prof.scope("mx.trainer.gather_state", "setup"):
                self._gather_state(data_shape=x.shape,
                                   label_shape=y.shape)
            with _prof.scope("mx.trainer.build_step", "setup"):
                self._build_step()

    def _first_step(self, x, y):
        """The step program's first call: trace and lower, compile or
        load from the cache, then hand the profiler the compiled step's
        text (its scope map and its cost map, parsed when first asked
        for) and XLA's own cost analysis.  The lowering made here is the
        one the call uses, and the executable the call built is the one
        whose text is read: nothing is lowered or compiled twice.  Span
        ``mx.step.first_call``; its args say where it went: `lower_s`
        (tracing and lowering), `call_s` (compile or load, and the
        dispatch), `scope_map_s` (the text, its packing and the cost
        analysis), and what JAX reported meanwhile under each compile
        event."""
        self._step_called = True
        with _prof.scope("mx.step.first_call", "setup") as span:
            before = _prof.compile_seconds()
            args = self._step_args(x, y)
            lowered = self._step_fn.lower(*args)
            t_lowered = time.perf_counter()
            out = self._step_fn(*args)
            t_called = time.perf_counter()
            # no new program: `compile()` hands back the executable the
            # call above built from this same lowering
            compiled = lowered.compile()  # graftlint: disable=JG014
            _prof.set_scope_map("parallel_step", compiled.as_text(),
                                compiled.cost_analysis())
            del lowered, compiled
            span.args = dict(
                {k: v - before[k]
                 for k, v in _prof.compile_seconds().items()},
                lower_s=t_lowered - span.start,
                call_s=t_called - t_lowered,
                scope_map_s=time.perf_counter() - t_called)
        return out

    def _device_batch(self, x):
        if isinstance(x, NDArray):
            x = x._data
        if self.multi_precision and jnp.issubdtype(x.dtype,
                                                   jnp.floating):
            x = x.astype(jnp.bfloat16)
        sh = NamedSharding(self.mesh, P("dp"))
        # already resident with the right layout (a DevicePrefetcher
        # ring batch, or the caller reusing an array a previous step
        # produced) — skip the transfer (counted, see
        # docs/perf_input_pipeline.md)
        if isinstance(x, jax.Array) and getattr(x, "sharding", None) == sh:
            _DEVICE_PUT_ELIDED.inc()
            return x
        # on a multihost mesh each process feeds only ITS rows and
        # _put assembles the global batch (multihost feeding contract)
        return self._put(x, P("dp"))

    def _mesh_is_multihost(self):
        return self._multihost

    def _label_batch(self, y):
        if isinstance(y, NDArray):
            y = y._data
        sh = NamedSharding(self.mesh, P("dp"))
        if isinstance(y, jax.Array) and getattr(y, "sharding", None) == sh:
            _DEVICE_PUT_ELIDED.inc()
            return y
        return self._put(y, P("dp"))

    def fit(self, train_data, num_epoch=1, checkpoint_prefix=None,
            batch_end_callback=None, logger=None, device_prefetch=None):
        """Epoch/batch loop over a ``DataIter`` — the trainer-level
        peer of ``Module.fit``, with the SAME batch-boundary
        resilience contract: a preemption request (SIGTERM flag,
        ``chaos.preempt_at_batch``) finishes the in-flight batch,
        writes a full-state checkpoint (params + optimizer state +
        aux + update counter, when *checkpoint_prefix* is given) and
        returns cleanly; every batch ticks the supervisor heartbeat.
        Returns the last batch's loss per epoch.

        ``device_prefetch=K`` (or ``MXNET_DEVICE_PREFETCH``) wraps
        *train_data* in a ``DevicePrefetcher`` bound to this trainer's
        MESH: batches arrive as ``NamedSharding(mesh, P('dp'))``
        arrays, so ``fit_batch``'s ``_device_batch`` skips its
        transfer entirely (docs/perf_input_pipeline.md)."""
        from ..io.device_prefetch import maybe_wrap
        # on a multi-host mesh device_put cannot place a global batch
        # (host_local_to_global owns that path in _device_batch) — the
        # wrap degrades to host-side decode overlap so batches reach
        # _device_batch unplaced and its multihost path runs once, not
        # after a wasted single-device transfer
        train_data, created_prefetcher = maybe_wrap(
            train_data, device_prefetch, mesh=self.mesh,
            decode_only=self._multihost)
        try:
            return self._fit_loop(train_data, num_epoch,
                                  checkpoint_prefix, batch_end_callback,
                                  logger)
        finally:
            if created_prefetcher:
                train_data.close()

    def _fit_loop(self, train_data, num_epoch, checkpoint_prefix,
                  batch_end_callback, logger):
        import logging as _logging
        from .. import resilience
        from ..resilience import supervisor as _sup
        log = logger or _logging.getLogger(__name__)
        losses = []
        for epoch in range(num_epoch):
            loss = None
            for nbatch, batch in enumerate(train_data):
                loss = self.fit_batch(batch.data[0], batch.label[0])
                if batch_end_callback is not None:
                    batch_end_callback(epoch, nbatch, loss)
                _sup.heartbeat()
                if resilience.preemption_requested(tick=True):
                    from ..observability import events as _obs_events
                    _obs_events.emit(
                        "preempt", epoch=epoch, batch=nbatch,
                        trainer="ParallelTrainer",
                        checkpointing=checkpoint_prefix is not None)
                    log.warning(
                        "preemption requested: checkpointing after "
                        "epoch %d batch %d and exiting ParallelTrainer"
                        ".fit", epoch, nbatch)
                    if checkpoint_prefix is not None:
                        self.save_checkpoint(checkpoint_prefix, epoch)
                    resilience.clear_preemption()
                    return losses
            losses.append(loss)
            if checkpoint_prefix is not None:
                self.save_checkpoint(checkpoint_prefix, epoch)
            train_data.reset()
        return losses

    def fit_batch(self, x, y):
        """Run one training step; returns the (replicated) mean loss."""
        if isinstance(x, NDArray):
            x = x._data
        if isinstance(y, NDArray):
            y = y._data
        from ..resilience import chaos
        # span mx.fit_batch: its self time is the trainer's own
        # bookkeeping.  Its one child holds everything handed to the
        # device (the batch's cast and placement, the key split, lr and t,
        # the step): whichever of them comes first waits when the
        # device's queue is full, and on the chip that was not the step
        # (PERF.md, PR 24)
        with _prof.scope("mx.fit_batch", "trainer"):
            chaos.on_train_step(self._num_update)
            self._ensure_built(x, y)
            self._refresh_frozen(x.shape, y.shape)
            _prof.bump_counter("parallel_step_dispatches")
            if self._step_called:
                with _prof.scope("mx.fit_batch.dispatch", "trainer"):
                    out = self._step_fn(*self._step_args(x, y))
            else:
                out = self._first_step(x, y)
            self._params, self._opt_state, self._aux, loss, stats = out
            self._num_update += 1
            if stats or self._stats_pending:
                self._keep_step_stats(stats)
        return loss

    def _keep_step_stats(self, stats):
        """Start *stats* (the step's counts, small device arrays) on
        their way to the host, and fold into the counters those of
        earlier steps that have arrived: no wait, no dispatch."""
        if stats:
            for a in stats.values():
                a.copy_to_host_async()
            self._stats_pending.append(stats)
        while self._stats_pending and all(
                a.is_ready() for a in self._stats_pending[0].values()):
            self._fold_oldest_stats()

    def _fold_oldest_stats(self):
        _prof.fold_step_stats(
            {k: np.asarray(a)
             for k, a in self._stats_pending.popleft().items()})

    def flush_step_stats(self):
        """Wait for the counts of every step so far and fold them."""
        while self._stats_pending:
            self._fold_oldest_stats()

    def _step_args(self, x, y):
        xd = self._device_batch(x)
        yd = self._label_batch(y)
        self._key, sub = jax.random.split(self._key)
        lr = jnp.asarray(self._current_lr(), jnp.float32)
        t = jnp.asarray(self._num_update + 1, jnp.int32)
        return (self._params, self._opt_state, self._aux, xd, yd, sub,
                lr, t)

    def _current_lr(self):
        sched = self.opt_params.get("lr_scheduler")
        if sched is not None:
            return float(sched(self._num_update))
        return float(self.opt_params.get("learning_rate", 0.01))

    def evaluate_batch(self, x, y):
        """Mean loss over one batch, inference mode (no aux updates)."""
        if isinstance(x, NDArray):
            x = x._data
        if isinstance(y, NDArray):
            y = y._data
        self._ensure_built(x, y)
        self._refresh_frozen(x.shape, y.shape)
        xd = self._device_batch(x)
        yd = self._label_batch(y)
        return self._eval_fn(self._params, self._aux, xd, yd,
                             jax.random.PRNGKey(0))

    def predict_batch(self, x):
        """Network outputs for one batch, inference mode."""
        if isinstance(x, NDArray):
            x = x._data
        if self._step_fn is None:
            raise RuntimeError("run fit_batch or evaluate_batch first")
        self._refresh_frozen(x.shape)
        xd = self._device_batch(x)
        out = self._predict_fn(self._params, self._aux, xd,
                               jax.random.PRNGKey(0))
        if self._multihost:
            # hand each process back ITS rows (the dp-sharded global
            # output is not locally addressable)
            from .multihost import global_to_host_local
            out = global_to_host_local(out, self.mesh, P("dp"))
        return NDArray(out)

    # -- checkpoint / resume -------------------------------------------------
    def save_checkpoint(self, prefix, epoch=0):
        """Write the FULL training state — params, optimizer state, aux
        (BN stats), update counter — in the framework checkpoint
        container (reference shape: Module.save_checkpoint +
        Trainer.save_states, fused into one file pair here because the
        compiled step owns all three).  Returns the params path."""
        import numpy as _np
        from .. import ndarray as _nd
        blob = {}
        # iterate param_names (graph topological order), NOT the state
        # dicts: jitted steps return dicts with SORTED keys, and
        # alphabetical order is not stable across name-counter suffixes
        # (dense10 < dense9) — the load-side positional remap depends on
        # structural order
        for n in self.param_names:
            blob["arg:%s" % n] = _nd.NDArray(self._params[n])
            for i, s in enumerate(self._opt_state[n]):
                blob["opt%d:%s" % (i, n)] = _nd.NDArray(s)
        for n in self.aux_names:
            blob["aux:%s" % n] = _nd.NDArray(self._aux[n])
        blob["meta:num_update"] = _nd.array(
            _np.asarray([self._num_update], _np.int64))
        path = "%s-%04d.params" % (prefix, epoch)
        _nd.save(path, blob)
        return path

    def load_checkpoint(self, prefix, epoch=0):
        """Restore state written by :meth:`save_checkpoint`; the trainer
        must already be built (same model/optimizer config)."""
        from .. import ndarray as _nd
        if self._step_fn is None:
            raise RuntimeError("build the trainer first (run one "
                               "fit_batch) before loading a checkpoint")
        loaded = _nd.load("%s-%04d.params" % (prefix, epoch))
        params, opt, aux = {}, {}, {}
        num_update = self._num_update
        for k, v in loaded.items():
            kind, name = k.split(":", 1)
            if kind == "arg":
                params[name] = v._data
            elif kind.startswith("opt"):
                opt.setdefault(name, {})[int(kind[3:])] = v._data
            elif kind == "aux":
                aux[name] = v._data
            elif k == "meta:num_update":
                num_update = int(v.asnumpy()[0])
        if set(params) != set(self._params):
            # same architecture under different auto-generated name
            # counters (e.g. several nets built in one process): map by
            # construction order, which both the save and param_names
            # preserve, and verify shapes before accepting
            if len(params) != len(self._params) or \
                    len(aux) != len(self._aux):
                raise ValueError(
                    "checkpoint has %d params / %d aux, trainer has "
                    "%d / %d" % (len(params), len(aux),
                                 len(self._params), len(self._aux)))
            # both sides in structural order: the checkpoint was written
            # in its trainer's param_names order (see save_checkpoint),
            # and this trainer's param_names is the same topological
            # order for the same architecture
            remap = dict(zip(params, self.param_names))
            remap.update(zip(aux, self.aux_names))
            for tables, current in ((params, self._params),
                                    (aux, self._aux)):
                for old in tables:
                    new = remap[old]
                    if new in self._frozen:
                        continue  # batch-geometry zeros, not restored
                    if tuple(tables[old].shape) != \
                            tuple(current[new].shape):
                        raise ValueError(
                            "checkpoint entry %r %s does not match "
                            "trainer entry %r %s"
                            % (old, tables[old].shape, new,
                               current[new].shape))
            params = {remap[n]: a for n, a in params.items()}
            opt = {remap[n]: s for n, s in opt.items()}
            aux = {remap[n]: a for n, a in aux.items()}
        # commit atomically only after every check passed; stateless
        # optimizers (plain sgd) save no opt entries and restore to
        # empty per-param tuples.  Frozen begin-state args keep the
        # CURRENT zeros: the checkpoint may have been written at a
        # different batch size, and they are always zeros anyway.
        self._params = {
            n: (self._params[n] if n in self._frozen
                else self._put(a, self._spec_for(a, n)))
            for n, a in params.items()}
        self._opt_state = {
            n: tuple(self._put(slots[i], self._spec_for(slots[i], n))
                     for i in sorted(slots))
            for n, slots in ((n, opt.get(n, {})) for n in params)}
        self._aux = {n: self._put(a, P()) for n, a in aux.items()}
        self._num_update = num_update

    # -- sync back to gluon parameters --------------------------------------
    def sync_params(self):
        """Write the trained values back into the Block's Parameters
        (gathered to a single device so eager ops can consume them)."""
        import numpy as _np
        params = {p.name: p for p in self.net.collect_params().values()}
        for n, arr in self._params.items():
            if n in self._frozen:
                continue  # zero-filled graph inputs, no Parameter behind
            if self.multi_precision:
                arr = self._opt_state[n][-1]   # f32 master copy
            params[n].data()._data = jnp.asarray(_np.asarray(arr))
        for n, arr in self._aux.items():
            params[n].data()._data = jnp.asarray(_np.asarray(arr))

    @property
    def params(self):
        return self._params


class PipelineTrainer(ParallelTrainer):
    """GPipe pipeline parallelism as a trainer-level peer of DP/TP.

    The net must be a stack (HybridSequential-style ``_children``) of
    ARCHITECTURALLY IDENTICAL blocks — same parameter shapes per block,
    activation shape preserved (the transformer-block case,
    parallel/pipeline.py).  With S = the mesh's ``pp`` axis size and
    C = len(children) (C % S == 0), each pp device owns C/S consecutive
    blocks; per-block parameters are STACKED into (C, ...) leaves
    sharded ``P('pp')``, so weights AND optimizer state live
    stage-local, and the train step streams ``microbatches``
    microbatches through the loop-skew schedule with activations
    hopping stage-to-stage over ``ppermute``.  Composes with a dp axis:
    mesh ``{'dp': d, 'pp': s}`` shards the batch over dp while the
    pipeline runs inside each dp row.

    Everything else (optimizer kernels, LARS, grad clip, LR schedule,
    checkpointed state) is inherited from ParallelTrainer — the stacked
    leaves are ordinary named parameters to the step builder.

    Restriction: blocks with auxiliary state (BatchNorm running stats)
    are rejected — per-stage aux writeback inside the scanned schedule
    is not implemented (reference group2ctx model parallelism has the
    same limitation per placed segment).
    """

    _STACK = "pp:"

    def __init__(self, net, loss, microbatches, **kwargs):
        super().__init__(net, loss, **kwargs)
        if "pp" not in self.mesh.shape:
            raise ValueError(
                "PipelineTrainer needs a mesh with a 'pp' axis "
                "(got axes %r); make_mesh({'dp': d, 'pp': s})"
                % (tuple(self.mesh.axis_names),))
        if "dp" not in self.mesh.shape:
            raise ValueError(
                "PipelineTrainer needs a 'dp' axis for the batch "
                "layout (use {'dp': 1, 'pp': s} for pure pipeline)")
        self.microbatches = int(microbatches)
        if self.shard_params:
            raise ValueError("shard_params (ZeRO over dp) is not "
                             "supported together with the pp stack")
        if self.opt_name in _LARS_NAMES:
            # LARS trust ratios are per named parameter; a (C, ...)
            # stacked leaf would get ONE stack-wide ratio instead of
            # per-layer rates, silently diverging from the sequential
            # trainer
            raise ValueError(
                "LARS-family optimizers are not supported by "
                "PipelineTrainer (stacked block leaves would share one "
                "trust ratio); use sgd/adam/... or per-stage LARS via "
                "the sequential trainer")
        # stacked leaves shard along pp on their leading (block) axis
        self.param_specs.setdefault(r"\App:", P("pp"))

    # -- tracing ----------------------------------------------------------
    def _trace(self, x, y):
        from .. import symbol as sym_mod
        from ..executor import _build_eval
        from .pipeline import pipeline_apply

        children = list(self.net._children.values())
        S = self.mesh.shape["pp"]
        if not children or len(children) % S != 0:
            raise ValueError(
                "net has %d child blocks; need a positive multiple of "
                "the pp axis size %d" % (len(children), S))
        per_stage = len(children) // S

        # trace child 0 once; all blocks share its graph with their own
        # parameter slice
        data = sym_mod.var("data0")
        out0 = children[0](data)
        if out0.list_auxiliary_states():
            raise NotImplementedError(
                "pipeline stages with auxiliary state (BatchNorm "
                "running stats) are not supported")
        child_eval_t = _build_eval(out0, True)
        child_eval_i = _build_eval(out0, False)
        child_args = [a for a in out0.list_arguments() if a != "data0"]

        # local (prefix-stripped) name -> child-0 graph arg name
        def locals_of(block):
            pre = block.prefix
            out = {}
            for p in block.collect_params().values():
                local = p.name[len(pre):] if p.name.startswith(pre) \
                    else p.name
                out[local] = p
            return out

        child0_locals = locals_of(children[0])
        self._local_to_arg = {}
        for arg in child_args:
            pre = children[0].prefix
            local = arg[len(pre):] if arg.startswith(pre) else arg
            if local not in child0_locals:
                raise ValueError(
                    "cannot map child graph arg %r to a block "
                    "parameter" % arg)
            self._local_to_arg[local] = arg
        self._block_locals = sorted(self._local_to_arg)
        self._per_block_params = []
        for i, c in enumerate(children):
            loc = locals_of(c)
            if sorted(loc) != self._block_locals:
                raise ValueError(
                    "block %d parameters %r differ from block 0's %r — "
                    "pipeline stages must be architecturally identical"
                    % (i, sorted(loc), self._block_locals))
            self._per_block_params.append(loc)

        # loss traced on the final activation
        pred = sym_mod.var("pred0")
        label = sym_mod.var("label0")
        loss_sym = self.loss(pred, label)
        loss_eval_t = _build_eval(loss_sym, True)
        loss_eval_i = _build_eval(loss_sym, False)
        extra = [a for a in loss_sym.list_arguments()
                 if a not in ("pred0", "label0")]
        if extra or loss_sym.list_auxiliary_states():
            raise NotImplementedError(
                "parametrized losses are not supported in the pipeline "
                "trainer (loss args %r)" % extra)

        M = self.microbatches
        mesh = self.mesh
        stack = self._STACK
        local_to_arg = self._local_to_arg
        locals_sorted = self._block_locals

        def _pipe_forward(amap, key, training):
            child_eval = child_eval_t if training else child_eval_i
            x_in = amap["data0"]
            B = x_in.shape[0]
            if B % M != 0:
                raise ValueError(
                    "batch %d not divisible by microbatches %d" % (B, M))
            xm = x_in.reshape((M, B // M) + x_in.shape[1:])
            stage_params = {
                loc: amap[stack + loc].reshape(
                    (S, per_stage) + amap[stack + loc].shape[1:])
                for loc in locals_sorted}

            def stage_fn(pslice, xmb):
                # distinct randomness per (stage, sub-block); masks DO
                # repeat across microbatches of one step — a pipeline-
                # semantics caveat vs the sequential trainer
                k_stage = jax.random.fold_in(
                    key, jax.lax.axis_index("pp"))

                def body(h, scanned):
                    pj, j = scanned
                    cam = {local_to_arg[loc]: pj[loc]
                           for loc in locals_sorted}
                    cam["data0"] = h
                    outs, _ = child_eval(
                        cam, {}, jax.random.fold_in(k_stage, j))
                    return outs[0], None
                h, _ = jax.lax.scan(body, xmb,
                                    (pslice, jnp.arange(per_stage)))
                return h

            out = pipeline_apply(stage_fn, stage_params, xm,
                                 axis_name="pp", mesh=mesh,
                                 x_spec=P(None, "dp"))
            return out.reshape((B,) + out.shape[2:])

        def eval_train(amap, aux, key):
            pred_v = _pipe_forward(amap, key, True)
            louts, _ = loss_eval_t(
                {"pred0": pred_v, "label0": amap["label0"]}, {}, key)
            return [louts[0]], {}

        def eval_infer(amap, aux, key):
            pred_v = _pipe_forward(amap, key, False)
            louts, _ = loss_eval_i(
                {"pred0": pred_v, "label0": amap["label0"]}, {}, key)
            return [louts[0]], {}

        def fwd_eval(amap, aux, key):
            return [_pipe_forward(amap, key, False)], {}

        self._eval = eval_train
        self._eval_infer = eval_infer
        self._fwd_eval = fwd_eval
        self.param_names = [stack + loc for loc in locals_sorted]
        self.aux_names = []

    def _gather_state(self, data_shape=None, label_shape=None):
        self._resolve_opt()
        self._frozen = frozenset()
        self._params = {}
        self._opt_state = {}
        for loc in self._block_locals:
            stacked = jnp.stack([blk[loc].data()._data
                                 for blk in self._per_block_params])
            name = self._STACK + loc
            arr, states = self._state_for_array(stacked)
            self._params[name] = self._put(arr, self._spec_for(arr, name))
            self._opt_state[name] = tuple(
                self._put(s, self._spec_for(s, name)) for s in states)
        self._aux = {}
