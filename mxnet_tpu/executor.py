"""Executor — binds a Symbol to devices + buffers and runs it.

Reference: ``python/mxnet/executor.py`` over ``src/executor/graph_executor.cc``
(SimpleBind :1593, Bind :1624, Forward :64 -> RunOps :1318, Backward :77).

TPU-native design: binding compiles the whole graph (forward, and
forward+vjp for training) into single XLA executables via ``jax.jit``.  The
reference's memory planning (PlanMemory pass), inplace-addto detection, op
segments/bulking and cross-device copy scheduling all collapse into XLA's
compiler — SURVEY.md §7 architecture stance.  Gradients come from one
``jax.vjp`` over the traced graph rather than a constructed backward graph.
``forward``/``backward``/``forward_backward`` mirror the reference's calling
conventions, including grad_req write/add/null and auxiliary-state updates
(BatchNorm moving stats).
"""

from __future__ import annotations

import functools
import logging

import numpy as _np
import jax
import jax.numpy as jnp

from .base import MXNetError, np_dtype
from .context import Context, current_context
from .ndarray import NDArray, zeros as nd_zeros
from .ndarray.ndarray import (_as_nd, _already_placed,
                              _DEVICE_PUT_ELIDED)
from .observability import metrics as _obs_metrics
from .symbol.symbol import Symbol, _infer_shapes

__all__ = ["Executor"]

# module-level ref — observed every legacy train step (no registry
# lookup per dispatch)
_EXEC_STEP_SECONDS = _obs_metrics.histogram(
    "executor_step_dispatch_seconds",
    "host-side latency of one legacy forward+backward dispatch")

# differentiable-leaf suffix for Embedding sparse_grad perturbations
# (train_step diff keys; see ops/sparse_graph.py SparseGradWeight)
_SPARSE_VALS = "!sparse_vals"


def _build_eval(symbol, training):
    """Build the pure graph-evaluation function:
    fn(arg_map, aux_map, key) -> (outputs, aux_updates)."""
    order = symbol._topo()
    out_entries = list(symbol._outputs)

    # ops that consume CSR carriers natively; every other op gets the
    # densified value — the reference's storage-type fallback
    # (infer_graph_attr_pass.cc dispatches to dense kernels with a
    # storage fallback warning)
    csr_aware = ("dot", "cast_storage")

    def fn(arg_map, aux_map, key):
        from .ops.sparse_graph import CsrCarrier
        vals = {}
        aux_updates = {}
        for pos, node in enumerate(order):
            if node.is_var:
                if node.name in arg_map:
                    vals[(id(node), 0)] = arg_map[node.name]
                elif node.name in aux_map:
                    vals[(id(node), 0)] = aux_map[node.name]
                else:
                    raise MXNetError("unbound variable %r" % node.name)
                continue
            op = node.op
            ins = [vals[(id(s), i)] for (s, i) in node.inputs]
            if op.name not in csr_aware:
                ins = [v.todense() if isinstance(v, CsrCarrier) else v
                       for v in ins]
            params = node.params
            if "training" in op.param_names:
                params = dict(params, training=training)
            # a device scope per node ("<op>:<node>" in every instruction's
            # op_name): metadata only, the program compiles as without it;
            # under the scope a block named for a group of its nodes, if it
            # did (`AttrScope(__scope__=...)`)
            scope = "%s:%s" % (op.name, node.name)
            if node.attrs.get("__scope__"):
                scope = "%s/%s" % (node.attrs["__scope__"], scope)
            with jax.named_scope(scope):
                if op.needs_rng:
                    sub = jax.random.fold_in(key, pos)
                    out = op.fn(sub, *ins, **params)
                else:
                    out = op.fn(*ins, **params)
            if not isinstance(out, tuple):
                out = (out,)
            for i, o in enumerate(out):
                vals[(id(node), i)] = o
            if training and op.aux_states:
                for in_idx, out_idx in op.aux_states.items():
                    src, _ = node.inputs[in_idx]
                    if src.is_var and src.name in aux_map:
                        aux_updates[src.name] = out[out_idx]
        outputs = [vals[(id(n), i)] for (n, i) in out_entries]
        return outputs, aux_updates

    return fn


def _wrap_out(o):
    """Graph output -> NDArray; CSR carriers surface as CSRNDArray."""
    from .ops.sparse_graph import CsrCarrier
    if isinstance(o, CsrCarrier):
        from .ndarray.sparse import CSRNDArray
        return CSRNDArray(NDArray(o.data), NDArray(o.indices),
                          NDArray(o.indptr), o.shape)
    return NDArray(o)


class Executor:
    """A bound computation graph."""

    def __init__(self, symbol, ctx, arg_dict, grad_dict, aux_dict,
                 grad_req, group2ctx=None):
        self._symbol = symbol
        self._ctx = ctx or current_context()
        self._group2ctx = dict(group2ctx or {})
        self.arg_dict = arg_dict
        self.grad_dict = grad_dict
        self.aux_dict = aux_dict
        self._arg_names = symbol.list_arguments()
        self._aux_names = symbol.list_auxiliary_states()
        if isinstance(grad_req, str):
            grad_req = {n: grad_req for n in self._arg_names}
        elif isinstance(grad_req, (list, tuple)):
            grad_req = dict(zip(self._arg_names, grad_req))
        self._grad_req = {n: grad_req.get(n, "null")
                          for n in self._arg_names}
        # CSR args flow through the traced graph as (values, indices,
        # indptr) carriers (ops/sparse_graph.py); gradients THROUGH a
        # csr input are not computed (the reference likewise has no
        # backward for its csr-lhs dot kernels) — a blanket grad_req
        # simply excludes them
        from .ndarray.sparse import CSRNDArray
        for n, a in arg_dict.items():
            if isinstance(a, CSRNDArray):
                self._grad_req[n] = "null"
        self._grad_names = [n for n in self._arg_names
                            if self._grad_req[n] != "null" and
                            grad_dict.get(n) is not None]
        # Embedding(sparse_grad=True): deliver the weight grad as
        # row_sparse (ids, rows) pairs instead of a dense (vocab, dim)
        # buffer — see ops/sparse_graph.py SparseGradWeight
        self._sparse_embeds = {}
        self._sparse_embed_nodes = {}
        for node in symbol._topo():
            if node.is_var or node.op.name != "Embedding":
                continue
            sg = node.params.get("sparse_grad", False)
            if isinstance(sg, str):
                sg = sg in ("True", "true", "1")
            if not sg:
                continue
            wsrc, _ = node.inputs[1]
            dsrc, _ = node.inputs[0]
            if self._grad_req.get(wsrc.name, "null") == "null":
                continue
            if not (wsrc.is_var and dsrc.is_var):
                raise MXNetError(
                    "Embedding sparse_grad=True needs variable data and "
                    "weight inputs (got computed inputs for %r)"
                    % node.name)
            if self._grad_req[wsrc.name] == "add":
                raise MXNetError(
                    "grad_req='add' is unsupported for sparse_grad "
                    "Embedding weights (rsp pair grads are rebuilt each "
                    "backward)")
            if wsrc.name in self._sparse_embeds:
                raise MXNetError(
                    "weight %r feeds multiple sparse_grad Embedding "
                    "nodes; share a dense-grad weight or split it"
                    % wsrc.name)
            self._sparse_embeds[wsrc.name] = (
                dsrc.name, int(node.params.get("output_dim")))
            self._sparse_embed_nodes[wsrc.name] = node
        # swap the grad buffer for an rsp container ONCE at bind so the
        # handle a caller grabs (args_grad, the C ABI's arg_grads) stays
        # aliased across backwards — writeback mutates it in place.
        # Until the first backward it holds one zero row at an
        # out-of-bounds id (todense == zeros).
        if self._sparse_embeds:
            from .ndarray.sparse import RowSparseNDArray
            for n in list(self._sparse_embeds):
                if n in self._grad_names:
                    dense = grad_dict[n]
                    dim = self._sparse_embeds[n][1]
                    grad_dict[n] = RowSparseNDArray(
                        NDArray(jnp.zeros((1, dim), dense.dtype)),
                        NDArray(jnp.full((1,), dense.shape[0],
                                         jnp.int32)),
                        tuple(dense.shape))
        if self._sparse_embeds:
            # a sparse-grad weight must feed ONLY its Embedding node:
            # train_step wraps it in a SparseGradWeight carrier, which
            # other ops (e.g. a tied output projection) cannot consume.
            # The exemption is the SPECIFIC registered node — a weight
            # shared with a second Embedding (sparse or not) must fail
            # here too, not surface as a trace-time shape error
            for node in symbol._topo():
                if node.is_var:
                    continue
                for i, (src, _) in enumerate(node.inputs):
                    if src.is_var and src.name in self._sparse_embeds \
                            and not (node is self._sparse_embed_nodes[
                                src.name] and i == 1):
                        raise MXNetError(
                            "weight %r has sparse_grad=True but is also "
                            "consumed by %r (%s); weight tying requires "
                            "a dense gradient" % (src.name, node.name,
                                                  node.op.name))
        self.outputs = []
        # the PRNG key must live on this executor's device: under a
        # two-platform session (cpu-vs-tpu consistency runs) a
        # default-device key mixed with ctx-placed args is a jit error
        self._key = jax.device_put(jax.random.PRNGKey(0),
                                   self._ctx.jax_device)
        self._fwd_jit = {}
        self._fused_jit = None
        self._monitor = None

        eval_train = _build_eval(symbol, True)
        eval_infer = _build_eval(symbol, False)

        def fwd(training, arg_map, aux_map, key):
            f = eval_train if training else eval_infer
            return f(arg_map, aux_map, key)

        self._eval_train = eval_train
        self._eval_infer = eval_infer
        self._jit_infer = jax.jit(
            lambda arg_map, aux_map, key: eval_infer(arg_map, aux_map, key))
        self._jit_train = jax.jit(
            lambda arg_map, aux_map, key: eval_train(arg_map, aux_map, key))

        grad_names = self._grad_names
        sparse_embeds = {n: v for n, v in self._sparse_embeds.items()
                         if n in grad_names}

        def train_step(arg_map, aux_map, key, out_cots):
            diff = {n: arg_map[n] for n in grad_names
                    if n not in sparse_embeds}
            for w, (dname, dim) in sparse_embeds.items():
                # the differentiable leaf is the zero per-occurrence
                # perturbation; the weight itself stays non-diff so no
                # dense (vocab, dim) cotangent is ever formed
                ids = arg_map[dname]
                diff[w + _SPARSE_VALS] = jnp.zeros(ids.shape + (dim,),
                                                   arg_map[w].dtype)
            rest = {n: v for n, v in arg_map.items() if n not in diff}

            def run(d):
                amap = dict(rest)
                for n, v in d.items():
                    if n.endswith(_SPARSE_VALS):
                        from .ops.sparse_graph import SparseGradWeight
                        w = n[:-len(_SPARSE_VALS)]
                        amap[w] = SparseGradWeight(rest[w], v)
                    else:
                        amap[n] = v
                outs, auxu = eval_train(amap, aux_map, key)
                return outs, auxu

            (outs, auxu), vjp_fn = jax.vjp(lambda d: run(d), diff)
            cots = [c if c is not None else jnp.ones_like(o)
                    for c, o in zip(out_cots, outs)]
            cots = [c.astype(o.dtype) if c.dtype != o.dtype else c
                    for c, o in zip(cots, outs)]
            zero_aux = jax.tree_util.tree_map(jnp.zeros_like, auxu)
            grads = vjp_fn((cots, zero_aux))[0]
            # canonicalize rsp grads in-graph: unique sorted rows with
            # summed values (row-wise optimizer kernels require
            # duplicate-free ids; tail slots pad with an out-of-bounds
            # id that every .at[] consumer drops)
            from .ops.sparse_graph import dedup_rsp_pairs
            for w, (dname, dim) in sparse_embeds.items():
                vals = grads.pop(w + _SPARSE_VALS)
                grads[w] = dedup_rsp_pairs(arg_map[dname], vals,
                                           arg_map[w].shape[0])
            return outs, auxu, grads

        self._jit_train_step = jax.jit(train_step)
        # unjitted core kept for nesting inside the fused
        # forward+backward+update program (init_fused_step)
        self._train_step_fn = train_step

        if self._group2ctx:
            self._init_grouped()

    def _init_grouped(self):
        """Replace the whole-graph jits with the segment-chained
        model-parallel path (see grouped_executor.py)."""
        if self._sparse_embeds:
            raise MXNetError(
                "Embedding sparse_grad=True is not supported together "
                "with group2ctx model parallelism")
        from .grouped_executor import build_grouped_eval
        sym = self._symbol
        aux_names = self._aux_names
        run_t, back_t, segs = build_grouped_eval(
            sym, self._group2ctx, self._ctx, True, aux_names)
        run_i, _, _ = build_grouped_eval(
            sym, self._group2ctx, self._ctx, False, aux_names)
        self._segments = segs
        grad_names = self._grad_names

        def jit_infer(arg_map, aux_map, key):
            outs, auxu, _ = run_i(arg_map, aux_map, key, False)
            return outs, auxu

        def jit_train(arg_map, aux_map, key):
            outs, auxu, _ = run_t(arg_map, aux_map, key, False)
            return outs, auxu

        def train_step(arg_map, aux_map, key, out_cots):
            outs, auxu, vjps = run_t(arg_map, aux_map, key, True)
            cots = [c.astype(o.dtype) if c.dtype != o.dtype else c
                    for c, o in zip(out_cots, outs)]
            all_grads = back_t(vjps, cots)
            grads = {}
            for n in grad_names:
                g = all_grads.get(n)
                if g is None:
                    g = jnp.zeros_like(arg_map[n])
                grads[n] = g
            return outs, auxu, grads

        self._jit_infer = jit_infer
        self._jit_train = jit_train
        self._jit_train_step = train_step
        # segment-chained evaluation is not one pure program; the fused
        # single-program step cannot be built on top of it
        self._train_step_fn = None

    def init_fused_step(self, tree_update_fn, guard_nonfinite=False):
        """Build the fused train step: forward + VJP + optimizer update
        in ONE donated ``jax.jit`` — weights and optimizer state stay
        device-resident and step N+1 chains on step N's donated
        buffers (no per-parameter host dispatch; the TVM/CUDA-Graph
        whole-step-capture idea applied at the XLA level).

        ``tree_update_fn(grads, params, state, lrs, wds, ts)`` is the
        pure tree-level optimizer sweep (optimizer/tree_opt.py).
        Signature of the returned callable::

            fused(params, rest, aux_map, base_key, opt_state, lrs,
                  wds, ts, step) -> (outs, new_aux, new_params,
                                     new_opt_state[, skipped])

        *params* holds only the UPDATABLE args (donated); data/labels/
        fixed params ride in *rest* undonated so caller-owned batch
        buffers stay valid.  *ts* carries the per-name update counts;
        *step* is the scalar step the PRNG key is folded with in-graph,
        so not even a key split dispatches per step.

        With *guard_nonfinite*, one fused ``isfinite`` reduction over
        the loss outputs + gradient tree decides in-graph whether the
        update applies: a non-finite step returns params, optimizer
        state AND aux (BatchNorm stats) bit-identical, plus a trailing
        int32 ``skipped`` flag — still the same single program, no
        recompile (see docs/resilience.md)."""
        if self._train_step_fn is None:
            raise MXNetError(
                "the fused train step is not supported with group2ctx "
                "model parallelism (segment-chained execution)")
        core = self._train_step_fn
        n_outs = len(self._symbol._outputs)
        from . import profiler as _prof
        from .optimizer import tree_opt as _tree_opt

        def fused_step(params, rest, aux_map, base_key, opt_state, lrs,
                       wds, ts, step):
            # the Python body only runs at trace time — this IS the
            # compile counter (cached executions bump nothing)
            _prof.bump_counter(  # graftlint: disable=JG003
                "fused_step_compiles")  # trace-time-only on purpose
            key = jax.random.fold_in(base_key, step)
            arg_map = dict(rest)
            arg_map.update(params)
            outs, auxu, grads = core(arg_map, aux_map, key,
                                     [None] * n_outs)
            new_params, new_state = tree_update_fn(
                grads, params, opt_state, lrs, wds, ts)
            new_aux = dict(aux_map)
            new_aux.update(auxu)
            if guard_nonfinite:
                bad = jnp.logical_or(_tree_opt.nonfinite_any(outs),
                                     _tree_opt.nonfinite_any(grads))
                new_params = _tree_opt.select_tree(bad, params,
                                                   new_params)
                new_state = _tree_opt.select_tree(bad, opt_state,
                                                  new_state)
                new_aux = _tree_opt.select_tree(bad, aux_map, new_aux)
                return (outs, new_aux, new_params, new_state,
                        bad.astype(jnp.int32))
            return outs, new_aux, new_params, new_state

        from .ops.registry import supports_donation
        # donate weights + optimizer state (argnums 0 and 4)
        donate = (0, 4) if supports_donation() else ()
        # the caller owns the program (Module keeps it in _fused["fn"]
        # and rebuilds on hyper-param mutation) — not stored here
        return jax.jit(fused_step, donate_argnums=donate)

    # -- binding constructors ---------------------------------------------
    @staticmethod
    def _simple_bind(symbol, ctx, grad_req, type_dict, shape_kwargs,
                     shared_exec=None, group2ctx=None):
        shapes = {k: tuple(v) for k, v in shape_kwargs.items()}
        _, var_sh = _infer_shapes(symbol, shapes)
        type_dict = type_dict or {}
        arg_dict = {}
        for n in symbol.list_arguments():
            dt = type_dict.get(n, "float32")
            if shared_exec is not None and n in shared_exec.arg_dict and \
                    tuple(shared_exec.arg_dict[n].shape) == var_sh[n]:
                arg_dict[n] = shared_exec.arg_dict[n]
            else:
                arg_dict[n] = nd_zeros(var_sh[n], ctx=ctx, dtype=dt)
        aux_dict = {}
        for n in symbol.list_auxiliary_states():
            if shared_exec is not None and n in shared_exec.aux_dict and \
                    tuple(shared_exec.aux_dict[n].shape) == var_sh[n]:
                aux_dict[n] = shared_exec.aux_dict[n]
            else:
                aux_dict[n] = nd_zeros(var_sh[n], ctx=ctx)
        if isinstance(grad_req, str):
            reqs = {n: grad_req for n in arg_dict}
        elif isinstance(grad_req, (list, tuple)):
            reqs = dict(zip(symbol.list_arguments(), grad_req))
        else:
            reqs = {n: grad_req.get(n, "null") for n in arg_dict}
        grad_dict = {n: nd_zeros(var_sh[n], ctx=ctx,
                                 dtype=type_dict.get(n, "float32"))
                     for n in arg_dict if reqs.get(n, "null") != "null"}
        return Executor(symbol, ctx, arg_dict, grad_dict, aux_dict, reqs,
                        group2ctx=group2ctx)

    @staticmethod
    def _bind(symbol, ctx, args, args_grad, grad_req, aux_states,
              group2ctx=None):
        arg_names = symbol.list_arguments()
        if isinstance(args, (list, tuple)):
            arg_dict = dict(zip(arg_names, [_as_nd(a) for a in args]))
        else:
            arg_dict = {k: _as_nd(v) for k, v in (args or {}).items()}
        if args_grad is None:
            grad_dict = {}
        elif isinstance(args_grad, (list, tuple)):
            grad_dict = dict(zip(arg_names, [_as_nd(g) if g is not None
                                             else None for g in args_grad]))
        else:
            grad_dict = {k: _as_nd(v) for k, v in args_grad.items()}
        grad_dict = {k: v for k, v in grad_dict.items() if v is not None}
        aux_names = symbol.list_auxiliary_states()
        if isinstance(aux_states, (list, tuple)):
            aux_dict = dict(zip(aux_names, [_as_nd(a) for a in aux_states]))
        else:
            aux_dict = {k: _as_nd(v) for k, v in (aux_states or {}).items()}
        for n in aux_names:
            if n not in aux_dict:
                raise MXNetError("missing auxiliary state %r" % n)
        return Executor(symbol, ctx, arg_dict, grad_dict, aux_dict, grad_req,
                        group2ctx=group2ctx)

    # -- properties --------------------------------------------------------
    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self._arg_names]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n) for n in self._arg_names]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self._aux_names]

    @property
    def output_dict(self):
        return dict(zip(self._symbol.list_outputs(), self.outputs))

    # -- execution ---------------------------------------------------------
    def _arg_map(self):
        from .ndarray.sparse import CSRNDArray
        from .ops.sparse_graph import CsrCarrier
        out = {}
        for n, a in self.arg_dict.items():
            if isinstance(a, CSRNDArray):
                out[n] = CsrCarrier(a._data, a._aux[0], a._aux[1],
                                    a.shape)
            else:
                out[n] = a._data
        return out

    def _aux_map(self):
        return {n: a._data for n, a in self.aux_dict.items()}

    def rng_state(self):
        """The executor's PRNG base key as plain ints (JSON-safe).

        This is the key the fused step folds the update count into
        in-graph (``fold_in(base_key, step)``), and the key the eager
        paths split per call — restoring it (plus the optimizer's
        update counts) makes dropout masks after a resume bit-identical
        to the uninterrupted run."""
        import numpy as _onp
        raw = _onp.asarray(jax.device_get(self._key))
        return {"shape": list(raw.shape),
                "data": [int(v) for v in raw.ravel().tolist()]}

    def set_rng_state(self, state):
        import numpy as _onp
        raw = _onp.asarray(state["data"], dtype=_onp.uint32).reshape(
            state["shape"])
        self._key = jax.device_put(jnp.asarray(raw),
                                   self._ctx.jax_device)

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def _place(self, arr):
        """Move an incoming array onto this executor's device (the
        reference's executor_group copies batch slices per ctx,
        executor_group.py:436).  An array already COMMITTED here — a
        DevicePrefetcher ring batch, or a slice of one — skips the put
        entirely (counted via ``device_put_elided_total``); an
        uncommitted on-device array still routes through device_put so
        its committedness can't flip the fused program's jit cache key
        between steps (the graftsan recompile lesson)."""
        import jax as _jax
        dev = self._ctx.jax_device
        if _already_placed(arr, dev):
            _DEVICE_PUT_ELIDED.inc()
            return arr
        return _jax.device_put(arr, dev)

    def forward(self, is_train=False, **kwargs):
        """Run the graph (reference: executor.py forward:114)."""
        for k, v in kwargs.items():
            if k in self.arg_dict:
                self.arg_dict[k]._data = self._place(_as_nd(v)._data.astype(
                    self.arg_dict[k].dtype))
            else:
                raise MXNetError("unknown forward argument %r" % k)
        from .runtime import engine as _engine
        key = self._next_key()
        if not _engine.bulk_enabled(is_train):
            # bulking disabled: per-node eager dispatch (the reference's
            # non-bulk engine path, graph_executor.cc:1187) — every op
            # runs as its own dispatch, fully debuggable
            outs, auxu = self._eval_per_node(self._arg_map(),
                                             self._aux_map(), key,
                                             is_train)
        else:
            fn = self._jit_train if is_train else self._jit_infer
            from . import profiler as _prof
            _prof.bump_counter("executor_dispatches")
            outs, auxu = fn(self._arg_map(), self._aux_map(), key)
        if is_train:
            # keep the key: backward() must replay the same stochastic
            # masks (Dropout etc.) that produced these outputs
            self._pending = (self._arg_map(), self._aux_map(), key)
        for n, v in auxu.items():
            self.aux_dict[n]._data = v
        self.outputs = [_wrap_out(o) for o in outs]
        if self._monitor is not None:
            if getattr(self, "_monitor_all", False):
                taps = self._monitor_taps(self._arg_map(),
                                          self._aux_map(), key, is_train)
                for name in sorted(taps):
                    self._monitor(name, NDArray(taps[name]))
            else:
                for name, val in zip(self._symbol.list_outputs(),
                                     self.outputs):
                    self._monitor(name, val)
        return self.outputs

    def backward(self, out_grads=None, is_train=True):
        """Gradients via whole-graph vjp (reference: backward:155 over the
        constructed gradient graph)."""
        self._run_train_step(out_grads, use_pending=True)

    def forward_backward(self, out_grads=None, **kwargs):
        """Fused forward+backward in one XLA program — the fast path the
        Module training loop uses (no double forward)."""
        for k, v in kwargs.items():
            if k in self.arg_dict:
                self.arg_dict[k]._data = self._place(_as_nd(v)._data.astype(
                    self.arg_dict[k].dtype))
        self._run_train_step(out_grads, use_pending=False)
        return self.outputs

    def _run_train_step(self, out_grads, use_pending):
        if out_grads is None:
            cots = [None] * len(self._symbol._outputs)
        elif isinstance(out_grads, NDArray):
            cots = [out_grads._data]
        else:
            cots = [g._data if g is not None else None for g in out_grads]
        if use_pending and getattr(self, "_pending", None) is not None:
            arg_map, aux_map, key = self._pending
            self._pending = None
        else:
            arg_map, aux_map = self._arg_map(), self._aux_map()
            key = self._next_key()
        # None cotangents must be materialized as ones for jit
        from . import profiler as _prof
        import time as _time
        _prof.bump_counter("executor_dispatches")
        t0 = _time.perf_counter()
        outs, auxu, grads = self._jit_train_step(
            arg_map, aux_map, key,
            _materialize(cots, self, arg_map, aux_map))
        # host-side latency to issue the legacy (non-fused)
        # forward+backward program — the fused path's histogram twin,
        # so an A/B of the two update paths is one scrape away
        _EXEC_STEP_SECONDS.observe(_time.perf_counter() - t0)
        for n, v in auxu.items():
            self.aux_dict[n]._data = v
        self.outputs = [_wrap_out(o) for o in outs]
        for n in self._grad_names:
            if n in self._sparse_embeds:
                # rsp pair grad, deduped to unique sorted rows
                # in-graph; the container object is stable from bind
                # time (caller handles alias it) — update in place
                ids, vals = grads[n]
                dst = self.grad_dict[n]
                dst._data = vals
                dst._aux[0] = ids
                continue
            g = grads[n]
            dst = self.grad_dict[n]
            g = g.astype(dst.dtype) if g.dtype != dst.dtype else g
            if self._grad_req[n] == "add":
                dst._data = dst._data + g
            else:
                dst._data = g

    # -- utilities ---------------------------------------------------------
    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for k, v in (arg_params or {}).items():
            if k in self.arg_dict:
                v.copyto(self.arg_dict[k])
            elif not allow_extra_params:
                raise MXNetError("unknown argument %r" % k)
        for k, v in (aux_params or {}).items():
            if k in self.aux_dict:
                v.copyto(self.aux_dict[k])
            elif not allow_extra_params:
                raise MXNetError("unknown aux state %r" % k)

    def reshape(self, partial_shaping=False, allow_up_sizing=False,
                **kwargs):
        """Re-bind with new shapes (reference: executor.py reshape:372);
        recompilation is per-shape cached by jit."""
        shapes = {}
        for n, a in self.arg_dict.items():
            shapes[n] = kwargs.get(n, a.shape)
        ex = Executor._simple_bind(self._symbol, self._ctx, self._grad_req,
                                   None, shapes)
        for n, a in self.arg_dict.items():
            if tuple(ex.arg_dict[n].shape) == tuple(a.shape):
                ex.arg_dict[n] = a
        for n, a in self.aux_dict.items():
            if tuple(ex.aux_dict[n].shape) == tuple(a.shape):
                ex.aux_dict[n] = a
        return ex

    def _eval_per_node(self, arg_map, aux_map, key, is_train):
        """Non-bulk execution: the same walk _build_eval traces, but
        dispatched eagerly op by op (reference: non-bulk engine ops,
        graph_executor.cc:1187-1215 / MXEngineSetBulkSize(0))."""
        fn = self._eval_train if is_train else self._eval_infer
        return fn(arg_map, aux_map, key)

    def set_monitor_callback(self, callback, monitor_all=False):
        """Install a per-op output tap (reference:
        MXExecutorSetMonitorCallback / graph_executor.cc:104,1295).
        With monitor_all, forward also reports every interior node's
        outputs, not just the graph outputs."""
        self._monitor = callback
        self._monitor_all = monitor_all
        self._jit_monitor = {}

    def _monitor_taps(self, arg_map, aux_map, key, is_train):
        """Evaluate the graph returning {tap_name: value} for every op
        node output (compiled once per training mode)."""
        if self._jit_monitor.get(is_train) is None:
            order = self._symbol._topo()

            def tap_eval(arg_map, aux_map, key):
                vals = {}
                taps = {}
                for pos, node in enumerate(order):
                    if node.is_var:
                        vals[(id(node), 0)] = arg_map.get(
                            node.name, aux_map.get(node.name))
                        continue
                    op = node.op
                    ins = [vals[(id(s), i)] for (s, i) in node.inputs]
                    params = node.params
                    if "training" in op.param_names:
                        params = dict(params, training=is_train)
                    if op.needs_rng:
                        out = op.fn(jax.random.fold_in(key, pos), *ins,
                                    **params)
                    else:
                        out = op.fn(*ins, **params)
                    if not isinstance(out, tuple):
                        out = (out,)
                    for i, o in enumerate(out):
                        vals[(id(node), i)] = o
                    n_vis = op.n_visible(node.params)
                    for i in range(n_vis):
                        nm = node.name + ("_output" if n_vis == 1
                                          else "_output%d" % i)
                        taps[nm] = out[i]
                return taps

            self._jit_monitor[is_train] = jax.jit(tap_eval)
        return self._jit_monitor[is_train](arg_map, aux_map, key)

    def debug_str(self):
        lines = ["Symbol outputs: %s" % self._symbol.list_outputs()]
        for node in self._symbol._topo():
            kind = "var" if node.is_var else node.op.name
            lines.append("%s %s <- %s" % (kind, node.name,
                                          [s.name for s, _ in node.inputs]))
        return "\n".join(lines)


def _materialize(cots, ex, arg_map, aux_map):
    """Replace None head-cotangents with ones of the right shape (the
    reference allows backward() without out_grads for loss heads)."""
    if all(c is not None for c in cots):
        return cots
    # cheap shape inference: run eval_shape on the infer function
    try:
        shapes = jax.eval_shape(ex._eval_infer, arg_map, aux_map,
                                ex._key)[0]
    except Exception as e:
        # fall back to a real forward for the shapes, but keep the
        # eval_shape failure diagnosable instead of eating it
        logging.getLogger(__name__).debug(
            "eval_shape failed in _materialize (%s: %s); falling back "
            "to an executed forward pass", type(e).__name__, e)
        outs, _ = ex._jit_infer(arg_map, aux_map, ex._key)
        shapes = outs
    dev = ex._ctx.jax_device
    return [c if c is not None
            else jax.device_put(jnp.ones(s.shape, s.dtype), dev)
            for c, s in zip(cots, shapes)]
