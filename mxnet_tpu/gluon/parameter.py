"""Gluon Parameter / ParameterDict.

Reference: ``python/mxnet/gluon/parameter.py`` (920 LoC: Parameter with
deferred init, per-context copies, ParameterDict with prefix scoping).

TPU note: per-context replicas exist for the multi-device ``kvstore=local``
path; the ``kvstore='tpu'`` data-parallel path keeps ONE logical copy and
shards/replicates via the device mesh instead (parallel/ package).
"""

from __future__ import annotations

from collections import OrderedDict

import numpy as _np

from ..base import MXNetError, np_dtype, dtype_name
from ..context import Context, current_context, cpu
from .. import ndarray as nd
from ..ndarray import NDArray
from .. import autograd
from .. import initializer as init_mod
from .. import symbol as sym_mod
from .. import profiler as _prof

__all__ = ["Parameter", "ParameterDict", "Constant",
           "DeferredInitializationError", "tensor_types"]

tensor_types = (NDArray,)


class DeferredInitializationError(MXNetError):
    """Parameter accessed before its shape was known."""


class Parameter:
    """A trainable weight (or state) of a Block."""

    def __init__(self, name, grad_req="write", shape=None, dtype="float32",
                 lr_mult=1.0, wd_mult=1.0, init=None,
                 allow_deferred_init=False, differentiable=True,
                 stype="default", grad_stype="default"):
        self.name = name
        self._grad_req = grad_req if differentiable else "null"
        self._shape = tuple(shape) if shape is not None else None
        self.dtype = np_dtype(dtype)
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self._allow_deferred_init = allow_deferred_init
        self._differentiable = differentiable
        self._stype = stype
        self._grad_stype = grad_stype
        self._data = None       # OrderedDict ctx -> NDArray
        self._grad = None
        self._deferred_init = None
        self._var = None

    def __repr__(self):
        return "Parameter %s (shape=%s, dtype=%s)" % (
            self.name, self._shape, self.dtype)

    # -- shape -------------------------------------------------------------
    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if self._shape is None:
            self._shape = tuple(new_shape)
            return
        unknown_ok = all(s1 in (0, s2)
                         for s1, s2 in zip(self._shape, new_shape)) and \
            len(self._shape) == len(new_shape)
        if not unknown_ok:
            raise AssertionError(
                "Expected shape %s is incompatible with given shape %s for "
                "Parameter %s" % (str(new_shape), str(self._shape),
                                  self.name))
        self._shape = tuple(new_shape)

    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        assert req in ("write", "add", "null")
        if not self._differentiable:
            req = "null"
        if self._grad_req == req:
            return
        self._grad_req = req
        if req == "null":
            self._grad = None
        elif self._data is not None:
            self._init_grad()

    # -- initialization ----------------------------------------------------
    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit=False):
        if default_init is None:
            default_init = init_mod.Uniform()
        if self._data is not None and not force_reinit:
            return
        if ctx is None:
            ctx = [current_context()]
        if isinstance(ctx, Context):
            ctx = [ctx]
        if self._shape is None or any(s == 0 for s in self._shape):
            if self._allow_deferred_init:
                self._deferred_init = (init, list(ctx), default_init)
                return
            raise ValueError(
                "Cannot initialize Parameter %s because it has invalid "
                "shape %s." % (self.name, str(self._shape)))
        self._finish_init(init, list(ctx), default_init)

    def _finish_init(self, init, ctx_list, default_init):
        # one mx.initialize span per parameter materialised, whichever
        # way it got here (Block.initialize, its own, deferred)
        with _prof.scope("mx.initialize", "setup"):
            data = nd.zeros(self._shape, dtype=dtype_name(self.dtype),
                            ctx=ctx_list[0])
            initializer = init or self.init or default_init
            if isinstance(initializer, str):
                initializer = init_mod.create(initializer)
            initializer(init_mod.InitDesc(self.name), data)
            self._init_impl(data, ctx_list)

    def _init_impl(self, data, ctx_list):
        self._data = OrderedDict()
        for c in ctx_list:
            self._data[c] = data.copyto(nd.zeros(
                data.shape, ctx=c, dtype=dtype_name(self.dtype)))
        self._deferred_init = None
        if self._grad_req != "null":
            self._init_grad()

    def _init_grad(self):
        self._grad = OrderedDict()
        for c, d in self._data.items():
            g = nd.zeros(d.shape, ctx=c, dtype=str(d.dtype))
            self._grad[c] = g
            autograd.mark_variables([d], [g], self._grad_req)

    def _finish_deferred_init(self):
        if self._deferred_init is None:
            raise DeferredInitializationError(
                "Parameter %s has not been initialized yet" % self.name)
        if self._shape is None or any(s == 0 for s in self._shape):
            raise DeferredInitializationError(
                "Parameter %s awaiting shape inference" % self.name)
        init, ctx_list, default_init = self._deferred_init
        self._finish_init(init, ctx_list, default_init)

    # -- access ------------------------------------------------------------
    def _check_initialized(self, ctx=None):
        if self._data is None:
            if self._deferred_init is not None:
                raise DeferredInitializationError(
                    "Parameter %s has not been initialized yet because "
                    "initialization was deferred. Actual initialization "
                    "happens during the first forward pass." % self.name)
            raise RuntimeError(
                "Parameter %s has not been initialized. You should "
                "initialize parameters with Block.collect_params()"
                ".initialize()" % self.name)
        if ctx is not None and ctx not in self._data:
            raise RuntimeError(
                "Parameter %s was not initialized on context %s." %
                (self.name, ctx))

    def data(self, ctx=None):
        self._check_initialized()
        if ctx is None:
            return next(iter(self._data.values()))
        self._check_initialized(Context(ctx))
        return self._data[Context(ctx)]

    def list_data(self):
        self._check_initialized()
        return list(self._data.values())

    def grad(self, ctx=None):
        self._check_initialized()
        if self._grad is None:
            raise RuntimeError(
                "Cannot get gradient array for Parameter %s because "
                "grad_req='null'" % self.name)
        if ctx is None:
            return next(iter(self._grad.values()))
        return self._grad[Context(ctx)]

    def list_grad(self):
        self._check_initialized()
        if self._grad is None:
            raise RuntimeError("grad_req='null' for Parameter %s" %
                               self.name)
        return list(self._grad.values())

    def list_ctx(self):
        if self._data is None and self._deferred_init is not None:
            return self._deferred_init[1]
        self._check_initialized()
        return list(self._data.keys())

    def zero_grad(self):
        if self._grad is None:
            return
        for g in self._grad.values():
            g[:] = 0

    def set_data(self, data):
        self.shape = data.shape
        if self._data is None:
            if self._deferred_init is None:
                raise RuntimeError("Parameter %s has not been initialized" %
                                   self.name)
            self._finish_deferred_init()
        for c, d in self._data.items():
            arr = data.as_in_context(c) if isinstance(data, NDArray) else \
                nd.array(data, ctx=c)
            d._data = arr._data.astype(d._data.dtype)
        # re-mark variables so the tape sees the new value
        if self._grad is not None:
            for c, d in self._data.items():
                autograd.mark_variables([d], [self._grad[c]],
                                        self._grad_req)

    def row_sparse_data(self, row_id):
        # row_sparse weights: full fetch then retain (ICI all-gather path
        # is in kvstore)
        from ..ndarray import sparse as _sp
        w = self.data()
        return w

    def reset_ctx(self, ctx):
        if isinstance(ctx, Context):
            ctx = [ctx]
        if self._data is not None:
            data = next(iter(self._data.values()))
            self._init_impl(data, ctx)
        elif self._deferred_init is not None:
            init, _, default_init = self._deferred_init
            self._deferred_init = (init, ctx, default_init)

    def cast(self, dtype):
        self.dtype = np_dtype(dtype)
        if self._data is None:
            return
        with autograd.pause():
            self._data = OrderedDict(
                (c, d.astype(dtype)) for c, d in self._data.items())
            if self._grad is not None:
                self._grad = OrderedDict(
                    (c, g.astype(dtype)) for c, g in self._grad.items())
                for c in self._data:
                    autograd.mark_variables([self._data[c]],
                                            [self._grad[c]],
                                            self._grad_req)

    def var(self):
        if self._var is None:
            shape = self._shape if (self._shape is not None and
                                    all(s != 0 for s in self._shape)) \
                else None
            self._var = sym_mod.var(self.name, shape=shape,
                                    lr_mult=self.lr_mult,
                                    wd_mult=self.wd_mult)
        return self._var


class Constant(Parameter):
    """Non-differentiable constant parameter
    (reference: parameter.py Constant)."""

    def __init__(self, name, value):
        if not isinstance(value, NDArray):
            value = nd.array(value)
        self.value = value

        class Init(init_mod.Initializer):
            def _init_weight(self, _, arr):
                value.copyto(arr)
        init_name = "Constant_{}_{}".format(name, id(self))
        init_mod._reg.register(Init, name=init_name)
        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=str(value.dtype), init=init_name,
                         differentiable=False)


class ParameterDict:
    """Ordered dict of Parameters with prefix scoping
    (reference: parameter.py ParameterDict)."""

    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params = OrderedDict()
        self._shared = shared

    def __repr__(self):
        return "ParameterDict %r (%d params)" % (self._prefix,
                                                 len(self._params))

    def __getitem__(self, key):
        return self._params[key]

    def __iter__(self):
        return iter(self._params)

    def __len__(self):
        return len(self._params)

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    @property
    def prefix(self):
        return self._prefix

    def _get_impl(self, name):
        if name in self._params:
            return self._params[name]
        if self._shared is not None and name in self._shared._params:
            self._params[name] = self._shared._params[name]
            return self._params[name]
        return None

    def get(self, name, **kwargs):
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            param = Parameter(name, **kwargs)
            self._params[name] = param
        else:
            for k, v in kwargs.items():
                if hasattr(param, k) and getattr(param, k) is not None:
                    existing = getattr(param, k)
                    if k == "shape" and v is not None and \
                            existing is not None:
                        # merge partial shapes
                        if len(v) == len(existing):
                            merged = tuple(
                                a if a != 0 else b
                                for a, b in zip(existing, v))
                            param._shape = merged
                        continue
                    if k == "dtype":
                        continue
                else:
                    setattr(param, k, v)
        return param

    def get_constant(self, name, value=None):
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            if value is None:
                raise KeyError("No constant named %r" % name)
            param = Constant(name, value)
            self._params[name] = param
        return param

    def update(self, other):
        for k, v in other.items():
            if k in self._params and self._params[k] is not v:
                raise ValueError("Cannot update self with other because "
                                 "they have different Parameters with the "
                                 "same name %r" % k)
            self._params[k] = v

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        if init is None:
            init = init_mod.Uniform()
        for _, v in self.items():
            v.initialize(None, ctx, init, force_reinit=force_reinit)

    def zero_grad(self):
        for p in self.values():
            p.zero_grad()

    def reset_ctx(self, ctx):
        for p in self.values():
            p.reset_ctx(ctx)

    def setattr(self, name, value):
        for p in self.values():
            setattr(p, name, value)

    def save(self, filename, strip_prefix=""):
        arg_dict = {}
        for param in self.values():
            weight = param.data()
            if not param.name.startswith(strip_prefix):
                raise ValueError("Prefix %r is to be striped before saving, "
                                 "but Parameter %r does not start with it" %
                                 (strip_prefix, param.name))
            arg_dict[param.name[len(strip_prefix):]] = weight
        nd.save(filename, arg_dict)

    def load(self, filename, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix=""):
        arg_dict = {restore_prefix + k: v
                    for k, v in nd.load(filename).items()}
        if not allow_missing:
            for name in self.keys():
                if name not in arg_dict:
                    raise IOError("Parameter %r is missing in file %r" %
                                  (name, filename))
        for name, arr in arg_dict.items():
            if name not in self._params:
                if not ignore_extra:
                    raise IOError("Parameter %r loaded from file %r is not "
                                  "present in this ParameterDict" %
                                  (name, filename))
                continue
            param = self[name]
            if param._data is None and param._deferred_init is not None:
                param.shape = arr.shape
                param._finish_deferred_init()
            elif param._data is None:
                param._shape = arr.shape
                param.initialize(ctx=ctx or cpu())
            param.set_data(arr)
