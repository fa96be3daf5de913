"""Runtime kernel compilation (reference: python/mxnet/rtc.py —
CudaModule:42 compiles CUDA source via NVRTC, get_kernel:112 extracts an
entry point, launch:185 runs it on NDArrays).

TPU equivalent: the "source" is Python defining JAX/Pallas kernels, and
"compilation" is jit/Mosaic — so ``Module`` exec's kernel source into an
isolated namespace, ``get_kernel`` wraps an entry point as an
NDArray-callable (jit-compiled per signature on first launch), and
``register_op`` promotes a kernel to a full framework operator usable
from nd/sym/gluon like any built-in.  This is the §2.8 RTC hook:
user-supplied kernels compiled at runtime without rebuilding the
framework.
"""

from __future__ import annotations

import jax

from .base import MXNetError
from .ndarray import NDArray

__all__ = ["Module", "Kernel", "register_op"]


class Kernel(object):
    """One launchable entry point (reference: rtc.py CudaKernel).

    The wrapped function takes and returns jax arrays; ``launch`` (and
    ``__call__``) move NDArray arguments in and wrap results back.  A
    jitted executable is cached per call signature, like the NVRTC
    kernel cache keyed by compiled PTX in the reference."""

    def __init__(self, fn, name, static_args=()):
        self._fn = fn
        self.name = name
        self._static = tuple(static_args)
        self._jitted = None

    def _compiled(self):
        if self._jitted is None:
            self._jitted = jax.jit(self._fn,
                                   static_argnames=self._static or None)
        return self._jitted

    def __call__(self, *args, **kwargs):
        vals = [a._data if isinstance(a, NDArray) else a for a in args]
        kw = {k: (v._data if isinstance(v, NDArray) else v)
              for k, v in kwargs.items()}
        out = self._compiled()(*vals, **kw)
        if isinstance(out, (list, tuple)):
            return [NDArray(o) for o in out]
        return NDArray(out)

    def launch(self, args, ctx=None, grid_dims=None, block_dims=None,
               shared_mem=0, **kwargs):
        """Reference-shaped launch API; grid/block dims are meaningless
        under XLA/Mosaic scheduling and accepted for compatibility."""
        return self(*args, **kwargs)


class Module(object):
    """Compile kernel source at runtime (reference: rtc.py
    CudaModule:42).  *source* is Python text defining functions over jax
    arrays (jnp ops or pallas_call kernels); it executes in an isolated
    namespace with jax/jnp/pallas preloaded, mirroring how the
    reference's source string gets nvrtc-compiled with exports."""

    def __init__(self, source, options=(), exports=()):
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
        self._namespace = {"jax": jax, "jnp": jnp, "pl": pl,
                           "pltpu": pltpu}
        try:
            exec(compile(source, "<rtc.Module>", "exec"),
                 self._namespace)
        except Exception as e:
            raise MXNetError("rtc source failed to compile: %s" % e)
        self._exports = set(exports) if exports else None

    def get_kernel(self, name, signature=None, static_args=()):
        """Fetch an entry point (reference: get_kernel:112; the CUDA
        signature string is accepted and ignored — jax infers types)."""
        if self._exports is not None and name not in self._exports:
            raise MXNetError("kernel %r not exported" % name)
        fn = self._namespace.get(name)
        if not callable(fn):
            raise MXNetError("kernel %r not found in rtc source" % name)
        return Kernel(fn, name, static_args)


def register_op(op_name, fn=None, num_outputs=1, input_names=None):
    """Promote a runtime-compiled kernel to a registered operator so it
    works from nd/sym/gluon/executor like a built-in (the deeper TPU
    analogue of launching an RTC kernel inside the engine).  Usable as
    a decorator::

        @mx.rtc.register_op("my_scale")
        def my_scale(x, scale=2.0):
            return x * scale
        ...
        mx.nd.my_scale(a, scale=3.0)
    """
    from .ops import registry as _reg
    from .ndarray import register as _nd_reg
    from .symbol import register as _sym_reg
    from . import ndarray as _nd_pkg
    from . import symbol as _sym_pkg

    def _do(f):
        _reg.register_op(op_name, num_outputs=num_outputs,
                         input_names=input_names)(f)
        op = _reg.get_op(op_name)
        _nd_pkg.__dict__[op_name] = _nd_reg._make_fn(op)
        _sym_pkg.__dict__[op_name] = _sym_reg._make_fn(op)
        return f

    if fn is not None:
        return _do(fn)
    return _do
