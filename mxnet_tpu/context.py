"""Device context management.

TPU-native equivalent of the reference's ``Context``
(``/root/reference/python/mxnet/context.py``): a lightweight handle naming a
device (``cpu(0)``, ``tpu(2)``) plus a thread-local "current context" stack
used by every array-creating call.  Unlike the reference, the device itself is
a live ``jax.Device`` — placement happens via ``jax.device_put`` / sharding
rather than a C++ storage manager.
"""

from __future__ import annotations

import threading

import jax

__all__ = [
    "Context", "cpu", "gpu", "tpu", "current_context", "num_tpus", "num_gpus",
]

# devtype ids mirror the reference's enum (kCPU=1, kGPU=2, kCPUPinned=3,
# reference include/mxnet/base.h); TPU takes the GPU slot's role.
_DEVTYPE2ID = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}
_ID2DEVTYPE = {v: k for k, v in _DEVTYPE2ID.items()}


class Context:
    """A device context.

    Parameters
    ----------
    device_type : str
        'cpu', 'tpu' or 'gpu' ('gpu' is accepted as an alias for the
        accelerator so reference scripts run unmodified).
    device_id : int
        Ordinal of the device within its platform.
    """

    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_type = device_type.device_type
            self.device_id = device_type.device_id
        else:
            if device_type not in _DEVTYPE2ID:
                raise ValueError("unknown device type %r" % (device_type,))
            self.device_type = device_type
            self.device_id = device_id

    # -- identity ---------------------------------------------------------
    @property
    def device_typeid(self):
        return _DEVTYPE2ID[self.device_type]

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __str__ = __repr__

    # -- jax mapping ------------------------------------------------------
    @property
    def jax_device(self):
        """The live ``jax.Device`` this context names."""
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            devs = _platform_devices("cpu")
        else:
            devs = _accelerator_devices()
        if not devs:
            raise RuntimeError("no %s devices visible to JAX" % self.device_type)
        return devs[self.device_id % len(devs)]

    def __enter__(self):
        if not hasattr(Context._default_ctx, "stack"):
            Context._default_ctx.stack = []
        Context._default_ctx.stack.append(self)
        return self

    def __exit__(self, *exc):
        Context._default_ctx.stack.pop()

    def empty_cache(self):
        """Release cached device memory (best-effort; XLA owns HBM)."""
        # XLA manages HBM with its own allocator; nothing to do but keep the
        # reference API (ndarray.py Context.empty_cache) available.
        return None


_backend_up = False


def _backend_init():
    """The package's first look at the devices, which is where JAX brings
    its backends up (seconds on a TPU host): span ``mx.backend_init``."""
    global _backend_up
    if not _backend_up:
        from . import profiler
        with profiler.scope("mx.backend_init", "setup"):
            jax.devices()
        _backend_up = True


def _platform_devices(platform):
    """THIS process's devices for a platform.  Under jax.distributed
    (multi-host) ``jax.devices()`` is the global list including peers'
    non-addressable devices; a Context must always name a local one."""
    _backend_init()
    try:
        return jax.local_devices(backend=platform)
    except RuntimeError:
        return []


def _accelerator_devices():
    """This process's devices of the default (non-cpu) platform, else cpu."""
    _backend_init()
    devs = jax.local_devices()
    non_cpu = [d for d in devs if d.platform != "cpu"]
    return non_cpu if non_cpu else devs


def cpu(device_id=0):
    """Return a CPU context."""
    return Context("cpu", device_id)


def tpu(device_id=0):
    """Return a TPU context (the accelerator platform JAX sees)."""
    return Context("tpu", device_id)


def gpu(device_id=0):
    """Alias of :func:`tpu` so reference scripts using ``mx.gpu()`` run."""
    return Context("tpu", device_id)


def num_tpus():
    _backend_init()
    devs = [d for d in jax.devices() if d.platform != "cpu"]
    return len(devs)


def num_gpus():
    return num_tpus()


def current_context():
    """The context on top of the ``with ctx:`` stack (default: accelerator
    if present, else cpu — unlike the reference which defaults to cpu, a TPU
    framework defaults to the chip)."""
    stack = getattr(Context._default_ctx, "stack", None)
    if stack:
        return stack[-1]
    return Context._default_ctx_value()


def _default_ctx_value():
    if num_tpus() > 0:
        return Context("tpu", 0)
    return Context("cpu", 0)


Context._default_ctx_value = staticmethod(_default_ctx_value)
