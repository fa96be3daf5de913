"""Device mesh management.

The reference scales via NCCL rings + a GPU-topology tree planner
(src/kvstore/comm_tree.h, gpu_topology.h — Kernighan-Lin over the PCIe/
NVLink link matrix).  On TPU none of that exists: the ICI torus is known to
XLA, so "topology planning" reduces to naming mesh axes and annotating
shardings — XLA inserts and schedules the collectives.  This module owns
the process-wide `jax.sharding.Mesh` the rest of the framework uses.

Axis convention (the full parallelism vocabulary, SURVEY.md §5.7/§5.8):
  dp — data parallel            tp — tensor (model) parallel
  pp — pipeline parallel        sp — sequence/context parallel
  ep — expert parallel
"""

from __future__ import annotations

import contextlib
import threading

import numpy as _np
import jax
from jax.sharding import Mesh, PartitionSpec, NamedSharding

from ..context import _backend_init

__all__ = ["make_mesh", "current_mesh", "use_mesh", "data_parallel_mesh",
           "PartitionSpec", "NamedSharding", "named_sharding"]

_state = threading.local()


def make_mesh(axes=None, devices=None):
    """Create a Mesh.

    axes: dict axis_name -> size (product must cover the device count;
    a -1 size is inferred), e.g. {"dp": -1} or {"dp": 2, "tp": 4}.
    """
    if devices is None:
        _backend_init()
        devices = jax.devices()
    n = len(devices)
    if axes is None:
        axes = {"dp": n}
    names = list(axes.keys())
    sizes = list(axes.values())
    n_known = 1
    for s in sizes:
        if s != -1:
            n_known *= s
    sizes = [s if s != -1 else n // n_known for s in sizes]
    total = 1
    for s in sizes:
        total *= s
    if total != n:
        raise ValueError("mesh axes %s do not cover %d devices" %
                         (dict(zip(names, sizes)), n))
    dev_array = _np.array(devices[:total]).reshape(sizes)
    return Mesh(dev_array, names)


def data_parallel_mesh(n=None):
    _backend_init()
    devs = jax.devices()
    if n is not None:
        devs = devs[:n]
    return make_mesh({"dp": len(devs)}, devs)


def current_mesh():
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh):
    prev = getattr(_state, "mesh", None)
    _state.mesh = mesh
    try:
        yield mesh
    finally:
        _state.mesh = prev


def named_sharding(mesh, *spec):
    return NamedSharding(mesh, PartitionSpec(*spec))
