"""Multi-host (multi-process) initialization and batch plumbing.

The reference scales across hosts with ps-lite processes launched by
`tools/launch.py` under `DMLC_*` env vars (SURVEY §2.3, §5.8).  The
TPU-native equivalent is jax.distributed: every process joins one
coordinator, `jax.devices()` becomes the GLOBAL device list (local
chips + every peer's), and a `Mesh` over it makes XLA route collectives
over ICI within a slice and DCN across slices — no NCCL/MPI port.

`init_multihost()` reads BOTH naming schemes, so the reference's
launcher bootstraps this path unchanged:

- DMLC_PS_ROOT_URI / DMLC_PS_ROOT_PORT -> coordinator address
- DMLC_NUM_WORKER                      -> process count
- DMLC_WORKER_ID / DMLC_WORKER_RANK    -> process id
- or the jax-native COORDINATOR_ADDRESS / NUM_PROCESSES / PROCESS_ID

Typical flow (each process)::

    from mxnet_tpu.parallel import multihost
    multihost.init_multihost()                  # env-driven
    mesh = multihost.global_mesh({"dp": -1})
    trainer = ParallelTrainer(net, loss, mesh=mesh, ...)
    trainer.fit_batch(x_local, y_local)         # host-local shards

`ParallelTrainer._device_batch` detects a mesh that spans processes and
assembles host-local arrays into global ones automatically
(`host_local_to_global`), so each host feeds only its own rows —
exactly the per-worker batch contract of the reference's data-parallel
kvstore path.
"""

from __future__ import annotations

import os

import jax

__all__ = ["init_multihost", "global_mesh", "host_local_to_global",
           "global_to_host_local", "is_multihost_mesh",
           "process_index", "process_count"]


def init_multihost(coordinator=None, num_processes=None,
                   process_id=None, **kwargs):
    """Join (or start) the jax.distributed coordination service.

    Arguments fall back to DMLC_* then jax-native env vars (table in
    the module docstring).  No-op if already initialized or if the
    process count resolves to 1."""
    env = os.environ
    if coordinator is None:
        uri = env.get("DMLC_PS_ROOT_URI")
        port = env.get("DMLC_PS_ROOT_PORT")
        if uri and port:
            coordinator = "%s:%s" % (uri, port)
        else:
            coordinator = env.get("COORDINATOR_ADDRESS")
    if num_processes is None:
        num_processes = int(env.get("DMLC_NUM_WORKER",
                                    env.get("NUM_PROCESSES", 0)) or 0)
    if process_id is None:
        pid = env.get("DMLC_WORKER_ID",
                      env.get("DMLC_WORKER_RANK",
                              env.get("DMLC_RANK",
                                      env.get("PROCESS_ID"))))
        process_id = int(pid) if pid is not None else None
    if num_processes in (0, 1):
        return False
    if jax.distributed.is_initialized():
        return True
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id, **kwargs)
    return True


def process_index():
    return jax.process_index()


def process_count():
    return jax.process_count()


def global_mesh(axes, devices=None):
    """Mesh over the GLOBAL device list (all processes).  ``axes`` maps
    name -> extent with at most one -1 (inferred)."""
    from .mesh import make_mesh
    return make_mesh(axes, devices if devices is not None
                     else jax.devices())


def is_multihost_mesh(mesh):
    """True when the mesh contains devices owned by other processes."""
    me = jax.process_index()
    return any(d.process_index != me for d in mesh.devices.flat)


def host_local_to_global(x, mesh, pspec):
    """Assemble per-host shard(s) into one global jax.Array.

    Each process passes its own rows of the batch; the result behaves
    as the concatenated global array laid out per ``pspec`` (the
    multihost feeding contract of the kvstore data-parallel path)."""
    from jax.experimental import multihost_utils
    return multihost_utils.host_local_array_to_global_array(
        x, mesh, pspec)


def global_to_host_local(x, mesh, pspec):
    """Inverse of :func:`host_local_to_global`: each process receives
    its own rows of a global array (e.g. its slice of predictions)."""
    from jax.experimental import multihost_utils
    return multihost_utils.global_array_to_host_local_array(
        x, mesh, pspec)
