"""Server-role bootstrap (reference: python/mxnet/kvstore_server.py:85 —
if DMLC_ROLE=server the process blocks in RunServer).

Launch:  DMLC_ROLE=server DMLC_PS_ROOT_PORT=9091 DMLC_NUM_WORKER=2 \
         python -m mxnet_tpu.kvstore_server dist_sync
"""

from __future__ import annotations

import os
import sys

from ._kvstore_impl import KVStoreServer


def run_server(kv_type="dist_sync", host=None, port=None, num_workers=None,
               snapshot_prefix=None):
    # The parameter server is a host-side service: aggregation and the
    # server-side optimizer run on CPU (the reference's ps-lite servers
    # are CPU processes), never on the accelerator.
    import jax
    jax.config.update("jax_platforms", "cpu")
    if jax.default_backend() != "cpu":
        # this process initialized an accelerator backend before the
        # pin: a server that holds the chip takes it from the workers
        raise RuntimeError(
            "kvstore server must run on the cpu backend, but this "
            "process already initialized %r" % jax.default_backend())
    sync = "async" not in kv_type
    # server s of a multi-server group listens at root port + s
    # (tools/launch.py sets DMLC_SERVER_ID; key sharding lives worker-side)
    server_id = int(os.environ.get("DMLC_SERVER_ID", "0"))
    server = KVStoreServer(
        sync_mode=sync,
        num_workers=num_workers or
        int(os.environ.get("DMLC_NUM_WORKER", "1")),
        host=host or os.environ.get("DMLC_PS_ROOT_URI", "127.0.0.1"),
        port=port if port is not None else
        int(os.environ.get("DMLC_PS_ROOT_PORT", "9091")) + server_id,
        server_id=server_id,
        # snapshot_prefix=None defers to MXNET_KVSTORE_SNAPSHOT_PREFIX;
        # with either set, the constructor restores the newest intact
        # snapshot before serving, so worker rejoin pulls resume from
        # committed state after a kill (docs/resilience.md)
        snapshot_prefix=snapshot_prefix)
    server.run()
    return server


if __name__ == "__main__":
    kv_type = sys.argv[1] if len(sys.argv) > 1 else "dist_sync"
    run_server(kv_type)
