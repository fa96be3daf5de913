#!/usr/bin/env python
"""Localhost cluster launcher for distributed kvstore jobs.

Reference: ``tools/launch.py`` (delegates to the dmlc-core local tracker,
``tools/launch.py:28-50``), which spawns scheduler + server + worker
processes on one host with ``DMLC_ROLE`` environment variables
(``tests/nightly/test_all.sh:55,98`` uses ``-n 4 --launcher local``).

TPU-native differences: there is no separate scheduler role — the first
server process binds the root port and doubles as the rendezvous point —
and worker ranks are assigned directly by this script.  A chip belongs
to one process, so on a TPU host each worker is bound to its own chip
(``mxnet_tpu.chips``) and more workers than chips is refused; servers
run on the CPU.

Usage:
    python tools/launch.py -n 2 python examples/train_mnist.py \
        --kv-store dist_sync
"""

from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time


def _load_chips():
    """``mxnet_tpu/chips.py`` loaded by path: importing the package
    would pull in jax, which a launcher has no use for."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_mxnet_tpu_chips", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "mxnet_tpu", "chips.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="Launch a distributed job on localhost "
                    "(reference: tools/launch.py --launcher local)")
    parser.add_argument("-n", "--num-workers", type=int, required=True,
                        help="number of worker processes")
    parser.add_argument("-s", "--num-servers", type=int, default=1,
                        help="number of server processes; server i "
                             "listens on root port + i and keys are "
                             "sharded across servers by stable hash "
                             "(reference: PSKV, kvstore_dist.h:161-169)")
    parser.add_argument("--launcher", default="local",
                        choices=["local"],
                        help="only the local (single-host multi-process) "
                             "launcher is implemented")
    parser.add_argument("--port", type=int, default=None,
                        help="root port (default: pick a free one)")
    parser.add_argument("--env", action="append", default=[],
                        help="extra KEY=VALUE env for all roles")
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="the training command to run per worker")
    args = parser.parse_args(argv)
    if not args.command:
        parser.error("no command given")
    if args.num_servers < 0:
        parser.error("-s/--num-servers must be >= 0 (0 = no parameter "
                     "servers: a pure jax.distributed worker group, "
                     "parallel.multihost)")
    command = args.command
    if command[0] == "--":
        command = command[1:]

    chips_mod = _load_chips()
    chips = chips_mod.host_chips()
    if chips and args.num_workers > len(chips):
        parser.error("%d workers need %d TPU chips (one process per "
                     "chip), this host has %d"
                     % (args.num_workers, args.num_workers, len(chips)))

    port = args.port or _free_port()
    base_env = dict(os.environ)
    for kv in args.env:
        k, _, v = kv.partition("=")
        base_env[k] = v
    base_env.update({
        "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": str(port),
        "DMLC_NUM_WORKER": str(args.num_workers),
        "DMLC_NUM_SERVER": str(args.num_servers),
    })

    procs = []
    try:
        servers = []
        for i in range(args.num_servers):
            env = dict(base_env)
            env["DMLC_ROLE"] = "server"
            env["DMLC_SERVER_ID"] = str(i)
            servers.append(("server%d" % i, subprocess.Popen(
                command, env=env)))
        procs.extend(servers)
        if servers:
            time.sleep(0.3)  # let the root server bind first
        workers = []
        for i in range(args.num_workers):
            env = dict(base_env)
            env["DMLC_ROLE"] = "worker"
            env["DMLC_WORKER_RANK"] = str(i)
            env["DMLC_WORKER_ID"] = str(i)
            if chips:
                env.update(chips_mod.one_chip_env(chips[i]))
            p = subprocess.Popen(command, env=env)
            workers.append(("worker%d" % i, p))
        procs.extend(workers)

        rc = 0
        pending = dict(workers)
        while pending:
            for name, p in list(pending.items()):
                r = p.poll()
                if r is None:
                    continue
                del pending[name]
                if r != 0:
                    print("launch.py: %s exited with code %d" % (name, r),
                          file=sys.stderr)
                    rc = rc or r
            for name, p in servers:
                r = p.poll()
                if r is not None and r != 0:
                    # a dead server deadlocks every worker; fail fast
                    print("launch.py: %s died with code %d — aborting"
                          % (name, r), file=sys.stderr)
                    return r
            time.sleep(0.2)
        return rc
    finally:
        for name, p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        deadline = time.time() + 5
        for name, p in procs:
            try:
                p.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                p.kill()


if __name__ == "__main__":
    sys.exit(main())
