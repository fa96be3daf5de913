"""Which TPU chips a process may use, decided without touching a backend.

A chip belongs to one process at a time: a process that has initialized
JAX on the TPU holds every chip it can see, and a second process that
needs one fails at start-up (libtpu's multi-process lockfile).  So a
parent that only moves bytes — the fleet router, a job launcher — must
neither initialize the TPU backend itself nor let its children each
claim the whole host.  This module counts the host's chips from the
environment and ``/dev`` (never from JAX) and builds the environment
that binds a child to exactly one of them.
"""

from __future__ import annotations

import glob
import os

__all__ = ["host_chips", "one_chip_env"]


def host_chips():
    """Indices of the TPU chips this process's children could open:
    ``TPU_VISIBLE_CHIPS`` where it is set, else one index per TPU device
    node (``/dev/vfio/<n>`` on v5e and later, ``/dev/accel<n>`` before).
    Empty when ``JAX_PLATFORMS`` excludes the TPU or the host has none —
    every process then shares the CPU and there is nothing to divide."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.lower().split(","):
        return []
    visible = os.environ.get("TPU_VISIBLE_CHIPS")
    if visible:
        return [int(c) for c in visible.split(",")]
    nodes = [n for n in glob.glob("/dev/vfio/*") + glob.glob("/dev/accel*")
             if n[-1].isdigit()]
    # libtpu numbers the chips it finds 0..n-1 whatever the nodes are
    # called (a one-chip slice shows /dev/vfio/3 and opens as chip 0)
    return list(range(len(nodes)))


def one_chip_env(chip):
    """Environment entries that make a child process see exactly the
    chip at index *chip* as its one TPU device (libtpu process bounds;
    honoured by the installed libtpu, CHANGES.md PR 21)."""
    return {"TPU_VISIBLE_CHIPS": str(int(chip)),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1"}
