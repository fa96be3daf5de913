#!/usr/bin/env python3
"""One run of one cell of the benchmark:

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

A new process each time: reach the chip, build the cell from its files,
warm up its shapes (set-up), measure for `--seconds`, check the output
against the plain reference, print the result as the last line.  Without a
TPU, or with fewer chips than the cell asks for, it prints no result and
exits 1; a run whose output is not correct prints `"correct": false` and
exits 1.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main(argv=None):
    started = time.perf_counter()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    # the compile cache sits at one fixed place inside the checkout (its
    # path is part of the cache key); the program takes the variable
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(root, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from benchmarks import harness
    cell = harness.Cell(args.workload, args.seed, args.seconds, args.trace,
                        started, root)

    import jax
    devices = jax.devices()
    print("bench: workload=%s seed=%d platform=%s kind=%r count=%d"
          % (cell.name, cell.seed, devices[0].platform,
             devices[0].device_kind, len(devices)), flush=True)
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("bench: the cell needs %d TPU chip(s); JAX offers %d %s "
              "device(s) -- nothing was run" % (
                  cell.chips, len(devices), devices[0].platform),
              file=sys.stderr)
        return 1
    devices = devices[:cell.chips]
    outcome = cell.kind().run(cell, devices)
    print(json.dumps(harness.result_line(cell, outcome, devices)),
          flush=True)
    return 0 if outcome.correct else 1


if __name__ == "__main__":
    sys.exit(main())
