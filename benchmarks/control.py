#!/usr/bin/env python3
"""The control of `correct`: the plain reference put in the program's
place and computed in the nearest precision below the one the
configuration states (fp8 for bf16 compute), compared with the float32
reference by the same numbers and limits as a run.  It has to come out as
not correct.  Runs on the chip at the cell's own size:

    python benchmarks/control.py --workload <name> --seeds 1,2,3

and tiny on the CPU in `tests/benchmark_suite`.  The benchmark's own runs
do not run it.
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def control_numbers(cell, devices):
    """``number -> (value, detail)`` of the fp8 reference against the
    float32 one, on the cell's own batches."""
    from benchmarks import compare
    from benchmarks.kinds import train_fit

    family = cell.family()
    table = family.reference.param_table(cell.config)
    rows = cell.config["train"]["per_chip_batch"] * len(devices)
    batches = family.batches(cell.config, cell.seed,
                             cell.traffic["ring_batches"], rows)
    low = train_fit._reference_readings(cell, family, table, batches,
                                        fp8=True)
    ref = train_fit._reference_readings(
        cell, family, table, batches, first_update=low.pop("first_update"))
    compare.keep_readings(
        os.path.join(cell.root, ".bench_out", "control-%s-%d.json"
                     % (cell.name, cell.seed)), low, ref)
    return compare.training_numbers(low, ref)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(root, ".jax_cache"))
    import jax
    from benchmarks import compare, harness

    for seed in (int(s) for s in args.seeds.split(",")):
        cell = harness.Cell(args.workload, seed, 0, 0, time.perf_counter(),
                            root)
        devices = jax.devices()[:cell.chips]
        t0 = time.perf_counter()
        numbers = control_numbers(cell, devices)
        print("control: workload=%s seed=%d fp8 platform=%s (%.1f s)"
              % (cell.name, seed, devices[0].platform,
                 time.perf_counter() - t0), flush=True)
        ok = compare.judge(numbers, cell.config["check"]["limits"])
        print("control: correct=%s" % ok, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
