#!/usr/bin/env python3
"""The second control of `correct` for a cell whose attention chooses its
keys: the plain reference with the selection switched off (every causal key
visible: a dense decoder of the same weights) put in the program's place,
compared with the reference as it is by the same numbers and limits as a
run.  It has to come out as not correct: a check that passes it cannot tell
this model from a dense one.  Runs on the chip at the cell's own size:

    python benchmarks/control_selection.py --workload <name> --seeds 1,2,3

and tiny on the CPU in `tests/benchmark_suite`.  The benchmark's own runs do
not run it; `control.py` is the precision's control.
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time
import types


def control_numbers(cell, devices):
    """``number -> (value, detail)`` of the reference without its selection
    against the reference with it, on the cell's own batches."""
    from benchmarks import compare
    from benchmarks.kinds import train_fit

    family = cell.family()
    ref = family.reference
    table = ref.param_table(cell.config)
    rows = cell.config["train"]["per_chip_batch"] * len(devices)
    batches = family.batches(cell.config, cell.seed,
                             cell.traffic["ring_batches"], rows)
    dense = types.SimpleNamespace(reference=types.SimpleNamespace(
        loss_sum=functools.partial(ref.loss_sum, select=False),
        ROWS_INDEPENDENT=ref.ROWS_INDEPENDENT))
    low = train_fit._reference_readings(cell, dense, table, batches)
    kept = train_fit._reference_readings(
        cell, family, table, batches, first_update=low.pop("first_update"))
    compare.keep_readings(
        os.path.join(cell.root, ".bench_out", "control-selection-%s-%d.json"
                     % (cell.name, cell.seed)), low, kept)
    return compare.training_numbers(low, kept)


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
        print("control: workload=%s seed=%d every key visible platform=%s "
              "(%.1f s)" % (cell.name, seed, devices[0].platform,
                            time.perf_counter() - t0), flush=True)
        ok = compare.judge(numbers, cell.config["check"]["limits"])
        print("control: correct=%s" % ok, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
