#!/usr/bin/env python3
"""The mask's controls of `correct` for a cell trained by diffusion over
blocks: the plain reference with another mask in the block-diffusion one's
place, put in the program's place and compared with the reference as it is
by the same numbers and limits as a run.  Two masks, `--sight`:

- ``causal``: a causal mask over the ``2L`` positions (what every other
  attention of the benchmark computes);
- ``block_diagonal``: the noised queries' sight of the clean copy taken
  away (a noised query sees its own noised block and nothing else).

Each has to come out as not correct: a check that passes either cannot tell
this model's attention from one that is not its own.  Runs on the chip at
the cell's own size:

    python benchmarks/control_mask.py --workload <name> --seeds 1,2,3

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

SIGHTS = ("causal", "block_diagonal")


def control_numbers(cell, devices, sight):
    """``number -> (value, detail)`` of the reference under the mask *sight*
    against the reference under its own, on the cell's own batches."""
    from benchmarks import compare
    from benchmarks.kinds import train_fit

    family = cell.family()
    ref = family.reference
    table = ref.param_table(cell.config)
    rows = cell.config["train"]["per_chip_batch"] * len(devices)
    batches = family.batches(cell.config, cell.seed,
                             cell.traffic["ring_batches"], rows)
    other = types.SimpleNamespace(reference=types.SimpleNamespace(
        loss_sum=functools.partial(ref.loss_sum, sight=sight),
        ROWS_INDEPENDENT=ref.ROWS_INDEPENDENT))
    low = train_fit._reference_readings(cell, other, table, batches)
    kept = train_fit._reference_readings(
        cell, family, table, batches, first_update=low.pop("first_update"))
    compare.keep_readings(
        os.path.join(cell.root, ".bench_out", "control-mask-%s-%s-%d.json"
                     % (sight, cell.name, cell.seed)), low, kept)
    return compare.training_numbers(low, kept)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sight", choices=SIGHTS, action="append")
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
        for sight in args.sight or SIGHTS:
            t0 = time.perf_counter()
            numbers = control_numbers(cell, devices, sight)
            print("control: workload=%s seed=%d mask %s platform=%s "
                  "(%.1f s)" % (cell.name, seed, sight,
                                devices[0].platform,
                                time.perf_counter() - t0), flush=True)
            ok = compare.judge(numbers, cell.config["check"]["limits"])
            print("control: correct=%s" % ok, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
