#!/usr/bin/env python3
"""The mechanism's controls of `correct` for a cell whose layers carry a
state by the selective state-space recurrence: the plain reference with a
part of the recurrence left out, put in the program's place and compared with
the reference as it is by the same numbers and limits as a run.  Two
departures, `--sight`:

- ``no_decay`` (the default): the state never decays, ``exp(dt A) = 1`` (a
  running sum of ``dt x (x) B``: linear attention with no forgetting);
- ``no_skip``: the skip ``D x`` left out.

Each has to come out as not correct: a check that passes either cannot tell
this model's layers from ones that are not its own.  Runs on the chip at the
cell's own size:

    python benchmarks/control_ssm.py --workload <name> --seeds 1,2,3 \
        [--sight no_skip]

and tiny on the CPU in `tests/benchmark_suite`.  It is `control_mask.py`'s
comparison with the recurrence's sights (the reference's `loss_sum` takes
`sight`); `control.py` is the precision's control.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

SIGHTS = ("no_decay", "no_skip")


def control_numbers(cell, devices, sight=SIGHTS[0]):
    """``number -> (value, detail)`` of the reference under *sight* against
    the reference under its own recurrence, on the cell's own batches."""
    from benchmarks import control_mask
    return control_mask.control_numbers(cell, devices, sight)


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
            print("control: workload=%s seed=%d recurrence %s platform=%s "
                  "(%.1f s)" % (cell.name, seed, sight,
                                devices[0].platform,
                                time.perf_counter() - t0), flush=True)
            ok = compare.judge(numbers, cell.config["check"]["limits"])
            print("control: correct=%s" % ok, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
