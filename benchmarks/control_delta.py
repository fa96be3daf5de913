#!/usr/bin/env python3
"""The rule's controls of `correct` for a cell whose layers carry a state by
the gated delta rule: the plain reference with a part of the rule left out,
put in the program's place and compared with the reference as it is by the
same numbers and limits as a run.  Two departures, `--sight`:

- ``no_erase`` (the default): the erase term left out, ``S_t = a_t S_{t-1} +
  b_t k_t v_t^T`` (gated linear attention with no delta rule);
- ``single_b``: the write strength not doubled, ``b`` in (0, 1) (the rule
  without its negative eigenvalues).

Each has to come out as not correct: a check that passes either cannot tell
this model's layers from ones that are not its own.  Runs on the chip at the
cell's own size:

    python benchmarks/control_delta.py --workload <name> --seeds 1,2,3 \
        [--sight single_b]

and tiny on the CPU in `tests/benchmark_suite`.  It is `control_mask.py`'s
comparison with the rule's sights (the reference's `loss_sum` takes
`sight`); `control.py` is the precision's control.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

SIGHTS = ("no_erase", "single_b")


def control_numbers(cell, devices, sight=SIGHTS[0]):
    """``number -> (value, detail)`` of the reference under *sight* against
    the reference under its own rule, on the cell's own batches."""
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
        for sight in args.sight or SIGHTS[:1]:
            t0 = time.perf_counter()
            numbers = control_numbers(cell, devices, sight)
            print("control: workload=%s seed=%d rule %s platform=%s "
                  "(%.1f s)" % (cell.name, seed, sight,
                                devices[0].platform,
                                time.perf_counter() - t0), flush=True)
            ok = compare.judge(numbers, cell.config["check"]["limits"])
            print("control: correct=%s" % ok, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
