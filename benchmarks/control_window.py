#!/usr/bin/env python3
"""The window's control of `correct` for a cell whose attention looks
through a sliding window in some layers: the plain reference with every
causal key visible in those layers (a decoder of the same weights that has
no window), put in the program's place and compared with the reference as it
is by the same numbers and limits as a run.  It has to come out as not
correct: a check that passes it cannot tell this model's attention from
plain causal attention.  Runs on the chip at the cell's own size:

    python benchmarks/control_window.py --workload <name> --seeds 1,2,3

and tiny on the CPU in `tests/benchmark_suite`.  It is `control_mask.py`
with the one sight ``causal`` (the reference's `loss_sum` takes `sight`);
`control.py` is the precision's control.
"""

from __future__ import annotations

import os
import sys

SIGHT = "causal"


def control_numbers(cell, devices):
    """``number -> (value, detail)`` of the reference without its window
    against the reference with it, on the cell's own batches."""
    from benchmarks import control_mask
    return control_mask.control_numbers(cell, devices, SIGHT)


def main(argv=None):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks import control_mask
    return control_mask.main(list(sys.argv[1:] if argv is None else argv)
                             + ["--sight", SIGHT])


if __name__ == "__main__":
    sys.exit(main())
