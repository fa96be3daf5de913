"""What the flash kernels' loops visit under the block-diffusion mask over
what they need to: the plan's `tiles_visited` (the `mx.flash.plan` spans
recorded where the op is traced, forward and backward, every traced call)
over the score tiles of the plan's own sub-tile size that hold a visible
pair, counted from the mask's definition by `benchmarks/bd_counts.py`.
1.0 is the aim and the least a correct kernel can read: more, and the loops
visit tiles whose every pair they then drop.  A count: static per shape, so
a CPU test reads the cell's own.  Prints the tiles that run a mask body
beside those a boundary crosses.  Nothing to read from a program that
records no such plan."""

from .. import bd_counts, program_spans
from . import bd_attention_ms_per_step

LAYER = "kernels"
UNIT = "ratio"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "program_counter"


def read(outcome):
    visited = needed = masked = crossed = 0
    for plan, calls in bd_attention_ms_per_step.plans(outcome):
        for kernel in ("fwd", "bwd"):
            need, cross = bd_counts.tiles(plan["half"], plan["block"],
                                          *plan[kernel]["sub_tile"])
            visited += calls * plan[kernel]["tiles_visited"]
            masked += calls * plan[kernel]["tiles_masked"]
            needed += calls * need
            crossed += calls * cross
    if not needed:
        return None
    program_spans.say_once(
        outcome, "bd-tiles",
        "bench: block diffusion tiles a head, all traced calls: %d visited "
        "of %d that hold a visible pair; %d run a mask body, a boundary "
        "crosses %d" % (visited, needed, masked, crossed))
    return visited / needed
