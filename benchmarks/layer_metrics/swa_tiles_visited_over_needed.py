"""What the flash kernels' loops visit through a causal window over what
they need to: the plan's `tiles_visited` (the `mx.flash.plan` spans recorded
where the op is traced, forward and backward, every traced call) over the
score tiles of the plan's own sub-tile size that hold a visible pair, counted
from the window's definition by `benchmarks/swa_counts.py`.  1.0 is the aim
and the least a correct kernel can read: more, and the loops visit tiles
whose every pair they then drop (a causal kernel's loops, bounded by the
diagonal alone, read 272 / 62 at 8192 positions through 512 keys).  A count:
static per shape, so a CPU test reads the cell's own.  Prints the tiles that
run a mask body beside those an edge crosses.  Nothing to read from a program
that records no such plan."""

from .. import program_spans, swa_counts
from . import swa_ms_per_step

LAYER = "kernels"
UNIT = "ratio"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "program_counter"


def read(outcome):
    visited = needed = masked = crossed = 0
    for plan, calls in swa_ms_per_step.plans(outcome):
        for kernel in ("fwd", "bwd"):
            need, cross = swa_counts.tiles(plan["sq"], plan["window"],
                                           *plan[kernel]["sub_tile"])
            visited += calls * plan[kernel]["tiles_visited"]
            masked += calls * plan[kernel]["tiles_masked"]
            needed += calls * need
            crossed += calls * cross
    if not needed:
        return None
    program_spans.say_once(
        outcome, "swa-tiles",
        "bench: window tiles a head, all traced calls: %d visited of %d that "
        "hold a visible pair; %d run a mask body, an edge crosses %d"
        % (visited, needed, masked, crossed))
    return visited / needed
