"""Set-up seconds inside `import mxnet_tpu` (span `mx.import`, first to last
line of the package's `__init__`) and the package's first look at the
devices (`mx.backend_init`).  The benchmark imports jax and asks for the
devices before it imports the program, so here the backend's start is not
the program's: the reader prints it beside, as the time before `mx.import`."""

from .. import program_spans

LAYER = "process and platform set-up"
UNIT = "s"
MOVES = "setup_s"
BETTER = "lower"
SOURCE = "program_span"


def read(outcome):
    value = program_spans.setup_seconds(
        outcome, ("mx.import", "mx.backend_init"))
    first = program_spans.named(outcome, ("mx.import",))
    if first:
        program_spans.say_once(
            outcome, "before-import",
            "bench: set-up before mx.import (interpreter, jax, the "
            "backend's start) %.3f s, mx.import %.3f s"
            % (first[0].start - outcome.cell.started,
               first[0].end - first[0].start))
    return value
