"""Share of the traced window in which the first device sat idle while the
loop's thread was in no `mx.*` span: the benchmark's own loop, the loss
readback.  `device_idle_pct` less this is the idle the program was in.
The program's spans are put on the trace's clock by the benchmark's own,
which exist on both (`program_spans.clock_offset`)."""

from .. import program_spans

LAYER = "device"
UNIT = "%"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"


def read(outcome):
    by = program_spans.idle_by_program_span(outcome)
    if by is None:
        return None
    program_spans.say_once(
        outcome, "idle",
        "bench: idle by program span " + ", ".join(
            "%s %.6f s" % kv for kv in sorted(by.items(),
                                               key=lambda kv: -kv[1])))
    return 100.0 * by.get("(outside)", 0.0) / outcome.trace.window_s
