"""Set-up seconds making the step program: `mx.trainer.trace` (Gluon net to
graph), `mx.trainer.build_step` (the jitted step's closure) and
`mx.step.first_call` (trace, lower, compile or load from the cache, the
scope map).  Prints the first call's own split, as the span carries it."""

from .. import program_spans

LAYER = "step program"
UNIT = "s"
MOVES = "setup_s"
BETTER = "lower"
SOURCE = "program_span"


def read(outcome):
    value = program_spans.setup_seconds(
        outcome, ("mx.trainer.trace", "mx.trainer.build_step",
                  "mx.step.first_call"))
    first = program_spans.named(outcome, ("mx.step.first_call",))
    if first and first[0].args:
        program_spans.say_once(
            outcome, "first-call",
            "bench: mx.step.first_call %.3f s: %s" % (
                first[0].end - first[0].start,
                ", ".join("%s %.3f" % kv
                          for kv in sorted(first[0].args.items()))))
    return value
