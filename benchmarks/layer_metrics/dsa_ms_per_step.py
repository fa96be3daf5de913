"""Device time per step under the graph's `_contrib_SparseAttention:*`
nodes, forward and backward: the projections with the per-head norms and
rotary positions (`mx.dsa.project`), the indexer's projections
(`mx.dsa.index`), its scores and each query's k-th largest
(`mx.dsa.select`), the two flash kernels with the selection as an operand
(`mx.flash.fwd`, `mx.flash.bwd`), the alignment term and its gradient
(`mx.dsa.align`), the output projection (`mx.dsa.out`).  Prints the
block's plan beside it, as the `mx.dsa.plan` spans carry it (one per traced
call), and the phases.  Nothing to read where the step holds no such
node."""

import json

from .. import program_spans

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"

NODE = r"_contrib_SparseAttention:"
PHASES = ("mx.dsa.project", "mx.dsa.index", "mx.dsa.select", "mx.flash.fwd",
          "mx.flash.bwd", "mx.dsa.align", "mx.dsa.out")


def phase_ms(outcome, *phases):
    """Device ms a step under *phases* inside the sparse attention nodes;
    None where none of them ran."""
    found = [program_spans.scope_ms_per_step(
        outcome, NODE + r".*/%s(/|$)" % p.replace(".", r"\."))
        for p in phases]
    found = [ms for ms in found if ms is not None]
    return sum(found) if found else None


def read(outcome):
    value = program_spans.scope_ms_per_step(outcome, r"[/(]" + NODE)
    seen = {}
    for span in program_spans.named(outcome, ("mx.dsa.plan",)) or ():
        if span.args:
            key = json.dumps(span.args, sort_keys=True)
            seen[key] = seen.get(key, 0) + 1
    for key, calls in seen.items():
        program_spans.say_once(
            outcome, "dsa-plan:" + key,
            "bench: mx.dsa.plan (%d traced calls) %s" % (calls, key))
    for phase in PHASES:
        ms = phase_ms(outcome, phase)
        if ms is not None:
            program_spans.say_once(
                outcome, "dsa-phase:" + phase,
                "bench: sparse attention %s %.3f ms a step" % (phase, ms))
    return value
