"""HBM bytes a step by the compiled text: the logical bytes that the
instructions a traced step executed read from and write to HBM
(`profiler.cost_map`, each device event priced by its instruction; the
on-chip memory's sums are printed beside it, and the eight scopes that
most of the bytes belong to, in whatever fusion they ride).  A count from the text laid
over the trace, not a measurement of traffic: tile padding and what a
kernel reads twice are not in it."""

from .. import program_costs

LAYER = "step program"
UNIT = "GB"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"


def read(outcome):
    p = program_costs.priced(outcome)
    if p is None:
        return None
    read_b = p.sum(lambda r: r["hbm_bytes_read"])
    written = p.sum(lambda r: r["hbm_bytes_written"])
    program_costs.program_spans.say_once(
        outcome, "costs-hbm",
        "bench: a step's instructions read %.3f GB from HBM and write "
        "%.3f; from the on-chip memory %.3f and %.3f" % (
            read_b / 1e9, written / 1e9,
            p.sum(lambda r: r["onchip_bytes_read"]) / 1e9,
            p.sum(lambda r: r["onchip_bytes_written"]) / 1e9))
    program_costs.say_credit(outcome, p)
    program_costs.say_not_priced(outcome, p)
    program_costs.say_totals(outcome)
    return (read_b + written) / 1e9
