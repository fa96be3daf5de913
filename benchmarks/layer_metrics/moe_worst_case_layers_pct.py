"""The pair buffer's miss rate: the share of routed layer-steps whose
token-expert pairs on held experts passed the bounded pair buffer, so that
the routed op took its worst-case branch for that layer and step (the
program's counters `moe_worst_case_buffer_layers_total /
moe_stat_layers_total`, folded from counts that leave the compiled step;
set-up's steps count with the window's).  0 where every layer-step fit:
a count of none, not a reading that failed.  Each such layer-step is some
14 ms longer in `kanana-2-30b-a3b_train_ep8share` (PERF.md section 6,
PR 30), so this is the first thing to read when an expert cell's rate
spreads.  Nothing to read from a program without the counters, or where
no routed layer ran."""

from .. import moe_counts, program_spans

LAYER = "step program"
UNIT = "%"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "program_counter"

NAMES = ("moe_worst_case_buffer_layers_total", "moe_stat_layers_total",
         "moe_buffer_rows_total", "moe_local_assignments_total")


def read(outcome):
    c = moe_counts.program_counters(outcome, NAMES)
    if not c or not c["moe_stat_layers_total"]:
        return None
    program_spans.say_once(
        outcome, "moe-buffer",
        "bench: pair buffer: %d of %d routed layer-steps took the "
        "worst-case branch; %.4f of the rows the layers ran at held a pair"
        % (c["moe_worst_case_buffer_layers_total"],
           c["moe_stat_layers_total"],
           c["moe_local_assignments_total"] / c["moe_buffer_rows_total"]))
    return 100.0 * c["moe_worst_case_buffer_layers_total"] \
        / c["moe_stat_layers_total"]
