"""How uneven the routing is: the tokens of the fullest of the router's
experts over the mean of all of them, averaged over routed layers and
steps (the program's counters `moe_expert_load_max_over_mean_sum /
moe_stat_layers_total`, folded from counts that leave the compiled step).
1 is even.  Prints the held experts' pairs a token and the share of
tokens with no held expert beside it."""

from .. import moe_counts, program_spans

LAYER = "step program"
UNIT = "ratio"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "program_counter"

NAMES = ("moe_expert_load_max_over_mean_sum", "moe_stat_layers_total",
         "moe_stat_steps_total", "moe_assignments_total",
         "moe_local_assignments_total",
         "moe_tokens_without_local_expert_total")


def read(outcome):
    c = moe_counts.program_counters(outcome, NAMES)
    if not c or not c["moe_stat_layers_total"]:
        return None
    k = outcome.cell.config["num_experts_per_tok"]
    tokens = c["moe_assignments_total"] / k     # summed over the layers
    program_spans.say_once(
        outcome, "moe-routing",
        "bench: routing over %d steps: %.4f pairs on held experts a "
        "token-layer, %.2f%% of token-layers with no held expert"
        % (c["moe_stat_steps_total"],
           c["moe_local_assignments_total"] / tokens,
           100.0 * c["moe_tokens_without_local_expert_total"] / tokens))
    return c["moe_expert_load_max_over_mean_sum"] \
        / c["moe_stat_layers_total"]
