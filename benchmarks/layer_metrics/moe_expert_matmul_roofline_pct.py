"""The grouped products' share of their roofline: the time their useful
work takes at the chip's peak (the larger of FLOPs over the bf16 peak and
bytes over the HBM peak, `benchmarks/peaks.json`) over the device time
measured under `mx.moe.experts`.  The work is counted, not expected: the
token-expert pairs on held experts a step are the program's counters
`moe_local_assignments_total / moe_stat_steps_total`, put through
`benchmarks/moe_counts.py` at the configuration's widths; how many layers
are routed and how many experts are held, the cell's family says
(`routed_layers_and_experts_held` in `benchmarks/models/<family>.py`).
Padding rows and the backward pass's recomputed hidden states are not
useful work.  Nothing to read without the counters (a program from before
them)."""

from .. import moe_counts, program_spans
from . import moe_expert_matmul_ms_per_step

LAYER = "kernels"
UNIT = "%"
MOVES = "train_samples_per_s"
BETTER = "higher"
SOURCE = "device_trace"


def read(outcome):
    ms = moe_expert_matmul_ms_per_step.read(outcome)
    count = moe_counts.program_counters(
        outcome, ("moe_local_assignments_total", "moe_stat_steps_total"))
    if not ms or not count or not count["moe_stat_steps_total"]:
        return None
    cfg, cell = outcome.cell.config, outcome.cell
    pairs = count["moe_local_assignments_total"] \
        / count["moe_stat_steps_total"]
    layers, held = cell.family().routed_layers_and_experts_held(cfg)
    flops = moe_counts.expert_matmul_flops(
        pairs, cfg["hidden_size"], cfg["moe_intermediate_size"])
    moved = moe_counts.expert_matmul_bytes(
        pairs, cfg["hidden_size"], cfg["moe_intermediate_size"],
        held, layers)
    kind = outcome.facts["device_kind"]
    least, bound = moe_counts.roofline_seconds(
        flops, moved, cell.peak(kind, "bf16_flops_per_s"),
        cell.peak(kind, "hbm_bytes_per_s"))
    program_spans.say_once(
        outcome, "moe-roofline",
        "bench: routed experts %.1f local pairs a step over %d layers: "
        "%.4g FLOP, %.4g bytes, %.3f ms at the %s peak against %.3f ms"
        % (pairs, layers, flops, moved, 1e3 * least, bound, ms))
    return 100.0 * 1e3 * least / ms
