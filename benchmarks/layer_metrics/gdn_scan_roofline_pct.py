"""The gated delta rule's share of its roofline: the time the RECURRENCE's
useful work takes at the chip's peak (the larger of FLOPs over the bf16 peak
and bytes over the HBM peak, `benchmarks/peaks.json`) over the device time
measured under `mx.gdn.scan` (`gdn_scan_ms_per_step`), forward and backward.
The work is counted from the configuration's shapes by
`benchmarks/gdn_counts.py`, as the recurrence states it and whatever
implements it: ``7 dk dv`` FLOPs a token and head forward and three times
that with the backward; q, k, v, o, g, b once each way and their gradients.
What a chunkwise form multiplies on top (the chunks' scores, the triangular
solve, the products against the chunk's starting state) is not useful work,
so an implementation in that form reads low, and one that wrote a state a
token to HBM lower still.  Nothing to read where the step holds no such
scope, or in a cell whose configuration has no linear layer."""

from .. import gdn_counts, moe_counts, program_spans
from . import gdn_scan_ms_per_step

LAYER = "kernels"
UNIT = "%"
MOVES = "train_samples_per_s"
BETTER = "higher"
SOURCE = "device_trace"


def read(outcome):
    ms = gdn_scan_ms_per_step.read(outcome)
    cfg, cell, f = outcome.cell.config, outcome.cell, outcome.facts
    layers = cfg.get("layer_types", []).count("linear_attention")
    if not ms or not layers:
        return None
    seq = cfg["train"]["sequence_length"]
    batch = f["rows"] // f["devices"]       # each device runs its own rows
    heads, dk, dv = cfg["linear_num_value_heads"], \
        cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    flops = layers * gdn_counts.rule_flops(batch, seq, heads, dk, dv)
    moved = layers * gdn_counts.rule_bytes(batch, seq, heads, dk, dv)
    kind = f["device_kind"]
    least, bound = moe_counts.roofline_seconds(
        flops, moved, cell.peak(kind, "bf16_flops_per_s"),
        cell.peak(kind, "hbm_bytes_per_s"))
    program_spans.say_once(
        outcome, "gdn-scan-roofline",
        "bench: the gated delta rule in %d layers, %d x %d heads x %d "
        "positions, a state of %d x %d: %.4g FLOP, %.4g bytes, %.3f ms at "
        "the %s peak against %.3f ms" % (
            layers, batch, heads, seq, dk, dv, flops, moved, 1e3 * least,
            bound, ms))
    return 100.0 * 1e3 * least / ms
