"""The state-space scan's share of its roofline: the time the RECURRENCE's
useful work takes at the chip's peak (the larger of FLOPs over the bf16 peak
and bytes over the HBM peak, `benchmarks/peaks.json`) over the device time
measured under `mx.ssm.scan` (`ssm_scan_ms_per_step`), forward and backward.
The work is counted from the configuration's shapes by
`benchmarks/ssm_counts.py`, as the recurrence states it and whatever
implements it: ``5 P N`` FLOPs a token and head forward (decay, write, read)
and three times that with the backward; x, dt, B, C and y once each way and
their gradients.  What a chunkwise form multiplies on top (the chunks' scores
and decay matrix, the products against the chunk's starting state) is not
useful work, so an implementation in that form reads low, and one that wrote
a state a token to HBM lower still.  Nothing to read where the step holds no
such scope, or in a cell whose configuration has no mamba layer."""

from .. import moe_counts, program_spans, ssm_counts
from . import ssm_scan_ms_per_step

LAYER = "kernels"
UNIT = "%"
MOVES = "train_samples_per_s"
BETTER = "higher"
SOURCE = "device_trace"


def read(outcome):
    ms = ssm_scan_ms_per_step.read(outcome)
    cfg, cell, f = outcome.cell.config, outcome.cell, outcome.facts
    layers = cfg.get("layer_types", []).count("mamba")
    if not ms or not layers:
        return None
    seq = cfg["train"]["sequence_length"]
    batch = f["rows"] // f["devices"]       # each device runs its own rows
    heads, width, state, groups = cfg["mamba_n_heads"], cfg["mamba_d_head"], \
        cfg["mamba_d_state"], cfg["mamba_n_groups"]
    flops = layers * ssm_counts.scan_flops(batch, seq, heads, width, state)
    moved = layers * ssm_counts.scan_bytes(batch, seq, heads, groups, width,
                                           state)
    kind = f["device_kind"]
    least, bound = moe_counts.roofline_seconds(
        flops, moved, cell.peak(kind, "bf16_flops_per_s"),
        cell.peak(kind, "hbm_bytes_per_s"))
    program_spans.say_once(
        outcome, "ssm-scan-roofline",
        "bench: the state-space recurrence in %d layers, %d x %d heads x %d "
        "positions, a state of %d x %d in %d group(s): %.4g FLOP, %.4g "
        "bytes, %.3f ms at the %s peak against %.3f ms" % (
            layers, batch, heads, seq, width, state, groups, flops, moved,
            1e3 * least, bound, ms))
    return 100.0 * 1e3 * least / ms
