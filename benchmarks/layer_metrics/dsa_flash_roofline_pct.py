"""The attention kernels' share of their roofline inside a sparse attention
block: the time the ALGORITHM's useful work takes at the chip's peak (the
larger of FLOPs over the bf16 peak and bytes over the HBM peak,
`benchmarks/peaks.json`) over the device time measured under `mx.flash.fwd`
and `mx.flash.bwd` inside the `_contrib_SparseAttention:*` nodes.  The work
is counted from the configuration's shapes by `benchmarks/dsa_counts.py`:
the core over the pairs a query SELECTED (`min(t + 1, topk)` a query),
forward and the backward's four contractions.  A masked kernel that visits
every causal tile and drops what the selection did not choose reads a small
share here, a kernel that touches the chosen pairs alone at most 100%.
Nothing to read where the step holds no such kernel."""

from .. import dsa_counts, moe_counts, program_spans
from . import dsa_ms_per_step

LAYER = "kernels"
UNIT = "%"
MOVES = "train_samples_per_s"
BETTER = "higher"
SOURCE = "device_trace"


def read(outcome):
    ms = dsa_ms_per_step.phase_ms(outcome, "mx.flash.fwd", "mx.flash.bwd")
    cfg, cell, f = outcome.cell.config, outcome.cell, outcome.facts
    if not ms or "sa_config" not in cfg:
        return None
    seq, topk = cfg["train"]["sequence_length"], cfg["sa_config"]["topk"]
    batch = f["rows"] // f["devices"]       # each device runs its own rows
    heads, kv, hd = cfg["num_attention_heads"], \
        cfg["num_key_value_heads"], cfg["head_dim"]
    layers = cfg["num_hidden_layers"]
    flops = layers * dsa_counts.core_flops(batch, heads, seq, topk, hd, hd)
    moved = layers * dsa_counts.core_bytes(batch, heads, kv, seq, topk, hd,
                                           hd)
    kind = f["device_kind"]
    least, bound = moe_counts.roofline_seconds(
        flops, moved, cell.peak(kind, "bf16_flops_per_s"),
        cell.peak(kind, "hbm_bytes_per_s"))
    program_spans.say_once(
        outcome, "dsa-flash-roofline",
        "bench: attention kernels in %d sparse attention layers, %d x %d "
        "heads x %d tokens, %d of %d causal pairs selected a head: %.4g "
        "FLOP, %.4g bytes, %.3f ms at the %s peak against %.3f ms"
        % (layers, batch, heads, seq,
           batch * dsa_counts.selected_pairs(seq, topk),
           batch * dsa_counts.visible_pairs(seq), flops, moved, 1e3 * least,
           bound, ms))
    return 100.0 * 1e3 * least / ms
