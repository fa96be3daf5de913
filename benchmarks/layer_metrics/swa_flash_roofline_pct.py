"""The flash kernels' share of their roofline through a causal window: the
time the ALGORITHM's useful work takes at the chip's peak (the larger of
FLOPs over the bf16 peak and bytes over the HBM peak,
`benchmarks/peaks.json`) over the device time measured in the two kernels
inside `mx.swa.attention` (`swa_flash_ms_per_step`).  The work is counted
from the configuration's shapes by `benchmarks/swa_counts.py`: the core over
the VISIBLE pairs (``w S - w (w - 1) / 2`` a head), forward and the
backward's five contractions, in every `sliding_attention` layer at its own
count of query heads.  A kernel that visited the whole causal triangle and
dropped what the window hides would read the window's share of it; the pairs
computed and dropped inside a tile that the window's edge or the diagonal
crosses keep the share under 100 (half of each visited tile at the cell's
256 x 512).  Nothing to read where the step holds no such kernel, or in a
cell whose configuration has no `sliding_window`."""

from .. import moe_counts, program_spans, swa_counts
from . import swa_flash_ms_per_step

LAYER = "kernels"
UNIT = "%"
MOVES = "train_samples_per_s"
BETTER = "higher"
SOURCE = "device_trace"


def read(outcome):
    ms = swa_flash_ms_per_step.read(outcome)
    cfg, cell, f = outcome.cell.config, outcome.cell, outcome.facts
    window = cfg.get("sliding_window")
    if not ms or not window:
        return None
    seq = cfg["train"]["sequence_length"]
    batch = f["rows"] // f["devices"]       # each device runs its own rows
    kv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    heads = [h for kind, h in zip(cfg["layer_types"],
                                  cfg["num_attention_heads_per_layer"])
             if kind == "sliding_attention"]
    flops = sum(swa_counts.core_flops(batch, h, seq, window, hd, hd)
                for h in heads)
    moved = sum(swa_counts.core_bytes(batch, h, kv, seq, hd, hd)
                for h in heads)
    kind = f["device_kind"]
    least, bound = moe_counts.roofline_seconds(
        flops, moved, cell.peak(kind, "bf16_flops_per_s"),
        cell.peak(kind, "hbm_bytes_per_s"))
    program_spans.say_once(
        outcome, "swa-flash-roofline",
        "bench: flash kernels in %d window layers, %d x %s heads x %d "
        "positions through %d keys, %d of %d causal pairs visible a head: "
        "%.4g FLOP, %.4g bytes, %.3f ms at the %s peak against %.3f ms"
        % (len(heads), batch, "/".join(str(h) for h in sorted(set(heads))),
           seq, window, batch * swa_counts.visible_pairs(seq, window),
           batch * swa_counts.causal_pairs(seq), flops, moved, 1e3 * least,
           bound, ms))
    return 100.0 * 1e3 * least / ms
