"""The flash kernels' share of their roofline under the block-diffusion
mask: the time the ALGORITHM's useful work takes at the chip's peak (the
larger of FLOPs over the bf16 peak and bytes over the HBM peak,
`benchmarks/peaks.json`) over the device time measured in the two kernels
inside `mx.bd.attention` (`bd_flash_ms_per_step`).  The work is counted from
the configuration's shapes by `benchmarks/bd_counts.py`: the core over the
VISIBLE pairs (``L^2 + L B`` a head), forward and the backward's four
contractions.  A kernel that visited the whole square and dropped three
quarters of it would read a quarter of what one reads that visits the
visible tiles alone; the pairs computed and dropped inside a tile that a
boundary crosses, and the scores the backward forms again, keep the share
under 100.  Nothing to read where the step holds no such kernel, or in a
cell whose configuration has no `diffusion_block`."""

from .. import bd_counts, moe_counts, program_spans
from . import bd_flash_ms_per_step

LAYER = "kernels"
UNIT = "%"
MOVES = "train_samples_per_s"
BETTER = "higher"
SOURCE = "device_trace"


def read(outcome):
    ms = bd_flash_ms_per_step.read(outcome)
    cfg, cell, f = outcome.cell.config, outcome.cell, outcome.facts
    block = cfg.get("train", {}).get("diffusion_block")
    if not ms or not block:
        return None
    half = cfg["train"]["sequence_length"]
    batch = f["rows"] // f["devices"]       # each device runs its own rows
    heads, kv, hd = cfg["num_attention_heads"], \
        cfg["num_key_value_heads"], cfg["head_dim"]
    layers = cfg["num_hidden_layers"]
    flops = layers * bd_counts.core_flops(batch, heads, half, block, hd, hd)
    moved = layers * bd_counts.core_bytes(batch, heads, kv, half, hd, hd)
    kind = f["device_kind"]
    least, bound = moe_counts.roofline_seconds(
        flops, moved, cell.peak(kind, "bf16_flops_per_s"),
        cell.peak(kind, "hbm_bytes_per_s"))
    program_spans.say_once(
        outcome, "bd-flash-roofline",
        "bench: flash kernels in %d block-diffusion layers, %d x %d heads x "
        "2 x %d positions in blocks of %d, %d of %d pairs visible a head: "
        "%.4g FLOP, %.4g bytes, %.3f ms at the %s peak against %.3f ms"
        % (layers, batch, heads, half, block,
           batch * bd_counts.visible_pairs(half, block),
           batch * 4 * half * half, flops, moved, 1e3 * least, bound, ms))
    return 100.0 * 1e3 * least / ms
