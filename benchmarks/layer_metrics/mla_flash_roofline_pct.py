"""The two flash kernels' share of their roofline where they run inside
a latent attention block (keys wider than values): the time their useful
causal work takes at the chip's peak (the larger of FLOPs over the bf16
peak and bytes over the HBM peak, `benchmarks/peaks.json`) over the device
time measured under `mx.flash.fwd` and `mx.flash.bwd` inside the
`_contrib_LatentAttention:*` nodes.  The work is counted from the
configuration's shapes by `benchmarks/mla_counts.py`: the scores on and
under the diagonal at the two widths, forward and the backward's four
contractions; the scores the backward kernel forms again from q and k,
and the tiles it visits above the diagonal, are not useful work.  Nothing
to read where the step holds no such kernel."""

from .. import mla_counts, moe_counts, program_spans

LAYER = "kernels"
UNIT = "%"
MOVES = "train_samples_per_s"
BETTER = "higher"
SOURCE = "device_trace"

KERNELS = r"_contrib_LatentAttention:.*/mx\.flash\.(fwd|bwd)(/|$)"


def read(outcome):
    ms = program_spans.scope_ms_per_step(outcome, KERNELS)
    cfg, cell, f = outcome.cell.config, outcome.cell, outcome.facts
    if not ms or "kv_lora_rank" not in cfg:
        return None
    seq = cfg["train"]["sequence_length"]
    # each device runs the kernels over its own rows
    batch_heads = f["rows"] // f["devices"] * cfg["num_attention_heads"]
    d = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    shape = (batch_heads, seq, seq, d, cfg["v_head_dim"])
    layers = cfg["num_hidden_layers"]
    flops = layers * mla_counts.flash_flops(*shape)
    moved = layers * mla_counts.flash_bytes(*shape)
    kind = f["device_kind"]
    least, bound = moe_counts.roofline_seconds(
        flops, moved, cell.peak(kind, "bf16_flops_per_s"),
        cell.peak(kind, "hbm_bytes_per_s"))
    program_spans.say_once(
        outcome, "mla-flash-roofline",
        "bench: flash kernels in %d latent attention layers, %d heads x "
        "%d tokens, keys %d and values %d wide: %.4g FLOP, %.4g bytes, "
        "%.3f ms at the %s peak against %.3f ms"
        % (layers, batch_heads, seq, d, cfg["v_head_dim"], flops, moved,
           1e3 * least, bound, ms))
    return 100.0 * 1e3 * least / ms
