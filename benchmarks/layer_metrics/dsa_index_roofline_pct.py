"""The indexer's share of its roofline in the forward pass: the time its
scores over the VISIBLE causal pairs take at the chip's peak (the larger of
FLOPs over the bf16 peak and bytes over the HBM peak,
`benchmarks/peaks.json`; `benchmarks/dsa_counts.py` counts both from the
configuration's shapes: 16 dots of 64 a pair) over the device time measured
under `mx.dsa.index` and `mx.dsa.select` in the forward pass of the
`_contrib_SparseAttention:*` nodes: the projections, the scores, and the
counting that finds each row's k-th largest, which is no useful FLOP and
so lowers the share.  Nothing to read where the step holds no sparse
attention."""

import re

from .. import dsa_counts, moe_counts, program_spans

LAYER = "kernels"
UNIT = "%"
MOVES = "train_samples_per_s"
BETTER = "higher"
SOURCE = "device_trace"

_CHOOSING = re.compile(
    r"_contrib_SparseAttention:.*/mx\.dsa\.(index|select)(/|$)").search


def read(outcome):
    ms = program_spans.device_ms_per_step(
        outcome, lambda op: bool(op and _CHOOSING(op))
        and program_spans.phase(op) == "forward")
    cfg, cell, f = outcome.cell.config, outcome.cell, outcome.facts
    if not ms or "sa_config" not in cfg:
        return None
    sa, seq = cfg["sa_config"], cfg["train"]["sequence_length"]
    batch = f["rows"] // f["devices"]
    layers = cfg["num_hidden_layers"]
    shape = (batch, sa["indexer_num_heads"], sa["indexer_head_dim"], seq)
    flops = layers * dsa_counts.index_flops(*shape, training=False)
    moved = layers * dsa_counts.index_bytes(*shape)
    kind = f["device_kind"]
    least, bound = moe_counts.roofline_seconds(
        flops, moved, cell.peak(kind, "bf16_flops_per_s"),
        cell.peak(kind, "hbm_bytes_per_s"))
    program_spans.say_once(
        outcome, "dsa-index-roofline",
        "bench: the indexer's forward scores in %d sparse attention layers, "
        "%d heads of %d over %d visible pairs: %.4g FLOP, %.4g bytes, %.3f "
        "ms at the %s peak against %.3f ms"
        % (layers, sa["indexer_num_heads"], sa["indexer_head_dim"],
           batch * dsa_counts.visible_pairs(seq), flops, moved, 1e3 * least,
           bound, ms))
    return 100.0 * 1e3 * least / ms
