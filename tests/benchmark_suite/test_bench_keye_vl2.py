"""The `keye-vl-2.0-30b-a3b_train_ep8share` cell's own pieces: its five
per-layer readers on made-up outcomes, `benchmarks/dsa_counts.py` and the
family's FLOPs against counts by hand, the configuration's published keys,
its entries in BENCHMARK.json, and the family through the `train_fit` loop
at a tiny size on the CPU (a fixture root of its own) with both controls:
the fp8 one and the one that sees every key."""

import collections
import json
import os

import pytest

import bench_suite_util as util
from benchmarks import dsa_counts, harness, trace
from benchmarks.layer_metrics import (dsa_align_ms_per_step,
                                      dsa_flash_roofline_pct,
                                      dsa_index_ms_per_step,
                                      dsa_index_roofline_pct,
                                      dsa_ms_per_step, flash_bwd_ms_per_step,
                                      flash_fwd_ms_per_step, moe_ms_per_step)
from benchmarks.models import keye_vl2 as family

CELL = "keye-vl-2.0-30b-a3b_train_ep8share"
CONFIG = "keye-vl-2.0-30b-a3b-ep8share"
Span = collections.namedtuple(
    "Span", "id name cat start end thread parent args")
READERS = {"dsa_ms_per_step": dsa_ms_per_step,
           "dsa_index_ms_per_step": dsa_index_ms_per_step,
           "dsa_align_ms_per_step": dsa_align_ms_per_step,
           "dsa_flash_roofline_pct": dsa_flash_roofline_pct,
           "dsa_index_roofline_pct": dsa_index_roofline_pct}
#: the catalog's `config` for `Keye-VL-2.0-30B-A3B`
#: (`/opt/skills/guides/model-configs/architectures.jsonl`), written out
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(util.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(util.REPO, "benchmarks", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


# -- the readers --------------------------------------------------------------
class Outcome:
    def __init__(self, spans, scope_map, events, cell=CELL, **facts):
        self.cell = harness.Cell(cell, 1, 1, 1, 0.0, util.REPO)
        self.facts = dict(facts, program_spans=spans,
                          program_scope_map=scope_map,
                          device_kind="TPU v5 lite", rows=1, devices=1)
        self.end_to_end = {"setup_s": 30.0}
        self.trace = trace.Trace(events) if events else None
        self.spans = None


def _step(sparse=True):
    """One traced step: the head's matmul and, with *sparse*, two layers of
    a sparse attention node (projections, the indexer's projections, the
    selection kernel, the flash kernels, the alignment kernel, the output
    projection; backward the projections', the indexer's and the one
    backward kernel) and a routed node each."""
    events = [{"plane": "/host:CPU", "line": "python",
               "name": "bench.fit_batch", "start_ns": 0, "dur_ns": 100000}]
    scope_map, want = {}, collections.Counter()
    t = [10]

    def op(name, scope, dur, *keys):
        events.append({"plane": "/device:TPU:0", "line": "XLA Ops",
                       "name": "%" + name + " = f32[] fusion()",
                       "start_ns": t[0], "dur_ns": dur})
        scope_map[name] = scope
        t[0] += dur + 5
        for key in keys:
            want[key] += dur

    op("fusion.0", "jit(parallel_step)/mx.loss/jvp(FullyConnected:fc)/dot",
       900)
    for layer in range(2):
        for way, wrap in (("f", "jvp(%s)"), ("b", "transpose(jvp(%s))")):
            op("gmm_%s.%d" % (way, layer), "jit(parallel_step)/mx.loss/"
               + wrap % ("_contrib_RoutedExperts:contrib_routedexperts%d"
                         % layer) + "/mx.moe.experts/gmm/pallas_call", 80,
               "moe")
            if not sparse:
                continue
            node = "jit(parallel_step)/mx.loss/" + wrap % (
                "_contrib_SparseAttention:contrib_sparseattention%d" % layer
            ) + "/mx.dsa"
            op("proj_%s.%d" % (way, layer),
               node + "/mx.dsa.project/dot_general", 200, "dsa")
            op("iproj_%s.%d" % (way, layer),
               node + "/mx.dsa.index/dot_general", 40, "dsa", "index",
               "index_" + way)
            if way == "f":
                op("mx_dsa_select.%d" % layer, node
                   + "/mx.dsa.select/mx_dsa_select/pallas_call", 120, "dsa",
                   "index", "index_f")
                op("mx_flash_fwd.%d" % layer, node
                   + "/mx.flash.fwd/mx_flash_fwd/pallas_call", 300, "dsa",
                   "kernels", "fwd")
                op("mx_dsa_align.%d" % layer, node
                   + "/mx.dsa.align/mx_dsa_align/pallas_call", 250, "dsa",
                   "align")
            else:
                op("scale.%d" % layer, node + "/mx.dsa.align/mul", 10,
                   "dsa", "align")
                op("delta.%d" % layer, node + "/reduce_sum", 20, "dsa")
                op("mx_flash_bwd.%d" % layer, node
                   + "/mx.flash.bwd/mx_flash_bwd/pallas_call", 700, "dsa",
                   "kernels", "bwd")
            op("out_%s.%d" % (way, layer), node + "/mx.dsa.out/dot_general",
               90, "dsa")
    return events, scope_map, want


def test_the_device_readers_sum_their_nodes_and_scopes(capsys):
    events, scope_map, want = _step()
    plan = {"tokens": 16384, "topk": 2048, "heads": 32, "kv_heads": 4,
            "index_heads": 16, "index_head_dim": 64, "select": "kernel",
            "select_rows_on_chip": 256, "select_vmem_limit_bytes": 46415872,
            "form": "masked flash: a selection operand, one bit a pair"}
    spans = [Span(i, "mx.dsa.plan", "dsa", 101.0 + i, 101.5 + i, 11, None,
                  plan) for i in range(2)]
    out = Outcome(spans, scope_map, events, traced_blocks=1,
                  steps_per_block=1)
    assert dsa_ms_per_step.read(out) == pytest.approx(want["dsa"] * 1e-6)
    assert dsa_index_ms_per_step.read(out) == pytest.approx(
        want["index"] * 1e-6)
    assert dsa_align_ms_per_step.read(out) == pytest.approx(
        want["align"] * 1e-6)
    # the accepted readers of the kernels and of the routed layer read this
    # family's nodes as they are
    assert flash_fwd_ms_per_step.read(out) == pytest.approx(
        want["fwd"] * 1e-6)
    assert flash_bwd_ms_per_step.read(out) == pytest.approx(
        want["bwd"] * 1e-6)
    assert moe_ms_per_step.read(out) == pytest.approx(want["moe"] * 1e-6)
    said = capsys.readouterr().out
    assert said.count("bench: mx.dsa.plan (2 traced calls)") == 1
    assert '"select_vmem_limit_bytes": 46415872' in said
    assert "masked flash" in said
    assert "bench: sparse attention mx.dsa.select %.3f ms a step" % (
        2 * 120e-6) in said
    assert "sparse attention mx.flash.bwd %.3f" % (2 * 700e-6) in said
    assert "sparse attention mx.dsa.align %.3f" % (
        want["align"] * 1e-6) in said
    dsa_ms_per_step.read(out)               # said once
    assert "mx.dsa.plan" not in capsys.readouterr().out


def test_the_roofline_shares_are_the_counted_work_over_the_scopes_time(
        capsys, cfg):
    events, scope_map, want = _step()
    out = Outcome([], scope_map, events, traced_blocks=1, steps_per_block=1)
    seq, topk = 16384, 2048
    selected = topk * (topk + 1) // 2 + (seq - topk) * topk
    visible = seq * (seq + 1) // 2
    assert (selected, visible) == (31458304, 134225920)
    # the core over the SELECTED pairs, three times for training
    flops = 4 * 3 * 2 * 32 * selected * (128 + 128)
    assert flops == 4 * dsa_counts.core_flops(1, 32, seq, topk, 128, 128)
    least = flops / 197e12                              # compute bound
    assert least > 4 * dsa_counts.core_bytes(1, 32, 4, seq, topk, 128,
                                             128) / 819e9
    ms = want["kernels"] * 1e-6
    assert dsa_flash_roofline_pct.read(out) == pytest.approx(
        100.0 * 1e3 * least / ms)
    assert 1e3 * least == pytest.approx(31.40, abs=0.01)
    # the indexer's scores over the VISIBLE pairs, the forward alone, over
    # the forward's `mx.dsa.index` + `mx.dsa.select`
    index = 4 * 2 * 16 * 64 * visible
    assert index == 4 * dsa_counts.index_flops(1, 16, 64, seq,
                                               training=False)
    assert dsa_index_roofline_pct.read(out) == pytest.approx(
        100.0 * 1e3 * (index / 197e12) / (want["index_f"] * 1e-6))
    assert 1e3 * index / 197e12 == pytest.approx(5.58, abs=0.01)
    said = capsys.readouterr().out
    assert "4 sparse attention layers, 1 x 32 heads x 16384 tokens, " \
        "31458304 of 134225920 causal pairs selected a head" in said
    assert "16 heads of 64 over 134225920 visible pairs" in said
    assert said.count("compute peak") == 2


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_reads_nothing_where_there_is_nothing(name):
    reader = READERS[name]
    events, scope_map, _ = _step(sparse=False)
    for out in (
            # a step without a sparse attention node
            Outcome([], scope_map, events, traced_blocks=1,
                    steps_per_block=1),
            # a program from before the span store and the scope map (a
            # parent commit)
            Outcome(None, None, events, traced_blocks=1, steps_per_block=1),
            # an untraced run of such a program
            Outcome(None, None, None, traced_blocks=1, steps_per_block=1)):
        assert reader.read(out) is None
    # ... and in a cell whose configuration has no indexer, whatever its
    # trace holds
    events, scope_map, _ = _step()
    if name.endswith("_roofline_pct"):
        out = Outcome([], scope_map, events, traced_blocks=1,
                      cell="kanana-2-30b-a3b_train_ep8share",
                      steps_per_block=1)
        assert reader.read(out) is None


# -- counts -------------------------------------------------------------------
def test_dsa_counts_against_counts_by_hand():
    # six queries keep at most three keys: 1 + 2 + 3 + 3 + 3 + 3 of the
    # 1 + 2 + 3 + 4 + 5 + 6 they see
    assert dsa_counts.visible_pairs(6) == 21
    assert dsa_counts.selected_pairs(6, 3) == 15
    assert dsa_counts.selected_pairs(6, 6) == dsa_counts.selected_pairs(
        6, 99) == 21
    assert dsa_counts.selected_pairs(6, 1) == 6
    # one head, one pair: 16 multiply-adds for the score and 8 for its share
    # of the output; training three times that
    assert dsa_counts.core_flops(1, 1, 1, 1, 16, 8, training=False) \
        == 2 * (16 + 8)
    assert dsa_counts.core_flops(2, 3, 6, 3, 16, 16) \
        == 3 * 2 * 2 * 3 * 15 * 32
    # the indexer scores every VISIBLE pair: 2 heads of 8 a pair
    assert dsa_counts.index_flops(1, 2, 8, 6, training=False) == 2 * 2 * 8 * 21
    assert dsa_counts.index_flops(2, 2, 8, 6) == 3 * 2 * 2 * 2 * 8 * 21
    # bytes: q, o over the query heads and k, v over the key/value heads in
    # bf16, a float32 logsumexp a row; the backward reads them and dO and
    # two float32 rows and writes dq, dk, dv
    fwd = (32 * 16384 * 256 + 4 * 16384 * 256) * 2 + 4 * 32 * 16384
    assert dsa_counts.core_bytes(1, 32, 4, 16384, 2048, 128, 128,
                                 training=False) == fwd
    bwd = (32 * 16384 * 256 + 4 * 16384 * 256) * 2 + 8 * 32 * 16384 \
        + (32 * 16384 * 128 + 4 * 16384 * 256) * 2
    assert dsa_counts.core_bytes(1, 32, 4, 16384, 2048, 128, 128) \
        == fwd + bwd
    # the indexer: its queries, key and head weights in, one bit a pair
    # both ways round and a logsumexp a row out
    assert dsa_counts.index_bytes(1, 16, 64, 16384) == \
        16384 * 17 * 64 * 2 + 16384 * 16 * 4 + 2 * 16384 * 16384 // 8 \
        + 4 * 16384


def test_the_family_s_flops_are_the_algorithm_s(cfg):
    """By hand at a small shape, then the cell's: the attention core over
    the selected pairs, the indexer over the visible ones, one expected
    local pair a token, the head over the rows held."""
    small = {"hidden_size": 8, "head_dim": 4, "num_attention_heads": 2,
             "num_key_value_heads": 1, "num_experts_per_tok": 2,
             "num_experts": 4, "router_experts": 16,
             "moe_intermediate_size": 6, "num_hidden_layers": 3,
             "vocab_size": 10,
             "sa_config": {"indexer_num_heads": 2, "indexer_head_dim": 3,
                           "topk": 3},
             "train": {"sequence_length": 6}}
    per_token = 2 * 8 * 8 + 2 * 8 * 4 + 8 * (6 + 3 + 2) + 8 * 16 \
        + 2 * 4 / 16 * 3 * 8 * 6
    layer = 2 * 6 * per_token + 2 * 2 * 15 * (4 + 4) + 2 * 2 * 3 * 21
    assert family.forward_flops(small) == pytest.approx(
        3 * layer + 2 * 6 * 10 * 8)
    assert family.flops_per_sample(small) == pytest.approx(
        3 * family.forward_flops(small))
    # the cell: 23.58 TFLOP a sample, of which the core over the selected
    # pairs is 6.19 and the indexer's scores 3.30
    assert family.flops_per_sample(cfg) / 1e12 == pytest.approx(23.58,
                                                                abs=0.01)
    assert 4 * dsa_counts.core_flops(1, 32, 16384, 2048, 128, 128) / 1e12 \
        == pytest.approx(6.19, abs=0.01)
    assert 4 * dsa_counts.index_flops(1, 16, 64, 16384) / 1e12 \
        == pytest.approx(3.30, abs=0.01)
    assert family.routed_layers_and_experts_held(cfg) == (4, 16)
    # one expected local pair a token: 8 chosen of 128, 16 held
    assert cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_experts"] == 1.0


def test_the_parameters_are_the_issue_s(cfg):
    """96.9 M a layer (attention 18.87, indexer 2.26, router 0.26, the 16
    held experts 75.50) and an eighth of the vocabulary twice: 465.4 M."""
    table = family.reference.param_table(cfg)
    sizes = collections.Counter()
    for name, (shape, _) in table.items():
        n = 1
        for dim in shape:
            n *= dim
        sizes[name.split(".")[-1] if name.startswith("l0.") else
              "rest" if name.startswith("l") else name] += n
    assert sizes["wq"] + sizes["wk"] + sizes["wv"] + sizes["wo"] == 18874368
    assert sizes["index_wq"] + sizes["index_wk"] + sizes["index_ww"] \
        == 2048 * (1024 + 64 + 16)
    assert sizes["router"] == 128 * 2048
    assert sizes["expert_w1"] * 3 == 16 * 3 * 2048 * 768
    assert sizes["embed"] == sizes["head"] == 18992 * 2048
    assert sum(sizes.values()) / 1e6 == pytest.approx(465.4, abs=0.05)


# -- the configuration and its entries ----------------------------------------
def test_every_unreduced_key_is_the_published_one(cfg):
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "num_local_experts", "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
            assert cfg[key] != value, key
        else:
            assert key in cfg and cfg[key] == value, key
    assert set(cfg["published"]) == set(cfg["reduced"])
    # nested groups are copied whole, and no width is cut
    assert cfg["sa_config"] == PUBLISHED["sa_config"]
    assert cfg["rope_scaling"] == PUBLISHED["rope_scaling"]
    # the router keeps its published width under a key of the file's own
    assert cfg["router_experts"] == PUBLISHED["num_experts"] == 128
    # the floors of a cut: four layers (the period is one layer, none is
    # dense), 16 of 128 experts, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] == 4 and cfg["mlp_only_layers"] == []
    assert cfg["num_experts"] == cfg["num_local_experts"] == 16
    assert cfg["first_expert"] == 0
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["num_experts"] * 8 == cfg["published"]["num_experts"]
    assert (cfg["train"]["sequence_length"],
            cfg["train"]["per_chip_batch"]) == (16384, 1)
    assert cfg["alignment_weight"] == 1.0
    # nothing sizes the routed op's pair buffer: the load is as found
    assert "expert_buffer_factor" not in cfg
    assert "as found" in cfg["assumed"]["router"]
    # the embedding at unit scale beside fan-in-scaled matrices, so that
    # the seeded residual stream does not collapse onto one vector
    assert cfg["embedding_initializer_range"] == 1.0
    assert "arXiv:2204.02311" in cfg["assumed"]["weights"]
    table = family.reference.param_table(cfg)
    assert table["embed"][1] == ("normal", 1.0)
    assert table["head"][1] == table["l0.wq"][1] == ("normal", 0.02)
    assert family.reference.param_table(
        {k: v for k, v in cfg.items() if k != "embedding_initializer_range"}
    )["embed"][1] == ("normal", 0.02)
    for item in ("weights", "optimizer", "precision", "qk_norm", "indexer",
                 "selection", "alignment", "router", "positions", "data",
                 "aux_loss", "per_chip_batch", "remat", "mtp"):
        assert cfg["assumed"][item], item
    for said in ("16^-1/2 * 64^-1/2", "LayerNorm", "partial rotation",
                 "fp8", "stop_gradient"):
        assert said in cfg["assumed"]["indexer"], said
    assert "ties" in cfg["assumed"]["selection"] or \
        "tie" in cfg["assumed"]["selection"]
    assert "q_chunk_size" in cfg["assumed"]["selection"]
    assert "No vision tower" in cfg["assumed"]["positions"]
    assert "eight chips share each layer" in cfg["deployment"]
    assert "experts 0-15, rows 0-18991" in cfg["deployment"]
    assert "an eighth of theirs" in cfg["deployment"]
    assert "_limits_from" in cfg["check"]
    assert set(cfg["check"]["limits"]) == {
        "first_update_difference", "loss_gap", "first_gradient_norm_gap",
        "first_gradient_norm_rms", "update_norm_gap", "update_norm_rms"}


def test_the_cell_is_declared_and_its_readers_list_it(spec, cfg):
    entry = util.named(spec["configs"], CONFIG)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["file"] == "benchmarks/configs/%s.json" % CONFIG
    cell = util.named(spec["workloads"], CELL)
    assert cell == {"name": CELL, "config": CONFIG,
                    "traffic": "fit_prefetch", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    for said in ("1x16384", "2048 of up to 16384 keys", "alignment term",
                 "top-8 of 128", "16 held"):
        assert said in cell["why"], said
    steps = cfg["train"]["steps_per_block"]
    assert ("every step" if steps == 1 else "every %d" % steps) \
        in cell["why"]
    # its own five readers are declared for it alone, wherever they stand
    for name in READERS:
        assert util.named(spec["per_layer"], name)["workloads"] == [CELL], name
    # since PR 42 the cell stands on the accepted lists whose readers find
    # something to read in it (PERF.md section 7 row 31), and on PR 37's four
    listed = {m["name"] for m in spec["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(READERS) | set(util.EXPERT_CELL_LISTS) \
        | set(util.COST_LISTS) <= listed
    # ... and reports the ones without a list
    unlisted = [m["name"] for m in spec["per_layer"] if "workloads" not in m]
    assert len(unlisted) == 9 and "model_flops_util_pct" in unlisted
    loaded = harness.Cell(CELL, 1, 1, 1, 0.0, util.REPO)
    assert {m["name"] for m in loaded.metric_names("per_layer")} \
        == set(unlisted) | listed


def test_the_declared_readers_are_read_through_the_harness(spec):
    """The five entries are the readers' own constants, and the harness
    reads all five for this cell."""
    events, scope_map, _ = _step()
    out = Outcome([], scope_map, events, traced_blocks=1, steps_per_block=1)
    layers = {m["layer"] for m in spec["per_layer"]
              if m["name"] not in READERS}
    declared = [util.named(spec["per_layer"], name)
                for name in sorted(READERS)]
    for m, (name, r) in zip(declared, sorted(READERS.items())):
        assert m == {"name": name, "unit": r.UNIT, "better": r.BETTER,
                     "source": r.SOURCE, "layer": r.LAYER, "moves": r.MOVES,
                     "workloads": [CELL]}
        assert m["layer"] in layers and m["moves"] == "train_samples_per_s"
        assert m["source"] == "device_trace"
        assert m["unit"] == ("%" if name.endswith("_roofline_pct") else "ms")
    out.cell.spec["per_layer"] = declared
    after = harness.per_layer_metrics(out.cell, out)
    assert set(after) == set(READERS)
    for name in ("dsa_flash_roofline_pct", "dsa_index_roofline_pct"):
        assert 0 < after[name]["value"]


def test_the_family_builds_the_file_s_widths(cfg):
    small = dict(cfg, num_hidden_layers=1, hidden_size=64,
                 moe_intermediate_size=32, vocab_size=64,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                 rope_scaling=dict(cfg["rope_scaling"],
                                   mrope_section=[2, 3, 3]))
    net, loss = family.build(small)
    # the net hands the alignment term to the objective through the loss
    assert type(loss).__name__ == "AlignedLoss"
    assert type(loss.loss).__name__ == "SoftmaxCrossEntropyLoss"
    layer = net.layers[0]
    assert type(layer.operator).__name__ == "SparseAttention"
    attrs = layer.operator._attrs
    assert (attrs["index_heads"], attrs["topk"]) == (16, 2048)
    assert attrs["mrope_section"] == (2, 3, 3) and attrs["rope_theta"] == 1e7
    assert net._term_scale == 1.0       # one layer: the mean is it
    routed = layer.feed_forward._attrs
    assert routed["scoring_func"] == "softmax"
    assert "buffer_factor" not in routed
    assert routed["num_experts_per_tok"] == 8 and routed["first_expert"] == 0
    assert "expert_bias" not in routed or not any(routed["expert_bias"])
    assert net.head_weight is not None


def test_the_family_refuses_a_program_without_the_kind(monkeypatch):
    """`build` raises at once, before anything is compiled, where the
    decoder lacks the kind: the parent commit on this cell."""
    from mxnet_tpu.gluon.model_zoo import decoder
    monkeypatch.setattr(decoder, "OPERATOR_KINDS",
                        ("conv", "full_attention", "latent_attention"))
    with pytest.raises(RuntimeError, match="no sparse_attention layer kind"):
        family.build({})


# -- the family through the loop, tiny, on the CPU ----------------------------
TINY = {
    "family": "keye_vl2", "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_experts_per_tok": 2,
    "router_experts": 16, "num_experts": 4, "first_expert": 0,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "rope_theta": 10000000,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                     "type": "default"},
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 8},
    "rms_norm_eps": 1e-6, "norm_topk_prob": True,
    "tie_word_embeddings": False, "num_hidden_layers": 4, "vocab_size": 96,
    "alignment_weight": 1.0, "initializer_range": 0.02, "reduced": [],
    "train": {"optimizer": "sgd", "lr": 0.01, "momentum": 0.9, "wd": 0.0,
              "multi_precision": True, "sequence_length": 48,
              "per_chip_batch": 2, "steps_per_block": 2},
    "check": {
        "reference_rows_per_block": 1,
        # CPU, seeds 7, 11 and 2**31 + 13.  The bf16 program chooses a key
        # or two of a row's 8 otherwise than the float32 reference does (a
        # score rounded across its row's threshold), and one key is an
        # eighth of a query's attention here: first_update_difference 0.137
        # to 0.259 (0.009 with every key chosen, `topk` 48), the fp8
        # control 0.475 to 0.520, the control that sees every key 0.627 to
        # 0.728: the limit lies between.  The leaves' norms do not tell the
        # program from the fp8 control at this size (gap 0.033 to 0.150
        # against 0.133 to 0.191): they carry three times the program's
        # largest.
        "limits": {"first_update_difference": 0.35, "loss_gap": 0.011,
                   "first_gradient_norm_gap": 0.45,
                   "first_gradient_norm_rms": 0.12,
                   "update_norm_gap": 0.32, "update_norm_rms": 0.09}}}


@pytest.fixture()
def root(tmp_path):
    """The suite's fixture root with a tiny cell of this family added as
    a new file and two new entries."""
    root = util.fixture_root(tmp_path)
    with open(os.path.join(root, "benchmarks", "configs",
                           "tiny_keye.json"), "w") as f:
        json.dump(TINY, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny_keye", "source": "test fixture", "reduced": [],
        "file": "benchmarks/configs/tiny_keye.json", "why": "fixture"})
    spec["workloads"].append({
        "name": "tiny_keye_train", "config": "tiny_keye",
        "traffic": "fit_prefetch", "chips": 1, "why": "fixture"})
    with open(path, "w") as f:
        json.dump(spec, f)
    return root


def test_the_tiny_cell_runs_and_is_correct(root, capsys):
    from mxnet_tpu import profiler
    from mxnet_tpu.observability import metrics
    names = ("moe_stat_layers_total", "dsa_selected_keys_total",
             "dsa_visible_keys_total")
    before = [profiler.counter_value(n) for n in names]
    outcome, line = util.run_cell(root, "tiny_keye_train",
                                  seed=2 ** 31 + 13, seconds=3.0)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 12 and line["metrics"] == {}
    layers, selected, visible = (
        profiler.counter_value(n) - b for n, b in zip(names, before))
    # four layers a step, each a sparse attention and a routed layer (whose
    # counter counts the layer-steps); a layer-step sees 2 x 48 x 49 / 2
    # pairs and keeps min(t + 1, 8) a query and the ties with the eighth
    assert 0 < layers and layers % 4 == 0
    assert visible == layers * 2 * dsa_counts.visible_pairs(48)
    assert layers * 2 * dsa_counts.selected_pairs(48, 8) <= selected \
        < visible
    assert metrics.snapshot()["dsa_alignment_loss"]["value"] > 0
    assert "correct: first_update_difference" in capsys.readouterr().out


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 13])
@pytest.mark.parametrize("which", ["fp8", "every-key-visible"])
def test_a_control_of_the_tiny_cell_is_not_correct(root, capsys, which,
                                                   seed):
    """The fp8 reference, and the reference that chooses no keys (a dense
    decoder of the same weights), each in the program's place: neither may
    pass for this model."""
    import jax
    from benchmarks import compare, control, control_selection
    cell = harness.Cell("tiny_keye_train", seed, 0, 0, 0.0, root)
    module = control if which == "fp8" else control_selection
    numbers = module.control_numbers(cell, jax.devices()[:1])
    limits = cell.config["check"]["limits"]
    assert not compare.judge(numbers, limits)
    assert numbers["first_update_difference"][0] > \
        1.3 * limits["first_update_difference"]
    assert "OUTSIDE" in capsys.readouterr().out
