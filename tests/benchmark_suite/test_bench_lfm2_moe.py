"""The `lfm2-8b-a1b_train_ep4share` cell's own pieces: its five per-layer
readers and the pair buffer's miss rate on made-up outcomes,
`benchmarks/moe_counts.py` against counts by hand, the family's FLOPs
against the table the cell was sized with, the configuration's published
widths, its entries in BENCHMARK.json, and the
family through the `train_fit` loop at a tiny size on the CPU (a fixture
root of its own) with its fp8 control."""

import collections
import json
import os

import pytest

import bench_suite_util as util
from benchmarks import harness, moe_counts, trace
from benchmarks.layer_metrics import (moe_expert_load_max_over_mean,
                                      moe_expert_matmul_ms_per_step,
                                      moe_expert_matmul_roofline_pct,
                                      moe_ms_per_step,
                                      moe_worst_case_layers_pct,
                                      short_conv_ms_per_step)
from benchmarks.models import lfm2_moe as family

CELL = "lfm2-8b-a1b_train_ep4share"
Span = collections.namedtuple(
    "Span", "id name cat start end thread parent args")
READERS = {"moe_ms_per_step": moe_ms_per_step,
           "moe_expert_matmul_ms_per_step": moe_expert_matmul_ms_per_step,
           "moe_expert_matmul_roofline_pct": moe_expert_matmul_roofline_pct,
           "moe_expert_load_max_over_mean": moe_expert_load_max_over_mean,
           "moe_worst_case_layers_pct": moe_worst_case_layers_pct,
           "short_conv_ms_per_step": short_conv_ms_per_step}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(util.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(util.REPO, "benchmarks", "configs",
                           "lfm2-8b-a1b-ep4share.json")) as f:
        return json.load(f)


# -- the readers --------------------------------------------------------------
class Outcome:
    def __init__(self, spans, scope_map, events, counters=None, **facts):
        self.cell = harness.Cell(CELL, 1, 1, 1, 0.0, util.REPO)
        self.facts = dict(facts, program_spans=spans,
                          program_scope_map=scope_map,
                          device_kind="TPU v5 lite")
        for names, values in (counters or {}).items():
            self.facts["program_counters:" + ",".join(names)] = values
        self.end_to_end = {"setup_s": 30.0}
        self.trace = trace.Trace(events) if events else None
        self.spans = None


ROOFLINE_NAMES = ("moe_local_assignments_total", "moe_stat_steps_total")


def _step(routed=True):
    """One traced step: a dense matmul, two short-convolution nodes and
    (with *routed*) two routed nodes, forward and backward, each routed
    node with its four phases."""
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
            conv = "jit(parallel_step)/mx.loss/" + wrap % (
                "_contrib_GatedShortConv:l%d_conv" % layer)
            op("conv_%s.%d" % (way, layer), conv + "/mx.shortconv/dot",
               300, "conv")
            if not routed:
                continue
            node = "jit(parallel_step)/mx.loss/" + wrap % (
                "_contrib_RoutedExperts:l%d_moe" % layer)
            op("route_%s.%d" % (way, layer), node + "/mx.moe.route/top_k",
               30, "moe")
            op("gather_%s.%d" % (way, layer),
               node + "/mx.moe.dispatch/gather", 50, "moe")
            op("gmm_%s.%d" % (way, layer),
               node + "/mx.moe.experts/gmm/pallas_call", 400, "moe",
               "experts")
            op("gate_%s.%d" % (way, layer), node + "/mx.moe.experts/mul",
               40, "moe", "experts")
            op("combine_%s.%d" % (way, layer),
               node + "/mx.moe.combine/dot_general", 60, "moe")
    return events, scope_map, want


def test_the_device_readers_sum_their_nodes_and_scopes(capsys):
    events, scope_map, want = _step()
    plan = {"router_experts": 32, "experts_per_token": 4, "experts_held": 8,
            "tokens": 16384, "pair_bound": 65536, "path": "megablox"}
    spans = [Span(i, "mx.moe.plan", "moe", 101.0 + i, 101.5 + i, 11, None,
                  plan) for i in range(2)]
    out = Outcome(spans, scope_map, events, traced_blocks=1,
                  steps_per_block=1)
    assert moe_ms_per_step.read(out) == pytest.approx(want["moe"] * 1e-6)
    assert moe_expert_matmul_ms_per_step.read(out) == pytest.approx(
        want["experts"] * 1e-6)
    assert short_conv_ms_per_step.read(out) == pytest.approx(
        want["conv"] * 1e-6)
    said = capsys.readouterr().out
    assert said.count("bench: mx.moe.plan (2 traced calls)") == 1
    assert '"pair_bound": 65536' in said
    assert "bench: mx.moe.experts %.3f ms a step" % (
        want["experts"] * 1e-6) in said
    assert "bench: mx.moe.route" in said and "mx.moe.combine" in said
    moe_ms_per_step.read(out)               # said once
    assert "mx.moe.plan" not in capsys.readouterr().out


def test_the_roofline_share_is_from_counted_assignments(capsys, cfg):
    events, scope_map, want = _step()
    # 40 steps counted, 18000 pairs on held experts a step over the layers
    counted = {"moe_local_assignments_total": 720000,
               "moe_stat_steps_total": 40}
    out = Outcome([], scope_map, events, {ROOFLINE_NAMES: counted},
                  traced_blocks=1, steps_per_block=1)
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    flops = 3 * 3 * 2 * 18000 * d * f
    moved = 3 * 3 * 2 * (18000 * (d + f) + 4 * 8 * d * f)
    least = max(flops / 197e12, moved / 819e9)
    assert least == flops / 197e12                      # compute bound
    ms = want["experts"] * 1e-6
    assert moe_expert_matmul_roofline_pct.read(out) == pytest.approx(
        100.0 * 1e3 * least / ms)
    assert "18000.0 local pairs a step over 4 layers" in \
        capsys.readouterr().out
    # twice the pairs counted, twice the share: nothing is expected
    double = dict(counted, moe_local_assignments_total=1440000)
    out2 = Outcome([], scope_map, events, {ROOFLINE_NAMES: double},
                   traced_blocks=1, steps_per_block=1)
    assert moe_expert_matmul_roofline_pct.read(out2) == pytest.approx(
        2 * 100.0 * 1e3 * least / ms, rel=1e-3)


def test_the_load_reader_averages_over_layers_and_steps(capsys):
    names = moe_expert_load_max_over_mean.NAMES
    counted = dict(zip(names, (336.0, 160, 40, 10485760, 3000000, 900000)))
    out = Outcome([], {}, None, {names: counted})
    assert moe_expert_load_max_over_mean.read(out) == pytest.approx(2.1)
    said = capsys.readouterr().out
    # 10485760 pairs / 4 a token = 2621440 token-layers
    assert "%.4f pairs on held experts a token-layer" % (
        3000000 / 2621440) in said
    assert "%.2f%% of token-layers with no held expert" % (
        100.0 * 900000 / 2621440) in said


@pytest.mark.parametrize("overflowed, layers, share", [
    (0, 1200, 0.0), (8, 2952, 100.0 * 8 / 2952), (4, 4, 100.0)])
def test_the_pair_buffer_s_miss_rate_is_a_share_of_layer_steps(
        capsys, overflowed, layers, share):
    """Counters `moe_worst_case_buffer_layers_total / moe_stat_layers_total`
    (PR 30 read 8 of 2952); no overflow is a count of none, 0, and not
    nothing to read."""
    names = moe_worst_case_layers_pct.NAMES
    # rows the layers ran at: the buffer's 24576 where the pairs fit it,
    # every choice of every token (65536) where not
    ran_at = (layers - overflowed) * 24576 + overflowed * 65536
    counted = dict(zip(names, (overflowed, layers, ran_at,
                               int(0.8 * 24576) * layers)))
    out = Outcome([], {}, None, {names: counted})
    got = moe_worst_case_layers_pct.read(out)
    assert got == pytest.approx(share) and isinstance(got, float)
    said = capsys.readouterr().out
    assert "%d of %d routed layer-steps took the worst-case branch" % (
        overflowed, layers) in said
    assert "%.4f of the rows" % (int(0.8 * 24576) * layers / ran_at) in said


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_reads_nothing_where_there_is_nothing(name):
    reader = READERS[name]
    events, scope_map, _ = _step(routed=False)
    no_counts = {n: 0 for n in moe_expert_load_max_over_mean.NAMES}
    zeros = {ROOFLINE_NAMES: {n: 0 for n in ROOFLINE_NAMES},
             moe_expert_load_max_over_mean.NAMES: no_counts,
             moe_worst_case_layers_pct.NAMES: {
                 n: 0 for n in moe_worst_case_layers_pct.NAMES}}
    for out in (
            # a step without a routed layer, counters at zero
            Outcome([], scope_map, events, zeros, traced_blocks=1,
                    steps_per_block=1),
            # a program from before the span store, the scope map and
            # the counters (a parent commit)
            Outcome(None, None, events, {k: None for k in zeros},
                    traced_blocks=1, steps_per_block=1),
            # an untraced run of such a program
            Outcome(None, None, None, {k: None for k in zeros},
                    traced_blocks=1, steps_per_block=1)):
        value = reader.read(out)
        if name == "short_conv_ms_per_step" and out.trace is not None \
                and out.facts["program_scope_map"]:
            assert value == pytest.approx(4 * 300e-6)
        else:
            assert value is None


# -- counts -------------------------------------------------------------------
def test_moe_counts_against_counts_by_hand():
    # one pair through one expert of 2048 -> 1792 -> 2048: three products
    # of 2048 * 1792 multiply-adds
    assert moe_counts.expert_matmul_flops(1, 2048, 1792, training=False) \
        == 3 * 2 * 2048 * 1792
    assert moe_counts.expert_matmul_flops(16384, 2048, 1792) \
        == 3 * 3 * 2 * 16384 * 2048 * 1792
    # bytes: each product reads a row on one side and writes one on the
    # other (2048 + 1792 numbers a pair) and reads its experts' weights
    rows, weights = 16384 * (2048 + 1792), 4 * 8 * 2048 * 1792
    assert moe_counts.expert_matmul_bytes(
        16384, 2048, 1792, 8, 4, training=False) == 3 * 2 * (rows + weights)
    assert moe_counts.expert_matmul_bytes(16384, 2048, 1792, 8, 4) \
        == 3 * 3 * 2 * (rows + weights)
    assert moe_counts.roofline_seconds(197e12, 1.0, 197e12, 819e9) == \
        (1.0, "compute")
    assert moe_counts.roofline_seconds(1.0, 819e9, 197e12, 819e9) == \
        (1.0, "memory")


def test_the_family_s_flops_are_the_table_s(cfg):
    """ISSUE 26's table, multiply-adds a token forward: dense conv layer
    60.8 M, routed attention layer 38.3 M, three routed conv layers of
    27.9 M, head 33.6 M: 216 M, 1.30 GFLOP a token trained."""
    d, seq = 2048, 8192
    conv = 3 * d * d + d * d
    dense = conv + 3 * d * 7168
    expert = 3 * d * 1792                       # one local assignment
    attention = 2 * d * d + 2 * d * 512 + 2 * (seq // 2) * d \
        + d * 32 + expert
    routed_conv = conv + d * 32 + expert
    head = 16384 * d
    by_hand = dense + attention + 3 * routed_conv + head
    assert [round(v / 1e6, 1) for v in (dense, attention, routed_conv,
                                        head)] == [60.8, 38.3, 27.9, 33.6]
    assert family.forward_macs_per_token(cfg) == by_hand
    assert family.flops_per_sample(cfg) == 3 * 2 * by_hand * seq
    assert family.flops_per_sample(cfg) / seq / 1e9 == pytest.approx(
        1.30, abs=0.005)
    # twice the experts held, twice the expected assignments
    more = dict(cfg, num_experts=16)
    assert family.forward_macs_per_token(more) - by_hand == 4 * expert


# -- the configuration and its entries ----------------------------------------
def test_published_widths_are_not_cut(cfg):
    published = {"hidden_size": 2048, "intermediate_size": 7168,
                 "moe_intermediate_size": 1792, "num_attention_heads": 32,
                 "num_key_value_heads": 8, "num_experts_per_tok": 4,
                 "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-05,
                 "norm_topk_prob": True, "rope_theta": 1000000,
                 "routed_scaling_factor": 1, "use_expert_bias": True,
                 "max_position_embeddings": 128000,
                 "model_type": "lfm2_moe",
                 # the router keeps its published width under its own key
                 "num_routed_experts": 32}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "layer_types", "num_experts", "vocab_size"]
    assert cfg["published"]["num_experts"] == cfg["num_routed_experts"]
    assert cfg["published"]["vocab_size"] == 65536
    assert cfg["published"]["num_hidden_layers"] == 24
    # the floors of a cut: a whole period after the dense layer, 8
    # experts, an eighth of the vocabulary
    assert cfg["layer_types"] == ["conv", "full_attention", "conv", "conv",
                                  "conv"]
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 5
    assert cfg["num_dense_layers"] == 1 and cfg["num_experts"] == 8
    assert cfg["vocab_size"] * 4 == cfg["published"]["vocab_size"]
    assert cfg["train"]["sequence_length"] == 8192
    for item in ("tied_head", "weights", "optimizer", "precision",
                 "kv_heads", "data", "expert_bias", "per_chip_batch"):
        assert cfg["assumed"][item], item
    assert "four chips share each layer" in cfg["deployment"]


def test_the_cell_is_declared_and_its_readers_list_it(spec, cfg):
    entry = util.named(spec["configs"], "lfm2-8b-a1b-ep4share")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    cell = util.named(spec["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2-8b-a1b-ep4share", "fit_prefetch", 1)
    assert len(cell["why"]) <= 200 and "4x their share" in cell["why"]
    assert "%d packed" % cfg["train"]["per_chip_batch"] in cell["why"]
    assert "every %d" % cfg["train"]["steps_per_block"] in cell["why"]
    # its own readers are declared for it: the short convolutions for it
    # alone, the routed layer's beside the other expert cell; the kernels'
    # and the phases' metrics list it beside the cells they read already
    assert util.named(spec["per_layer"], "short_conv_ms_per_step")[
        "workloads"] == [CELL]
    for name in set(READERS) | {
            "flash_fwd_ms_per_step", "flash_bwd_ms_per_step",
            "attention_ms_per_step", "mosaic_time_share_pct",
            "step_forward_ms", "step_backward_ms", "step_optimizer_ms",
            "step_unscoped_pct", "setup_program_s"}:
        assert CELL in util.named(spec["per_layer"], name)["workloads"], name
    # no latent block, no shared experts, no batch norm here
    for name in ("mla_ms_per_step", "moe_shared_ms_per_step",
                 "batchnorm_ms_per_step"):
        assert CELL not in util.named(
            spec["per_layer"], name)["workloads"], name


def test_the_declared_readers_are_read_through_the_harness(spec):
    """The entries are the readers' own constants, and the harness reads
    all of them for this cell."""
    events, scope_map, _ = _step()
    counted = {"moe_local_assignments_total": 720000,
               "moe_stat_steps_total": 40}
    names = moe_expert_load_max_over_mean.NAMES
    load = dict(zip(names, (336.0, 160, 40, 10485760, 3000000, 900000)))
    buffer = dict(zip(moe_worst_case_layers_pct.NAMES,
                      (0, 160, 160 * 24576, 3000000)))
    out = Outcome([], scope_map, events,
                  {ROOFLINE_NAMES: counted, names: load,
                   moe_worst_case_layers_pct.NAMES: buffer},
                  traced_blocks=1, steps_per_block=1)
    layers = {m["layer"] for m in spec["per_layer"]
              if m["name"] not in READERS}
    declared = [util.named(spec["per_layer"], name)
                for name in sorted(READERS)]
    for m, (name, r) in zip(declared, sorted(READERS.items())):
        assert m == {"name": name, "unit": r.UNIT, "better": r.BETTER,
                     "source": r.SOURCE, "layer": r.LAYER, "moves": r.MOVES,
                     "workloads": m["workloads"]}
        assert m["layer"] in layers and m["moves"] == "train_samples_per_s"
    out.cell.spec["per_layer"] = declared
    after = harness.per_layer_metrics(out.cell, out)
    assert set(after) == set(READERS)
    assert {after[n]["unit"] for n in READERS} == {"ms", "%", "ratio"}
    # no layer-step overflowed: the miss rate is reported, as 0
    assert after["moe_worst_case_layers_pct"]["value"] == 0.0


def test_the_bias_is_the_configuration_s_in_the_program_and_the_reference(
        cfg):
    b = family.reference.expert_bias(cfg)
    assert len(b) == 32 and b.max() == cfg["expert_bias_scale"] == -b.min()
    assert b[0] == b.max() and b[1] == pytest.approx(0.1 * (1 - 14 / 31))
    small = dict(cfg, layer_types=["conv"], num_dense_layers=0,
                 hidden_size=64, moe_intermediate_size=32, vocab_size=64,
                 num_attention_heads=2, num_key_value_heads=1)
    net, _ = family.build(small)
    assert list(net.layers[0].feed_forward._attrs["expert_bias"]) == list(b)


# -- the family through the loop, tiny, on the CPU ----------------------------
TINY = {
    "family": "lfm2_moe", "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32,
    "layer_types": ["conv", "full_attention", "conv"],
    "num_dense_layers": 1, "num_experts": 4, "num_routed_experts": 8,
    "first_expert": 0, "num_experts_per_tok": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "rope_theta": 1000000, "norm_eps": 1e-5,
    "conv_L_cache": 3, "vocab_size": 96, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "expert_bias_scale": 0.05,
    "initializer_range": 0.02, "reduced": [],
    "train": {"optimizer": "sgd", "lr": 0.01, "momentum": 0.9, "wd": 0.0,
              "multi_precision": True, "sequence_length": 32,
              "per_chip_batch": 4, "steps_per_block": 2},
    "check": {
        "reference_rows_per_block": 2,
        # CPU, 4 seeds: the bf16 program reads first_update_difference
        # 0.0067 to 0.0084, the fp8 control 0.086 to 0.089; the others at
        # three times the program's largest
        "limits": {"first_update_difference": 0.03, "loss_gap": 0.006,
                   "first_gradient_norm_gap": 0.06,
                   "first_gradient_norm_rms": 0.012,
                   "update_norm_gap": 0.05, "update_norm_rms": 0.01}}}


@pytest.fixture()
def root(tmp_path):
    """The suite's fixture root with a tiny cell of this family added as
    a new file and two new entries."""
    root = util.fixture_root(tmp_path)
    with open(os.path.join(root, "benchmarks", "configs",
                           "tiny_lfm2.json"), "w") as f:
        json.dump(TINY, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny_lfm2", "source": "test fixture", "reduced": [],
        "file": "benchmarks/configs/tiny_lfm2.json", "why": "fixture"})
    spec["workloads"].append({
        "name": "tiny_lfm2_train", "config": "tiny_lfm2",
        "traffic": "fit_prefetch", "chips": 1, "why": "fixture"})
    with open(path, "w") as f:
        json.dump(spec, f)
    return root


def test_the_tiny_cell_runs_and_is_correct(root, capsys):
    from mxnet_tpu import profiler
    steps0 = profiler.counter_value("moe_stat_steps_total")
    outcome, line = util.run_cell(root, "tiny_lfm2_train",
                                  seed=2 ** 31 + 13, seconds=3.0)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 24 and line["metrics"] == {}
    # the counts left the steps and were folded, at no dispatch of their
    # own: `correct` holds dispatched = completed = steps
    folded = profiler.counter_value("moe_stat_steps_total") - steps0
    assert 0 < folded <= line["attempted"] + 3 + 2
    assert "correct: first_update_difference" in capsys.readouterr().out


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 13])
def test_the_fp8_control_of_the_tiny_cell_is_not_correct(root, capsys, seed):
    import jax
    from benchmarks import compare, control
    cell = harness.Cell("tiny_lfm2_train", seed, 0, 0, 0.0, root)
    numbers = control.control_numbers(cell, jax.devices()[:1])
    limits = cell.config["check"]["limits"]
    assert not compare.judge(numbers, limits)
    assert numbers["first_update_difference"][0] > \
        2 * limits["first_update_difference"]
    assert "OUTSIDE" in capsys.readouterr().out
