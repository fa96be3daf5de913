"""The `kanana-2-30b-a3b_train_ep8share` cell's own pieces: its four
per-layer readers on made-up outcomes, `benchmarks/mla_counts.py` against
counts by hand, the family's FLOPs against the table the cell was sized
with, the configuration's published keys, its entries in BENCHMARK.json,
and the family through the `train_fit` loop at a tiny size on the CPU (a
fixture root of its own) with its fp8 control."""

import collections
import json
import os

import pytest

import bench_suite_util as util
from benchmarks import harness, mla_counts, trace
from benchmarks.layer_metrics import (mla_assemble_ms_per_step,
                                      mla_flash_roofline_pct,
                                      mla_ms_per_step,
                                      moe_expert_matmul_roofline_pct,
                                      moe_ms_per_step,
                                      moe_shared_ms_per_step)
from benchmarks.models import deepseek_v3 as family

CELL = "kanana-2-30b-a3b_train_ep8share"
CONFIG = "kanana-2-30b-a3b-ep8share"
Span = collections.namedtuple(
    "Span", "id name cat start end thread parent args")
READERS = {"mla_ms_per_step": mla_ms_per_step,
           "mla_assemble_ms_per_step": mla_assemble_ms_per_step,
           "mla_flash_roofline_pct": mla_flash_roofline_pct,
           "moe_shared_ms_per_step": moe_shared_ms_per_step}
#: the catalog's `config` for `kanana-2-30b-a3b-instruct-2601`
#: (`/opt/skills/guides/model-configs/architectures.jsonl`), written out
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 1000000, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 128256}


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
    def __init__(self, spans, scope_map, events, counters=None, **facts):
        self.cell = harness.Cell(CELL, 1, 1, 1, 0.0, util.REPO)
        self.facts = dict(facts, program_spans=spans,
                          program_scope_map=scope_map,
                          device_kind="TPU v5 lite", rows=1, devices=1)
        for names, values in (counters or {}).items():
            self.facts["program_counters:" + ",".join(names)] = values
        self.end_to_end = {"setup_s": 30.0}
        self.trace = trace.Trace(events) if events else None
        self.spans = None


def _step(latent=True):
    """One traced step: a dense matmul and, with *latent*, two layers of a
    latent attention node (projections, assembly, the kernels, the output
    projection; backward the same with the one backward kernel), a shared
    expert node and a routed node each."""
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
            mlp = "jit(parallel_step)/mx.loss/" + wrap % (
                "_contrib_GatedMLP:contrib_gatedmlp%d" % layer)
            op("dense_%s.%d" % (way, layer), mlp + "/dot_general", 70)
            if not latent:
                continue
            node = "jit(parallel_step)/mx.loss/" + wrap % (
                "_contrib_LatentAttention:contrib_latentattention%d" % layer
            ) + "/mx.mla"
            op("proj_%s.%d" % (way, layer),
               node + "/mx.mla.project/dot_general", 200, "mla")
            op("rope_%s.%d" % (way, layer),
               node + "/mx.mla.assemble/mul", 30, "mla", "assemble")
            op("cat_%s.%d" % (way, layer),
               node + "/mx.mla.assemble/concatenate", 50, "mla", "assemble")
            if way == "f":
                op("mx_flash_fwd.%d" % layer, node
                   + "/mx.flash.fwd/mx_flash_fwd/pallas_call", 300, "mla",
                   "kernels")
            else:
                op("delta.%d" % layer, node + "/reduce_sum", 20, "mla")
                op("mx_flash_bwd.%d" % layer, node
                   + "/mx.flash.bwd/mx_flash_bwd/pallas_call", 900, "mla",
                   "kernels")
            op("out_%s.%d" % (way, layer), node + "/mx.mla.out/dot_general",
               90, "mla")
            op("shared_%s.%d" % (way, layer), "jit(parallel_step)/mx.loss/"
               + wrap % ("_contrib_GatedMLP:contrib_gatedmlp%d" % (9 + layer))
               + "/mx.moe.shared/dot_general", 110, "shared")
            op("gmm_%s.%d" % (way, layer), "jit(parallel_step)/mx.loss/"
               + wrap % ("_contrib_RoutedExperts:contrib_routedexperts%d"
                         % layer) + "/mx.moe.experts/gmm/pallas_call", 80,
               "moe")
    return events, scope_map, want


def test_the_device_readers_sum_their_nodes_and_scopes(capsys):
    events, scope_map, want = _step()
    plan = {"heads": 32, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "v_head_dim": 128, "kv_lora_rank": 512, "batch": 1, "seq": 8192,
            "assembled_k_bytes": 100663296}
    spans = [Span(i, "mx.mla.plan", "mla", 101.0 + i, 101.5 + i, 11, None,
                  plan) for i in range(2)]
    out = Outcome(spans, scope_map, events, traced_blocks=1,
                  steps_per_block=1)
    assert mla_ms_per_step.read(out) == pytest.approx(want["mla"] * 1e-6)
    assert mla_assemble_ms_per_step.read(out) == pytest.approx(
        want["assemble"] * 1e-6)
    assert moe_shared_ms_per_step.read(out) == pytest.approx(
        want["shared"] * 1e-6)
    # the routed layer's accepted reader reads this family's nodes as it is
    assert moe_ms_per_step.read(out) == pytest.approx(want["moe"] * 1e-6)
    said = capsys.readouterr().out
    assert said.count("bench: mx.mla.plan (2 traced calls)") == 1
    assert '"assembled_k_bytes": 100663296' in said
    assert "bench: latent attention mx.mla.assemble %.3f ms a step" % (
        want["assemble"] * 1e-6) in said
    assert "latent attention mx.flash.bwd %.3f" % (2 * 900e-6) in said
    assert "mx.flash.dkdv" not in said and "mx.flash.dq" not in said
    mla_ms_per_step.read(out)               # said once
    assert "mx.mla.plan" not in capsys.readouterr().out


def test_the_roofline_share_is_the_counted_work_over_the_kernels_time(
        capsys, cfg):
    events, scope_map, want = _step()
    out = Outcome([], scope_map, events, traced_blocks=1, steps_per_block=1)
    scores = 8192 * 8193 // 2
    flops = 5 * 3 * 2 * 32 * scores * (192 + 128)
    assert flops == 5 * mla_counts.flash_flops(32, 8192, 8192, 192, 128)
    least = flops / 197e12                              # compute bound
    assert least > 5 * mla_counts.flash_bytes(32, 8192, 8192, 192, 128) \
        / 819e9
    ms = want["kernels"] * 1e-6
    assert mla_flash_roofline_pct.read(out) == pytest.approx(
        100.0 * 1e3 * least / ms)
    said = capsys.readouterr().out
    assert "5 latent attention layers, 32 heads x 8192 tokens, keys 192 " \
        "and values 128 wide" in said and "compute peak" in said
    # a whole step of useful work at the peak takes 52.3 ms
    assert 1e3 * least == pytest.approx(52.33, abs=0.01)


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_reads_nothing_where_there_is_nothing(name):
    reader = READERS[name]
    events, scope_map, _ = _step(latent=False)
    for out in (
            # a step without a latent attention node or shared experts
            Outcome([], scope_map, events, traced_blocks=1,
                    steps_per_block=1),
            # a program from before the span store and the scope map (a
            # parent commit)
            Outcome(None, None, events, traced_blocks=1, steps_per_block=1),
            # an untraced run of such a program
            Outcome(None, None, None, traced_blocks=1, steps_per_block=1)):
        assert reader.read(out) is None


def test_the_routed_layer_s_roofline_reader_finds_its_keys_here(cfg, capsys):
    """`moe_expert_matmul_roofline_pct` was written beside `lfm2_moe`: it
    asks the cell's family how many layers are routed and how many experts
    are held, and the file says nothing twice for it."""
    for key in ("layer_types", "num_dense_layers", "num_experts",
                "reader_keys"):
        assert key not in cfg, key
    assert family.routed_layers_and_experts_held(cfg) == (4, 16)
    assert family.routed_layers_and_experts_held(
        dict(cfg, num_hidden_layers=9, n_routed_experts=32)) == (8, 32)
    events, scope_map, want = _step()
    names = ("moe_local_assignments_total", "moe_stat_steps_total")
    out = Outcome([], scope_map, events,
                  {names: dict(zip(names, (6500 * 4 * 10, 10)))},
                  traced_blocks=1, steps_per_block=1)
    pairs, d, f = 26000, cfg["hidden_size"], cfg["moe_intermediate_size"]
    moved = 3 * 3 * 2 * (pairs * (d + f) + 4 * 16 * d * f)
    flops = 3 * 3 * 2 * pairs * d * f
    least = max(flops / 197e12, moved / 819e9)
    assert least == moved / 819e9          # few pairs: the weights' bytes
    assert moe_expert_matmul_roofline_pct.read(out) == pytest.approx(
        100.0 * 1e3 * least / (want["moe"] * 1e-6))
    assert "26000.0 local pairs a step over 4 layers" in \
        capsys.readouterr().out


# -- counts -------------------------------------------------------------------
def test_mla_counts_against_counts_by_hand():
    # causal, ends aligned: row i of 4 sees i + 1 keys of 4; of 6, i + 3
    assert mla_counts.visible_scores(4, 4) == 1 + 2 + 3 + 4
    assert mla_counts.visible_scores(4, 6) == 3 + 4 + 5 + 6
    assert mla_counts.visible_scores(6, 4) == 1 + 2 + 3 + 4     # two see none
    assert mla_counts.visible_scores(4, 6, causal=False) == 24
    # one head, one visible pair: 192 multiply-adds for the score, 128 for
    # its share of the output; the backward's four contractions twice that
    assert mla_counts.flash_flops(1, 1, 1, 192, 128, training=False) \
        == 2 * (192 + 128)
    assert mla_counts.flash_flops(1, 1, 1, 192, 128) == 3 * 2 * (192 + 128)
    assert mla_counts.flash_flops(2, 4, 4, 64, 64) == 3 * 2 * 2 * 10 * 128
    # bytes: q, k at 192 and v, o at 128 in bf16, a float32 row statistic
    fwd = 32 * (8192 * (2 * 192 + 2 * 128) * 2 + 8192 * 4)
    assert mla_counts.flash_bytes(32, 8192, 8192, 192, 128,
                                  training=False) == fwd
    bwd = 32 * (8192 * (2 * 192 + 2 * 128) * 2 + 8192 * 8
                + 8192 * (2 * 192 + 128) * 2)
    assert mla_counts.flash_bytes(32, 8192, 8192, 192, 128) == fwd + bwd


def test_the_family_s_flops_are_the_table_s(cfg):
    """ISSUE 30's table, multiply-adds a token forward: the latent block's
    projections 26.35 M and its causal core 41.9 M, the shared experts
    9.44 M, the held experts' expected share 3.54 M, the dense MLP 37.7 M,
    the head 32.8 M: 2.79 GFLOP a token trained, 22.9 TFLOP a step."""
    d, seq, heads = 2048, 8192, 32
    projections = d * heads * 192 + d * 576 + 512 * heads * 256 \
        + heads * 128 * d
    core = (seq // 2) * heads * (192 + 128)
    expert = 3 * d * 768
    dense = projections + core + 3 * d * 6144
    routed = projections + core + 2 * expert + d * 128 \
        + 6 * 16 / 128 * expert
    head = 16032 * d
    by_hand = dense + 4 * routed + head
    assert [round(v / 1e6, 2) for v in (projections, core, 2 * expert,
                                        6 * 16 / 128 * expert)] == \
        [26.35, 41.94, 9.44, 3.54]
    assert family.forward_macs_per_token(cfg) == pytest.approx(by_hand)
    assert family.flops_per_sample(cfg) == pytest.approx(
        3 * 2 * by_hand * seq)
    assert family.flops_per_sample(cfg) / seq / 1e9 == pytest.approx(
        2.79, abs=0.005)
    assert 5 * (projections + core) / by_hand == pytest.approx(0.734,
                                                               abs=0.002)
    # twice the experts held, twice the expected assignments
    more = dict(cfg, n_routed_experts=32)
    assert family.forward_macs_per_token(more) - by_hand == pytest.approx(
        4 * 6 * 16 / 128 * expert)


# -- the configuration and its entries ----------------------------------------
def test_every_unreduced_key_is_the_published_one(cfg):
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
            assert cfg[key] != value, key
        else:
            assert key in cfg and cfg[key] == value, key
    assert set(cfg["published"]) == set(cfg["reduced"])
    # the router keeps its published width under a key of the file's own
    assert cfg["router_experts"] == PUBLISHED["n_routed_experts"] == 128
    assert cfg["qk_head_dim"] == \
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    # the floors of a cut: four layers after the dense one (the period is
    # one layer), 8 experts, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] == 4
    assert cfg["n_routed_experts"] == 16 and cfg["first_expert"] == 0
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["n_routed_experts"] * 8 == cfg["published"]["n_routed_experts"]
    assert (cfg["train"]["sequence_length"],
            cfg["train"]["per_chip_batch"]) == (8192, 1)
    for item in ("weights", "optimizer", "precision", "latent_attention",
                 "norm_denominator", "data", "expert_bias", "aux_loss",
                 "per_chip_batch", "remat"):
        assert cfg["assumed"][item], item
    assert "eight chips share each layer" in cfg["deployment"]
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
    for said in ("1x8192", "192/128", "top-6 of 128", "16 held", "2 shared",
                 "an eighth of their load"):
        assert said in cell["why"], said
    assert "every %d" % cfg["train"]["steps_per_block"] in cell["why"]
    # its own four readers are declared for it, and for no cell without a
    # latent block or shared experts; the routed layer's readers, the
    # kernels' and the phases' list it beside the cells they read already
    for name in READERS:
        assert util.named(spec["per_layer"], name)["workloads"] == [CELL], name
    for name in ("moe_ms_per_step", "moe_expert_matmul_ms_per_step",
                 "moe_expert_matmul_roofline_pct",
                 "moe_expert_load_max_over_mean",
                 "moe_worst_case_layers_pct", "flash_fwd_ms_per_step",
                 "flash_bwd_ms_per_step", "mosaic_time_share_pct",
                 "step_forward_ms", "step_backward_ms", "step_optimizer_ms",
                 "step_unscoped_pct", "setup_program_s"):
        assert CELL in util.named(spec["per_layer"], name)["workloads"], name
    # the latent block is no `_contrib_DotProductAttention` node, and
    # the cell holds no short convolution and no batch norm
    for name in ("attention_ms_per_step", "short_conv_ms_per_step",
                 "batchnorm_ms_per_step"):
        assert CELL not in util.named(
            spec["per_layer"], name)["workloads"], name


def test_the_declared_readers_are_read_through_the_harness(spec):
    """The four entries are the readers' own constants, and the harness
    reads all four for this cell."""
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
    assert util.named(declared, "mla_flash_roofline_pct")["unit"] == "%"
    out.cell.spec["per_layer"] = declared
    after = harness.per_layer_metrics(out.cell, out)
    assert set(after) == set(READERS)
    assert {after[n]["unit"] for n in READERS} == {"ms", "%"}
    assert 0 < after["mla_flash_roofline_pct"]["value"]
    # ... and among the tree's entries for the step program and its
    # kernels that list the cell, each that finds something in this
    # made-up step
    out = Outcome([], scope_map, events, traced_blocks=1, steps_per_block=1)
    out.cell.spec["per_layer"] = [
        m for m in spec["per_layer"] if CELL in m.get("workloads", ())
        and m["layer"] in ("step program", "kernels")
        and m["source"] == "device_trace"]
    assert set(READERS) | {
        "moe_ms_per_step", "moe_expert_matmul_ms_per_step",
        "flash_fwd_ms_per_step", "flash_bwd_ms_per_step",
        "step_forward_ms", "step_backward_ms"} <= set(
        harness.per_layer_metrics(out.cell, out))


def test_the_bias_is_the_configuration_s_in_the_program_and_the_reference(
        cfg):
    b = family.reference.expert_bias(cfg)
    scale = cfg["expert_bias_scale"]
    assert len(b) == 128 and b[0] == scale == -b[73]    # 7 * 73 = 127 mod 128
    assert abs(b).max() == abs(scale)
    assert b[1] == pytest.approx(scale * (1 - 14 / 127))
    # small and positive: the held experts 0-15 keep their share of the
    # load, a little over the even 0.75 pairs a token (their mean bias is
    # 0.17 of the scale), and the pair buffer sees what imbalance there is
    assert 0 < scale <= 0.02 and b[:16].mean() == pytest.approx(
        scale * (1 - 105 / 127))
    small = dict(cfg, num_hidden_layers=1, first_k_dense_replace=0,
                 hidden_size=64, moe_intermediate_size=32, vocab_size=64,
                 num_attention_heads=2, kv_lora_rank=16, qk_nope_head_dim=8,
                 qk_rope_head_dim=4, v_head_dim=8)
    net, _ = family.build(small)
    ffn = net.layers[0].feed_forward
    assert list(ffn.routed._attrs["expert_bias"]) == list(b)
    assert ffn.routed._attrs["routed_scaling_factor"] == 2.448
    assert ffn.routed._attrs["num_experts_per_tok"] == 6
    assert type(ffn.shared).__name__ == "SharedExperts"
    assert net.head_weight is not None


# -- the family through the loop, tiny, on the CPU ----------------------------
TINY = {
    "family": "deepseek_v3", "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_shared_experts": 2,
    "num_experts_per_tok": 3, "router_experts": 16, "n_routed_experts": 4,
    "first_expert": 0, "num_attention_heads": 4, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_theta": 1000000, "rope_interleave": True, "rope_scaling": None,
    "q_lora_rank": None, "n_group": 1, "topk_group": 1,
    "rms_norm_eps": 1e-6, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "norm_topk_prob": True,
    "first_k_dense_replace": 1, "tie_word_embeddings": False,
    "num_hidden_layers": 3, "vocab_size": 96, "expert_bias_scale": 0.05,
    "initializer_range": 0.02, "reduced": [],
    "train": {"optimizer": "sgd", "lr": 0.01, "momentum": 0.9, "wd": 0.0,
              "multi_precision": True, "sequence_length": 32,
              "per_chip_batch": 4, "steps_per_block": 2},
    "check": {
        "reference_rows_per_block": 2,
        # CPU, 4 seeds: the bf16 program reads first_update_difference
        # 0.0078 to 0.0121, the fp8 control 0.0990 to 0.1011; the others at
        # three times the program's largest (loss_gap 0.0017,
        # first_gradient_norm_gap 0.0063 / rms 0.0015, update_norm_gap
        # 0.0079 / rms 0.0017)
        "limits": {"first_update_difference": 0.03, "loss_gap": 0.006,
                   "first_gradient_norm_gap": 0.02,
                   "first_gradient_norm_rms": 0.005,
                   "update_norm_gap": 0.025, "update_norm_rms": 0.005}}}


@pytest.fixture()
def root(tmp_path):
    """The suite's fixture root with a tiny cell of this family added as
    a new file and two new entries."""
    root = util.fixture_root(tmp_path)
    with open(os.path.join(root, "benchmarks", "configs",
                           "tiny_kanana.json"), "w") as f:
        json.dump(TINY, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny_kanana", "source": "test fixture", "reduced": [],
        "file": "benchmarks/configs/tiny_kanana.json", "why": "fixture"})
    spec["workloads"].append({
        "name": "tiny_kanana_train", "config": "tiny_kanana",
        "traffic": "fit_prefetch", "chips": 1, "why": "fixture"})
    with open(path, "w") as f:
        json.dump(spec, f)
    return root


def test_the_tiny_cell_runs_and_is_correct(root, capsys):
    from mxnet_tpu import profiler
    steps0 = profiler.counter_value("moe_stat_steps_total")
    outcome, line = util.run_cell(root, "tiny_kanana_train",
                                  seed=2 ** 31 + 13, seconds=3.0)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 24 and line["metrics"] == {}
    folded = profiler.counter_value("moe_stat_steps_total") - steps0
    assert 0 < folded <= line["attempted"] + 3 + 2
    assert "correct: first_update_difference" in capsys.readouterr().out


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 13])
def test_the_fp8_control_of_the_tiny_cell_is_not_correct(root, capsys, seed):
    import jax
    from benchmarks import compare, control
    cell = harness.Cell("tiny_kanana_train", seed, 0, 0, 0.0, root)
    numbers = control.control_numbers(cell, jax.devices()[:1])
    limits = cell.config["check"]["limits"]
    assert not compare.judge(numbers, limits)
    assert numbers["first_update_difference"][0] > \
        2 * limits["first_update_difference"]
    assert "OUTSIDE" in capsys.readouterr().out
