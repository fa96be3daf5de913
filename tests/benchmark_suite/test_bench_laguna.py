"""The `laguna-s-2.1_train_ep32share` cell's own pieces: its five per-layer
readers on made-up outcomes, `benchmarks/swa_counts.py` and the family's
FLOPs against counts by hand and against the reference's own count, the
configuration's published keys, its limits against the chip's readings on
record, its entries in BENCHMARK.json (found by name, wherever they stand),
and the family through the `train_fit` loop at a tiny size on the CPU (a
fixture root of its own) with its two controls: the fp8 one and the
reference without its window."""

import collections
import json
import os

import numpy as np
import pytest

import bench_suite_util as util
from benchmarks import harness, swa_counts, trace
from benchmarks.layer_metrics import (flash_bwd_ms_per_step,
                                      flash_fwd_ms_per_step,
                                      gqa_full_ms_per_step, moe_ms_per_step,
                                      swa_flash_ms_per_step,
                                      swa_flash_roofline_pct, swa_ms_per_step,
                                      swa_tiles_visited_over_needed)
from benchmarks.models import laguna as family

CELL = "laguna-s-2.1_train_ep32share"
CONFIG = "laguna-s-2.1-ep32share"
Span = collections.namedtuple(
    "Span", "id name cat start end thread parent args")
READERS = {"swa_ms_per_step": swa_ms_per_step,
           "swa_flash_ms_per_step": swa_flash_ms_per_step,
           "swa_flash_roofline_pct": swa_flash_roofline_pct,
           "swa_tiles_visited_over_needed": swa_tiles_visited_over_needed,
           "gqa_full_ms_per_step": gqa_full_ms_per_step}
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types",
           "gating_types", "num_attention_heads_per_layer", "num_experts",
           "vocab_size"]
NUMBERS = ("first_update_difference", "loss_gap", "first_gradient_norm_gap",
           "first_gradient_norm_rms", "update_norm_gap", "update_norm_rms")
PERIOD = ["full_attention"] + ["sliding_attention"] * 3
#: the catalog's `config` for `Laguna-S-2.1`
#: (`/opt/skills/guides/model-configs/architectures.jsonl`), written out
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
    "intermediate_size": 12288, "num_hidden_layers": 48,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head",
    "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": PERIOD * 12,
    "moe_apply_router_weight_on_input": False,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "gating_types": ["per_head"] * 48, "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
    "moe_router_logit_softcapping": 0}


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


def plan(seq=4096, window=512, visited=None):
    needed, crossed = swa_counts.tiles(seq, window, 256, 512)
    kernel = {"tiles_visited": needed if visited is None else visited,
              "tiles_needed": needed, "tiles_masked": crossed,
              "tiles_ideal": round(
                  swa_counts.visible_pairs(seq, window) / (256 * 512), 3),
              "sub_tile": [256, 512]}
    return {"sq": seq, "sk": seq, "d": 128, "dtype": "bfloat16",
            "causal": True, "mask": "window", "window": window,
            "fwd": dict(kernel), "bwd": dict(kernel)}


def _step(windowed=True):
    """One traced step: the head's matmul, a gated full layer and a sliding
    layer, each its projections, its attention node with the two kernels
    and its output product, and a routed node.  Not *windowed*: two ungated
    causal layers as LFM2's, no scope."""
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
    for layer, family_ in enumerate(("mx.gqa", "mx.swa")):
        group = (family_ + ".%s/") if windowed else "%.0s"
        for way, wrap in (("f", "jvp(%s)"), ("b", "transpose(jvp(%s))")):
            op("gmm_%s.%d" % (way, layer), "jit(parallel_step)/mx.loss/"
               + wrap % ("_contrib_RoutedExperts:contrib_routedexperts%d"
                         % layer) + "/mx.moe.experts/gmm/pallas_call", 80,
               "moe")
            op("proj_%s.%d" % (way, layer), "jit(parallel_step)/mx.loss/"
               + wrap % (group % "project"
                         + "FullyConnected:fullyconnected%d" % layer)
               + "/dot_general", 200, family_, family_ + ".project")
            op("out_%s.%d" % (way, layer), "jit(parallel_step)/mx.loss/"
               + wrap % (group % "out"
                         + "FullyConnected:fullyconnected9%d" % layer)
               + "/dot_general", 150, family_, family_ + ".out")
            node = "jit(parallel_step)/mx.loss/" + wrap % (
                group % "attention"
                + "_contrib_DotProductAttention:contrib_dotproductattention%d"
                % layer)
            if way == "f":
                op("mx_flash_fwd.%d" % layer,
                   node + "/mx.flash.fwd/mx_flash_fwd/pallas_call", 300,
                   family_, family_ + ".attention", family_ + ".kernels",
                   "fwd")
            else:
                op("delta.%d" % layer, node + "/reduce_sum", 20, family_,
                   family_ + ".attention")
                op("mx_flash_bwd.%d" % layer,
                   node + "/mx.flash.bwd/mx_flash_bwd/pallas_call", 700,
                   family_, family_ + ".attention", family_ + ".kernels",
                   "bwd")
    return events, scope_map, want


def _plans(calls=2, **changes):
    return [Span(i, "mx.flash.plan", "flash", 101.0 + i, 101.5 + i, 11, None,
                 dict(plan(), **changes)) for i in range(calls)]


def test_the_device_readers_sum_their_scopes(capsys):
    events, scope_map, want = _step()
    out = Outcome(_plans(), scope_map, events, traced_blocks=1,
                  steps_per_block=1)
    assert swa_ms_per_step.read(out) == pytest.approx(want["mx.swa"] * 1e-6)
    assert gqa_full_ms_per_step.read(out) == pytest.approx(
        want["mx.gqa"] * 1e-6)
    assert swa_flash_ms_per_step.read(out) == pytest.approx(
        want["mx.swa.kernels"] * 1e-6)
    # the accepted readers of the kernels and of the routed layer read this
    # family's nodes as they are, both kinds of layer together
    assert flash_fwd_ms_per_step.read(out) == pytest.approx(
        want["fwd"] * 1e-6)
    assert flash_bwd_ms_per_step.read(out) == pytest.approx(
        want["bwd"] * 1e-6)
    assert moe_ms_per_step.read(out) == pytest.approx(want["moe"] * 1e-6)
    said = capsys.readouterr().out
    assert said.count("bench: mx.flash.plan (2 traced calls)") == 1
    assert '"mask": "window"' in said and '"window": 512' in said
    for family_ in ("mx.swa", "mx.gqa"):
        assert "bench: %s %.3f ms a step: project %.3f, attention %.3f, " \
            "out %.3f" % ((family_,) + tuple(
                want[k] * 1e-6 for k in (
                    family_, family_ + ".project", family_ + ".attention",
                    family_ + ".out"))) in said
    swa_ms_per_step.read(out)          # said once
    assert "mx.flash.plan" not in capsys.readouterr().out


def test_the_roofline_share_is_the_visible_work_over_the_kernels_time(
        capsys, cfg):
    events, scope_map, want = _step()
    out = Outcome([], scope_map, events, traced_blocks=1, steps_per_block=1)
    seq, window = cfg["train"]["sequence_length"], 512
    visible = window * seq - window * (window - 1) // 2
    assert visible == swa_counts.visible_pairs(seq, window)
    # 14 FLOPs a visible pair and unit of width, 72 heads, three layers
    flops = 3 * 14 * 128 * visible * 72
    assert flops == 3 * swa_counts.core_flops(1, 72, seq, window, 128, 128)
    least = flops / 197e12                              # compute bound
    assert least > 3 * swa_counts.core_bytes(1, 72, 8, seq, 128,
                                             128) / 819e9
    ms = want["mx.swa.kernels"] * 1e-6
    assert swa_flash_roofline_pct.read(out) == pytest.approx(
        100.0 * 1e3 * least / ms)
    said = capsys.readouterr().out
    assert "3 window layers, 1 x 72 heads x %d positions through 512 keys, " \
        "%d of %d causal pairs visible a head" % (
            seq, visible, seq * (seq + 1) // 2) in said
    assert said.count("compute peak") == 1
    # the issue's own number at 8192 positions: 524 GFLOP a layer, 8.0 ms
    # for the three at the chip's peak
    layer = swa_counts.core_flops(1, 72, 8192, 512, 128, 128)
    assert layer == 14 * 128 * 4063488 * 72
    assert layer / 1e9 == pytest.approx(524.3, abs=0.05)
    assert 3 * layer / 197e12 * 1e3 == pytest.approx(7.98, abs=0.01)


def test_the_tiles_reader_counts_the_cell_s_plan_without_a_chip(capsys, cfg):
    """The plan's visits over `swa_counts`' tiles that hold a visible pair,
    at the cell's shape: 1.0.  A plan that visited every causal tile would
    read the causal kernel's count over it."""
    seq = cfg["train"]["sequence_length"]
    needed, crossed = swa_counts.tiles(seq, 512, 256, 512)
    out = Outcome(_plans(4), None, None, traced_blocks=1, steps_per_block=1)
    assert swa_tiles_visited_over_needed.read(out) == 1.0
    assert "%d visited of %d that hold a visible pair; %d run a mask body, " \
        "an edge crosses %d" % (8 * needed, 8 * needed, 8 * crossed,
                                8 * crossed) in capsys.readouterr().out
    out = Outcome([Span(0, "mx.flash.plan", "flash", 1.0, 2.0, 11, None,
                        plan(8192, 512, visited=272))], None, None,
                  traced_blocks=1, steps_per_block=1)
    assert swa_tiles_visited_over_needed.read(out) == pytest.approx(272 / 62)
    # ... and the program's own plan at the cell's shape is the one made up
    # here
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention
    own = attention._plan_args(
        attention._flash_plan(seq, seq, 128, jnp.bfloat16), seq, seq, 128,
        jnp.bfloat16, True, None, attention.Window(512))
    for key, value in plan(seq).items():
        if isinstance(value, dict):
            assert {k: own[key][k] for k in value} == value, key
        else:
            assert own[key] == value, key
    out = Outcome([Span(0, "mx.flash.plan", "flash", 1.0, 2.0, 11, None,
                        own)], None, None, traced_blocks=1,
                  steps_per_block=1)
    assert swa_tiles_visited_over_needed.read(out) == 1.0


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_reads_nothing_where_there_is_nothing(name):
    reader = READERS[name]
    events, scope_map, _ = _step(windowed=False)
    causal = _plans(2)
    for span in causal:
        del span.args["mask"], span.args["window"]
    for out in (
            # a step whose attention is causal and ungated, with its causal
            # plans
            Outcome(causal, scope_map, events, traced_blocks=1,
                    steps_per_block=1),
            # a program from before the span store and the scope map (a
            # parent commit)
            Outcome(None, None, events, traced_blocks=1, steps_per_block=1),
            # an untraced run of such a program
            Outcome(None, None, None, traced_blocks=1, steps_per_block=1)):
        assert reader.read(out) is None
    # ... and the roofline in a cell whose configuration has no
    # `sliding_window`, whatever its trace holds
    events, scope_map, _ = _step()
    if name == "swa_flash_roofline_pct":
        out = Outcome([], scope_map, events, traced_blocks=1,
                      cell="lfm2-8b-a1b_train_ep4share", steps_per_block=1)
        assert reader.read(out) is None


# -- counts -------------------------------------------------------------------
@pytest.mark.parametrize("seq,window,sub_q,sub_k", [
    (64, 20, 16, 16), (60, 7, 16, 32), (48, 48, 16, 16), (50, 1, 8, 16),
    (96, 33, 32, 16)])
def test_swa_counts_against_a_brute_force_count(seq, window, sub_q, sub_k):
    """Every pair, one at a time, from the inequality itself."""
    seen = np.zeros((seq, seq), bool)
    for t in range(seq):
        for s in range(seq):
            seen[t, s] = t - window < s <= t
    pos = np.arange(seq)
    assert (swa_counts.visible(pos[:, None], pos[None, :], window)
            == seen).all()
    assert swa_counts.visible_pairs(seq, window) == seen.sum()
    assert swa_counts.causal_pairs(seq) == np.tril(np.ones((seq, seq))).sum()
    needed = crossed = 0
    for q0 in range(0, seq, sub_q):
        for k0 in range(0, seq, sub_k):
            tile = seen[q0:q0 + sub_q, k0:k0 + sub_k]
            needed += bool(tile.any())
            crossed += bool(tile.any() and not tile.all())
    assert swa_counts.tiles(seq, window, sub_q, sub_k) == (needed, crossed)
    # forward 2 contractions a pair, training 7
    assert swa_counts.core_flops(2, 3, seq, window, 8, 4, False) \
        == 2 * 2 * 3 * seen.sum() * (8 + 4)
    assert swa_counts.core_flops(2, 3, seq, window, 8, 8) \
        == 14 * 8 * 2 * 3 * seen.sum()
    # q and o, k and v, a float32 row; backward those, dO, two rows, dq,
    # dk, dv
    assert swa_counts.core_bytes(1, 4, 2, seq, 8, 8, training=False) \
        == (4 * seq * 16 + 2 * seq * 16) * 2 + 4 * 4 * seq
    assert swa_counts.core_bytes(1, 4, 2, seq, 8, 8) \
        == 2 * ((4 * seq * 16 + 2 * seq * 16) * 2) + 12 * 4 * seq \
        + (4 * seq * 8 + 2 * seq * 16) * 2


def test_the_family_s_flops_are_the_algorithm_s(cfg):
    """By hand at a small shape, against the reference's own count of the
    visible pairs, then the cell's."""
    small = {"hidden_size": 8, "head_dim": 4, "num_key_value_heads": 1,
             "num_attention_heads_per_layer": [2, 3],
             "layer_types": ["full_attention", "sliding_attention"],
             "mlp_layer_types": ["dense", "sparse"], "sliding_window": 3,
             "intermediate_size": 10, "num_experts_per_tok": 2,
             "num_experts": 4, "router_experts": 16,
             "moe_intermediate_size": 6,
             "shared_expert_intermediate_size": 5, "vocab_size": 10,
             "train": {"sequence_length": 6}}
    full = 2 * 8 * 2 * 4 + 2 * 8 * 4 + 8 * 2 + 3 * 8 * 10
    sliding = 2 * 8 * 3 * 4 + 2 * 8 * 4 + 8 * 3 + 8 * 16 + 3 * 8 * 5 \
        + 2 * 4 / 16 * 3 * 8 * 6
    pairs = family.reference.visible_pairs(small, 6)
    assert pairs == [21, 3 * 6 - 3]
    want = 2 * 6 * (full + sliding) \
        + 2 * (2 * pairs[0] + 3 * pairs[1]) * (4 + 4) + 2 * 6 * 10 * 8
    assert family.forward_flops(small) == pytest.approx(want)
    assert family.flops_per_sample(small) == pytest.approx(3 * want)
    # the cell: the core over each layer's own visible pairs
    seq = cfg["train"]["sequence_length"]
    pairs = family.reference.visible_pairs(cfg, seq)
    assert pairs == [swa_counts.causal_pairs(seq) if kind == "full_attention"
                     else swa_counts.visible_pairs(seq, 512)
                     for kind in cfg["layer_types"]]
    core = sum(2 * h * p * 256 for h, p in zip(
        cfg["num_attention_heads_per_layer"], pairs))
    assert family.flops_per_sample(cfg) > 3 * core
    assert family.routed_layers_and_experts_held(cfg) == (4, 8)
    # 0.3125 expected local pairs a token: 10 chosen of 256, 8 held
    assert cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_experts"] == 0.3125


def test_the_parameters_are_the_issue_s(cfg):
    """Full attention 44.19 M, sliding 63.14 M, the dense MLP 113.25 M, a
    routed layer's feed-forward 85.72 M, an eighth of the vocabulary twice:
    811.0 M, 8.11 GB of arguments at 10 bytes a parameter."""
    table = family.reference.param_table(cfg)
    sizes = collections.Counter()
    for name, (shape, _) in table.items():
        layer, _, leaf = name.partition(".")
        group = "attention" if leaf in ("wq", "wk", "wv", "wo", "wg") \
            else "mlp" if leaf in ("w1", "w2", "w3") \
            else "routed" if leaf.startswith(("router", "shared", "expert")) \
            else "rest"
        sizes[(layer if leaf else name, group)] += int(np.prod(shape))
    assert sizes[("l0", "attention")] == sizes[("l4", "attention")] \
        == 3072 * 8192 + 6144 * 3072 + 3072 * 48
    assert sizes[("l1", "attention")] == 3072 * 11264 + 9216 * 3072 \
        + 3072 * 72
    assert sizes[("l0", "mlp")] == 3 * 3072 * 12288
    assert sizes[("l1", "routed")] == 256 * 3072 + 9 * 3 * 3072 * 1024
    assert sizes[("embed", "rest")] == sizes[("head", "rest")] == 12544 * 3072
    total = sum(sizes.values())
    assert total / 1e6 == pytest.approx(811.0, abs=0.05)
    assert 10 * total / 1e9 == pytest.approx(8.11, abs=0.005)


# -- the configuration and its entries ----------------------------------------
def test_every_unreduced_key_is_the_published_one(cfg):
    assert cfg["reduced"] == REDUCED
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert key in cfg["published"], key
            assert cfg[key] != value, key
        else:
            assert key in cfg and cfg[key] == value, key
    assert set(cfg["published"]) == set(cfg["reduced"])
    for key in ("num_hidden_layers", "num_experts", "vocab_size"):
        assert cfg["published"][key] == PUBLISHED[key]
    assert cfg["family"] == "laguna" and cfg["model_type"] == "laguna"
    # the lists are the published ones cut to the depth: the leading dense
    # layer and one whole period, every kind in its published ratio
    depth = cfg["num_hidden_layers"]
    assert depth == 5
    for key in ("layer_types", "mlp_layer_types", "gating_types",
                "num_attention_heads_per_layer"):
        assert cfg[key] == PUBLISHED[key][:depth], key
    assert cfg["layer_types"][1:] == PERIOD[1:] + PERIOD[:1]
    # the router keeps its published width under a key of the file's own;
    # the floors of a cut: 8 experts held, an eighth of the vocabulary
    assert cfg["router_experts"] == PUBLISHED["num_experts"] == 256
    assert cfg["num_experts"] == 8 and cfg["first_expert"] == 0
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["train"]["sequence_length"] in (8192, 6144, 4096)
    for said in ("32 chips", "experts 0-7", "eight ways", "rows 0-12543",
                 "five layers"):
        assert said in cfg["deployment"], said
    for item in ("router", "qk_norm", "gate", "window", "rotary", "weights",
                 "optimizer", "norm_denominator", "data", "aux_loss"):
        assert item in cfg["assumed"], item
    assert "_limits_from" in cfg["check"]
    assert set(cfg["check"]["limits"]) == set(NUMBERS)


@pytest.fixture(scope="module")
def readings():
    with open(os.path.join(util.FIXTURES,
                           "laguna_check_readings.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("number", NUMBERS)
def test_a_limit_stands_off_the_program_s_runs_and_the_controls_it_decides(
        cfg, readings, number):
    """The chip's readings of the cell's check, one row a run
    (`fixtures/laguna_check_readings.json`: the program, the fp8 control,
    the window's control), hold the file's limits: every run of the program
    1.5 times under its limit or more, every run of a control that the
    number is said to decide 1.5 times over; and `_limits_from` says which
    runs."""
    limit = cfg["check"]["limits"][number]
    rows = readings["rows"]

    def of(tree):
        return [r[number] for r in rows if r["tree"] == tree]

    program = of("program")
    assert len({r["seed"] for r in rows if r["tree"] == "program"}) >= 10
    assert 1.5 * max(program) <= limit
    decides = readings["decides"][number]
    for control in ("fp8", "window"):
        assert len(of(control)) >= 4
        if control in decides:
            assert min(of(control)) >= 1.5 * limit, control
    # every control is some number's to fail, on every seed it ran on
    if number == "first_update_difference":
        assert set(decides) == {"fp8", "window"}
    said = cfg["check"]["_limits_from"]
    assert "%d runs on %d seeds" % (len(program), len(
        {r["seed"] for r in rows if r["tree"] == "program"})) in said
    line = "%s %.4g: " % (number, limit)
    assert line in said
    text = said[said.index(line):].split(";")[0]
    assert "%.4g" % max(program) in text
    for control in decides:
        assert "%s %.4g" % (control, min(of(control))) in text


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
    seq = cfg["train"]["sequence_length"]
    for said in ("1x%d" % seq, "512-key window", "3 of 5 layers",
                 "top-10 of 256", "8 held"):
        assert said in cell["why"], said
    steps = cfg["train"]["steps_per_block"]
    assert ("every step" if steps == 1 else "every %d" % steps) \
        in cell["why"]
    # the new entries stand last, in the order they were added
    assert spec["configs"][-1] is entry and spec["workloads"][-1] is cell
    assert [m["name"] for m in spec["per_layer"][-5:]] == [
        "swa_ms_per_step", "swa_flash_ms_per_step", "swa_flash_roofline_pct",
        "swa_tiles_visited_over_needed", "gqa_full_ms_per_step"]
    # its own five readers are declared for it alone
    for name in READERS:
        assert util.named(spec["per_layer"], name)["workloads"] == [CELL], name
    # the accepted lists the cell is owed are a `benchmark` PR's to extend
    # (PERF.md section 7): today it stands on its own five and reports the
    # ones without a list
    listed = {m["name"] for m in spec["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(READERS)
    unlisted = [m["name"] for m in spec["per_layer"] if "workloads" not in m]
    assert len(unlisted) == 9 and "model_flops_util_pct" in unlisted
    loaded = harness.Cell(CELL, 1, 1, 1, 0.0, util.REPO)
    assert {m["name"] for m in loaded.metric_names("per_layer")} \
        == set(unlisted) | listed
    assert {m["name"] for m in loaded.metric_names("end_to_end")} \
        == {"train_samples_per_s", "setup_s"}


def test_the_declared_readers_are_read_through_the_harness(spec):
    """The five entries are the readers' own constants, and the harness
    reads all five for this cell."""
    events, scope_map, _ = _step()
    out = Outcome(_plans(), scope_map, events, traced_blocks=1,
                  steps_per_block=1)
    layers = {m["layer"] for m in spec["per_layer"]
              if m["name"] not in READERS}
    declared = [util.named(spec["per_layer"], name)
                for name in sorted(READERS)]
    for m, (name, r) in zip(declared, sorted(READERS.items())):
        assert m == {"name": name, "unit": r.UNIT, "better": r.BETTER,
                     "source": r.SOURCE, "layer": r.LAYER, "moves": r.MOVES,
                     "workloads": [CELL]}
        assert m["layer"] in layers and m["moves"] == "train_samples_per_s"
    out.cell.spec["per_layer"] = declared
    after = harness.per_layer_metrics(out.cell, out)
    assert set(after) == set(READERS)
    assert 0 < after["swa_flash_roofline_pct"]["value"]
    assert after["swa_tiles_visited_over_needed"]["value"] == 1.0


def test_the_family_builds_the_file_s_widths(cfg):
    small = dict(cfg, hidden_size=64, intermediate_size=96,
                 moe_intermediate_size=32, shared_expert_intermediate_size=32,
                 vocab_size=64, head_dim=16, num_key_value_heads=2,
                 num_attention_heads_per_layer=[4, 6, 6, 6, 4])
    net, loss = family.build(small)
    assert type(loss).__name__ == "SoftmaxCrossEntropyLoss"
    ops = [layer.operator for layer in net.layers]
    assert {type(o).__name__ for o in ops} == {"GroupedQueryAttention"}
    assert [o._heads for o in ops] == [4, 6, 6, 6, 4]
    assert [o._mask.get("window") for o in ops] == [None, 512, 512, 512,
                                                    None]
    assert all(o.gate_weight is not None for o in ops)
    assert ops[0]._rotary["rotary_dim"] == 8
    assert ops[0]._rotary["table_scale"] == 1.4852030263919618
    assert ops[1]._rotary == {"theta": 10000.0}
    assert type(net.layers[0].feed_forward).__name__ == "GatedMLP"
    routed = net.layers[1].feed_forward.routed._attrs
    assert "scoring_func" not in routed and "buffer_factor" not in routed
    assert routed["num_experts_per_tok"] == 10 and routed["first_expert"] == 0
    assert routed["routed_scaling_factor"] == 2.5
    assert routed["expert_bias"] == ()
    assert net.head_weight is not None


def test_the_family_refuses_a_program_without_the_kind(monkeypatch):
    """`build` raises at once, before anything is compiled, where the
    decoder lacks the kind: the parent commit on this cell."""
    from mxnet_tpu.gluon.model_zoo import decoder
    monkeypatch.setattr(decoder, "OPERATOR_KINDS",
                        ("conv", "full_attention", "latent_attention",
                         "sparse_attention", "block_diffusion_attention"))
    with pytest.raises(RuntimeError,
                       match="no sliding_attention layer kind"):
        family.build({})
    # ... and sooner still: the loop asks for the batches before it makes
    # the seeded weights
    with pytest.raises(RuntimeError,
                       match="no sliding_attention layer kind"):
        family.batches({}, 1, 1, 1)


def test_the_window_s_control_is_the_mask_s_with_one_sight(monkeypatch):
    from benchmarks import control_mask, control_window
    calls = []
    monkeypatch.setattr(control_mask, "main", lambda argv: calls.append(argv))
    control_window.main(["--workload", CELL, "--seeds", "1,2"])
    assert calls == [["--workload", CELL, "--seeds", "1,2", "--sight",
                      "causal"]]
    assert control_window.SIGHT in control_mask.SIGHTS
    assert control_window.SIGHT in family.reference.SIGHTS


# -- the family through the loop, tiny, on the CPU ----------------------------
TINY = {
    "family": "laguna", "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "num_experts_per_tok": 3, "router_experts": 16, "num_experts": 4,
    "first_expert": 0, "num_attention_heads": 4,
    "num_attention_heads_per_layer": [4, 6, 6, 4],
    "num_key_value_heads": 2, "head_dim": 16,
    "layer_types": ["full_attention", "sliding_attention",
                    "sliding_attention", "full_attention"],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"],
    "gating_types": ["per_head"] * 4, "gating": "per-head",
    "sliding_window": 12, "rms_norm_eps": 1e-6, "norm_topk_prob": True,
    "moe_routed_scaling_factor": 2.5,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 4, "attention_factor": 1.2,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "tie_word_embeddings": False, "num_hidden_layers": 4, "vocab_size": 96,
    "initializer_range": 0.02, "embedding_initializer_range": 1.0,
    "reduced": [],
    "train": {"optimizer": "sgd", "lr": 0.01, "momentum": 0.9, "wd": 0.0,
              "multi_precision": True, "sequence_length": 48,
              "per_chip_batch": 2, "steps_per_block": 2},
    "check": {
        "reference_rows_per_block": 1,
        # CPU, seeds 7, 11 and 2**31 + 13: the bf16 program reads
        # first_update_difference 0.0051 to 0.0054, the fp8 control 0.027 to
        # 0.029, the reference without its window 0.012 to 0.013 (the update
        # of all leaves together is mostly the embedding's and the head's,
        # which the window hardly moves): that limit decides the precision.
        # The leaves' norms decide the window: the program's worst gap 0.003
        # to 0.012 (root mean square 0.0016 to 0.0028), the window control's
        # 0.14 to 0.20 (0.045 to 0.064), the fp8 control's 0.013 to 0.026
        # (0.004 to 0.007), which they do not tell from the program.
        "limits": {"first_update_difference": 0.012, "loss_gap": 0.011,
                   "first_gradient_norm_gap": 0.05,
                   "first_gradient_norm_rms": 0.015,
                   "update_norm_gap": 0.05, "update_norm_rms": 0.015}}}


@pytest.fixture()
def root(tmp_path):
    """The suite's fixture root with a tiny cell of this family added as
    a new file and two new entries."""
    root = util.fixture_root(tmp_path)
    with open(os.path.join(root, "benchmarks", "configs",
                           "tiny_laguna.json"), "w") as f:
        json.dump(TINY, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny_laguna", "source": "test fixture", "reduced": [],
        "file": "benchmarks/configs/tiny_laguna.json", "why": "fixture"})
    spec["workloads"].append({
        "name": "tiny_laguna_train", "config": "tiny_laguna",
        "traffic": "fit_prefetch", "chips": 1, "why": "fixture"})
    with open(path, "w") as f:
        json.dump(spec, f)
    return root


def test_the_tiny_cell_runs_and_is_correct(root, capsys):
    """Asserts on counts and on `correct`, never on how many blocks the
    window held."""
    from mxnet_tpu import profiler
    names = ("moe_stat_layers_total", "swa_visible_pairs_total")
    before = [profiler.counter_value(n) for n in names]
    outcome, line = util.run_cell(root, "tiny_laguna_train",
                                  seed=2 ** 31 + 13, seconds=3.0)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["metrics"] == {}
    layers, pairs = (profiler.counter_value(n) - b
                     for n, b in zip(names, before))
    # three routed layers and two sliding ones a step; a sliding layer-step
    # sees 2 rows of the window's pairs
    assert 0 < layers and layers % 3 == 0
    assert pairs == layers // 3 * 2 * 2 * swa_counts.visible_pairs(48, 12)
    assert "correct: first_update_difference" in capsys.readouterr().out


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 13])
@pytest.mark.parametrize("which", ["fp8", "window"])
def test_a_control_of_the_tiny_cell_is_not_correct(root, capsys, which,
                                                   seed):
    """The fp8 reference and the reference with every causal key visible in
    its sliding layers, each in the program's place: neither may pass for
    this model."""
    import jax
    from benchmarks import compare, control, control_window
    cell = harness.Cell("tiny_laguna_train", seed, 0, 0, 0.0, root)
    devices = jax.devices()[:1]
    numbers = control.control_numbers(cell, devices) if which == "fp8" \
        else control_window.control_numbers(cell, devices)
    limits = cell.config["check"]["limits"]
    assert not compare.judge(numbers, limits)
    # the whole update's direction decides the precision, the leaves' norms
    # the window
    for number in (["first_update_difference"] if which == "fp8" else [
            "first_gradient_norm_gap", "first_gradient_norm_rms",
            "update_norm_gap", "update_norm_rms"]):
        assert numbers[number][0] > 1.5 * limits[number], number
    assert "OUTSIDE" in capsys.readouterr().out
