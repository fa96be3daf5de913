"""The `sdar-30b-a3b-chat_train_ep8share` cell's own pieces: its four
per-layer readers on made-up outcomes, `benchmarks/bd_counts.py` and the
family's FLOPs against counts by hand, the configuration's published keys,
its entries in BENCHMARK.json (found by name, wherever they stand), and the
family through the `train_fit` loop at a tiny size on the CPU (a fixture
root of its own) with its three controls: the fp8 one and the two masks that
are not the model's."""

import collections
import json
import math
import os

import numpy as np
import pytest

import bench_suite_util as util
from benchmarks import bd_counts, harness, trace
from benchmarks.layer_metrics import (bd_attention_ms_per_step,
                                      bd_flash_ms_per_step,
                                      bd_flash_roofline_pct,
                                      bd_tiles_visited_over_needed,
                                      flash_bwd_ms_per_step,
                                      flash_fwd_ms_per_step, moe_ms_per_step)
from benchmarks.models import sdar_moe as family

CELL = "sdar-30b-a3b-chat_train_ep8share"
CONFIG = "sdar-30b-a3b-chat-ep8share"
Span = collections.namedtuple(
    "Span", "id name cat start end thread parent args")
READERS = {"bd_attention_ms_per_step": bd_attention_ms_per_step,
           "bd_flash_ms_per_step": bd_flash_ms_per_step,
           "bd_flash_roofline_pct": bd_flash_roofline_pct,
           "bd_tiles_visited_over_needed": bd_tiles_visited_over_needed}
#: the catalog's `config` for `SDAR-30B-A3B-Chat`
#: (`/opt/skills/guides/model-configs/architectures.jsonl`), written out
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
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


PLAN = {"sq": 16384, "sk": 16384, "d": 128, "dtype": "bfloat16",
        "causal": False, "mask": "block_diffusion", "block": 4, "half": 8192,
        "fwd": {"tiles_visited": 576, "tiles_masked": 96,
                "tiles_ideal": 512.25, "sub_tile": [256, 512]},
        "bwd": {"tiles_visited": 576, "tiles_masked": 96,
                "tiles_ideal": 512.25, "sub_tile": [256, 512]}}


def _step(masked=True):
    """One traced step: the head's matmul and two layers, each a routed node
    and an attention node: under the block-diffusion mask (*masked*) its
    projections lie under `mx.bd.project` and its kernels under
    `mx.bd.attention`; else it is a causal node as LFM2's."""
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
            group = "mx.bd.project/" if masked else ""
            op("proj_%s.%d" % (way, layer), "jit(parallel_step)/mx.loss/"
               + wrap % (group + "FullyConnected:fullyconnected%d" % layer)
               + "/dot_general", 200, "project")
            node = "jit(parallel_step)/mx.loss/" + wrap % (
                "_contrib_DotProductAttention:contrib_dotproductattention%d"
                % layer) + ("/mx.bd.attention" if masked else "")
            if way == "f":
                op("mx_flash_fwd.%d" % layer,
                   node + "/mx.flash.fwd/mx_flash_fwd/pallas_call", 300,
                   "node", "kernels", "fwd")
            else:
                op("delta.%d" % layer, node + "/reduce_sum", 20, "node")
                op("mx_flash_bwd.%d" % layer,
                   node + "/mx.flash.bwd/mx_flash_bwd/pallas_call", 700,
                   "node", "kernels", "bwd")
    return events, scope_map, want


def _plans(calls=2, **changes):
    return [Span(i, "mx.flash.plan", "flash", 101.0 + i, 101.5 + i, 11, None,
                 dict(PLAN, **changes)) for i in range(calls)]


def test_the_device_readers_sum_their_scopes(capsys):
    events, scope_map, want = _step()
    out = Outcome(_plans(), scope_map, events, traced_blocks=1,
                  steps_per_block=1)
    assert bd_attention_ms_per_step.read(out) == pytest.approx(
        want["node"] * 1e-6)
    assert bd_flash_ms_per_step.read(out) == pytest.approx(
        want["kernels"] * 1e-6)
    # the accepted readers of the kernels and of the routed layer read this
    # family's nodes as they are
    assert flash_fwd_ms_per_step.read(out) == pytest.approx(
        want["fwd"] * 1e-6)
    assert flash_bwd_ms_per_step.read(out) == pytest.approx(
        want["bwd"] * 1e-6)
    assert moe_ms_per_step.read(out) == pytest.approx(want["moe"] * 1e-6)
    said = capsys.readouterr().out
    assert said.count("bench: mx.flash.plan (2 traced calls)") == 1
    assert '"mask": "block_diffusion"' in said and '"half": 8192' in said
    assert "bench: block diffusion mx.bd.project %.3f ms a step beside " \
        "mx.bd.attention %.3f" % (want["project"] * 1e-6,
                                  want["node"] * 1e-6) in said
    bd_attention_ms_per_step.read(out)          # said once
    assert "mx.flash.plan" not in capsys.readouterr().out


def test_the_roofline_share_is_the_visible_work_over_the_kernels_time(
        capsys, cfg):
    events, scope_map, want = _step()
    out = Outcome([], scope_map, events, traced_blocks=1, steps_per_block=1)
    half, block = 8192, 4
    visible = half * (half + block)
    assert visible == 67141632
    # the core over the VISIBLE pairs, three times for training
    flops = 4 * 3 * 2 * 32 * visible * (128 + 128)
    assert flops == 4 * bd_counts.core_flops(1, 32, half, block, 128, 128)
    least = flops / 197e12                              # compute bound
    assert least > 4 * bd_counts.core_bytes(1, 32, 4, half, 128,
                                            128) / 819e9
    ms = want["kernels"] * 1e-6
    assert bd_flash_roofline_pct.read(out) == pytest.approx(
        100.0 * 1e3 * least / ms)
    assert 1e3 * least == pytest.approx(67.01, abs=0.01)
    said = capsys.readouterr().out
    assert "4 block-diffusion layers, 1 x 32 heads x 2 x 8192 positions in " \
        "blocks of 4, 67141632 of 268435456 pairs visible a head" in said
    assert said.count("compute peak") == 1


def test_the_tiles_reader_counts_the_cell_s_plan_without_a_chip(capsys):
    """The plan's visits over `bd_counts`' tiles that hold a visible pair,
    at the cell's shape: 1.0.  A plan that visited every causal tile of the
    16384 positions would read 1056 / 576."""
    out = Outcome(_plans(4), None, None, traced_blocks=1, steps_per_block=1)
    assert bd_tiles_visited_over_needed.read(out) == 1.0
    assert "4608 visited of 4608 that hold a visible pair; 768 run a mask " \
        "body, a boundary crosses 768" in capsys.readouterr().out
    wide = dict(PLAN["fwd"], tiles_visited=1056)
    out = Outcome(_plans(1, fwd=wide, bwd=wide), None, None,
                  traced_blocks=1, steps_per_block=1)
    assert bd_tiles_visited_over_needed.read(out) == pytest.approx(
        1056 / 576)
    # ... and the program's own plan at that shape is the one made up here
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention
    plan = attention._plan_args(
        attention._flash_plan(16384, 16384, 128, jnp.bfloat16, halves=2),
        16384, 16384, 128, jnp.bfloat16, False, None,
        attention.BlockDiffusion(4, 8192))
    for key, value in PLAN.items():
        if isinstance(value, dict):
            assert {k: plan[key][k] for k in value} == value, key
        else:
            assert plan[key] == value, key
    out = Outcome([Span(0, "mx.flash.plan", "flash", 1.0, 2.0, 11, None,
                        plan)], None, None, traced_blocks=1,
                  steps_per_block=1)
    assert bd_tiles_visited_over_needed.read(out) == 1.0


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_reads_nothing_where_there_is_nothing(name):
    reader = READERS[name]
    events, scope_map, _ = _step(masked=False)
    causal = _plans(2)
    for span in causal:
        del span.args["mask"], span.args["block"], span.args["half"]
    for out in (
            # a step whose attention is causal, with its causal plans
            Outcome(causal, scope_map, events, traced_blocks=1,
                    steps_per_block=1),
            # a program from before the span store and the scope map (a
            # parent commit)
            Outcome(None, None, events, traced_blocks=1, steps_per_block=1),
            # an untraced run of such a program
            Outcome(None, None, None, traced_blocks=1, steps_per_block=1)):
        assert reader.read(out) is None
    # ... and the roofline in a cell whose configuration has no
    # `diffusion_block`, whatever its trace holds
    events, scope_map, _ = _step()
    if name == "bd_flash_roofline_pct":
        out = Outcome([], scope_map, events, traced_blocks=1,
                      cell="keye-vl-2.0-30b-a3b_train_ep8share",
                      steps_per_block=1)
        assert reader.read(out) is None


# -- counts -------------------------------------------------------------------
def test_bd_counts_against_counts_by_hand():
    # two blocks of two: clean on clean 4 + 2 * 4 / 2 ... by the formula
    # K(K+1)/2 B^2 + K(K-1)/2 B^2 + K B^2 with K = 2, B = 2: 12 + 4 + 8
    assert bd_counts.visible_pairs(4, 2) == 24 == 4 * (4 + 2)
    pos = np.arange(8)
    seen = bd_counts.visible(pos[:, None], pos[None, :], 4, 2)
    assert seen.astype(int).tolist() == [
        [1, 1, 0, 0, 0, 0, 0, 0],
        [1, 1, 0, 0, 0, 0, 0, 0],
        [1, 1, 1, 1, 0, 0, 0, 0],
        [1, 1, 1, 1, 0, 0, 0, 0],
        [0, 0, 0, 0, 1, 1, 0, 0],
        [0, 0, 0, 0, 1, 1, 0, 0],
        [1, 1, 0, 0, 0, 0, 1, 1],
        [1, 1, 0, 0, 0, 0, 1, 1]]
    assert bd_counts.causal_pairs(8) == 36
    # one head, the 24 pairs: 16 multiply-adds for a score and 8 for its
    # share of the output; training three times that
    assert bd_counts.core_flops(1, 1, 4, 2, 16, 8, training=False) \
        == 2 * 24 * (16 + 8)
    assert bd_counts.core_flops(2, 3, 4, 2, 16, 16) \
        == 3 * 2 * 2 * 3 * 24 * 32
    # tiles of 2 x 2 over the 8 x 8 square above: 6 hold a visible pair and
    # no boundary crosses any; of 4 x 4: all but clean on noised, each
    # crossed
    assert bd_counts.tiles(4, 2, 2, 2) == (6, 0)
    assert bd_counts.tiles(4, 2, 4, 4) == (3, 3)
    assert bd_counts.tiles(4, 2, 1, 1) == (24, 0)
    # a last tile may be short: 6 positions a copy (3 blocks) in tiles of 4.
    # Clean rows 0-3 on clean keys 0-3 (crossed), clean rows 4-5 on both
    # clean tiles (whole), noised rows 0-3 on clean keys 0-3 and on their own
    # noised ones (crossed both), noised rows 4-5 on clean keys 0-3 and on
    # their own block (whole)
    assert bd_counts.tiles(6, 2, 4, 4) == (7, 3)
    # bytes: q, o over the query heads and k, v over the key/value heads in
    # bf16 over the 2 x 8192 positions, a float32 logsumexp a row; the
    # backward reads them and dO and two float32 rows and writes dq, dk, dv
    fwd = (32 * 16384 * 256 + 4 * 16384 * 256) * 2 + 4 * 32 * 16384
    assert bd_counts.core_bytes(1, 32, 4, 8192, 128, 128,
                                training=False) == fwd
    bwd = (32 * 16384 * 256 + 4 * 16384 * 256) * 2 + 8 * 32 * 16384 \
        + (32 * 16384 * 128 + 4 * 16384 * 256) * 2
    assert bd_counts.core_bytes(1, 32, 4, 8192, 128, 128) == fwd + bwd


def test_the_family_s_flops_are_the_algorithm_s(cfg):
    """By hand at a small shape, then the cell's: the projections, the
    router and one expected local pair over the 2L positions, the attention
    core over the visible pairs, the head over the L rows of the noised
    half."""
    small = {"hidden_size": 8, "head_dim": 4, "num_attention_heads": 2,
             "num_key_value_heads": 1, "num_experts_per_tok": 2,
             "num_experts": 4, "router_experts": 16,
             "moe_intermediate_size": 6, "num_hidden_layers": 3,
             "vocab_size": 10,
             "train": {"sequence_length": 6, "diffusion_block": 2}}
    per_position = 2 * 8 * 8 + 2 * 8 * 4 + 8 * 16 + 2 * 4 / 16 * 3 * 8 * 6
    layer = 2 * 12 * per_position + 2 * 2 * 6 * 8 * (4 + 4)
    assert family.forward_flops(small) == pytest.approx(
        3 * layer + 2 * 6 * 10 * 8)
    assert family.flops_per_sample(small) == pytest.approx(
        3 * family.forward_flops(small))
    # the cell: 24.49 TFLOP a sample, of which the core over the visible
    # pairs is 13.20
    assert family.flops_per_sample(cfg) / 1e12 == pytest.approx(24.49,
                                                                abs=0.01)
    assert 4 * bd_counts.core_flops(1, 32, 8192, 4, 128, 128) / 1e12 \
        == pytest.approx(13.20, abs=0.01)
    assert family.routed_layers_and_experts_held(cfg) == (4, 16)
    # one expected local pair a position: 8 chosen of 128, 16 held
    assert cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_experts"] == 1.0


def test_the_parameters_are_the_issue_s(cfg):
    """94.63 M a layer (attention 18.87, router 0.26, the 16 held experts
    75.50) and an eighth of the vocabulary twice: 456.3 M."""
    table = family.reference.param_table(cfg)
    sizes = collections.Counter()
    for name, (shape, _) in table.items():
        sizes[name.split(".")[-1] if name.startswith("l0.") else
              "rest" if name.startswith("l") else name] += int(
                  np.prod(shape))
    assert sizes["wq"] + sizes["wk"] + sizes["wv"] + sizes["wo"] == 18874368
    assert sizes["router"] == 128 * 2048
    assert sizes["expert_w1"] * 3 == 16 * 3 * 2048 * 768
    assert sizes["embed"] == sizes["head"] == 18992 * 2048
    assert sum(sizes.values()) / 1e6 == pytest.approx(456.3, abs=0.05)


# -- the configuration and its entries ----------------------------------------
def test_every_unreduced_key_is_the_published_one(cfg):
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
            assert cfg[key] != value, key
        else:
            assert key in cfg and cfg[key] == value, key
    assert set(cfg["published"]) == set(cfg["reduced"])
    assert cfg["family"] == "sdar_moe" and cfg["model_type"] == "sdar_moe"
    # the router keeps its published width under a key of the file's own
    assert cfg["router_experts"] == PUBLISHED["num_experts"] == 128
    # the floors of a cut: four layers (the period is one layer, none is
    # dense), 16 of 128 experts, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] == 4 and cfg["mlp_only_layers"] == []
    assert cfg["num_experts"] == 16 and cfg["first_expert"] == 0
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["num_experts"] * 8 == cfg["published"]["num_experts"]
    train = cfg["train"]
    assert (train["sequence_length"], train["per_chip_batch"],
            train["diffusion_block"], train["t_min"]) == (8192, 1, 4, 1e-3)
    # the MASK id is the last row held, and the traffic draws none of it
    assert train["mask_token_id"] == cfg["vocab_size"] - 1 == 18991
    # nothing sizes the routed op's pair buffer: the load is as found
    assert "expert_buffer_factor" not in cfg
    table = family.reference.param_table(cfg)
    # the embedding's rows at unit scale, the MASK row alone at the
    # matrices' 0.02: a standard deviation a row
    kind, rows = table["embed"][1]
    assert kind == "normal" and rows.shape == (cfg["vocab_size"], 1)
    assert cfg["mask_embedding_initializer_range"] \
        == cfg["initializer_range"] == 0.02
    assert rows[train["mask_token_id"], 0] == np.float32(0.02)
    assert (np.delete(rows, train["mask_token_id"], 0)
            == cfg["embedding_initializer_range"]).all()
    assert table["head"][1] == table["l0.wq"][1] == ("normal", 0.02)
    # the embedding at unit scale (as the decoder whose sizes these are),
    # and the two scales that are the file's own: the MASK row above, and
    # the per-head norms of q and k at 1.573 (a score log2(L^2 - L) times
    # the cosine, arXiv:2010.04245), so that attention tells the masked
    # positions apart and the bf16 program still follows its reference
    assert cfg["embedding_initializer_range"] == 1.0
    assert cfg["qk_norm_initializer"] == 1.573
    assert cfg["qk_norm_initializer"] ** 2 * 128 ** 0.5 == pytest.approx(
        math.log2(16384 ** 2 - 16384), abs=0.01)
    assert table["l0.q_norm"][1] == table["l3.k_norm"][1] \
        == ("const", 1.573)
    assert table["l0.attn_norm"][1] == table["final_norm"][1] == ("ones",)
    for said in ("arXiv:2204.02311", "arXiv:2010.04245", "arXiv:2202.12172",
                 "E[MASK]", "worst-case branch",
                 "mask_embedding_initializer_range"):
        assert said in cfg["assumed"]["weights"], said
    assert family.reference.param_table(
        {k: v for k, v in cfg.items() if k != "qk_norm_initializer"}
    )["l0.q_norm"][1] == ("const", 1.0)
    for item in ("block_length", "noise", "shift", "mask_token", "weights",
                 "optimizer", "precision", "qk_norm", "router", "positions",
                 "data", "aux_loss", "per_chip_batch", "remat"):
        assert cfg["assumed"][item], item
    assert "not_given" in cfg["assumed"]["block_length"]
    assert "arXiv:2503.09573" in cfg["assumed"]["noise"]
    assert "eight chips share each layer" in cfg["deployment"]
    assert "experts 0-15" in cfg["deployment"] \
        and "rows 0-18991" in cfg["deployment"]
    assert "_limits_from" in cfg["check"]
    assert set(cfg["check"]["limits"]) == {
        "first_update_difference", "loss_gap", "first_gradient_norm_gap",
        "first_gradient_norm_rms", "update_norm_gap", "update_norm_rms"}


@pytest.fixture(scope="module")
def readings():
    with open(os.path.join(util.FIXTURES, "sdar_check_readings.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("number", [
    "first_update_difference", "loss_gap", "first_gradient_norm_gap",
    "first_gradient_norm_rms", "update_norm_gap", "update_norm_rms"])
def test_a_limit_stands_off_the_sound_runs_and_the_controls_it_decides(
        cfg, readings, number):
    """The chip's readings of the cell's check, one row a run
    (`fixtures/sdar_check_readings.json`: the accepted program, PRs 40 and
    41's fused projection as a sound variant, the three controls), hold the
    file's limits: every sound run under its limit, the accepted program's
    1.5 times under, every run of a control that the number is said to
    decide 1.5 times over; and `_limits_from` says what the rows say."""
    limit = cfg["check"]["limits"][number]
    rows = readings["rows"]

    def of(tree):
        return [r[number] for r in rows if r["tree"] == tree]

    accepted, variant = of("accepted"), of("variant_headrope")
    seeds = {r["seed"] for r in rows if r["tree"] == "accepted"}
    assert len(seeds) >= 47 and {4015, 2147486113} <= seeds
    assert len(variant) == 47
    assert max(accepted + variant) < limit
    assert 1.5 * max(accepted) <= limit
    decides = readings["decides"][number]
    for control in ("fp8", "causal", "block_diagonal"):
        runs = of(control)
        assert len(runs) >= 3 and 2147486113 in {
            r["seed"] for r in rows if r["tree"] == control}
        if control in decides:
            assert min(runs) >= 1.5 * limit, control
    # every control is some number's to fail, on every seed it ran on
    if number == "first_update_difference":
        assert set(decides) == {"fp8", "causal", "block_diagonal"}
    else:
        assert "fp8" not in decides
    # `_limits_from` is written from these rows: per number the limit, the
    # largest sound reading of either tree, the nearest control it decides
    # and both distances
    said = cfg["check"]["_limits_from"]
    assert "%d runs on %d seeds" % (len(accepted), len(seeds)) in said
    line = "%s %.4g: " % (number, limit)
    assert line in said
    text = said[said.index(line):].split(";")[0]
    assert "%.4g" % max(accepted) in text and "%.4g" % max(variant) in text
    if decides:
        nearest = min(decides, key=lambda c: min(of(c)))
        assert "%s %.4g" % (nearest, min(of(nearest))) in text
        assert "%.2f times under" % (min(of(nearest)) / limit) in text
    assert "%.2f times over" % (limit / max(accepted + variant)) in text


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
    for said in ("1x8192", "16384 positions", "block of 4", "top-8 of 128",
                 "16 held"):
        assert said in cell["why"], said
    steps = cfg["train"]["steps_per_block"]
    assert ("every step" if steps == 1 else "every %d" % steps) \
        in cell["why"]
    # its own four readers are declared for it alone, wherever they stand
    for name in READERS:
        assert util.named(spec["per_layer"], name)["workloads"] == [CELL], name
    # since PR 42 the cell stands on the 25 accepted lists whose readers find
    # something to read in it (PERF.md section 7 row 31): the expert cells'
    # twenty, PR 37's four, and `attention_ms_per_step`, which reads its
    # `_contrib_DotProductAttention` nodes as `bd_attention_ms_per_step` does
    listed = {m["name"] for m in spec["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(READERS) | set(util.EXPERT_CELL_LISTS) \
        | set(util.COST_LISTS) | {"attention_ms_per_step"} <= listed
    # ... and the cell reports the ones without a list
    unlisted = [m["name"] for m in spec["per_layer"] if "workloads" not in m]
    assert len(unlisted) == 9 and "model_flops_util_pct" in unlisted
    loaded = harness.Cell(CELL, 1, 1, 1, 0.0, util.REPO)
    assert {m["name"] for m in loaded.metric_names("per_layer")} \
        == set(unlisted) | listed


def test_the_declared_readers_are_read_through_the_harness(spec):
    """The four entries are the readers' own constants, and the harness
    reads all four for this cell."""
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
    assert 0 < after["bd_flash_roofline_pct"]["value"]
    assert after["bd_tiles_visited_over_needed"]["value"] == 1.0


def test_the_family_builds_the_file_s_widths(cfg):
    small = dict(cfg, num_hidden_layers=1, hidden_size=64,
                 moe_intermediate_size=32, vocab_size=64,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                 train=dict(cfg["train"], mask_token_id=63))
    net, loss = family.build(small)
    assert type(loss).__name__ == "BlockDiffusionLoss"
    layer = net.layers[0]
    assert type(layer.operator).__name__ == "GroupedQueryAttention"
    assert layer.operator._mask == {"mask": "block_diffusion",
                                    "mask_block": 4}
    assert layer.operator._theta == 1e6 and net._two_copies
    routed = layer.feed_forward._attrs
    assert routed["scoring_func"] == "softmax"
    assert "buffer_factor" not in routed
    assert routed["num_experts_per_tok"] == 8 and routed["first_expert"] == 0
    assert net.head_weight is not None


def test_the_family_refuses_a_program_without_the_kind(monkeypatch):
    """`build` raises at once, before anything is compiled, where the
    decoder lacks the kind: the parent commit on this cell."""
    from mxnet_tpu.gluon.model_zoo import decoder
    monkeypatch.setattr(decoder, "OPERATOR_KINDS",
                        ("conv", "full_attention", "latent_attention",
                         "sparse_attention"))
    with pytest.raises(RuntimeError,
                       match="no block_diffusion_attention layer kind"):
        family.build({})
    # ... and sooner still: the loop asks for the batches before it makes
    # the seeded weights
    with pytest.raises(RuntimeError,
                       match="no block_diffusion_attention layer kind"):
        family.batches({}, 1, 1, 1)


# -- the family through the loop, tiny, on the CPU ----------------------------
TINY = {
    "family": "sdar_moe", "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_experts_per_tok": 2,
    "router_experts": 16, "num_experts": 4, "first_expert": 0,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "rope_theta": 1000000, "rope_scaling": None, "rms_norm_eps": 1e-6,
    "norm_topk_prob": True, "tie_word_embeddings": False,
    "num_hidden_layers": 4, "vocab_size": 96, "initializer_range": 0.02,
    "embedding_initializer_range": 1.0, "reduced": [],
    "train": {"optimizer": "sgd", "lr": 0.01, "momentum": 0.9, "wd": 0.0,
              "multi_precision": True, "sequence_length": 48,
              "diffusion_block": 4, "t_min": 0.001, "mask_token_id": 95,
              "per_chip_batch": 2, "steps_per_block": 2},
    "check": {
        "reference_rows_per_block": 1,
        # CPU, seeds 7, 11 and 2**31 + 13: the bf16 program reads
        # first_update_difference 0.0041 to 0.0045, the fp8 control 0.044 to
        # 0.047, the causal mask 0.024 to 0.030, the block-diagonal one 0.058
        # to 0.068: the limit lies between the program and the nearest
        # control.  The leaves' norms: the program's worst gap 0.006 to
        # 0.018, the controls' 0.07 to 1.5.
        "limits": {"first_update_difference": 0.012, "loss_gap": 0.011,
                   "first_gradient_norm_gap": 0.05,
                   "first_gradient_norm_rms": 0.012,
                   "update_norm_gap": 0.05, "update_norm_rms": 0.012}}}


@pytest.fixture()
def root(tmp_path):
    """The suite's fixture root with a tiny cell of this family added as
    a new file and two new entries."""
    root = util.fixture_root(tmp_path)
    with open(os.path.join(root, "benchmarks", "configs",
                           "tiny_sdar.json"), "w") as f:
        json.dump(TINY, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny_sdar", "source": "test fixture", "reduced": [],
        "file": "benchmarks/configs/tiny_sdar.json", "why": "fixture"})
    spec["workloads"].append({
        "name": "tiny_sdar_train", "config": "tiny_sdar",
        "traffic": "fit_prefetch", "chips": 1, "why": "fixture"})
    with open(path, "w") as f:
        json.dump(spec, f)
    return root


def test_the_tiny_cell_runs_and_is_correct(root, capsys):
    """Asserts on counts and on `correct`, never on how many blocks the
    window held: the window is long enough for twelve blocks with busy
    workers beside it."""
    from mxnet_tpu import profiler
    from mxnet_tpu.observability import metrics
    names = ("moe_stat_layers_total", "bd_visible_pairs_total",
             "bd_positions_total", "bd_masked_positions_total")
    before = [profiler.counter_value(n) for n in names]
    outcome, line = util.run_cell(root, "tiny_sdar_train",
                                  seed=2 ** 31 + 13, seconds=3.0)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["metrics"] == {}
    layers, pairs, positions, masked = (
        profiler.counter_value(n) - b for n, b in zip(names, before))
    # four layers a step, each an attention node under the mask and a routed
    # layer (whose counter counts the layer-steps); a layer-step sees 2 rows
    # of 48 x 52 pairs, a step 2 x 48 positions that may carry loss
    assert 0 < layers and layers % 4 == 0
    assert pairs == layers * 2 * bd_counts.visible_pairs(48, 4)
    assert positions == layers // 4 * 2 * 48
    assert 0 < masked < positions
    assert metrics.snapshot()["bd_loss"]["value"] > 0
    assert "correct: first_update_difference" in capsys.readouterr().out


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 13])
@pytest.mark.parametrize("which", ["fp8", "causal", "block_diagonal"])
def test_a_control_of_the_tiny_cell_is_not_correct(root, capsys, which,
                                                   seed):
    """The fp8 reference, the reference under a causal mask over the 2L
    positions and the reference whose noised queries see nothing of the
    clean copy, each in the program's place: none may pass for this
    model."""
    import jax
    from benchmarks import compare, control, control_mask
    cell = harness.Cell("tiny_sdar_train", seed, 0, 0, 0.0, root)
    devices = jax.devices()[:1]
    numbers = control.control_numbers(cell, devices) if which == "fp8" \
        else control_mask.control_numbers(cell, devices, which)
    limits = cell.config["check"]["limits"]
    assert not compare.judge(numbers, limits)
    assert numbers["first_update_difference"][0] > \
        1.5 * limits["first_update_difference"]
    assert "OUTSIDE" in capsys.readouterr().out
