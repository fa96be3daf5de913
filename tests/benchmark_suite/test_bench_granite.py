"""The `granite-4.0-h-micro_train_vp8share` cell's own pieces: its five
per-layer readers on made-up outcomes, `benchmarks/ssm_counts.py` and the
family's FLOPs against counts by hand, a brute-force count and the reference's
own count, the configuration's published keys and parameters, its limits
against the chip's readings on record, its entries in BENCHMARK.json (found by
name, wherever they stand), and the family through the `train_fit` loop at a
tiny size on the CPU (a fixture root of its own) with its controls: the fp8
one and the reference with a part of the recurrence left out."""

import collections
import json
import os

import numpy as np
import pytest

import bench_suite_util as util
from benchmarks import harness, ssm_counts, swa_counts, trace
from benchmarks.layer_metrics import (flash_bwd_ms_per_step,
                                      flash_fwd_ms_per_step,
                                      gqa_full_ms_per_step,
                                      ssm_conv_ms_per_step, ssm_ms_per_step,
                                      ssm_scan_ms_per_step,
                                      ssm_scan_roofline_pct,
                                      ssm_state_kept_gb)
from benchmarks.models import granitemoehybrid as family

CELL = "granite-4.0-h-micro_train_vp8share"
CONFIG = "granite-4.0-h-micro-vp8share"
Span = collections.namedtuple(
    "Span", "id name cat start end thread parent args")
READERS = {"ssm_ms_per_step": ssm_ms_per_step,
           "ssm_scan_ms_per_step": ssm_scan_ms_per_step,
           "ssm_scan_roofline_pct": ssm_scan_roofline_pct,
           "ssm_conv_ms_per_step": ssm_conv_ms_per_step,
           "ssm_state_kept_gb": ssm_state_kept_gb}
REDUCED = ["num_hidden_layers", "layer_types", "vocab_size"]
NUMBERS = ("first_update_difference", "loss_gap", "first_gradient_norm_gap",
           "first_gradient_norm_rms", "update_norm_gap", "update_norm_rms")
CONTROLS = ("fp8", "no_decay", "no_skip")
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
# https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json,
# as the guide's catalog holds it
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": PERIOD * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}


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


def plan(seq=4096, heads=64, width=64, state=128, chunk=256):
    kept = ssm_counts.state_kept_bytes(1, seq, heads, width, state, chunk)
    return {"batch": 1, "seq": seq, "heads": heads, "head_dim": width,
            "state": state, "groups": 1, "chunk": chunk,
            "chunks": seq // chunk, "dtype": "bfloat16", "path": "xla",
            "why": "no kernel computes this recurrence yet",
            "state_kept_bytes": kept, "per_token_state_bytes": kept * chunk}


def _step(mamba=True):
    """One traced step: the head's matmul, nine mamba layers each with its
    four groups forward and backward, and the attention layer under
    `mx.gqa.*` with the two flash kernels.  Not *mamba*: the attention layer
    alone."""
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
    parts = {"project": ("FullyConnected:fullyconnected%d", "dot_general",
                         400),
             "conv": ("_contrib_ShortConvSilu:contrib_shortconvsilu%d",
                      "mul", 120),
             "scan": ("_contrib_StateSpaceScan:contrib_statespacescan%d",
                      "while", 700),
             "out": ("_contrib_GatedRMSNorm:contrib_gatedrmsnorm%d", "mul",
                     90)}
    for way, wrap in (("f", "jvp(%s)"), ("b", "transpose(jvp(%s))")):
        for layer in range(9 if mamba else 0):
            for part, (node, prim, dur) in parts.items():
                op("%s_%s.%d" % (part, way, layer),
                   "jit(parallel_step)/mx.loss/" + wrap % (
                       "mx.ssm.%s/" % part + node % layer) + "/" + prim,
                   dur * (2 if way == "b" else 1), "mx.ssm", part)
        op("proj_%s" % way, "jit(parallel_step)/mx.loss/" + wrap % (
            "mx.gqa.project/FullyConnected:fullyconnected30")
           + "/dot_general", 200, "mx.gqa")
        node = "jit(parallel_step)/mx.loss/" + wrap % (
            "mx.gqa.attention/_contrib_DotProductAttention:"
            "contrib_dotproductattention0")
        if way == "f":
            op("mx_flash_fwd.0",
               node + "/mx.flash.fwd/mx_flash_fwd/pallas_call", 300,
               "mx.gqa", "fwd")
        else:
            op("mx_flash_bwd.0",
               node + "/mx.flash.bwd/mx_flash_bwd/pallas_call", 700,
               "mx.gqa", "bwd")
    return events, scope_map, want


def _plans(calls=9, **changes):
    return [Span(i, "mx.ssm.plan", "ssm", 101.0 + i, 101.5 + i, 11, None,
                 dict(plan(), **changes)) for i in range(calls)]


def test_the_device_readers_sum_their_scopes(capsys):
    events, scope_map, want = _step()
    out = Outcome(_plans(), scope_map, events, traced_blocks=1,
                  steps_per_block=1)
    assert ssm_ms_per_step.read(out) == pytest.approx(want["mx.ssm"] * 1e-6)
    assert ssm_scan_ms_per_step.read(out) == pytest.approx(
        want["scan"] * 1e-6)
    assert ssm_conv_ms_per_step.read(out) == pytest.approx(
        want["conv"] * 1e-6)
    assert want["scan"] == 9 * 3 * 700 and want["conv"] == 9 * 3 * 120
    # the accepted readers of the attention layer and of its kernels read
    # this family's nodes as they are
    assert gqa_full_ms_per_step.read(out) == pytest.approx(
        want["mx.gqa"] * 1e-6)
    assert flash_fwd_ms_per_step.read(out) == pytest.approx(
        want["fwd"] * 1e-6)
    assert flash_bwd_ms_per_step.read(out) == pytest.approx(
        want["bwd"] * 1e-6)
    said = capsys.readouterr().out
    assert said.count("bench: mx.ssm.plan (9 traced calls)") == 1
    assert '"path": "xla"' in said and '"chunks": 16' in said
    assert "bench: mx.ssm %.3f ms a step: project %.3f, conv %.3f, scan " \
        "%.3f, out %.3f" % tuple(want[k] * 1e-6 for k in (
            "mx.ssm", "project", "conv", "scan", "out")) in said
    ssm_ms_per_step.read(out)          # said once
    assert "mx.ssm.plan" not in capsys.readouterr().out


def test_the_roofline_share_is_the_recurrence_s_work_over_the_scan_s_time(
        capsys, cfg):
    events, scope_map, want = _step()
    out = Outcome([], scope_map, events, traced_blocks=1, steps_per_block=1)
    seq = cfg["train"]["sequence_length"]
    # 5 P N a token and head forward, three times that with the backward:
    # 40,960 and 122,880 at 64 x 128; nine layers of 64 heads
    assert ssm_counts.token_flops(64, 128, False) == 40960
    flops = 9 * seq * 64 * 122880
    assert flops == 9 * ssm_counts.scan_flops(1, seq, 64, 64, 128)
    # a token, all heads: x and y (64 x 64 in bf16 each), dt (64 float32), B
    # and C (128 in bf16 each) forward; backward the inputs again, dy, and
    # the four gradients
    assert ssm_counts.token_bytes(64, 1, 64, 128, training=False) \
        == 8192 + 8192 + 256 + 512
    assert ssm_counts.token_bytes(64, 1, 64, 128) == 17152 + 8960 + 8192 \
        + 8960
    moved = 9 * seq * 43264
    least = moved / 819e9                       # memory's floor
    assert least > flops / 197e12
    ms = want["scan"] * 1e-6
    assert ssm_scan_roofline_pct.read(out) == pytest.approx(
        100.0 * 1e3 * least / ms)
    said = capsys.readouterr().out
    assert "the state-space recurrence in 9 layers, 1 x 64 heads x %d " \
        "positions, a state of 64 x 128 in 1 group(s)" % seq in said
    assert said.count("memory peak") == 1
    # the issue's own numbers at 4096 positions: 0.29 TFLOP, 1.5 ms at the
    # bf16 peak; the bytes as this file defines them are 1.59 GB, 1.9 ms
    assert 9 * ssm_counts.scan_flops(1, 4096, 64, 64, 128) / 1e12 \
        == pytest.approx(0.2899, abs=0.0001)
    assert 9 * ssm_counts.scan_bytes(1, 4096, 64, 1, 64, 128) / 819e9 * 1e3 \
        == pytest.approx(1.947, abs=0.001)


def test_the_state_reader_counts_the_cell_s_plan_without_a_chip(capsys, cfg):
    """0.30 GB over the nine layers at 4096 tokens and chunks of 256; a
    state a token would read 77."""
    seq = cfg["train"]["sequence_length"]
    out = Outcome(_plans(18, **plan(seq)), {}, None)
    kept = 9 * (seq // 256) * 64 * 64 * 128 * 4 / 1e9
    assert ssm_state_kept_gb.read(out) == pytest.approx(kept)
    if seq == 4096:
        assert kept == pytest.approx(0.3020, abs=1e-4)
    said = capsys.readouterr().out
    assert "state kept for the backward in 9 mamba layers: %.4g GB" % kept \
        in said
    assert "a state a token would be %.4g GB" % (256 * kept) in said
    # the plan the program records at the cell's shape is this one
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import profiler
    from mxnet_tpu.ops import state_space
    since = max([s.id for s in profiler.spans()] or [0])
    maps = jnp.zeros((1, seq, 1, 128), jnp.bfloat16)
    # (a function of its own: `eval_shape` would not trace one it has traced)
    jax.eval_shape(lambda *a: state_space._state_space_scan_op(*a),
                   jnp.zeros((1, seq, 64, 64), jnp.bfloat16),
                   jnp.zeros((1, seq, 64), jnp.float32),
                   jnp.zeros((64,), jnp.float32), maps, maps,
                   jnp.zeros((64,), jnp.bfloat16))
    recorded, = [s.args for s in profiler.spans()
                 if s.name == "mx.ssm.plan" and s.id > since]
    assert recorded == plan(seq)


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_reads_nothing_where_there_is_nothing(name):
    reader = READERS[name]
    events, scope_map, _ = _step(mamba=False)
    for out in (
            # a step with no mamba layer and no plan of the scan
            Outcome([], scope_map, events, traced_blocks=1,
                    steps_per_block=1),
            # a program from before the span store and the scope map (a
            # parent commit)
            Outcome(None, None, events, traced_blocks=1, steps_per_block=1),
            # an untraced run of such a program
            Outcome(None, None, None, traced_blocks=1, steps_per_block=1)):
        assert reader.read(out) is None
    # ... and in a cell whose configuration has no mamba layer, whatever
    # its trace and its spans hold
    events, scope_map, _ = _step()
    if name in ("ssm_scan_roofline_pct", "ssm_state_kept_gb"):
        out = Outcome(_plans(), scope_map, events, traced_blocks=1,
                      cell="lfm2-8b-a1b_train_ep4share", steps_per_block=1)
        assert reader.read(out) is None


# -- counts -------------------------------------------------------------------
@pytest.mark.parametrize("batch,seq,heads,groups,width,state",
                         [(1, 5, 2, 1, 3, 4), (2, 7, 4, 2, 4, 2)])
def test_ssm_counts_against_a_brute_force_count(batch, seq, heads, groups,
                                                width, state):
    """`recurrence` counts its multiply-adds as it does them: 5 P N a token
    and head; the bytes are the arrays' own sizes."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, seq, heads, width))
    dt = rng.uniform(size=(batch, seq, heads))
    a, d = -rng.uniform(size=heads), rng.normal(size=heads)
    b, c = rng.normal(size=(2, batch, seq, groups, state))
    out, counted = ssm_counts.recurrence(x, dt, a, b, c, d)
    assert counted == ssm_counts.scan_flops(batch, seq, heads, width, state,
                                            training=False)
    assert ssm_counts.scan_flops(batch, seq, heads, width, state) \
        == 3 * counted

    def nbytes(*arrays, itemsize=2):
        return sum(v.size for v in arrays) * itemsize

    inputs = nbytes(x, b, c) + nbytes(dt, itemsize=4)
    forward = inputs + nbytes(out)
    assert ssm_counts.scan_bytes(batch, seq, heads, groups, width, state,
                                 training=False) == forward
    assert ssm_counts.scan_bytes(batch, seq, heads, groups, width, state) \
        == forward + inputs + nbytes(out) + inputs
    # one step by hand: S = dt x (x) b, y = S c + d x
    one, _ = ssm_counts.recurrence(x[:, :1], dt[:, :1], a, b[:, :1],
                                   c[:, :1], d)
    np.testing.assert_allclose(
        one[0, 0, 0], (dt[0, 0, 0] * (b[0, 0, 0] @ c[0, 0, 0]) + d[0])
        * x[0, 0, 0])
    for sight in ({"decay": False}, {"skip": False}):
        other, same = ssm_counts.recurrence(x, dt, a, b, c, d, **sight)
        assert np.abs(other - out).max() > 0 and same == counted
    assert ssm_counts.state_kept_bytes(batch, 512, heads, width, state, 256) \
        == 4 * batch * 2 * heads * width * state


def test_the_family_s_flops_are_the_algorithm_s(cfg):
    """By hand at a small shape, against the reference's own count (from
    its parameter table), then the cell's."""
    small = dict(cfg, hidden_size=8, shared_intermediate_size=10,
                 vocab_size=12, num_attention_heads=2,
                 num_key_value_heads=1, mamba_n_heads=4, mamba_d_head=4,
                 mamba_d_state=3, mamba_n_groups=1, num_hidden_layers=2,
                 layer_types=["mamba", "attention"],
                 train={"sequence_length": 6})
    mamba = 8 * (16 + 16 + 6 + 4) + 16 * 8 + 3 * 8 * 10
    full = 2 * 8 * 8 + 2 * 8 * 4 + 3 * 8 * 10
    want = 2 * 6 * (mamba + full) + 6 * 4 * 5 * 4 * 3 \
        + 2 * 21 * (4 + 4) * 2 + 2 * 6 * 12 * 8
    assert family.forward_flops(small) == want
    assert family.reference.forward_flops(small, 6) == want
    assert family.flops_per_sample(small) == 3 * want
    seq = cfg["train"]["sequence_length"]
    assert family.forward_flops(cfg) == family.reference.forward_flops(
        cfg, seq)
    # the recurrence is a small part of the useful work: the recurrence, not
    # the chunk algebra that computes it
    scan = 9 * ssm_counts.scan_flops(1, seq, 64, 64, 128)
    assert 0.01 < scan / family.flops_per_sample(cfg) < 0.02
    core = swa_counts.core_flops(1, 32, seq, seq, 64, 64, False)
    assert family.forward_flops(cfg) > core + scan / 3


def test_the_parameters_are_the_issue_s(cfg):
    """A mamba layer 76.18 M, the attention layer 60.82 M, an eighth of the
    tied vocabulary 25.69 M: 772.2 M, 7.72 GB of arguments at 10 bytes a
    parameter and 1.54 GB of gradient."""
    table = family.reference.param_table(cfg)
    sizes = collections.Counter()
    for name, (shape, _) in table.items():
        layer, _, leaf = name.partition(".")
        sizes[layer if leaf else name] += int(np.prod(shape))
    mamba = 2048 * 8512 + 4096 * 2048 + 4352 * 5 + 3 * 64 + 4096 \
        + 3 * 2048 * 8192 + 2 * 2048
    assert sizes["l0"] == sizes["l9"] == mamba
    assert mamba / 1e6 == pytest.approx(76.18, abs=0.005)
    assert sizes["l5"] == 2 * 2048 ** 2 + 2 * 512 * 2048 \
        + 3 * 2048 * 8192 + 2 * 2048
    assert sizes["l5"] / 1e6 == pytest.approx(60.82, abs=0.005)
    assert sizes["embed"] == 12544 * 2048 and "head" not in sizes
    total = sum(sizes.values())
    assert total / 1e6 == pytest.approx(772.2, abs=0.05)
    assert 10 * total / 1e9 == pytest.approx(7.72, abs=0.005)
    assert 2 * total / 1e9 == pytest.approx(1.54, abs=0.005)
    # the mixer's two vectors are the public block's start, the same for
    # every run's seed: A in (1, 16), dt in (0.001, 0.1)
    a_log, dt_bias = family.reference.gate_starts(cfg, 0)
    assert a_log.shape == dt_bias.shape == (64,)
    assert np.exp(a_log).min() >= 1 and np.exp(a_log).max() < 16
    dt = np.log1p(np.exp(dt_bias.astype(np.float64)))
    assert 0.001 <= dt.min() and dt.max() <= 0.1 + 1e-6
    assert not np.array_equal(a_log, family.reference.gate_starts(cfg, 1)[0])
    np.testing.assert_array_equal(table["l0.a_log"][1][1], a_log)
    assert table["l0.conv_b"][1] == ("zeros",) \
        and table["l0.skip"][1] == ("ones",)


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
    for key in ("num_hidden_layers", "vocab_size"):
        assert cfg["published"][key] == PUBLISHED[key]
    assert cfg["family"] == "granitemoehybrid"
    # one whole period, published layers 0 to 9: every kind in its ratio
    assert cfg["num_hidden_layers"] == 10 and cfg["layer_types"] == PERIOD \
        == PUBLISHED["layer_types"][:10]
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["train"]["sequence_length"] in (8192, 6144, 4096, 3072, 2048)
    assert cfg["train"]["sequence_length"] % cfg["mamba_chunk_size"] == 0
    for said in ("layer whole", "Eight chips share the vocabulary",
                 "rows 0-12543", "one whole period", "772.2 M"):
        assert said in cfg["deployment"], said
    for item in ("readings", "mamba_block", "attention_block", "multipliers",
                 "stored_matrices", "weights", "gates", "optimizer",
                 "precision", "data", "aux_loss", "per_chip_batch", "remat"):
        assert item in cfg["assumed"], item
        assert "TO BE WRITTEN" not in cfg["assumed"][item], item
    assert "_limits_from" in cfg["check"]
    assert set(cfg["check"]["limits"]) == set(NUMBERS)


@pytest.fixture(scope="module")
def readings():
    with open(os.path.join(util.FIXTURES,
                           "granite_check_readings.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("number", NUMBERS)
def test_a_limit_stands_off_the_program_s_runs_and_the_controls_it_decides(
        cfg, readings, number):
    """The chip's readings of the cell's check, one row a run
    (`fixtures/granite_check_readings.json`: the program, the fp8 control,
    the recurrence's two controls), hold the file's limits: every run of the
    program 1.5 times under its limit or more, every run of a control that
    the number is said to decide 1.5 times over; and `_limits_from` says
    which runs."""
    limit = cfg["check"]["limits"][number]
    rows = readings["rows"]

    def of(tree):
        return [r[number] for r in rows if r["tree"] == tree]

    program = of("program")
    assert len({r["seed"] for r in rows if r["tree"] == "program"}) >= 10
    assert 1.5 * max(program) <= limit
    decides = readings["decides"][number]
    for control in CONTROLS:
        assert len(of(control)) >= 3
        if control in decides:
            assert min(of(control)) >= 1.5 * limit, control
    said = cfg["check"]["_limits_from"]
    assert "%d runs on %d seeds" % (len(program), len(
        {r["seed"] for r in rows if r["tree"] == "program"})) in said
    line = "%s %.4g: " % (number, limit)
    assert line in said
    text = said[said.index(line):].split(";")[0]
    assert "%.4g" % max(program) in text
    for control in decides:
        assert "%s %.4g" % (control, min(of(control))) in text


def test_every_control_is_some_number_s_to_fail(readings):
    decided = set()
    for number in NUMBERS:
        decided |= set(readings["decides"][number])
    assert decided == set(CONTROLS)


def test_the_cell_is_declared_and_its_readers_list_it(spec, cfg):
    """Every entry found by name, wherever it stands."""
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
    for said in ("1x%d" % seq, "chunks of 256", "nine of ten layers",
                 "64 x 128", "one attention layer"):
        assert said in cell["why"], said
    steps = cfg["train"]["steps_per_block"]
    assert ("every step" if steps == 1 else "every %d" % steps) \
        in cell["why"]
    for name in READERS:
        assert util.named(spec["per_layer"], name)["workloads"] == [CELL], name
    # the accepted lists the cell is owed are a `benchmark` PR's to extend
    # (PERF.md section 7): today it stands on its own five and reports the
    # ones without a list
    listed = {m["name"] for m in spec["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(READERS)
    unlisted = [m["name"] for m in spec["per_layer"] if "workloads" not in m]
    assert "model_flops_util_pct" in unlisted and "hbm_peak_gb" in unlisted
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
    assert 0 < after["ssm_scan_roofline_pct"]["value"]
    assert after["ssm_state_kept_gb"]["value"] == pytest.approx(
        0.3020, abs=1e-4)


def test_the_family_builds_the_file_s_widths(cfg):
    small = dict(cfg, hidden_size=64, shared_intermediate_size=96,
                 vocab_size=64, num_attention_heads=4,
                 num_key_value_heads=2, mamba_n_heads=4, mamba_d_head=32,
                 mamba_d_state=8)
    net, loss = family.build(small)
    assert type(loss).__name__ == "SoftmaxCrossEntropyLoss"
    ops = [layer.operator for layer in net.layers]
    assert [type(o).__name__ for o in ops] == ["StateSpaceMixer"] * 5 + [
        "GroupedQueryAttention"] + ["StateSpaceMixer"] * 4
    assert all((o._heads, o._state, o._groups, o._chunk, o._eps)
               == (4, 8, 1, 256, 1e-5) for o in ops[:5])
    assert ops[0].conv_weight.shape == (128 + 16, 4)
    assert ops[0].conv_bias.shape == (144,)
    assert ops[5]._rotary == {"rotary": False}
    assert ops[5]._scale == 0.015625 and not hasattr(ops[5], "q_gamma")
    assert {layer._residual for layer in net.layers} == {0.22}
    assert {type(layer.feed_forward).__name__ for layer in net.layers} \
        == {"GatedMLP"}
    assert net.head_weight is None


def test_the_family_refuses_a_program_without_the_kind(monkeypatch):
    """`build` raises at once, before anything is compiled, where the
    decoder lacks the kind: the parent commit on this cell."""
    from mxnet_tpu.gluon.model_zoo import decoder
    monkeypatch.setattr(decoder, "OPERATOR_KINDS",
                        tuple(k for k in decoder.OPERATOR_KINDS
                              if k != "mamba"))
    with pytest.raises(RuntimeError, match="no mamba layer kind"):
        family.build({})
    # ... and sooner still: the loop asks for the batches before it makes
    # the seeded weights
    with pytest.raises(RuntimeError, match="no mamba layer kind"):
        family.batches({}, 1, 1, 1)


def test_the_recurrence_s_control_is_the_mask_s_comparison_with_its_sights(
        monkeypatch):
    from benchmarks import control_mask, control_ssm
    calls = []
    monkeypatch.setattr(control_mask, "control_numbers",
                        lambda *a: calls.append(a))
    control_ssm.control_numbers("cell", "devices")
    control_ssm.control_numbers("cell", "devices", "no_skip")
    assert calls == [("cell", "devices", "no_decay"),
                     ("cell", "devices", "no_skip")]
    assert set(control_ssm.SIGHTS) < set(family.reference.SIGHTS)


# -- the family through the loop, tiny, on the CPU ----------------------------
TINY = {
    "family": "granitemoehybrid", "model_type": "granitemoehybrid",
    "hidden_size": 32, "intermediate_size": 48,
    "shared_intermediate_size": 48, "num_attention_heads": 4,
    "num_key_value_heads": 2, "hidden_act": "silu", "attention_bias": False,
    "attention_multiplier": 0.125, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 8, "mamba_n_heads": 4,
    "mamba_d_head": 16, "mamba_d_state": 8, "mamba_n_groups": 1,
    "mamba_d_conv": 4, "mamba_chunk_size": 32, "mamba_expand": 2,
    "mamba_conv_bias": True, "mamba_proj_bias": False,
    "position_embedding_type": "nope", "num_local_experts": 0,
    "num_experts_per_tok": 0, "normalization_function": "rmsnorm",
    "layer_types": ["mamba", "attention"], "num_hidden_layers": 2,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": True, "vocab_size": 64,
    # 0.15 over 32 inputs is the scale 0.02 has over the cell's 2048
    "initializer_range": 0.15, "conv_initializer_range": 0.2887,
    "gate_init_seed": 0, "reduced": [],
    "train": {"optimizer": "sgd", "lr": 0.01, "momentum": 0.9, "wd": 0.0,
              "multi_precision": True, "sequence_length": 64,
              "per_chip_batch": 2, "steps_per_block": 1},
    "check": {"reference_rows_per_block": 1, "limits": None}}
#: CPU, seeds 7, 11 and 2**31 + 13 (one mamba and one attention layer, two
#: chunks of 32): the bf16 program reads first_update_difference 0.0053 to
#: 0.0055, the fp8 control 0.0396 to 0.0406, the reference without the decay
#: 0.0237 to 0.0347 (64 tokens: the cell's 4096 forget far more), without the
#: skip 0.318 to 0.353; by leaf the program's worst gaps read 0.0017 to 0.0037
#: (first gradient) and 0.0012 to 0.0029 (three updates), root mean square
#: 0.0009 to 0.0014, where the controls' smallest are 0.0247 and 0.0180, root
#: mean square 0.0065; the loss is a bf16 readback (0.0023 to 0.0032) and
#: tells nothing apart.  Each limit two times or more from both sides
TINY_LIMITS = {"first_update_difference": 0.012, "loss_gap": 0.011,
               "first_gradient_norm_gap": 0.012,
               "first_gradient_norm_rms": 0.004, "update_norm_gap": 0.009,
               "update_norm_rms": 0.004}


@pytest.fixture()
def root(tmp_path):
    """The suite's fixture root with a tiny cell of this family added as
    a new file and two new entries."""
    root = util.fixture_root(tmp_path)
    tiny = dict(TINY, check=dict(TINY["check"], limits=TINY_LIMITS))
    with open(os.path.join(root, "benchmarks", "configs",
                           "tiny_granite.json"), "w") as f:
        json.dump(tiny, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny_granite", "source": "test fixture", "reduced": [],
        "file": "benchmarks/configs/tiny_granite.json", "why": "fixture"})
    spec["workloads"].append({
        "name": "tiny_granite_train", "config": "tiny_granite",
        "traffic": "fit_prefetch", "chips": 1, "why": "fixture"})
    with open(path, "w") as f:
        json.dump(spec, f)
    return root


def test_the_tiny_cell_runs_and_is_correct(root, capsys):
    """Asserts on counts and on `correct`, never on how many blocks the
    window held."""
    outcome, line = util.run_cell(root, "tiny_granite_train",
                                  seed=2 ** 31 + 13, seconds=6.0)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["metrics"] == {}
    from mxnet_tpu.observability import metrics
    kept = ssm_counts.state_kept_bytes(2, 64, 4, 16, 8, 32)
    assert "mxnet_ssm_state_kept_bytes %s" % float(kept) \
        in metrics.exposition()
    assert "correct: first_update_difference" in capsys.readouterr().out


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 13])
@pytest.mark.parametrize("which", CONTROLS)
def test_a_control_of_the_tiny_cell_is_not_correct(root, capsys, which,
                                                   seed):
    """The fp8 reference and the reference with a part of the recurrence
    left out, each in the program's place: none may pass for this model."""
    import jax
    from benchmarks import compare, control, control_ssm
    cell = harness.Cell("tiny_granite_train", seed, 0, 0, 0.0, root)
    devices = jax.devices()[:1]
    numbers = control.control_numbers(cell, devices) if which == "fp8" \
        else control_ssm.control_numbers(cell, devices, which)
    assert not compare.judge(numbers, cell.config["check"]["limits"])
    assert "OUTSIDE" in capsys.readouterr().out
