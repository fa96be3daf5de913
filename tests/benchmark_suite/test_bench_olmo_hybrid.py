"""The `olmo-hybrid-7b_train_vp8share` cell's own pieces: its five per-layer
readers on made-up outcomes, `benchmarks/gdn_counts.py` and the family's
FLOPs against counts by hand, a brute-force count and the reference's own
count, the configuration's published keys and parameters, its limits against
the chip's readings on record, its entries in BENCHMARK.json (found by name,
wherever they stand), and the family through the `train_fit` loop at a tiny
size on the CPU (a fixture root of its own) with its controls: the fp8 one
and the reference with a part of the rule left out."""

import collections
import json
import os

import numpy as np
import pytest

import bench_suite_util as util
from benchmarks import gdn_counts, harness, swa_counts, trace
from benchmarks.layer_metrics import (flash_bwd_ms_per_step,
                                      flash_fwd_ms_per_step,
                                      gdn_conv_ms_per_step, gdn_ms_per_step,
                                      gdn_scan_ms_per_step,
                                      gdn_scan_roofline_pct,
                                      gdn_state_kept_gb,
                                      gqa_full_ms_per_step)
from benchmarks.models import olmo_hybrid as family

CELL = "olmo-hybrid-7b_train_vp8share"
CONFIG = "olmo-hybrid-7b-vp8share"
Span = collections.namedtuple(
    "Span", "id name cat start end thread parent args")
READERS = {"gdn_ms_per_step": gdn_ms_per_step,
           "gdn_scan_ms_per_step": gdn_scan_ms_per_step,
           "gdn_scan_roofline_pct": gdn_scan_roofline_pct,
           "gdn_conv_ms_per_step": gdn_conv_ms_per_step,
           "gdn_state_kept_gb": gdn_state_kept_gb}
REDUCED = ["num_hidden_layers", "layer_types", "vocab_size"]
NUMBERS = ("first_update_difference", "loss_gap", "first_gradient_norm_gap",
           "first_gradient_norm_rms", "update_norm_gap", "update_norm_rms")
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
# https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json, as
# the guide's catalog holds it
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": PERIOD * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}


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


def plan(seq=4096, heads=30, dk=96, dv=192, chunk=64):
    kept = gdn_counts.state_kept_bytes(1, seq, heads, dk, dv, chunk)
    return {"batch": 1, "tokens": seq, "heads": heads, "key_dim": dk,
            "value_dim": dv, "chunk": chunk, "chunks": seq // chunk,
            "dtype": "bfloat16", "path": "xla", "state_kept_bytes": kept,
            "per_token_state_bytes": kept * chunk}


def _step(linear=True):
    """One traced step: the head's matmul, three linear layers each with
    its four groups forward and backward, and a full layer under `mx.gqa.*`
    with the two flash kernels.  Not *linear*: the full layer alone."""
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
             "conv": ("_contrib_ShortConvHeads:contrib_shortconvheads%d",
                      "mul", 120),
             "scan": ("_contrib_GatedDeltaRule:contrib_gateddeltarule%d",
                      "while", 700),
             "out": ("_contrib_GatedRMSNorm:contrib_gatedrmsnorm%d", "mul",
                     90)}
    for way, wrap in (("f", "jvp(%s)"), ("b", "transpose(jvp(%s))")):
        for layer in range(3 if linear else 0):
            for part, (node, prim, dur) in parts.items():
                op("%s_%s.%d" % (part, way, layer),
                   "jit(parallel_step)/mx.loss/" + wrap % (
                       "mx.gdn.%s/" % part + node % layer) + "/" + prim,
                   dur * (2 if way == "b" else 1), "mx.gdn", part)
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


def _plans(calls=3, **changes):
    return [Span(i, "mx.gdn.plan", "gdn", 101.0 + i, 101.5 + i, 11, None,
                 dict(plan(), **changes)) for i in range(calls)]


def test_the_device_readers_sum_their_scopes(capsys):
    events, scope_map, want = _step()
    out = Outcome(_plans(), scope_map, events, traced_blocks=1,
                  steps_per_block=1)
    assert gdn_ms_per_step.read(out) == pytest.approx(want["mx.gdn"] * 1e-6)
    assert gdn_scan_ms_per_step.read(out) == pytest.approx(
        want["scan"] * 1e-6)
    assert gdn_conv_ms_per_step.read(out) == pytest.approx(
        want["conv"] * 1e-6)
    assert want["scan"] == 3 * 3 * 700 and want["conv"] == 3 * 3 * 120
    # the accepted readers of the full layer and of its kernels read this
    # family's nodes as they are
    assert gqa_full_ms_per_step.read(out) == pytest.approx(
        want["mx.gqa"] * 1e-6)
    assert flash_fwd_ms_per_step.read(out) == pytest.approx(
        want["fwd"] * 1e-6)
    assert flash_bwd_ms_per_step.read(out) == pytest.approx(
        want["bwd"] * 1e-6)
    said = capsys.readouterr().out
    assert said.count("bench: mx.gdn.plan (3 traced calls)") == 1
    assert '"path": "xla"' in said and '"chunks": 64' in said
    assert "bench: mx.gdn %.3f ms a step: project %.3f, conv %.3f, scan " \
        "%.3f, out %.3f" % tuple(want[k] * 1e-6 for k in (
            "mx.gdn", "project", "conv", "scan", "out")) in said
    gdn_ms_per_step.read(out)          # said once
    assert "mx.gdn.plan" not in capsys.readouterr().out


def test_the_roofline_share_is_the_recurrence_s_work_over_the_scan_s_time(
        capsys, cfg):
    events, scope_map, want = _step()
    out = Outcome([], scope_map, events, traced_blocks=1, steps_per_block=1)
    seq = cfg["train"]["sequence_length"]
    # 7 dk dv a token and head forward, three times that with the backward:
    # 129,024 and 387,072 at 96 x 192; three layers of 30 heads
    assert gdn_counts.token_flops(96, 192, False) == 129024
    flops = 3 * seq * 30 * 387072
    assert flops == 3 * gdn_counts.rule_flops(1, seq, 30, 96, 192)
    # q, k, v, o, b in bf16 and g in float32 forward (1158 bytes a token and
    # head); backward the inputs again, dO, and the five gradients
    assert gdn_counts.token_bytes(96, 192, training=False) == 1158
    assert gdn_counts.token_bytes(96, 192) == 1158 + 774 + 384 + 774
    moved = 3 * seq * 30 * 3090
    least = moved / 819e9                       # memory's floor
    assert least > flops / 197e12
    ms = want["scan"] * 1e-6
    assert gdn_scan_roofline_pct.read(out) == pytest.approx(
        100.0 * 1e3 * least / ms)
    said = capsys.readouterr().out
    assert "the gated delta rule in 3 layers, 1 x 30 heads x %d positions, " \
        "a state of 96 x 192" % seq in said
    assert said.count("memory peak") == 1
    # the issue's own numbers at 4096 positions: 143 GFLOP, 0.7 ms at the
    # bf16 peak; the bytes as this file defines them are 1.14 GB, 1.4 ms
    assert 3 * gdn_counts.rule_flops(1, 4096, 30, 96, 192) / 1e9 \
        == pytest.approx(142.7, abs=0.05)
    assert 3 * gdn_counts.rule_flops(1, 4096, 30, 96, 192) / 197e12 * 1e3 \
        == pytest.approx(0.724, abs=0.001)
    assert 3 * gdn_counts.rule_bytes(1, 4096, 30, 96, 192) / 819e9 * 1e3 \
        == pytest.approx(1.391, abs=0.001)


def test_the_state_reader_counts_the_cell_s_plan_without_a_chip(capsys, cfg):
    """0.42 GB over the three layers at 4096 tokens and chunks of 64; a
    state a token would read 27."""
    seq = cfg["train"]["sequence_length"]
    out = Outcome(_plans(6, **plan(seq)), {}, None)
    kept = 3 * (seq // 64) * 30 * 96 * 192 * 4 / 1e9
    assert gdn_state_kept_gb.read(out) == pytest.approx(kept)
    if seq == 4096:
        assert kept == pytest.approx(0.4247, abs=1e-4) and kept < 0.5
    said = capsys.readouterr().out
    assert "state kept for the backward in 3 linear layers: %.4g GB" % kept \
        in said
    assert "a state a token would be %.4g GB" % (64 * kept) in said
    # the plan the program records at the cell's shape is this one
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import profiler
    from mxnet_tpu.ops import delta_rule
    since = max([s.id for s in profiler.spans()] or [0])
    q = jnp.zeros((1, seq, 30, 96), jnp.bfloat16)
    # (a function of its own: `eval_shape` would not trace one it has traced)
    jax.eval_shape(lambda *a: delta_rule._gated_delta_rule_op(*a), q, q,
                   jnp.zeros((1, seq, 30, 192), jnp.bfloat16),
                   jnp.zeros((1, seq, 30), jnp.float32),
                   jnp.zeros((1, seq, 30), jnp.bfloat16))
    recorded, = [s.args for s in profiler.spans()
                 if s.name == "mx.gdn.plan" and s.id > since]
    assert recorded == plan(seq)


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_reads_nothing_where_there_is_nothing(name):
    reader = READERS[name]
    events, scope_map, _ = _step(linear=False)
    for out in (
            # a step with no linear layer and no plan of the rule
            Outcome([], scope_map, events, traced_blocks=1,
                    steps_per_block=1),
            # a program from before the span store and the scope map (a
            # parent commit)
            Outcome(None, None, events, traced_blocks=1, steps_per_block=1),
            # an untraced run of such a program
            Outcome(None, None, None, traced_blocks=1, steps_per_block=1)):
        assert reader.read(out) is None
    # ... and in a cell whose configuration has no linear layer, whatever
    # its trace and its spans hold
    events, scope_map, _ = _step()
    if name in ("gdn_scan_roofline_pct", "gdn_state_kept_gb"):
        out = Outcome(_plans(), scope_map, events, traced_blocks=1,
                      cell="lfm2-8b-a1b_train_ep4share", steps_per_block=1)
        assert reader.read(out) is None


# -- counts -------------------------------------------------------------------
@pytest.mark.parametrize("batch,seq,heads,dk,dv", [(1, 5, 2, 3, 4),
                                                   (2, 7, 1, 4, 2)])
def test_gdn_counts_against_a_brute_force_count(batch, seq, heads, dk, dv):
    """`recurrence` counts its multiply-adds as it does them: 7 dk dv a token
    and head; the bytes are the arrays' own sizes."""
    rng = np.random.default_rng(0)
    q, k = rng.normal(size=(2, batch, seq, heads, dk))
    v = rng.normal(size=(batch, seq, heads, dv))
    g, b = -rng.uniform(size=(batch, seq, heads)), rng.uniform(
        size=(batch, seq, heads))
    out, counted = gdn_counts.recurrence(q, k, v, g, b)
    assert counted == gdn_counts.rule_flops(batch, seq, heads, dk, dv,
                                            training=False)
    assert gdn_counts.rule_flops(batch, seq, heads, dk, dv) == 3 * counted

    def nbytes(*arrays, itemsize=2):
        return sum(a.size for a in arrays) * itemsize

    forward = nbytes(q, k, v, b, out) + nbytes(g, itemsize=4)
    assert gdn_counts.rule_bytes(batch, seq, heads, dk, dv,
                                 training=False) == forward
    backward = nbytes(q, k, v, b) + nbytes(g, itemsize=4) + nbytes(out) \
        + nbytes(q, k, v, b) + nbytes(g, itemsize=4)
    assert gdn_counts.rule_bytes(batch, seq, heads, dk, dv) \
        == forward + backward
    # one step by hand: S = b k v^T, o = S^T q
    one, _ = gdn_counts.recurrence(q[:, :1], k[:, :1], v[:, :1], g[:, :1],
                                   b[:, :1])
    np.testing.assert_allclose(
        one[0, 0, 0], b[0, 0, 0] * (k[0, 0, 0] @ q[0, 0, 0]) * v[0, 0, 0])
    plain, _ = gdn_counts.recurrence(q, k, v, g, b, erase=False)
    assert np.abs(plain - out).max() > 0
    assert gdn_counts.state_kept_bytes(batch, 128, heads, dk, dv, 64) \
        == 4 * batch * 2 * heads * dk * dv


def test_the_family_s_flops_are_the_algorithm_s(cfg):
    """By hand at a small shape, against the reference's own count (from
    its parameter table), then the cell's."""
    small = {"hidden_size": 8, "intermediate_size": 10, "vocab_size": 12,
             "num_attention_heads": 2, "num_key_value_heads": 2,
             "linear_num_key_heads": 2, "linear_num_value_heads": 2,
             "linear_key_head_dim": 3, "linear_value_head_dim": 5,
             "linear_conv_kernel_dim": 4, "num_hidden_layers": 2,
             "layer_types": ["linear_attention", "full_attention"],
             "rope_parameters": {"rope_theta": None},
             "train": {"sequence_length": 6}}
    linear = 8 * (6 + 6 + 10 + 10 + 2 + 2) + 10 * 8 + 3 * 8 * 10
    full = 4 * 8 * 8 + 3 * 8 * 10
    want = 2 * 6 * (linear + full) + 6 * 2 * 7 * 3 * 5 \
        + 2 * 2 * 21 * (4 + 4) + 2 * 6 * 12 * 8
    assert family.forward_flops(small) == want
    assert family.reference.forward_flops(small, 6) == want
    assert family.flops_per_sample(small) == 3 * want
    seq = cfg["train"]["sequence_length"]
    assert family.forward_flops(cfg) == family.reference.forward_flops(
        cfg, seq)
    # the rule is a small part of the useful work: the recurrence, not the
    # chunk algebra that computes it
    rule = 3 * gdn_counts.rule_flops(1, seq, 30, 96, 192)
    assert 0.004 < rule / family.flops_per_sample(cfg) < 0.01
    core = swa_counts.core_flops(1, 30, seq, seq, 128, 128, False)
    assert family.forward_flops(cfg) > core + rule / 3
    assert family.linear_layers(cfg) == 3


def test_the_parameters_are_the_issue_s(cfg):
    """A linear block 88.75 M, a full block 58.99 M, the feed-forward 126.81
    M, an eighth of the vocabulary twice: 928.9 M, 9.29 GB of arguments at
    10 bytes a parameter and 1.86 GB of gradient."""
    table = family.reference.param_table(cfg)
    sizes = collections.Counter()
    for name, (shape, _) in table.items():
        layer, _, leaf = name.partition(".")
        group = "mlp" if leaf in ("w1", "w2", "w3") \
            else "norms" if leaf in ("attn_norm", "ffn_norm") \
            else "operator" if leaf else "rest"
        sizes[(layer if leaf else name, group)] += int(np.prod(shape))
    linear = 3840 * (2880 + 2880 + 5760 + 5760) + 5760 * 3840 \
        + 2 * 3840 * 30 + 11520 * 4 + 60 + 192
    assert sizes[("l0", "operator")] == sizes[("l2", "operator")] == linear
    assert linear / 1e6 == pytest.approx(88.75, abs=0.005)
    assert sizes[("l3", "operator")] == 4 * 3840 ** 2 + 7680
    assert sizes[("l0", "mlp")] == 3 * 3840 * 11008
    assert sizes[("embed", "rest")] == sizes[("head", "rest")] \
        == 12544 * 3840
    total = sum(sizes.values())
    assert total / 1e6 == pytest.approx(928.9, abs=0.05)
    assert 10 * total / 1e9 == pytest.approx(9.29, abs=0.005)
    assert 2 * total / 1e9 == pytest.approx(1.86, abs=0.005)
    # the decay's two vectors are the public block's start, the same for
    # every run's seed: A in (0, 16), dt in (0.001, 0.1)
    a_log, dt_bias = family.reference.gate_starts(cfg, 0)
    assert a_log.shape == dt_bias.shape == (30,)
    assert np.exp(a_log).min() > 0 and np.exp(a_log).max() < 16
    dt = np.log1p(np.exp(dt_bias.astype(np.float64)))
    assert 0.001 <= dt.min() and dt.max() <= 0.1 + 1e-6
    assert not np.array_equal(a_log, family.reference.gate_starts(cfg, 1)[0])
    np.testing.assert_array_equal(table["l0.a_log"][1][1], a_log)


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
    assert cfg["family"] == "olmo_hybrid"
    # one whole period, published layers 0 to 3: every kind in its ratio
    assert cfg["num_hidden_layers"] == 4 and cfg["layer_types"] == PERIOD \
        == PUBLISHED["layer_types"][:4]
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["train"]["sequence_length"] in (4096, 3072, 2048)
    assert cfg["train"]["sequence_length"] % 64 == 0
    for said in ("layer whole", "Eight chips share the vocabulary",
                 "rows 0-12543", "four layers", "928.9 M"):
        assert said in cfg["deployment"], said
    for item in ("readings", "linear_block", "stored_matrices", "norms",
                 "rotary", "weights", "gates", "optimizer", "precision",
                 "data", "aux_loss", "per_chip_batch", "remat"):
        assert item in cfg["assumed"], item
    assert "_limits_from" in cfg["check"]
    assert set(cfg["check"]["limits"]) == set(NUMBERS)


@pytest.fixture(scope="module")
def readings():
    with open(os.path.join(util.FIXTURES,
                           "olmo_hybrid_check_readings.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("number", NUMBERS)
def test_a_limit_stands_off_the_program_s_runs_and_the_controls_it_decides(
        cfg, readings, number):
    """The chip's readings of the cell's check, one row a run
    (`fixtures/olmo_hybrid_check_readings.json`: the program, the fp8
    control, the rule's two controls), hold the file's limits: every run of the
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
    for control in ("fp8", "delta", "single_b"):
        assert len(of(control)) >= 4
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
    assert decided == {"fp8", "delta", "single_b"}


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
    for said in ("1x%d" % seq, "chunks of 64", "3 of 4 layers", "dense",
                 "MLP is the largest single part"):
        assert said in cell["why"], said
    steps = cfg["train"]["steps_per_block"]
    assert ("every step" if steps == 1 else "every %d" % steps) \
        in cell["why"]
    # its own five readers are declared for it alone, each found by name,
    # in the order they were added, after every entry the parent had
    names = [m["name"] for m in spec["per_layer"]]
    places = [names.index(n) for n in (
        "gdn_ms_per_step", "gdn_scan_ms_per_step", "gdn_scan_roofline_pct",
        "gdn_conv_ms_per_step", "gdn_state_kept_gb")]
    assert places == sorted(places) and places[0] > names.index(
        "gqa_full_ms_per_step")
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


def test_laguna_s_declaration_with_every_entry_found_by_name(spec):
    """What `test_bench_laguna.py::test_the_cell_is_declared_and_its_
    readers_list_it` holds Laguna's declaration to, with the entries found
    by `name`.  That test looks for Laguna's entries by their place (the
    last configuration, the last workload, `per_layer[-5:]`), and this cell's
    entries, appended after them as the contract asks, fail it (PERF.md
    section 7); a `benchmark` PR repairs it as PR 42 repaired Keye's, and
    this copy stays."""
    name, config = "laguna-s-2.1_train_ep32share", "laguna-s-2.1-ep32share"
    with open(os.path.join(util.REPO, "benchmarks", "configs",
                           config + ".json")) as f:
        laguna = json.load(f)
    entry = util.named(spec["configs"], config)
    assert entry["reduced"] == laguna["reduced"]
    assert entry["source"] == laguna["source"]
    cell = util.named(spec["workloads"], name)
    assert cell == {"name": name, "config": config,
                    "traffic": "fit_prefetch", "chips": 1,
                    "why": cell["why"]}
    names = [m["name"] for m in spec["per_layer"]]
    own = ["swa_ms_per_step", "swa_flash_ms_per_step",
           "swa_flash_roofline_pct", "swa_tiles_visited_over_needed",
           "gqa_full_ms_per_step"]
    places = [names.index(n) for n in own]
    assert places == list(range(places[0], places[0] + 5))
    for n in own:
        assert util.named(spec["per_layer"], n)["workloads"] == [name], n
    assert {m["name"] for m in spec["per_layer"]
            if name in m.get("workloads", ())} == set(own)


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
    assert 0 < after["gdn_scan_roofline_pct"]["value"]
    assert after["gdn_state_kept_gb"]["value"] == pytest.approx(
        0.4247, abs=1e-4)


def test_the_family_builds_the_file_s_widths(cfg):
    small = dict(cfg, hidden_size=96, intermediate_size=128, vocab_size=64,
                 num_attention_heads=3, num_key_value_heads=3,
                 linear_num_key_heads=3, linear_num_value_heads=3,
                 linear_key_head_dim=8, linear_value_head_dim=16)
    net, loss = family.build(small)
    assert type(loss).__name__ == "SoftmaxCrossEntropyLoss"
    ops = [layer.operator for layer in net.layers]
    assert [type(o).__name__ for o in ops] == ["GatedDeltaNet"] * 3 + [
        "GroupedQueryAttention"]
    assert [layer._norm_output for layer in net.layers] == [False] * 3 \
        + [True]
    assert all((o._heads, o._dk, o._dv, o._neg) == (3, 8, 16, True)
               for o in ops[:3])
    assert ops[0].conv_weight.shape == (96, 4)
    assert ops[3]._rotary == {"rotary": False, "norm_over": "width"}
    assert ops[3].q_gamma.shape == (96,)
    assert {type(layer.feed_forward).__name__ for layer in net.layers} \
        == {"GatedMLP"}
    assert net.head_weight is not None


def test_the_family_refuses_a_program_without_the_kind(monkeypatch):
    """`build` raises at once, before anything is compiled, where the
    decoder lacks the kind: the parent commit on this cell."""
    from mxnet_tpu.gluon.model_zoo import decoder
    monkeypatch.setattr(decoder, "OPERATOR_KINDS",
                        ("conv", "full_attention", "latent_attention",
                         "sparse_attention", "block_diffusion_attention",
                         "sliding_attention"))
    with pytest.raises(RuntimeError, match="no linear_attention layer kind"):
        family.build({})
    # ... and sooner still: the loop asks for the batches before it makes
    # the seeded weights
    with pytest.raises(RuntimeError, match="no linear_attention layer kind"):
        family.batches({}, 1, 1, 1)


def test_the_rule_s_control_is_the_mask_s_comparison_with_its_sights(
        monkeypatch):
    from benchmarks import control_delta, control_mask
    calls = []
    monkeypatch.setattr(control_mask, "control_numbers",
                        lambda *a: calls.append(a))
    control_delta.control_numbers("cell", "devices")
    control_delta.control_numbers("cell", "devices", "single_b")
    assert calls == [("cell", "devices", "no_erase"),
                     ("cell", "devices", "single_b")]
    assert set(control_delta.SIGHTS) < set(family.reference.SIGHTS)


# -- the family through the loop, tiny, on the CPU ----------------------------
TINY = {
    "family": "olmo_hybrid", "model_type": "olmo_hybrid", "hidden_size": 48,
    "intermediate_size": 80, "num_attention_heads": 3,
    "num_key_value_heads": 3, "hidden_act": "silu", "attention_bias": False,
    "linear_num_key_heads": 3, "linear_num_value_heads": 3,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
    "layer_types": ["linear_attention", "full_attention"],
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "num_hidden_layers": 2, "vocab_size": 96, "initializer_range": 0.02,
    "embedding_initializer_range": 1.0, "conv_initializer_range": 0.2887,
    "gate_init_seed": 0, "reduced": [],
    "train": {"optimizer": "sgd", "lr": 0.01, "momentum": 0.9, "wd": 0.0,
              "multi_precision": True, "sequence_length": 64,
              "per_chip_batch": 2, "steps_per_block": 1},
    "check": {
        "reference_rows_per_block": 1,
        # CPU, seeds 7, 11 and 2**31 + 13 (one linear and one full layer, one
        # chunk of 64: a step small enough for a loaded worker): the bf16
        # program reads first_update_difference 0.0095 to 0.0100 and the fp8
        # control 0.135 to 0.149; the rule's two controls read it 0.019 to
        # 0.020 (no_erase) and 0.0098 to 0.0103 (single_b), which it does not
        # tell from the program: the first gradient's norms by leaf decide
        # them (the program's worst gap 0.0014 to 0.0039, root mean square
        # 0.0005 to 0.0011; no_erase 0.100 to 0.21 and 0.0215 to 0.042;
        # single_b 0.073 to 0.16 and 0.0193 to 0.032; fp8 0.019 to 0.028 and
        # 0.0076 to 0.0096, which they do not tell from it).
        "limits": {"first_update_difference": 0.02, "loss_gap": 0.011,
                   "first_gradient_norm_gap": 0.04,
                   "first_gradient_norm_rms": 0.012,
                   "update_norm_gap": 0.04, "update_norm_rms": 0.012}}}


@pytest.fixture()
def root(tmp_path):
    """The suite's fixture root with a tiny cell of this family added as
    a new file and two new entries."""
    root = util.fixture_root(tmp_path)
    with open(os.path.join(root, "benchmarks", "configs",
                           "tiny_olmo_hybrid.json"), "w") as f:
        json.dump(TINY, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny_olmo_hybrid", "source": "test fixture", "reduced": [],
        "file": "benchmarks/configs/tiny_olmo_hybrid.json",
        "why": "fixture"})
    spec["workloads"].append({
        "name": "tiny_olmo_hybrid_train", "config": "tiny_olmo_hybrid",
        "traffic": "fit_prefetch", "chips": 1, "why": "fixture"})
    with open(path, "w") as f:
        json.dump(spec, f)
    return root


def test_the_tiny_cell_runs_and_is_correct(root, capsys):
    """Asserts on counts and on `correct`, never on how many blocks the
    window held."""
    # 42 blocks in 3 s on an idle worker, 12 needed: 6 s for a loaded one
    # (PERF.md section 7 row 40)
    outcome, line = util.run_cell(root, "tiny_olmo_hybrid_train",
                                  seed=2 ** 31 + 13, seconds=6.0)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["metrics"] == {}
    from mxnet_tpu.observability import metrics
    kept = gdn_counts.state_kept_bytes(2, 64, 3, 8, 16, 64)
    assert "mxnet_gdn_state_kept_bytes %s" % float(kept) \
        in metrics.exposition()
    assert "correct: first_update_difference" in capsys.readouterr().out


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 13])
@pytest.mark.parametrize("which", ["fp8", "no_erase", "single_b"])
def test_a_control_of_the_tiny_cell_is_not_correct(root, capsys, which,
                                                   seed):
    """The fp8 reference and the reference with a part of the rule left
    out, each in the program's place: none may pass for this model."""
    import jax
    from benchmarks import compare, control, control_delta
    cell = harness.Cell("tiny_olmo_hybrid_train", seed, 0, 0, 0.0, root)
    devices = jax.devices()[:1]
    numbers = control.control_numbers(cell, devices) if which == "fp8" \
        else control_delta.control_numbers(cell, devices, which)
    limits = cell.config["check"]["limits"]
    assert not compare.judge(numbers, limits)
    # the whole update's direction decides the precision, the first
    # gradient's norms by leaf the two departures from the rule
    decided = {"fp8": ["first_update_difference"],
               "no_erase": list(NUMBERS[2:4]),
               "single_b": list(NUMBERS[2:4])}[which]
    for number in decided:
        assert numbers[number][0] > 1.5 * limits[number], number
    assert "OUTSIDE" in capsys.readouterr().out
