"""`attention_ms_per_step` (benchmarks/layer_metrics): the attention
nodes' device time, kernels and wrappers, on a hand-made trace against a
separate sum; its plan print; and its entry in BENCHMARK.json."""

import collections
import json
import os

import pytest

import bench_suite_util as util
from benchmarks import trace
from benchmarks.layer_metrics import (attention_ms_per_step,
                                      flash_bwd_ms_per_step,
                                      flash_fwd_ms_per_step)

Span = collections.namedtuple(
    "Span", "id name cat start end thread parent args")
NODE = "_contrib_DotProductAttention:opt_l%d_att"


class Outcome:
    def __init__(self, spans, scope_map, events, **facts):
        self.facts = dict(facts, program_spans=spans,
                          program_scope_map=scope_map)
        self.end_to_end = {"setup_s": 30.0}
        self.trace = trace.Trace(events) if events else None
        self.spans = None


def _step(with_attention=True):
    """One traced step of two layers: per layer a matmul, then (in an
    attention node) the wrapper's copy, the kernel; backward the delta
    pass and the one backward kernel."""
    events = [{"plane": "/host:CPU", "line": "python",
               "name": "bench.fit_batch", "start_ns": 0,
               "dur_ns": 100000}]
    scope_map, want = {}, collections.Counter()
    t = [10]

    def op(name, scope, dur, key=None):
        events.append({"plane": "/device:TPU:0", "line": "XLA Ops",
                       "name": "%" + name + " = f32[] fusion()",
                       "start_ns": t[0], "dur_ns": dur})
        scope_map[name] = scope
        t[0] += dur + 5
        if key:
            want[key] += dur
            want["attention"] += dur

    for layer in range(2):
        fwd = "jit(parallel_step)/mx.loss/jvp(%s)" % (NODE % layer)
        bwd = "jit(parallel_step)/mx.loss/transpose(jvp(%s))" % (
            NODE % layer)
        op("fusion.%d" % layer,
           "jit(parallel_step)/mx.loss/jvp(FullyConnected:fc%d)/dot"
           % layer, 700)
        if not with_attention:
            continue
        op("copy.%d" % layer, fwd + "/transpose", 40, "wrapper")
        op("mx_flash_fwd.%d" % layer,
           fwd + "/mx.flash.fwd/mx_flash_fwd/pallas_call", 300, "fwd")
        op("reduce.%d" % layer, bwd + "/reduce_sum", 20, "wrapper")
        op("mx_flash_bwd.%d" % layer,
           bwd + "/mx.flash.bwd/mx_flash_bwd/pallas_call", 900, "bwd")
    return events, scope_map, want


def test_attention_is_the_kernels_and_their_wrappers(capsys):
    events, scope_map, want = _step()
    plan = {"sq": 2048, "sk": 2048, "d": 64, "d_block": 64,
            "fwd": {"tiles_visited": 36, "tiles_masked": 8,
                    "tiles_ideal": 32.016, "resident": [2048, 2048],
                    "sub_tile": [256, 256]}}
    spans = [Span(i, "mx.flash.plan", "flash", 101.0 + i, 101.5 + i, 11,
                  None, plan) for i in range(2)]
    out = Outcome(spans, scope_map, events, traced_blocks=1,
                  steps_per_block=1)
    got = attention_ms_per_step.read(out)
    assert got == pytest.approx(want["attention"] * 1e-6)
    kernels = [m.read(out) for m in (flash_fwd_ms_per_step,
                                     flash_bwd_ms_per_step)]
    assert kernels == [pytest.approx(want[k] * 1e-6)
                       for k in ("fwd", "bwd")]
    assert got == pytest.approx(sum(kernels) + want["wrapper"] * 1e-6)
    said = capsys.readouterr().out
    assert said.count("bench: mx.flash.plan (2 traced calls)") == 1
    assert '"tiles_visited": 36' in said and '"sub_tile": [256, 256]' in said
    attention_ms_per_step.read(out)          # the plan is said once
    assert "mx.flash.plan" not in capsys.readouterr().out


def test_attention_reads_nothing_where_there_is_none(capsys):
    events, scope_map, _ = _step(with_attention=False)
    out = Outcome([], scope_map, events, traced_blocks=1,
                  steps_per_block=1)
    assert attention_ms_per_step.read(out) is None
    # a program from before the span store and the scope map (a parent)
    out = Outcome(None, None, events, traced_blocks=1, steps_per_block=1)
    assert attention_ms_per_step.read(out) is None
    # ... and an untraced run
    out = Outcome([], scope_map, None, traced_blocks=1, steps_per_block=1)
    assert attention_ms_per_step.read(out) is None
    assert "mx.flash.plan" not in capsys.readouterr().out


@pytest.mark.parametrize("node", [
    "transpose(jvp(_contrib_DotProductAttention:opt_l0_att))",
    "transpose(jvp(_contrib_LatentAttention:contrib_latentattention0))"
    "/mx.mla"],
    ids=["in_an_attention_node", "in_a_latent_attention_block"])
def test_the_backward_kernel_is_read_wherever_it_runs(node):
    """One kernel, one scope (`mx.flash.bwd`), under whichever operator
    calls it; the retired pair's scopes and a scope that only starts
    alike are not it."""
    events = [{"plane": "/host:CPU", "line": "python",
               "name": "bench.fit_batch", "start_ns": 0, "dur_ns": 10000}]
    scope_map = {}
    bwd = "jit(parallel_step)/mx.loss/" + node
    for i, (name, scope, dur) in enumerate((
            ("mx_flash_bwd.1", "/mx.flash.bwd/mx_flash_bwd/pallas_call",
             700),
            ("mx_flash_bwd.2", "/mx.flash.bwd", 200),
            ("mx_flash_dq.3", "/mx.flash.dq/mx_flash_dq/pallas_call", 400),
            ("fusion.4", "/mx.flash.bwd_delta/reduce_sum", 50),
            ("mx_flash_fwd.5", "/mx.flash.fwd/mx_flash_fwd/pallas_call",
             300))):
        events.append({"plane": "/device:TPU:0", "line": "XLA Ops",
                       "name": "%" + name, "start_ns": 10 + 1000 * i,
                       "dur_ns": dur})
        scope_map[name] = bwd + scope
    out = Outcome([], scope_map, events, traced_blocks=1,
                  steps_per_block=3)
    assert flash_bwd_ms_per_step.read(out) == pytest.approx(900e-6 / 3)
    assert flash_fwd_ms_per_step.read(out) == pytest.approx(300e-6 / 3)


def test_attention_metric_is_declared_for_the_cells_with_attention_nodes():
    with open(os.path.join(util.REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for name, r in (("attention_ms_per_step", attention_ms_per_step),
                    ("flash_bwd_ms_per_step", flash_bwd_ms_per_step)):
        m = util.named(spec["per_layer"], name)
        assert m == {"name": name, "unit": r.UNIT, "better": r.BETTER,
                     "source": r.SOURCE, "layer": r.LAYER, "moves": r.MOVES,
                     "workloads": m["workloads"]}
        assert "opt-1.3b_train_1chip" in m["workloads"]
        # ResNet-50 traces no attention
        assert "resnet50_train" not in m["workloads"]
    # the latent block is a node of its own (`_contrib_LatentAttention`):
    # its kernels are read, its node is `mla_ms_per_step`'s
    attention, latent, backward = (
        set(util.named(spec["per_layer"], name)["workloads"])
        for name in ("attention_ms_per_step", "mla_ms_per_step",
                     "flash_bwd_ms_per_step"))
    assert latent.isdisjoint(attention) and latent <= backward
    # the pair the one kernel replaced is gone, files and entries
    for gone in ("flash_dkdv_ms_per_step", "flash_dq_ms_per_step"):
        assert gone not in {m["name"] for m in spec["per_layer"]}
        assert not os.path.exists(os.path.join(
            util.REPO, "benchmarks", "layer_metrics", gone + ".py"))
