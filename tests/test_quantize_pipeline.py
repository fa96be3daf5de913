"""Graph-level post-training quantization pipeline (ISSUE 17
tentpole): calibrate -> quantize_model -> registry load with the
accuracy gate.
"""

import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.quantize import (CalibTable, QuantizationError,
                                QuantizePolicy, calibrate,
                                hlo_has_int8_compute, quantize_model)
from mxnet_tpu.serve.buckets import BucketLadder
from mxnet_tpu.serve.registry import ModelRegistry


def _convnet():
    data = mx.sym.var("data")
    c1 = mx.sym.Convolution(data=data, kernel=(3, 3), num_filter=8,
                            name="c1")
    a1 = mx.sym.Activation(data=c1, act_type="relu", name="a1")
    p1 = mx.sym.Pooling(data=a1, kernel=(2, 2), stride=(2, 2),
                        pool_type="max", name="p1")
    f1 = mx.sym.FullyConnected(data=p1, num_hidden=10, name="f1")
    return f1


def _params(rs):
    return {
        "c1_weight": nd.array(rs.randn(8, 3, 3, 3).astype(np.float32)
                              * 0.2),
        "c1_bias": nd.array(rs.randn(8).astype(np.float32) * 0.1),
        "f1_weight": nd.array(rs.randn(10, 8 * 5 * 5)
                              .astype(np.float32) * 0.1),
        "f1_bias": nd.array(rs.randn(10).astype(np.float32) * 0.1),
    }


@pytest.fixture
def net():
    rs = np.random.RandomState(4)
    sym = _convnet()
    params = _params(rs)
    batches = [rs.randn(4, 3, 12, 12).astype(np.float32)
               for _ in range(4)]
    return sym, params, batches, rs


# -- calibration ------------------------------------------------------------

def test_calibrate_covers_every_float_tensor(net):
    sym, params, batches, _ = net
    table = calibrate(sym, params, batches)
    for tname in ("data", "c1", "a1", "p1", "f1"):
        assert table.covers(tname), tname
    assert table.batches == 4 and table.mode == "minmax"
    lo, hi = table.range("a1")
    assert lo == 0.0 and hi > 0.0          # post-relu range


def test_calibrate_minmax_is_running_envelope(net):
    sym, params, batches, _ = net
    one = calibrate(sym, params, batches[:1])
    full = calibrate(sym, params, batches)
    lo1, hi1 = one.range("c1")
    lo4, hi4 = full.range("c1")
    assert lo4 <= lo1 and hi4 >= hi1


def test_calibrate_percentile_tightens_ranges(net):
    sym, params, batches, _ = net
    mm = calibrate(sym, params, batches)
    pc = calibrate(sym, params, batches, mode="percentile",
                   percentile=90.0)
    assert pc.max_abs("c1") < mm.max_abs("c1")
    assert pc.sha != mm.sha


def test_calibrate_rejects_empty_and_bad_mode(net):
    sym, params, _, _ = net
    with pytest.raises(QuantizationError):
        calibrate(sym, params, [])
    with pytest.raises(QuantizationError):
        calibrate(sym, params, [np.zeros((1, 3, 12, 12), np.float32)],
                  mode="bogus")


def test_calib_table_sha_identity_and_atomic_roundtrip(net, tmp_path):
    sym, params, batches, _ = net
    table = calibrate(sym, params, batches)
    path = os.path.join(str(tmp_path), "calib.json")
    sha = table.save(path)
    loaded = CalibTable.load(path)
    assert loaded.sha == sha == table.sha
    assert loaded.ranges == table.ranges


def test_calib_table_corruption_fails_typed(net, tmp_path):
    sym, params, batches, _ = net
    table = calibrate(sym, params, batches)
    path = os.path.join(str(tmp_path), "calib.json")
    table.save(path)
    doc = json.load(open(path))
    doc["calib_table"]["ranges"]["c1"] = [-99.0, 99.0]
    open(path, "w").write(json.dumps(doc))
    with pytest.raises(QuantizationError, match="sha check"):
        CalibTable.load(path)
    with pytest.raises(QuantizationError, match="unreadable"):
        CalibTable.load(os.path.join(str(tmp_path), "missing.json"))


# -- lowering ---------------------------------------------------------------

def test_quantize_model_int8_close_to_fp32_with_fused_chain(net):
    sym, params, batches, rs = net
    x = batches[-1]
    ref = sym.bind(args={**params, "data": nd.array(x)}) \
        .forward()[0].asnumpy()
    table = calibrate(sym, params, batches)
    qsym, qargs, _, report = quantize_model(sym, params, calib=table,
                                            policy="int8")
    out = qsym.bind(args={**qargs, "data": nd.array(x)}) \
        .forward()[0].asnumpy()
    err = np.abs(out - ref).max() / np.abs(ref).max()
    assert err < 0.05, err
    assert report["layers"] == {"c1": "int8", "f1": "int8"}
    # relu + pool ride the int8 domain between the two layers
    assert report["passthrough"] == ["a1", "p1"]
    assert report["covered"] == 2 and report["total"] == 2
    assert report["calib_sha"] == table.sha
    args = qsym.list_arguments()
    assert "c1_weight_quantized" in args and "c1_weight" not in args
    assert str(qargs["c1_weight_quantized"].dtype) == "int8"
    # fused: ONE quantize at the graph input, no dequantize between
    # c1 and f1
    assert "f1_data_min" not in args


def test_quantize_model_weight_only_needs_no_calib(net):
    sym, params, batches, _ = net
    x = batches[-1]
    ref = sym.bind(args={**params, "data": nd.array(x)}) \
        .forward()[0].asnumpy()
    qsym, qargs, _, report = quantize_model(
        sym, params, policy="int8-weight-only")
    out = qsym.bind(args={**qargs, "data": nd.array(x)}) \
        .forward()[0].asnumpy()
    err = np.abs(out - ref).max() / np.abs(ref).max()
    assert err < 0.05, err
    assert report["calib_sha"] is None
    assert set(report["layers"].values()) == {"int8-weight-only"}


def test_quantize_model_int8_requires_calib(net):
    sym, params, _, _ = net
    with pytest.raises(QuantizationError, match="CalibTable"):
        quantize_model(sym, params, policy="int8")


def test_policy_exclude_and_first_last(net):
    sym, params, batches, _ = net
    table = calibrate(sym, params, batches)
    _, _, _, rep = quantize_model(
        sym, params, calib=table,
        policy=QuantizePolicy(mode="int8", exclude=("f1",)))
    assert rep["layers"] == {"c1": "int8", "f1": "fp32:excluded"}
    _, _, _, rep = quantize_model(
        sym, params, calib=table,
        policy=QuantizePolicy(mode="int8", first_last_fp32=True))
    assert set(rep["layers"].values()) == {"fp32:first-last-fp32"}


def test_missing_calib_range_falls_back_fp32(net):
    sym, params, batches, _ = net
    table = calibrate(sym, params, batches)
    # drop c1's INPUT range -> c1 cannot quantize, f1 still can
    ranges = dict(table.ranges)
    del ranges["data"]
    partial = CalibTable(ranges)
    _, _, _, rep = quantize_model(sym, params, calib=partial,
                                  policy="int8")
    assert rep["layers"]["c1"] == "fp32:no-calib-range"
    assert rep["layers"]["f1"] == "int8"


def test_policy_coerce_boundary():
    assert QuantizePolicy.coerce(None) is None
    assert QuantizePolicy.coerce("off") is None
    assert QuantizePolicy.coerce("int8").mode == "int8"
    assert QuantizePolicy.coerce(
        {"mode": "int8", "max_rel_err": 0.2}).max_rel_err == 0.2
    p = QuantizePolicy(mode="int8-weight-only")
    assert QuantizePolicy.coerce(p) is p
    with pytest.raises(QuantizationError):
        QuantizePolicy.coerce("int4")
    with pytest.raises(QuantizationError):
        QuantizePolicy.coerce(42)


# -- serving integration ----------------------------------------------------

def test_registry_load_quantized_gate_health_and_unload(net):
    sym, params, batches, rs = net
    reg = ModelRegistry()
    pred = reg.load("qm", sym, params,
                    data_shapes={"data": (4, 3, 12, 12)},
                    ladder=BucketLadder(batches=(1, 2, 4)),
                    quantize="int8", calib_batches=batches)
    try:
        assert pred.jit_cache_size() == 0
        h = reg.health("qm")
        q = h["quantization"]
        assert q["mode"] == "int8"
        assert q["covered"] == 2 and q["total"] == 2
        assert len(q["calib_sha"]) == 64
        assert set(q["gate"]["rungs"]) == {1, 2, 4}
        assert q["gate"]["max_rel_err"] <= 0.1
        # int8 compute provably present at every rung
        for b in (1, 2, 4):
            assert hlo_has_int8_compute(
                pred.lowered_text(pred.rung_shapes(b)))
        # request path stays compile-free
        before = pred.compile_count
        out = pred.predict(
            {"data": rs.randn(3, 3, 12, 12).astype(np.float32)})
        assert out[0].shape == (3, 10)
        assert pred.compile_count == before
    finally:
        reg.unload("qm", drain=False)
    assert reg.health().get("qm") is None


@pytest.mark.parametrize("rung", [1, 2, 4])
def test_int8_rung_moves_fewer_compute_bytes_than_f32_rung(net, rung):
    """Counted from the lowered text of each rung: the operands and
    results of the quantized program's dot/convolution ops are fewer
    bytes than the float32 program's at the same rung."""
    from mxnet_tpu.observability import costs
    sym, params, batches, _ = net
    reg = ModelRegistry()
    try:
        compute_bytes = {}
        for name, quantized in (("f32", {}),
                                ("int8", {"quantize": "int8",
                                          "calib_batches": batches})):
            pred = reg.load(name, sym, params,
                            data_shapes={"data": (4, 3, 12, 12)},
                            ladder=BucketLadder(batches=(rung,)),
                            **quantized)
            text = pred.lowered_text(pred.rung_shapes(rung))
            compute_bytes[name] = sum(
                r["bytes"] for r in costs.parse_hlo_ops(text)
                if r["op"] in ("dot_general", "dot", "convolution"))
            if name == "int8":
                assert hlo_has_int8_compute(text)
    finally:
        reg.close()
    assert 0 < compute_bytes["int8"] < compute_bytes["f32"], \
        compute_bytes


def test_registry_gate_failure_is_typed_and_installs_nothing(net):
    sym, params, batches, _ = net
    reg = ModelRegistry()
    with pytest.raises(QuantizationError, match="gate"):
        reg.load("qm", sym, params,
                 data_shapes={"data": (4, 3, 12, 12)},
                 quantize=QuantizePolicy(mode="int8",
                                         max_rel_err=1e-9),
                 calib_batches=batches)
    assert reg.health().get("qm") is None
    assert reg.names() == []


def test_registry_int8_without_calib_fails_typed(net):
    sym, params, _, _ = net
    reg = ModelRegistry()
    with pytest.raises(QuantizationError, match="calib"):
        reg.load("qm", sym, params,
                 data_shapes={"data": (4, 3, 12, 12)},
                 quantize="int8")


def test_registry_load_from_saved_calib_path_and_broken_path(
        net, tmp_path):
    sym, params, batches, _ = net
    table = calibrate(sym, params, batches)
    path = os.path.join(str(tmp_path), "calib.json")
    table.save(path)
    reg = ModelRegistry()
    pred = reg.load("qm", sym, params,
                    data_shapes={"data": (4, 3, 12, 12)},
                    ladder=BucketLadder(batches=(1, 4)),
                    quantize="int8", calib=path)
    assert pred.quantization["calib_sha"] == table.sha
    reg.unload("qm", drain=False)
    # a torn table file must fail the LOAD, typed
    doc = json.load(open(path))
    doc["sha"] = "0" * 64
    open(path, "w").write(json.dumps(doc))
    with pytest.raises(QuantizationError, match="sha check"):
        reg.load("qm2", sym, params,
                 data_shapes={"data": (4, 3, 12, 12)},
                 quantize="int8", calib=path)


def test_registry_weight_only_load(net):
    sym, params, _, _ = net
    reg = ModelRegistry()
    pred = reg.load("wq", sym, params,
                    data_shapes={"data": (4, 3, 12, 12)},
                    ladder=BucketLadder(batches=(1, 4)),
                    quantize="int8-weight-only")
    try:
        assert pred.quantization["mode"] == "int8-weight-only"
        assert pred.quantization["calib_sha"] is None
        assert reg.health("wq")["quantization"]["mode"] == \
            "int8-weight-only"
    finally:
        reg.unload("wq", drain=False)


# -- autotune integration ---------------------------------------------------

def test_serve_space_has_quantize_choice():
    from mxnet_tpu.autotune.space import serve_space
    space = serve_space(max_rows=8)
    cfg = space.default()
    assert cfg["quantize"] == "off"
    assert "quantize" in space.params
    assert tuple(space.params["quantize"].options) == \
        ("off", "int8-weight-only", "int8")


def test_serve_measurer_quantized_artifact_records_calib_sha():
    from mxnet_tpu.autotune import trace as T
    from mxnet_tpu.autotune.measure import ServeMeasurer
    tr = T.synth_serve_trace(rate=150.0, seconds=0.3, dim=16, seed=0)
    m = ServeMeasurer(tr, name="qtune")
    art = m.measure({"ladder": (1, 2, 4), "quantize": "int8"},
                    budget_frac=0.5)
    assert art["ok"]
    assert art["quantize"] == "int8"
    assert len(art["calib_sha"]) == 64
    assert art["quant_max_rel_err"] <= 0.1
    assert art["request_path_compiles"] == 0
    base = m.measure({"ladder": (1, 2, 4)}, budget_frac=0.5)
    assert "quantize" not in base
