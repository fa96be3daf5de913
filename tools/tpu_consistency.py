#!/usr/bin/env python
"""Cross-backend consistency sweep on real hardware (reference:
tests/python/gpu/test_operator_gpu.py reusing the CPU suite through
check_consistency, test_utils.py:1207 — "the single most important
harness to reproduce", SURVEY §4.1).

Runs a library of small symbols through ``test_utils.check_consistency``
comparing the TPU backend against CPU — outputs AND gradients must agree
within per-dtype tolerance.  One process runs every case; it needs a
TPU as the default backend and the CPU backend beside it
(``JAX_PLATFORMS=tpu,cpu`` or unset):

    python tools/tpu_consistency.py [case ...]

Exits 1 listing any mismatching case, 2 when a backend is missing.
"""

from __future__ import annotations

import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as _np


def _cases(mx):
    """(name, symbol, shapes, tolerances) — one per op family."""
    s = mx.sym
    d = s.var("data")
    w = s.var("w")
    cases = []

    def add(name, sym, shapes, rtol=2e-3, atol=2e-3, grad_req="write",
            location=None):
        cases.append((name, sym, shapes, rtol, atol, grad_req, location))

    add("fc_relu", s.Activation(s.FullyConnected(
        d, num_hidden=16, name="fc"), act_type="relu"),
        {"data": (4, 8)})
    add("conv_bn_pool", s.Pooling(s.Activation(s.Convolution(
        d, num_filter=8, kernel=(3, 3), pad=(1, 1), name="c"),
        act_type="relu"), kernel=(2, 2), stride=(2, 2),
        pool_type="max"), {"data": (2, 3, 8, 8)})
    add("softmax_ce", s.SoftmaxOutput(s.FullyConnected(
        d, num_hidden=5, name="f2"), s.var("lbl")),
        {"data": (6, 10), "lbl": (6,)})
    add("layernorm", s.LayerNorm(d, s.var("g"), s.var("b")),
        {"data": (4, 12), "g": (12,), "b": (12,)})
    add("batch_dot", s.batch_dot(d, w),
        {"data": (3, 4, 5), "w": (3, 5, 6)})
    add("broadcast_chain", s.broadcast_mul(
        s.broadcast_add(d, w), s.exp(-d)),
        {"data": (4, 6), "w": (1, 6)})
    add("reduce_stack", s.sum(s.square(d), axis=1),
        {"data": (5, 7)})
    add("transpose_reshape", s.Reshape(s.transpose(d, (0, 2, 1)),
                                       (0, -1)),
        {"data": (2, 3, 4)})
    add("take_embed", s.Embedding(s.var("idx"), w, input_dim=20,
                                  output_dim=6),
        {"idx": (3, 4), "w": (20, 6)})
    add("rnn_tanh", s.RNN(d, s.var("p"), s.var("st"),
                          state_size=8, num_layers=1, mode="rnn_tanh",
                          name="r"),
        {"data": (5, 2, 4), "p": (8 * (4 + 8 + 2),), "st": (1, 2, 8)})
    add("attention", s.contrib.DotProductAttention(
        s.var("q"), s.var("k"), s.var("v")),
        {"q": (1, 2, 16, 8), "k": (1, 2, 16, 8), "v": (1, 2, 16, 8)})

    # --- round 4: one case per remaining op family ---------------------
    # recurrent: multi-layer bidirectional LSTM / GRU
    add("lstm_bidir", s.RNN(d, s.var("pl"), s.var("sl"), s.var("cl"),
                            state_size=6, num_layers=2, mode="lstm",
                            bidirectional=True, name="rl"),
        {"data": (4, 2, 5)})
    add("gru", s.RNN(d, s.var("pg"), s.var("sg"), state_size=6,
                     num_layers=1, mode="gru", name="rg"),
        {"data": (4, 2, 5)})
    # dense NN long tail
    add("deconv", s.Deconvolution(d, num_filter=4, kernel=(2, 2),
                                  stride=(2, 2), name="dc"),
        {"data": (2, 3, 5, 5)})
    add("pool_avg_global", s.Pooling(d, global_pool=True,
                                     pool_type="avg", kernel=(1, 1)),
        {"data": (2, 4, 6, 6)})
    add("dropout_eval", s.Dropout(d, p=0.5), {"data": (4, 6)})
    add("lrn", s.LRN(d, nsize=3), {"data": (2, 4, 5, 5)})
    add("svm_output", s.SVMOutput(s.FullyConnected(
        d, num_hidden=4, name="f3"), s.var("lbl2")),
        {"data": (5, 6), "lbl2": (5,)})
    # detection / spatial
    add("roi_align", s.contrib.ROIAlign(
        d, s.var("rois"), pooled_size=(2, 2), spatial_scale=1.0),
        {"data": (1, 3, 8, 8), "rois": (2, 5)})
    add("bilinear_sampler", s.BilinearSampler(d, s.var("grid")),
        {"data": (1, 2, 6, 6), "grid": (1, 2, 4, 4)})
    add("spatial_transformer", s.SpatialTransformer(
        d, s.FullyConnected(s.var("loc"), num_hidden=6, name="lf"),
        target_shape=(4, 4), transform_type="affine",
        sampler_type="bilinear"),
        {"data": (1, 2, 6, 6), "loc": (1, 8)})
    # forward-only families (integer / index outputs)
    add("box_nms", s.contrib.box_nms(d, overlap_thresh=0.5),
        {"data": (1, 6, 6)}, grad_req="null")
    add("topk_argsort", s.topk(d, k=3, ret_typ="indices"),
        {"data": (4, 9)}, grad_req="null")
    add("bipartite_match", s.contrib.bipartite_matching(
        d, threshold=1e-12), {"data": (5, 4)}, grad_req="null")
    add("quantize_int8", s.contrib.quantize(
        d, s.var("qmin"), s.var("qmax"), out_type="int8"),
        {"data": (3, 7), "qmin": (1,), "qmax": (1,)}, grad_req="null",
        location={"qmin": _np.array([-3.0], _np.float32),
                  "qmax": _np.array([3.0], _np.float32)})
    # graph-level sparse ops (explicit integer row ids)
    add("sparse_square_sum", s._square_sum(s._sparse_retain(
        d, s.var("sridx")), axis=1),
        {"data": (6, 5), "sridx": (3,)}, grad_req="null",
        location={"sridx": _np.array([0, 2, 5], _np.float32)})
    add("sparse_dot_dense", s.dot(s.cast_storage(d, stype="default"), w),
        {"data": (4, 6), "w": (6, 3)})
    # flash vs chunked vs oracle attention agree ON the device itself
    add("attention_causal", s.contrib.DotProductAttention(
        s.var("q"), s.var("k"), s.var("v"), causal=True),
        {"q": (1, 2, 32, 8), "k": (1, 2, 32, 8), "v": (1, 2, 32, 8)})

    # --- session-2 additions: remaining op families ---------------------
    add("conv_depthwise", s.Convolution(
        d, num_filter=6, kernel=(3, 3), pad=(1, 1), num_group=6,
        name="dwc"), {"data": (2, 6, 8, 8)})
    add("conv_dilated", s.Convolution(
        d, num_filter=4, kernel=(3, 3), pad=(2, 2), dilate=(2, 2),
        name="dlc"), {"data": (1, 3, 9, 9)})
    add("embedding_take", s.take(w, s.var("idx2")),
        {"w": (10, 5), "idx2": (4,)}, grad_req="null",
        location={"idx2": _np.array([1, 3, 5, 7], _np.float32)})
    add("linalg_chain", s.linalg_gemm2(d, w),
        {"data": (3, 4), "w": (4, 5)})
    add("l2norm_channel", s.L2Normalization(d, mode="channel"),
        {"data": (2, 4, 5, 5)})
    add("adaptive_avg_pool", s.contrib.AdaptiveAvgPooling2D(
        d, output_size=(3, 3)), {"data": (2, 3, 7, 7)})
    add("bilinear_resize", s.contrib.BilinearResize2D(
        d, height=9, width=9), {"data": (1, 2, 5, 5)})
    add("instance_norm", s.InstanceNorm(d, s.var("g2"), s.var("b2")),
        {"data": (2, 3, 6, 6), "g2": (3,), "b2": (3,)})
    add("smooth_l1_where", s.smooth_l1(
        s.where(s.var("c") > 0, d, -d), scalar=1.0),
        {"data": (4, 5), "c": (4, 5)})
    add("foreach_scan", s.contrib.foreach(
        lambda x_, st: (x_ * st[0], [st[0] + 1.0]),
        d, [s.var("st0")])[0],
        {"data": (5, 3, 4), "st0": (3, 4)})
    add("stem_s2d", s.space_to_depth(d, block_size=2),
        {"data": (2, 4, 6, 6)})
    add("multibox_prior_det", s.concat(
        s.Reshape(s.MultiBoxPrior(d, sizes=(0.3,), ratios=(1.0, 2.0)),
                  (1, -1, 4)), dim=1),
        {"data": (1, 3, 4, 4)}, grad_req="null")

    # --- round-5 additions ----------------------------------------------
    # CTC with per-sequence lengths (flag-gated optional graph inputs)
    add("ctc_lengths", s.CTCLoss(
        d, s.var("clab"), s.var("cdl"), s.var("cll"),
        use_data_lengths=True, use_label_lengths=True,
        blank_label="last"),
        {"data": (6, 2, 5), "clab": (2, 3), "cdl": (2,), "cll": (2,)},
        grad_req="null",
        location={"clab": _np.array([[1, 2, 0], [3, 1, 2]], _np.float32),
                  "cdl": _np.array([4, 6], _np.float32),
                  "cll": _np.array([2, 3], _np.float32)})
    # 'full'-convention pooling (the SSD/VGG pool3 path)
    add("pool_full_conv", s.Pooling(
        d, kernel=(2, 2), stride=(2, 2), pool_type="max",
        pooling_convention="full"), {"data": (1, 2, 7, 7)})
    # GShard-einsum MoE (routing argmax ties break identically only at
    # matched precision — exactly what the sweep checks)
    add("moe_ffn", s.MoEFFN(d, s.var("mgw"), s.var("mw1"),
                            s.var("mw2"), capacity_factor=2.0),
        {"data": (16, 8), "mgw": (8, 4), "mw1": (4, 8, 16),
         "mw2": (4, 16, 8)})
    return cases


def run_cases(only=None):
    """Run the named cases (default: all) in this process."""
    import mxnet_tpu as mx
    from mxnet_tpu import test_utils

    backends = test_utils.list_backends()
    print("backends:", backends)
    if "tpu" not in backends:
        print("no TPU backend available — nothing to compare")
        return 2
    if "cpu" not in backends:
        print("no CPU backend available — cannot compare (JAX_PLATFORMS"
              " must include cpu alongside the accelerator)")
        return 2

    failures = []
    cases = _cases(mx)
    if only:
        known = {c[0] for c in cases}
        unknown = [n for n in only if n not in known]
        if unknown:
            print("unknown case name(s): %s\navailable: %s"
                  % (unknown, sorted(known)))
            return 2
    n_run = 0
    for name, sym, shapes, rtol, atol, grad_req, location in cases:
        if only and name not in only:
            continue
        n_run += 1
        try:
            # complete the shape dict (weights etc.) via inference
            arg_shapes, _, _ = sym.infer_shape(**shapes)
            full = dict(zip(sym.list_arguments(), arg_shapes))
            test_utils.check_consistency(
                sym, shapes=full, location=location,
                backends=["cpu", "tpu"], rtol=rtol, atol=atol,
                grad_req=grad_req)
            print("OK   %s" % name, flush=True)
        except Exception:
            failures.append(name)
            print("FAIL %s\n%s" % (name, traceback.format_exc()),
                  flush=True)
    print("%d/%d consistent%s" % (n_run - len(failures), n_run,
                                  "; failing: " + " ".join(failures)
                                  if failures else ""))
    return 1 if failures or not n_run else 0


def main():
    return run_cases(sys.argv[1:] or None)


if __name__ == "__main__":
    sys.exit(main())
