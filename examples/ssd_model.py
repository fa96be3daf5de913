"""SSD-300 with the VGG16-reduced backbone — the reference's detection
headline architecture (example/ssd/symbol/{vgg16_reduced,symbol_builder}.py),
built symbolically on this framework's op set.

Six feature scales (38/19/10/5/3/1 for 300 input), per-scale class +
offset heads, `MultiBoxPrior` anchors (8732 total at the reference's
sizes/ratios), and `MultiBoxDetection` (decode + NMS) for inference.
`build_ssd300_train` attaches the MultiBoxTarget +
SoftmaxOutput/smooth-L1 training heads the same way
example/ssd/symbol/symbol_builder.py:training does.
"""

from __future__ import annotations

# per-scale anchor config — reference example/ssd/symbol/symbol_factory.py
# get_config('vgg16_reduced', 300)
_SIZES = [(0.1, 0.141), (0.2, 0.272), (0.37, 0.447), (0.54, 0.619),
          (0.71, 0.79), (0.88, 0.961)]
_RATIOS = [(1.0, 2.0, 0.5), (1.0, 2.0, 0.5, 3.0, 1.0 / 3),
           (1.0, 2.0, 0.5, 3.0, 1.0 / 3), (1.0, 2.0, 0.5, 3.0, 1.0 / 3),
           (1.0, 2.0, 0.5), (1.0, 2.0, 0.5)]


def _vgg16_reduced(sym, data):
    """VGG16 through conv5_3, then the SSD 'reduced' conv6 (dilated) +
    conv7 — reference example/ssd/symbol/vgg16_reduced.py."""
    x = data
    cfg = [(2, 64), (2, 128), (3, 256), (3, 512)]
    feats = []
    for b, (n, f) in enumerate(cfg):
        for i in range(n):
            x = sym.Convolution(x, num_filter=f, kernel=(3, 3),
                                pad=(1, 1),
                                name="conv%d_%d" % (b + 1, i + 1))
            x = sym.Activation(x, act_type="relu")
        if b == 3:
            feats.append(x)       # conv4_3 -> 38x38 scale
        # ceil-mode pooling (SSD caffe heritage): 75 -> 38, not 37 —
        # required for the reference's 8732-anchor grid
        x = sym.Pooling(x, kernel=(2, 2), stride=(2, 2),
                        pool_type="max", pooling_convention="full",
                        name="pool%d" % (b + 1))
    for i in range(3):            # conv5_1..5_3
        x = sym.Convolution(x, num_filter=512, kernel=(3, 3),
                            pad=(1, 1), name="conv5_%d" % (i + 1))
        x = sym.Activation(x, act_type="relu")
    x = sym.Pooling(x, kernel=(3, 3), stride=(1, 1), pad=(1, 1),
                    pool_type="max", name="pool5")
    # reduced fc6/fc7: dilated 3x3 + 1x1
    x = sym.Convolution(x, num_filter=1024, kernel=(3, 3), pad=(6, 6),
                        dilate=(6, 6), name="fc6")
    x = sym.Activation(x, act_type="relu")
    x = sym.Convolution(x, num_filter=1024, kernel=(1, 1), name="fc7")
    x = sym.Activation(x, act_type="relu")
    feats.append(x)               # 19x19 scale
    # extra feature blocks: 10x10, 5x5, 3x3, 1x1
    for j, (f1, f2, s, p) in enumerate(
            [(256, 512, 2, 1), (128, 256, 2, 1),
             (128, 256, 1, 0), (128, 256, 1, 0)]):
        x = sym.Convolution(x, num_filter=f1, kernel=(1, 1),
                            name="extra%d_1x1" % j)
        x = sym.Activation(x, act_type="relu")
        x = sym.Convolution(x, num_filter=f2, kernel=(3, 3),
                            stride=(s, s), pad=(p, p),
                            name="extra%d_3x3" % j)
        x = sym.Activation(x, act_type="relu")
        feats.append(x)
    return feats


def _multibox_layers(sym, feats, num_classes):
    """Per-scale heads + anchors, concatenated over scales
    (reference symbol_builder.py multibox_layer)."""
    cls_preds, loc_preds, anchors = [], [], []
    for i, feat in enumerate(feats):
        na = len(_SIZES[i]) + len(_RATIOS[i]) - 1
        if i == 0:
            # conv4_3 features are L2-normalized with a learned scale
            # (reference vgg16_reduced.py relu4_3_scale)
            feat = sym.L2Normalization(feat, mode="channel",
                                       name="relu4_3_norm")
        cp = sym.Convolution(feat, num_filter=na * (num_classes + 1),
                             kernel=(3, 3), pad=(1, 1),
                             name="cls_pred%d" % i)
        cp = sym.transpose(cp, (0, 2, 3, 1))
        cls_preds.append(sym.Reshape(cp, (0, -1, num_classes + 1)))
        lp = sym.Convolution(feat, num_filter=na * 4, kernel=(3, 3),
                             pad=(1, 1), name="loc_pred%d" % i)
        lp = sym.transpose(lp, (0, 2, 3, 1))
        loc_preds.append(sym.Flatten(lp))
        anchors.append(sym.Reshape(
            sym.MultiBoxPrior(feat, sizes=_SIZES[i], ratios=_RATIOS[i],
                              clip=True, name="anchors%d" % i),
            (1, -1, 4)))
    cls_pred = sym.concat(*cls_preds, dim=1)    # (B, A, C+1)
    loc_pred = sym.concat(*loc_preds, dim=1)    # (B, A*4)
    anchor = sym.concat(*anchors, dim=1)        # (1, A, 4)
    return cls_pred, loc_pred, anchor


def build_ssd300_infer(num_classes=20, nms_thresh=0.45, nms_topk=400):
    """Inference graph: data0 -> (B, A, 6) [cls, score, 4 box coords]."""
    import mxnet_tpu as mx
    sym = mx.sym
    data = sym.var("data0")
    feats = _vgg16_reduced(sym, data)
    cls_pred, loc_pred, anchor = _multibox_layers(sym, feats,
                                                  num_classes)
    cls_prob = sym.transpose(
        sym.softmax(cls_pred, axis=-1), (0, 2, 1))
    return sym.MultiBoxDetection(
        cls_prob, loc_pred, anchor, nms_threshold=nms_thresh,
        nms_topk=nms_topk, name="detection")


def build_ssd300_train(num_classes=20):
    """Training graph: cls softmax (hard-negative-mined targets) +
    smooth-L1 on offsets, mirroring symbol_builder.py's heads."""
    import mxnet_tpu as mx
    sym = mx.sym
    data = sym.var("data0")
    label = sym.var("label")
    feats = _vgg16_reduced(sym, data)
    cls_pred, loc_pred, anchor = _multibox_layers(sym, feats,
                                                  num_classes)
    cls_prob_t = sym.transpose(
        sym.softmax(cls_pred, axis=-1), (0, 2, 1))
    tgt_loc, tgt_mask, tgt_cls = sym.MultiBoxTarget(
        anchor, label, cls_prob_t, name="target")
    cls_loss = sym.SoftmaxOutput(
        sym.Reshape(cls_pred, (-1, num_classes + 1)),
        sym.Reshape(tgt_cls, (-1,)),
        ignore_label=-1, use_ignore=True, normalization="valid",
        name="cls_prob")
    loc_loss = sym.MakeLoss(
        sym.smooth_l1((loc_pred - tgt_loc) * tgt_mask, scalar=1.0),
        name="loc_loss")
    return sym.Group([cls_loss, loc_loss, sym.BlockGrad(anchor)])
