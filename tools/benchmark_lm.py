#!/usr/bin/env python
"""Transformer-LM training throughput (tokens/s + MFU) on one chip —
the long-context counterpart of bench.py's ResNet number (SURVEY §5.7;
the reference has no transformer to compare against, so the roofline
probe is the yardstick).

Flash attention (Pallas, causal block skipping) is on the hot path via
`gluon.contrib.nn.MultiHeadAttention`; the whole step is one donated
XLA program scanned scan_n deep (bench.timed_train_steps discipline).

    python tools/benchmark_lm.py \
        [--dim 1024 --heads 16 --layers 12 --seq 2048 --batch 8]

Needs the chip to itself: one process per chip.  On CPU
(JAX_PLATFORMS=cpu) shrinks shapes for a plumbing smoke.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _lstm_lm(vocab, dim, layers):
    from mxnet_tpu.gluon.model_zoo.lm import get_lstm_lm
    return get_lstm_lm(vocab, dim, layers)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="transformer",
                    choices=["transformer", "lstm"])
    ap.add_argument("--dim", type=int, default=1024)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=32000)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--scan", type=int, default=5)
    ap.add_argument("--remat", default="none",
                    choices=["none", "dots", "full"],
                    help="activation rematerialization policy (long "
                         "sequences need 'dots' to fit HBM)")
    args = ap.parse_args()

    import mxnet_tpu as mx
    import jax
    import bench
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo.transformer import get_transformer_lm
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.data_parallel import ParallelTrainer

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu:
        args.dim, args.heads, args.layers = 64, 4, 2
        args.seq, args.batch, args.vocab = 128, 2, 64
        args.iters, args.scan = 4, 2
    if args.arch == "lstm":
        # reference LSTM-LM shapes: 2x650 medium / 2x1500 large PTB
        n_layers = max(2, args.layers // 6)
        net = _lstm_lm(args.vocab, args.dim, n_layers)
    else:
        n_layers = args.layers
        net = get_transformer_lm(vocab=args.vocab, dim=args.dim,
                                 heads=args.heads, layers=args.layers,
                                 max_seq=max(args.seq, 16))
    net.initialize()
    trainer = ParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        optimizer="sgd",
        optimizer_params={"learning_rate": 0.01, "momentum": 0.9},
        mesh=make_mesh({"dp": 1}, [dev]),
        multi_precision=on_tpu,
        remat=None if args.remat == "none" else args.remat)

    rng = np.random.RandomState(0)
    # token ids travel as int32: a float32 id cast to bf16 by the
    # multi-precision input path rounds to multiples of 128 above 256,
    # silently corrupting every embedding lookup (integer dtypes are
    # exempt from the compute-dtype cast)
    x = mx.nd.array(rng.randint(0, args.vocab, (args.batch, args.seq))
                    .astype(np.int32), dtype="int32")
    y = mx.nd.array(rng.randint(0, args.vocab, (args.batch, args.seq))
                    .astype(np.float32))

    r = bench.timed_train_steps(trainer, x, y, args.iters, args.scan,
                                warmup=2)
    tokens = args.batch * args.seq
    tok_s = tokens * r["iters"] / r["dt"]
    flops = r["flops_per_step"]
    if not flops:
        # 6*P per token (fwd+bwd); transformer adds the attention
        # 12*S*D-per-token term, lstm has 8*D^2 params per layer
        if args.arch == "lstm":
            p_count = (args.vocab * args.dim * 2
                       + n_layers * 8 * args.dim * args.dim)
            flops = tokens * 6.0 * p_count
        else:
            p_count = (args.vocab * args.dim * 2
                       + n_layers * 12 * args.dim * args.dim)
            flops = tokens * (6.0 * p_count
                              + 12.0 * n_layers * args.seq * args.dim)
    out = {
        "metric": "%s_lm_train" % args.arch,
        "tokens_per_s": round(tok_s, 1),
        "ms_per_step": round(r["dt"] / r["iters"] * 1e3, 2),
        "batch": args.batch, "seq": args.seq, "dim": args.dim,
        "heads": args.heads, "layers": n_layers,
        "flops_per_step": flops,
        "final_loss": r["final_loss"],
        "device": getattr(dev, "device_kind", str(dev)),
    }
    if on_tpu:
        peak = bench._probe_peak_flops()
        out["mfu"] = round(flops * r["iters"] / r["dt"] / peak, 4)
        out["probe_tf_s"] = round(peak / 1e12, 1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
