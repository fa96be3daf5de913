#!/usr/bin/env python
"""ResNet-50 training-step MFU sweep on the real chip.

Times bench.py's exact harness (`bench.timed_resnet_train` — same scan
dispatch shape, same readback discipline, same cost-analysis FLOPs)
across batch size x remat policy in ONE process, so a single chip
call answers "which config should bench.py ship?".

    python tools/mfu_sweep.py \
        [--configs 128:none 128:dots 256:none 256:dots]

Needs the chip to itself: one process per chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import bench


def _decompose(peak, batch, iters):
    """Time the step's constituent configurations: fwd-only, then full
    steps with increasing optimizer machinery.  Differences between
    rows locate the non-conv time (PERF_NOTES 'remaining gap' list).
    Ends with the PER-OP cost table of the ship config's lowered step
    (observability.costs): flops, bytes, roofline class, % of step —
    the row an MFU regression blames (ROADMAP item 3)."""
    rows = [
        ("fwd_only", dict(fwd=True)),
        ("sgd_plain_f32", dict(optimizer="sgd", multi_precision=False,
                               momentum=0.0, stem="conv7")),
        ("sgd_mom_mp", dict(optimizer="sgd", multi_precision=True,
                            momentum=0.9, stem="conv7")),
        ("lbsgd_mp_percoparam", dict(optimizer="lbsgd",
                                     multi_precision=True,
                                     coalesce_small=False,
                                     stem="conv7")),
        ("lbsgd_mp_coalesced", dict(optimizer="lbsgd",
                                    multi_precision=True,
                                    coalesce_small=True,
                                    stem="conv7")),
        ("lbsgd_mp_coal_s2d", dict(optimizer="lbsgd",
                                   multi_precision=True,
                                   coalesce_small=True, stem="s2d")),
    ]
    # per-op attribution target: the LAST successful full-step variant
    # (the rows run cheapest->ship config, so later = closer to ship);
    # the emitted JSON names which variant the HLO actually came from
    ship_hlo = None
    ship_variant = None
    for name, kw in rows:
        try:
            if kw.pop("fwd", False):
                r = bench.timed_resnet_fwd(batch, 224, iters=iters,
                                           scan_n=5, warmup=2)
            else:
                r = bench.timed_resnet_train(batch, 224, None,
                                             iters=iters, scan_n=5,
                                             warmup=2, **kw)
                if r.get("hlo_text"):
                    ship_hlo = r["hlo_text"]
                    ship_variant = name
            tf_s = r["flops_per_step"] * r["iters"] / r["dt"] / 1e12
            print(json.dumps({
                "variant": name, "batch": batch,
                "ms_per_step": round(r["dt"] / r["iters"] * 1e3, 2),
                "img_s": round(r["img_s"], 1),
                "tf_s": round(tf_s, 1),
                "mfu": round(tf_s * 1e12 / peak, 4),
            }), flush=True)
        except Exception as e:
            print(json.dumps({"variant": name,
                              "error": repr(e)[:300]}), flush=True)

    if ship_hlo:
        try:
            from mxnet_tpu.observability import costs as _costs
            bw = bench._probe_peak_bw()
            table = _costs.cost_table(text=ship_hlo, peak_flops=peak,
                                      peak_bytes_s=bw, top=20)
            print("per-op attribution (variant=%s)" % ship_variant,
                  file=sys.stderr, flush=True)
            print(_costs.format_table(table, limit=24),
                  file=sys.stderr, flush=True)
            print(json.dumps({
                "per_op": table["rows"],
                "per_op_variant": ship_variant,
                "machine_balance": table["machine_balance"],
                "peak_bw_probe": bw,
                "total_flops": table["total_flops"],
                "total_bytes": table["total_bytes"],
            }), flush=True)
        except Exception as e:
            print(json.dumps({"per_op_error": repr(e)[:300]}),
                  flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", nargs="*",
                    default=["128:none", "128:dots", "256:none",
                             "256:dots"],
                    help="batch:remat pairs (remat none|dots|full)")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--decompose", action="store_true",
                    help="time fwd-only + optimizer-variant full steps")
    ap.add_argument("--batch", type=int, default=128)
    args = ap.parse_args()

    peak = bench._probe_peak_flops()
    print(json.dumps({"probe_tf_s": round(peak / 1e12, 1)}), flush=True)

    if args.decompose:
        _decompose(peak, args.batch, args.iters)
        return

    for cfg in args.configs:
        bs, _, rm = cfg.partition(":")
        rm = None if rm in ("", "none") else rm
        try:
            r = bench.timed_resnet_train(int(bs), 224, rm,
                                         iters=args.iters, scan_n=5,
                                         warmup=2)
            tf_s = r["flops_per_step"] * r["iters"] / r["dt"] / 1e12
            print(json.dumps({
                "batch": int(bs), "remat": rm or "none",
                "ms_per_step": round(r["dt"] / r["iters"] * 1e3, 2),
                "img_s": round(r["img_s"], 1),
                "tf_s": round(tf_s, 1),
                "mfu": round(tf_s * 1e12 / peak, 4),
                "flops_per_step": r["flops_per_step"],
            }), flush=True)
        except Exception as e:
            print(json.dumps({"batch": bs, "remat": rm or "none",
                              "error": repr(e)[:300]}), flush=True)


if __name__ == "__main__":
    main()
