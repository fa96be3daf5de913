"""Input-pipeline throughput benchmark: can the host feed the chip?

Measures ImageRecordIter decode+augment+batch throughput (img/s) at
ImageNet shapes across thread counts, against the training-side demand
(ResNet-50 at ~2,300-3,000 img/s on one chip).  Mirrors the reference's
design point: `src/io/iter_image_recordio_2.cc:141-149` sizes an OMP
decode team for exactly this reason.

Usage:  python tools/io_bench.py [--images 2048] [--threads 1,4,8,16]

Writes one JSON line per config and a summary to stdout; run it on the
chip machine's host and paste the table into docs/PERF_NOTES.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def make_recfile(path, n, side=512, quality=90):
    """Synthetic ImageNet-ish recordio: n JPEG-encoded random images."""
    from mxnet_tpu import recordio
    rec = recordio.MXIndexedRecordIO(path + ".idx", path + ".rec", "w")
    rs = np.random.RandomState(0)
    # a small pool of distinct images re-packed n times keeps build time
    # down while every record still pays full JPEG decode cost
    pool = []
    for i in range(32):
        img = (rs.rand(side, side, 3) * 255).astype(np.uint8)
        header = recordio.IRHeader(0, float(i % 1000), i, 0)
        pool.append(recordio.pack_img(header, img, quality=quality))
    for i in range(n):
        rec.write_idx(i, pool[i % len(pool)])
    rec.close()


def bench_once(recpath, batch_size, threads, n_images, augment):
    from mxnet_tpu.io import ImageRecordIter
    kwargs = dict(
        path_imgrec=recpath + ".rec", path_imgidx=recpath + ".idx",
        data_shape=(3, 224, 224), batch_size=batch_size,
        preprocess_threads=threads, shuffle=False)
    if augment:
        kwargs.update(rand_crop=True, rand_mirror=True, resize=256,
                      mean_r=123.68, mean_g=116.78, mean_b=103.94,
                      std_r=58.4, std_g=57.1, std_b=57.4)
    else:
        kwargs.update(resize=256)
    it = ImageRecordIter(**kwargs)
    # warm one batch (thread pool spin-up), then time the epoch
    batch = next(iter(it))
    n_seen = batch.data[0].shape[0]
    t0 = time.perf_counter()
    for batch in it:
        n_seen += batch.data[0].shape[0]
        if n_seen >= n_images:
            break
    dt = time.perf_counter() - t0
    return (n_seen - batch_size) / dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--images", type=int, default=2048)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--threads", default="1,2,4,8,16")
    ap.add_argument("--target", type=float, default=2500.0,
                    help="img/s the chip consumes (ResNet-50 demand)")
    args = ap.parse_args()

    with tempfile.TemporaryDirectory() as td:
        recpath = os.path.join(td, "synth")
        make_recfile(recpath, max(args.images, 512))
        results = []
        for threads in [int(t) for t in args.threads.split(",")]:
            for augment in (False, True):
                rate = bench_once(recpath, args.batch_size, threads,
                                  args.images, augment)
                row = {"metric": "image_record_iter_throughput",
                       "value": round(rate, 1), "unit": "images/sec",
                       "threads": threads, "augment": augment,
                       "vs_target": round(rate / args.target, 3)}
                results.append(row)
                print(json.dumps(row), flush=True)
    best = max(r["value"] for r in results)
    print(json.dumps({"metric": "image_record_iter_best",
                      "value": best, "unit": "images/sec",
                      "feeds_chip": best >= args.target}))
    return 0 if best >= args.target else 1


if __name__ == "__main__":
    sys.exit(main())
