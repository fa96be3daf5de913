"""Alignment-term sweep on the real chip: numbers and device time of
`mx_dsa_align` (ops/sparse_attention.py `_align_pallas`) alone at the
sparse-attention cell's shape: 16384 tokens, 32 / 4 heads of 128, 16 indexer
heads of 64, 2048 keys a query.

One command: the selection and the kept logsumexps made as the operator
makes them (`index_select`, `selected_attention`), then the kernel at
several column tiles, each checked against `_align_body` (the `jax.numpy`
body and JAX's derivative of it) on the same chip, and each timed; then that
body itself as XLA compiles it.  ``--against FILE`` times another version of
ops/sparse_attention.py beside this tree's (the parent commit's, a candidate
form's) on the same inputs.  `ALIGN_COLS`, and the table in PERF.md section
6 (PR 38), come from it.

    python tools/dsa_align_sweep.py [--default-only] [--against FILE ...]

Timing is `tools/shortconv_sweep.py`'s: the device's busy time a call
under the profiler.  Needs the chip to itself: one process per chip.
"""

from __future__ import annotations

import argparse
import importlib.util
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the cell's batch, tokens, heads, key/value heads and their width, the
#: indexer's heads and width, the keys a query keeps
BATCH, SEQ, H, KV, D, J, DI, TOPK = 1, 16384, 32, 4, 128, 16, 64, 2048

#: key columns of a tile
COLS = (256, 512, 1024)


def other_version(path, n):
    """Another ops/sparse_attention.py as a module of this tree's
    ``mxnet_tpu.ops`` (its relative imports are this tree's)."""
    spec = importlib.util.spec_from_file_location(
        "mxnet_tpu.ops._sparse_attention_%d" % n, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--default-only", action="store_true",
                    help="ALIGN_COLS as it stands and no other tile")
    ap.add_argument("--against", action="append", default=[], metavar="FILE",
                    help="another version of ops/sparse_attention.py, timed "
                    "on the same inputs at its own constants")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention, sparse_attention
    from tools.shortconv_sweep import device_ms, gap

    dev = jax.devices()[0]
    print("dsa_align_sweep: platform=%s kind=%r" % (
        dev.platform, dev.device_kind), flush=True)
    if dev.platform != "tpu":
        sys.exit("dsa_align_sweep: no TPU: a device time comes only from "
                 "the chip")
    bf, scale = jnp.bfloat16, D ** -0.5
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q = jax.random.normal(ks[0], (BATCH, H, SEQ, D), bf)
    k = jax.random.normal(ks[1], (BATCH, KV, SEQ, D), bf)
    v = jax.random.normal(ks[2], (BATCH, KV, SEQ, D), bf)
    qi = jax.random.normal(ks[3], (BATCH, SEQ, J * DI), bf)
    ki = jax.random.normal(ks[4], (BATCH, SEQ, DI), bf)
    w = jax.random.normal(ks[5], (BATCH, SEQ, J)) * (J ** -0.5 * DI ** -0.5)

    @jax.jit
    def operands(qi, ki, w, q, k, v):
        sel_q, sel_k, lse_i = sparse_attention.index_select(qi, ki, w, TOPK)
        _, lse = attention.selected_attention(
            q, jnp.repeat(k, H // KV, 1), jnp.repeat(v, H // KV, 1), sel_q,
            sel_k, scale)
        return lse, lse_i, sel_q

    a = (qi, ki, w, q, k) + operands(qi, ki, w, q, k, v)
    body = jax.jit(lambda *a: sparse_attention._align_body(
        *a, sm_scale=scale))
    want = body(*a)
    print("body (XLA)  %.3f ms  value %.6f" % (
        device_ms(body, *a), float(want[0])), flush=True)

    def report(label, module):
        def fn(*a):
            return module._align_pallas(*a, sm_scale=scale)
        try:
            module._align_pallas.clear_cache()
            got = fn(*a)
            gaps = "value rel %.3g  " % abs(
                float(got[0]) / float(want[0]) - 1) + "  ".join(
                "d%s max %.3g rel %.3g" % ((name,) + gap(x, y))
                for name, x, y in zip(("qi", "ki", "w"), got[1:], want[1:]))
            ms = device_ms(fn, *a)
        except Exception as e:      # a tile Mosaic refuses
            print("%s  refused: %s" % (label, str(e).splitlines()[0][:120]),
                  flush=True)
            return
        # the tiles at or under the diagonal, ROWS by the tile's columns
        tiles = sum(r * module.ROWS // module.ALIGN_COLS + 1
                    for r in range(SEQ // module.ROWS)) * BATCH
        print("%s  %.3f ms  %.2f us a tile  %s" % (
            label, ms, 1e3 * ms / tiles, gaps), flush=True)

    default = sparse_attention.ALIGN_COLS
    for cols in ([default] if args.default_only else COLS):
        sparse_attention.ALIGN_COLS = cols
        report("this tree  cols %4d" % cols, sparse_attention)
    sparse_attention.ALIGN_COLS = default
    for n, path in enumerate(args.against):
        module = other_version(path, n)
        report("%s  cols %4d" % (path, module.ALIGN_COLS), module)
    print("dsa_align_sweep: done", flush=True)


if __name__ == "__main__":
    main()
