#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the three main paths once, through the entry points a
user calls, at the full width of models the repo ships:

1. train  — ResNet-50 through ``ParallelTrainer`` (LARS, bf16 compute +
   f32 masters), data parallel over every visible chip, 128 x 224^2 per
   chip;
2. serve  — the same ResNet-50 as a symbol through
   ``serve.ModelRegistry.load`` + the ``DynamicBatcher``, a bucket ladder
   up to 32, mixed-size requests from concurrent threads, on device 0;
3. lm     — the 1024-wide, 16-head transformer LM at sequence 2048
   through ``ParallelTrainer``, so the Pallas flash-attention forward and
   backward kernels compile and run inside a real step, plus the
   kernels against ``attention_reference``.

Weights are random, from a seed.  Every phase checks its own result and
raises on the first thing that is wrong; nothing is skipped and nothing
falls back.  Without a TPU as JAX's default backend the script exits 1
before running a phase.  It starts no other process (a chip belongs to
one process at a time) and needs no network.

Output: the device first, one ``chip_smoke: <phase> ok {...}`` line per
phase, ``chip_smoke: compile_cache {"dir": ..., "hits": n, "misses": m}``
— ``misses`` is what this run had to compile; a second run against the
same cache (``JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache/`` beside
this file) reports 0 — and as the last line one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Each phase is an importable function taking its sizes as arguments, so
``tests/test_chip_smoke.py`` drives them tiny on the CPU.
"""

from __future__ import annotations

import json
import sys
import threading
import time

import numpy as np


def _require(cond, what):
    if not cond:
        raise RuntimeError("chip_smoke: " + what)


def _fit_steps(trainer, x, y, steps):
    """*steps* updates on one repeated batch; the losses, read back."""
    losses = [float(trainer.fit_batch(x, y)) for _ in range(steps)]
    _require(all(np.isfinite(losses)), "non-finite loss: %r" % losses)
    _require(losses[-1] < losses[0],
             "loss is not falling on a repeated batch: %r" % losses)
    return losses


def _one_device_loss(net, loss, x, y, device, chunks=1):
    """The loss a first step reports, computed on ONE device from the
    same initial weights — what a dp=n first step must reproduce.
    Forward only, in the trainer's own precision, so the global batch
    fits where the whole step would not; models whose rows do not
    interact (no BatchNorm) may split it into *chunks* more."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel.data_parallel import ParallelTrainer
    from mxnet_tpu.parallel.mesh import make_mesh

    ref = ParallelTrainer(net, loss, mesh=make_mesh({"dp": 1}, [device]),
                          multi_precision=True)
    xs, ys = np.split(x, chunks), np.split(y, chunks)
    ref._ensure_built(xs[0], ys[0])

    @jax.jit
    def forward(params, aux, xb, yb):
        args = dict(params, data0=xb, label0=yb)
        outs, _ = ref._eval(args, aux, jax.random.PRNGKey(0))
        return jnp.mean(outs[0].astype(jnp.float32))

    return float(np.mean([
        float(forward(ref._params, ref._aux, ref._device_batch(xc),
                      ref._label_batch(yc)))
        for xc, yc in zip(xs, ys)]))


def _check_placement(trainer, x, devices):
    """Parameters, optimizer state and the batch live on *devices* — read
    from the arrays themselves, not from a Context or a spec."""
    want = set(devices)
    n = len(devices)
    state = list(trainer._params.values()) + list(trainer._aux.values())
    state += [s for slots in trainer._opt_state.values() for s in slots]
    for a in state:
        _require(a.devices() == want,
                 "state on %r, mesh is %r" % (a.devices(), want))
    xb = trainer._device_batch(x)
    rows = {s.device: s.data.shape[0] for s in xb.addressable_shards}
    _require(rows == {d: x.shape[0] // n for d in devices},
             "batch rows per device %r" % rows)
    sharded_share = None
    if n > 1:
        # ZeRO-1: each leaf's shards really sit on n distinct devices,
        # 1/n of its rows on each
        sharded = [a for a in state if not a.sharding.is_fully_replicated]
        for a in sharded:
            shards = a.addressable_shards
            _require({s.device for s in shards} == want
                     and all(s.data.shape[0] * n == a.shape[0]
                             for s in shards),
                     "leaf %r is not split over the mesh" % (a.shape,))
        sharded_share = (sum(a.nbytes for a in sharded)
                         / sum(a.nbytes for a in state))
        _require(sharded_share > 0.5, "only %.0f%% of the state bytes are "
                 "sharded" % (100 * sharded_share))
    return sharded_share


def _train(net, loss, optimizer, optimizer_params, x, y, steps, devices,
           chunks):
    """Shared body of the two train phases: a dp=n ``ParallelTrainer``
    over *devices* (ZeRO-1 when n > 1), *steps* steps, placement checks
    and — when n > 1 — the first loss against one device's."""
    from mxnet_tpu.parallel.data_parallel import ParallelTrainer
    from mxnet_tpu.parallel.mesh import make_mesh

    n = len(devices)
    out = {"dp": n, "global_batch": int(x.shape[0])}
    if n > 1:
        out["one_device_first_loss"] = _one_device_loss(
            net, loss, x, y, devices[0], chunks)
    trainer = ParallelTrainer(
        net, loss, optimizer=optimizer, optimizer_params=optimizer_params,
        mesh=make_mesh({"dp": n}, devices), multi_precision=True,
        shard_params=n > 1)
    out["losses"] = _fit_steps(trainer, x, y, steps)
    out["sharded_state_share"] = _check_placement(trainer, x, devices)
    if n > 1:
        ref, got = out["one_device_first_loss"], out["losses"][0]
        # same arithmetic in another reduction order: bf16 tolerance
        _require(abs(got - ref) <= 1e-2 * abs(ref),
                 "dp=%d first loss %.5f, one device %.5f" % (n, got, ref))
    return trainer, out


def phase_train(model="resnet50_v1", classes=1000, per_chip_batch=128,
                image=224, steps=4, devices=None):
    """The north-star path: what the ``resnet50_train`` cell and
    ``examples/train_imagenet.py --trainer parallel`` build."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import vision

    devices = devices or jax.devices()
    mx.random.seed(0)
    net = vision.get_model(model, classes=classes)
    net.initialize()
    rng = np.random.RandomState(0)
    batch = per_chip_batch * len(devices)
    x = rng.randn(batch, 3, image, image).astype(np.float32)
    y = rng.randint(0, classes, (batch,)).astype(np.float32)
    _, out = _train(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "lbsgd",
        {"learning_rate": 0.1, "momentum": 0.9, "eta": 0.001},
        x, y, steps, devices, chunks=1)
    return out


def phase_serve(model="resnet50_v1", classes=1000, image=224,
                rungs=(1, 2, 4, 8, 16, 32), requests=36, threads=6):
    """Compiled serving on the default device: one AOT program per rung
    at load, none in the request path, rows equal to the eager forward."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import serve
    from mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(0)
    net = vision.get_model(model, classes=classes)
    net.initialize()
    rng = np.random.RandomState(1)
    pool = rng.randn(rungs[-1], 3, image, image).astype(np.float32)
    # the reference: the imperative forward, op by op (inference-mode
    # BatchNorm, so a row's output does not depend on its batch)
    eager = net(mx.nd.array(pool)).asnumpy()
    _require(np.isfinite(eager).all() and eager.shape ==
             (rungs[-1], classes), "eager forward %r" % (eager.shape,))

    sym = net(mx.sym.var("data"))
    values = {p.name: p.data() for p in net.collect_params().values()}
    args = {a: values[a] for a in sym.list_arguments() if a != "data"}
    aux = {a: values[a] for a in sym.list_auxiliary_states()}
    registry = serve.ModelRegistry()
    try:
        pred = registry.load(
            "smoke", sym, args, aux,
            data_shapes={"data": (1, 3, image, image)},
            ladder=serve.BucketLadder(batches=rungs))
        _require(pred.compile_count == len(rungs),
                 "%d compiles for %d rungs" % (pred.compile_count,
                                               len(rungs)))
        first = pred.predict(pool[:1])[0]._data
        _require(first.devices() == {jax.devices()[0]},
                 "program output on %r" % (first.devices(),))

        # mixed sizes, every rung reachable; each request is a slice of
        # the pool so its reference rows are known
        sizes = [1, 2, 3, 5, 8, 13, 21, rungs[-1]]
        spans = []
        for i in range(requests):
            rows = min(sizes[i % len(sizes)], rungs[-1])
            lo = (7 * i) % (rungs[-1] - rows + 1)
            spans.append((lo, lo + rows))
        worst = [0.0] * threads
        failures = []

        def client(t):
            try:
                for lo, hi in spans[t::threads]:
                    got = registry.submit(
                        "smoke", pool[lo:hi]).result(300)[0]
                    _require(got.shape == (hi - lo, classes),
                             "request %d:%d answered %r"
                             % (lo, hi, got.shape))
                    worst[t] = max(worst[t], float(
                        np.abs(got - eager[lo:hi]).max()))
            except Exception as exc:    # re-raised on the main thread
                failures.append(exc)

        workers = [threading.Thread(target=client, args=(t,))
                   for t in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(600)
        _require(not any(w.is_alive() for w in workers),
                 "a request thread is stuck")
        if failures:
            raise failures[0]
        batches = registry.batcher("smoke").batch_count
        _require(pred.compile_count == len(rungs)
                 and pred.jit_cache_size() == 0,
                 "the request path compiled (%d programs, jit cache %d)"
                 % (pred.compile_count, pred.jit_cache_size()))
    finally:
        registry.close()
    scale = float(np.abs(eager).max())
    # f32 through HIGHEST-precision contractions on both sides
    _require(max(worst) <= 1e-2 * scale,
             "rows differ from the eager forward by %g (scale %g)"
             % (max(worst), scale))
    return {"rungs": list(rungs), "compiles": len(rungs),
            "requests": requests, "batches": batches,
            "max_abs_err": max(worst), "output_scale": scale}


def phase_lm(vocab=32000, dim=1024, heads=16, layers=12, seq=2048,
             per_chip_batch=8, steps=3, devices=None, kernels_per_layer=2,
             flash_shape=(2, 4, 2048, 64)):
    """The kernels that must compile: the zoo's transformer LM
    inside a real step.  On a TPU the compiled step holds two
    Mosaic calls per layer (flash forward and the one backward)."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo.transformer import get_transformer_lm
    from tools.flash_sweep import numeric_check

    devices = devices or jax.devices()
    mx.random.seed(0)
    net = get_transformer_lm(vocab=vocab, dim=dim, heads=heads,
                             layers=layers, max_seq=seq)
    net.initialize()
    rng = np.random.RandomState(2)
    batch = per_chip_batch * len(devices)
    # ids travel as int32: the bf16 input cast would round them
    x = rng.randint(0, vocab, (batch, seq)).astype(np.int32)
    y = rng.randint(0, vocab, (batch, seq)).astype(np.float32)
    trainer, out = _train(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.01, "momentum": 0.9},
        x, y, steps, devices, chunks=len(devices))

    compiled = trainer._step_fn.lower(
        trainer._params, trainer._opt_state, trainer._aux,
        trainer._device_batch(x), trainer._label_batch(y),
        jax.random.PRNGKey(0), np.float32(0.01), np.int32(1)).compile()
    kernels = compiled.as_text().count(
        'custom_call_target="tpu_custom_call"')
    _require(kernels == kernels_per_layer * layers,
             "%d Mosaic calls in the compiled step, want %d x %d layers"
             % (kernels, kernels_per_layer, layers))
    out["mosaic_calls"] = kernels

    # forward and dq/dk/dv against the einsum oracle, causal and not,
    # on the default device; raises on a mismatch
    numeric_check(flash_shape)
    out["flash_vs_reference"] = list(flash_shape)
    return out


def main():
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print("chip_smoke: platform=%(platform)s device_kind=\"%(kind)s\" "
          "count=%(count)d" % device, flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: JAX's default backend is not a TPU — nothing "
              "was run", file=sys.stderr)
        return 1

    from mxnet_tpu.config import compile_cache_dir
    cache = {"dir": compile_cache_dir(), "hits": 0, "misses": 0}

    def count(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(count)

    phases = {}
    for name, phase in (("train", phase_train), ("serve", phase_serve),
                        ("lm", phase_lm)):
        t0 = time.monotonic()
        phases[name] = phase()
        phases[name]["seconds"] = round(time.monotonic() - t0, 1)
        print("chip_smoke: %s ok %s" % (name, json.dumps(phases[name])),
              flush=True)
    print("chip_smoke: compile_cache %s" % json.dumps(cache))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
