"""Round benchmark: ResNet-50 synthetic-data training throughput + MFU.

Mirrors the reference harness
(`example/image-classification/benchmark_score.py`, methodology of
`docs/faq/perf.md:42-219`): synthetic NCHW batch, warmup, timed steps.
Prints ONE JSON line:
  {"metric": ..., "value": img/s, "unit": "images/sec", "vs_baseline": x}
vs_baseline is against the reference's strongest published ResNet-50
training number (V100 bs=128, 363.69 img/s, docs/faq/perf.md:219).

Measurement notes:
 * JAX dispatch is asynchronous: every timed window here ends in a host
   readback of a scalar that depends on all of the window's work (buffer
   donation chains step N+1 on step N's outputs, so reading the final
   loss serializes the whole window).  ``block_until_ready`` waits just
   as well on the directly attached chip (re-established on the v5e,
   CHANGES.md PR 21); the readback is kept because the loss is wanted
   anyway.
 * The MFU denominator is probed EMPIRICALLY: a chain of large bf16
   matmuls (data-dependent, so they cannot overlap) timed with the
   same readback discipline.  Datasheet numbers are reported alongside
   for reference but the probe is the denominator.  MFU is asserted to
   lie in (0, 1].
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

import numpy as np

def _ensure_platform():
    """In-process platform check, called from main() so that ``import
    bench`` (tools reuse the harnesses) has no side effects.  A bench
    number is a device number: anything but a TPU exits non-zero, unless
    ``BENCH_ALLOW_CPU=1`` acknowledges a CPU run (plumbing and counts
    only — a CPU timing is never a device metric)."""
    import jax
    platform = jax.devices()[0].platform
    if platform == "tpu":
        return
    if platform == "cpu" and os.environ.get("BENCH_ALLOW_CPU"):
        return
    print("bench: default backend is %r, not a TPU — refusing to print "
          "a number (BENCH_ALLOW_CPU=1 acknowledges a CPU run)"
          % platform, file=sys.stderr)
    sys.exit(3)


def _fleet_parent_off_chip():
    """Platform check for the fleet benches.  A chip belongs to one
    process, and the parent here only routes bytes: it pins ITS jax to
    the CPU and leaves the chips to the replica processes, which
    inherit the unpinned environment.  Returns the host's chip list
    ([] on an acknowledged CPU run)."""
    from mxnet_tpu import chips
    found = chips.host_chips()
    if not found and not os.environ.get("BENCH_ALLOW_CPU"):
        print("bench: no TPU chip on this host — refusing to print a "
              "number (BENCH_ALLOW_CPU=1 acknowledges a CPU run)",
              file=sys.stderr)
        sys.exit(3)
    import jax
    jax.config.update("jax_platforms", "cpu")
    return found


BASELINE_IMG_S = 363.69  # V100 bs=128 training, docs/faq/perf.md:219

# published bf16 peaks keyed by ``device_kind`` (Google Cloud TPU docs;
# reported for context only — the empirical probe below is the MFU
# denominator)
_DATASHEET = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def _datasheet_peak(dev):
    """Published peak of *dev*; a device that is not in the table is an
    error, not a default."""
    try:
        return _DATASHEET[dev.device_kind]
    except KeyError:
        raise KeyError("no published peak for device_kind %r — add it "
                       "to bench._DATASHEET with its source"
                       % dev.device_kind) from None


def _probe_peak_flops(iters=40, n=8192):
    """Achievable bf16 matmul FLOP/s: chained (serialized) matmuls,
    timed to a scalar host readback."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (n, n), jnp.bfloat16)
    b = jax.random.normal(key, (n, n), jnp.bfloat16)

    def chain(a, b, length):
        def body(c, _):
            return jnp.tanh(c @ b), None
        c, _ = jax.lax.scan(body, a, None, length=length)
        return jnp.sum(c.astype(jnp.float32))

    short = jax.jit(lambda a, b: chain(a, b, iters // 4))
    full = jax.jit(lambda a, b: chain(a, b, iters))
    float(short(a, b))  # warm
    float(full(a, b))
    t0 = time.perf_counter()
    float(short(a, b))
    t_short = time.perf_counter() - t0
    t0 = time.perf_counter()
    float(full(a, b))
    t_full = time.perf_counter() - t0
    # subtracting the short run removes fixed dispatch/sync latency
    per = (t_full - t_short) / (iters - iters // 4)
    return 2.0 * n ** 3 / per


def _probe_peak_bw(mb=256, iters=16):
    """Achievable HBM/memory bandwidth (bytes/s): a chained
    elementwise add over an *mb*-megabyte f32 buffer — each scan step
    reads and writes the whole buffer (2x its size in traffic) and
    depends on the previous one, same short-vs-full readback
    discipline as the flops probe.  This is the roofline denominator
    the MFU decompose classifies ops against."""
    import jax
    import jax.numpy as jnp

    n = max(1, int(mb * 1e6) // 4)
    x = jnp.ones((n,), jnp.float32)

    def chain(x, length):
        def body(c, _):
            return c + jnp.float32(1.0), None
        c, _ = jax.lax.scan(body, x, None, length=length)
        return jnp.sum(c)

    short = jax.jit(lambda x: chain(x, iters // 4))
    full = jax.jit(lambda x: chain(x, iters))
    float(short(x))  # warm
    float(full(x))
    t0 = time.perf_counter()
    float(short(x))
    t_short = time.perf_counter() - t0
    t0 = time.perf_counter()
    float(full(x))
    t_full = time.perf_counter() - t0
    per = (t_full - t_short) / (iters - iters // 4)
    if per <= 0:
        # a GC pause / scheduler hiccup during the millisecond-scale
        # short run can make the delta non-positive; a None denominator
        # degrades the decompose to flops-share-only (cost_table
        # accepts it) instead of crashing the run or silently
        # classifying every op against a negative balance point
        print("bench: bandwidth probe degenerate (short %.4fs >= full "
              "%.4fs) — no roofline denominator" % (t_short, t_full),
              file=sys.stderr)
        return None
    return 2.0 * n * 4 / per


def timed_resnet_train(batch, image, remat, iters, scan_n, warmup=2,
                       optimizer="lbsgd", multi_precision=True,
                       coalesce_small=None, momentum=0.9, stem=None):
    """Build the north-star ResNet-50 trainer and time its step.

    This is THE measurement harness (tools/mfu_sweep.py reuses it):
    steps are scanned inside ONE dispatch per host call — the idiomatic
    TPU training-loop shape, which also keeps per-call dispatch latency
    out of the device number — and the timed window is forced complete
    by a host readback of the final loss (donation chains the steps).
    Data parallel over every visible chip, *batch* rows per chip.

    Returns a dict with img_s / dt / iters / flops_per_step /
    final_loss."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.data_parallel import ParallelTrainer

    mesh = make_mesh({"dp": len(jax.devices())})
    batch *= mesh.size
    # BENCH_STEM=s2d swaps the 7x7 stem for the space-to-depth variant
    # (model_zoo SpaceToDepthStem — the MXU-utilization stem)
    stem = stem or os.environ.get("BENCH_STEM") or "conv7"
    net = vision.get_model("resnet50_v1", classes=1000, stem=stem)
    net.initialize()
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    # north-star config: bf16 compute weights + f32 masters + LARS
    # (docs/faq/perf.md fp16 ≈ 2x fp32 sanity ratio applies to bf16)
    opt_params = {"learning_rate": 0.1, "eta": 0.001}
    if momentum:
        opt_params["momentum"] = momentum
    trainer = ParallelTrainer(
        net, loss, optimizer=optimizer, optimizer_params=opt_params,
        mesh=mesh, multi_precision=multi_precision, remat=remat,
        coalesce_small=coalesce_small)

    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.randn(batch, 3, image, image).astype(np.float32))
    y = mx.nd.array(rng.randint(0, 1000, (batch,)).astype(np.float32))

    r = timed_train_steps(trainer, x, y, iters, scan_n, warmup)
    if not r["flops_per_step"]:
        # analytic fwd+bwd ResNet-50, scaled from the 224x224 figure
        r["flops_per_step"] = 3 * 4.089e9 * batch * (image / 224.0) ** 2
    r["img_s"] = batch * r["iters"] / r["dt"]
    return r


def timed_train_steps(trainer, x, y, iters, scan_n, warmup=2):
    """Shared training-step timing harness (tools/benchmark_lm.py and
    timed_resnet_train use it): scan_n steps chained by donation inside
    ONE jit per host call, timed to a host readback of the final loss.
    Returns {dt, iters, flops_per_step (None if cost analysis
    unavailable), final_loss}."""
    import jax
    import jax.numpy as jnp

    for _ in range(max(1, warmup)):
        l = trainer.fit_batch(x, y)
    float(np.asarray(l))  # forced readback

    step = trainer._step_fn

    def multi(params, opt_state, aux, xb, yb, key, lr, t):
        def body(carry, i):
            p, s, a = carry
            p, s, a, l = step(p, s, a, xb, yb,
                              jax.random.fold_in(key, i), lr, t)
            return (p, s, a), l
        (p, s, a), ls = jax.lax.scan(
            body, (params, opt_state, aux), jnp.arange(scan_n))
        return p, s, a, ls[-1]

    multi_j = jax.jit(multi, donate_argnums=(0, 1, 2))
    # the trainer's own placement: dp-sharded over its mesh, bf16 under
    # multi_precision
    xd = trainer._device_batch(x)
    yd = trainer._label_batch(y)
    # the trainer's OWN configured hyperparameters — this harness is
    # shared (benchmark_lm runs lr=0.01), hard-coding resnet's 0.1
    # would time steps the model never takes
    lr = np.float32(trainer._current_lr())
    t = np.int32(trainer._num_update + 1)
    p, s, a = trainer._params, trainer._opt_state, trainer._aux
    p, s, a, l = multi_j(p, s, a, xd, yd, jax.random.PRNGKey(0), lr, t)
    float(np.asarray(l))  # warm the scanned executable

    t0 = time.perf_counter()
    for it in range(max(1, iters // scan_n)):
        p, s, a, l = multi_j(p, s, a, xd, yd,
                             jax.random.PRNGKey(it + 1), lr, t)
    final_loss = float(np.asarray(l))  # donation chains all timed steps
    dt = time.perf_counter() - t0
    n = max(1, iters // scan_n) * scan_n
    trainer._params, trainer._opt_state, trainer._aux = p, s, a

    # exact per-step FLOPs from the compiled program when available;
    # the lowered StableHLO text rides along for the per-op MFU
    # decompose (observability.costs — bench --decompose and the
    # "decompose" key of the round artifact)
    flops = None
    hlo_text = None
    try:
        low = trainer._step_fn.lower(
            trainer._params, trainer._opt_state, trainer._aux,
            trainer._device_batch(x._data), y._data,
            jax.random.PRNGKey(0), lr, t)
        try:
            hlo_text = low.as_text()
        except Exception:
            hlo_text = None
        ca = low.compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        if ca and "flops" in ca:
            flops = float(ca["flops"])
    except Exception:
        pass
    return {"dt": dt, "iters": n, "flops_per_step": flops,
            "final_loss": final_loss, "hlo_text": hlo_text}


def timed_scan_forward(eval_fn, params, aux, xd, extra, scan_n, iters,
                       warmup=2):
    """Shared forward-timing harness (tools/benchmark_score.py reuses
    it): scan_n forwards chained through a carry inside ONE jit — the
    data depends on the carry so XLA cannot hoist the loop-invariant
    computation — timed to a host readback.

    ``extra`` maps additional eval-graph inputs (e.g. label0).
    Returns (dt_seconds, iters_run, flops_per_call_or_None)."""
    import jax
    import jax.numpy as jnp

    def multi(params, aux, xb, key):
        def body(c, i):
            amap = dict(params)
            amap["data0"] = xb + (c * 0).astype(xb.dtype)
            amap.update(extra)
            outs, _ = eval_fn(amap, aux, jax.random.fold_in(key, i))
            return c + jnp.mean(outs[0].astype(jnp.float32)), None
        s, _ = jax.lax.scan(body, jnp.float32(0), jnp.arange(scan_n))
        return s

    mj = jax.jit(multi)
    for _ in range(max(1, warmup)):
        float(np.asarray(mj(params, aux, xd, jax.random.PRNGKey(0))))
    t0 = time.perf_counter()
    for it in range(max(1, iters // scan_n)):
        s = mj(params, aux, xd, jax.random.PRNGKey(it + 1))
    float(np.asarray(s))  # device FIFO: the last readback drains all
    dt = time.perf_counter() - t0
    n = max(1, iters // scan_n) * scan_n
    flops = None
    try:
        ca = mj.lower(params, aux, xd,
                      jax.random.PRNGKey(0)).compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        if ca and "flops" in ca:
            flops = float(ca["flops"]) / scan_n
    except Exception:
        pass
    return dt, n, flops


def timed_resnet_fwd(batch, image, iters, scan_n, warmup=2,
                     multi_precision=True):
    """Training-mode FORWARD only, same scan/readback discipline as
    timed_resnet_train — the fwd/bwd/optimizer decomposition baseline
    for tools/mfu_sweep.py --decompose."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.data_parallel import ParallelTrainer

    dev = jax.devices()[0]
    net = vision.get_model("resnet50_v1", classes=1000)
    net.initialize()
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = ParallelTrainer(
        net, loss, optimizer="sgd",
        optimizer_params={"learning_rate": 0.1},
        mesh=make_mesh({"dp": 1}, [dev]),
        multi_precision=multi_precision)

    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.randn(batch, 3, image, image).astype(np.float32))
    y = mx.nd.array(rng.randint(0, 1000, (batch,)).astype(np.float32))
    trainer.fit_batch(x, y)  # build + gather state

    xd = trainer._device_batch(x._data)
    dt, n, flops = timed_scan_forward(
        trainer._eval, trainer._params, trainer._aux, xd,
        {"label0": y._data}, scan_n, iters, warmup)
    if not flops:
        # analytic fwd ResNet-50, scaled from the 224x224 figure
        flops = 4.089e9 * batch * (image / 224.0) ** 2
    return {"img_s": batch * n / dt, "dt": dt, "iters": n,
            "flops_per_step": flops}


def compare_update_paths(n_layers=30, dim=64, batch=32, steps=30,
                         optimizer="sgd", opt_params=None):
    """``--compare-update-paths``: fused ``forward_backward_update``
    (one donated XLA program per step) vs the legacy
    forward_backward + per-parameter Updater loop, on a deep synthetic
    MLP (2*n_layers+2 parameters — launch-overhead bound, so the
    per-step dispatch count is what's measured).  Runs anywhere; on CPU
    it is the fused-step acceptance microbench.  Prints one JSON line
    and returns the dict."""
    import mxnet_tpu as mx
    from mxnet_tpu import sym
    from mxnet_tpu.io import DataBatch

    def build():
        data = sym.var("data")
        net = data
        for i in range(n_layers):
            net = sym.FullyConnected(net, num_hidden=dim, name="l%d" % i)
            net = sym.Activation(net, act_type="relu")
        net = sym.FullyConnected(net, num_hidden=4, name="out")
        return sym.SoftmaxOutput(net, name="softmax")

    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.randn(batch, dim).astype(np.float32))
    y = mx.nd.array(rng.randint(0, 4, (batch,)).astype(np.float32))
    data_batch = DataBatch(data=[x], label=[y])
    params = dict(opt_params or {"learning_rate": 0.01, "momentum": 0.9})

    def run(fused):
        prior = os.environ.get("MXNET_MODULE_FUSED_STEP")
        os.environ["MXNET_MODULE_FUSED_STEP"] = "1" if fused else "0"
        try:
            mod = mx.Module(build(), context=mx.cpu())
            mod.bind([("data", (batch, dim))],
                     [("softmax_label", (batch,))])
            mod.init_params(mx.init.Xavier())
            mod.init_optimizer(optimizer=optimizer,
                               optimizer_params=dict(params))
            for _ in range(3):                       # warmup/compile
                mod.forward_backward_update(data_batch)
            mod.get_outputs()[0].asnumpy()
            t0 = time.perf_counter()
            for _ in range(steps):
                mod.forward_backward_update(data_batch)
            # readbacks drain the async chain before the clock stops
            mod.get_outputs()[0].asnumpy()
            mod._exec_group.execs[0].arg_dict["l0_weight"].asnumpy()
            return steps / (time.perf_counter() - t0)
        finally:
            if prior is None:
                os.environ.pop("MXNET_MODULE_FUSED_STEP", None)
            else:
                os.environ["MXNET_MODULE_FUSED_STEP"] = prior

    legacy = run(False)
    fused = run(True)
    out = {
        "metric": "fused_vs_legacy_update_paths",
        "fused_steps_per_s": round(fused, 2),
        "legacy_steps_per_s": round(legacy, 2),
        "speedup": round(fused / legacy, 3),
        "n_params": 2 * n_layers + 2,
        "optimizer": optimizer,
        "batch_size": batch,
    }
    print(json.dumps(out))
    return out


class _SlowDecodeIter:
    """Host-bound iterator simulator for ``--compare-input-paths``: a
    DataIter-shaped source whose ``next()`` burns *decode_s* seconds
    of host time (the stand-in for jpeg decode / augmentation) and
    hands out HOST numpy batches — exactly what a decode pipeline
    produces.  The serial path then pays the host→device transfer
    inside the step loop; the pipelined path pays it on the
    DevicePrefetcher's producer thread."""

    def __init__(self, data, label, batch_size, decode_s):
        self.batch_size = batch_size
        self.decode_s = decode_s
        n = (data.shape[0] // batch_size) * batch_size
        self._data = [data[i:i + batch_size]
                      for i in range(0, n, batch_size)]
        self._label = [label[i:i + batch_size]
                       for i in range(0, n, batch_size)]
        self._cursor = 0

    @property
    def provide_data(self):
        from mxnet_tpu.io import DataDesc
        return [DataDesc("data", self._data[0].shape,
                         self._data[0].dtype)]

    @property
    def provide_label(self):
        from mxnet_tpu.io import DataDesc
        return [DataDesc("softmax_label", self._label[0].shape,
                         self._label[0].dtype)]

    def reset(self):
        self._cursor = 0

    def next(self):
        from mxnet_tpu.io import DataBatch
        if self._cursor >= len(self._data):
            raise StopIteration
        time.sleep(self.decode_s)
        i = self._cursor
        self._cursor += 1
        return DataBatch(data=[self._data[i]], label=[self._label[i]],
                         pad=0)

    def __next__(self):
        return self.next()

    def __iter__(self):
        return self

    def state_dict(self):
        return {"type": type(self).__name__, "cursor": self._cursor}

    def load_state(self, state):
        self._cursor = int(state["cursor"])


def compare_input_paths(batch=128, dim=128, hidden=768, n_layers=8,
                        steps=16, depth=3, lag=2):
    """``--compare-input-paths``: serial input path (host decode +
    device_put inside the step loop, guard readback blocking every
    step) vs the pipelined path (``DevicePrefetcher`` ring +
    ``MXNET_GUARD_READBACK_LAG`` async guard accounting), on a
    synthetic host-bound iterator whose decode time X is calibrated to
    the measured device step time Y.  Serial pays ≈ X+Y per step; the
    pipelined steady state pays ≈ max(X, Y) — decode and transfer run
    on the producer thread while the device computes, and the host
    dispatches step N+1 while step N runs.  Runs on CPU by design (a
    dispatch-overlap measurement, like --compare-update-paths).
    Prints one BENCH-schema JSON line (with ``input_stall_share``) and
    returns the dict; ``overlap_ok`` asserts pipelined < 0.7×serial."""
    import mxnet_tpu as mx
    from mxnet_tpu import sym
    from mxnet_tpu.io import DevicePrefetcher
    from mxnet_tpu.observability import metrics as _obs_metrics

    rng = np.random.RandomState(0)
    n = batch * (steps + depth + 12)
    X_data = rng.randn(n, dim).astype(np.float32)
    Y_data = rng.randint(0, 8, (n,)).astype(np.float32)

    def build():
        mx.random.seed(7)
        data = sym.var("data")
        net = data
        for i in range(n_layers):
            net = sym.FullyConnected(net, num_hidden=hidden,
                                     name="l%d" % i)
            net = sym.Activation(net, act_type="relu")
        net = sym.FullyConnected(net, num_hidden=8, name="out")
        net = sym.SoftmaxOutput(net, name="softmax")
        mod = mx.Module(net, context=mx.cpu())
        mod.bind([("data", (batch, dim))], [("softmax_label", (batch,))])
        mod.init_params(mx.init.Xavier())
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.05})
        # the guard's skip-counter readback is the per-step host sync
        # the async path amortizes (see docs/perf_input_pipeline.md)
        mod.set_nonfinite_guard(max_consecutive=0)
        return mod

    def fresh_iter(decode_s):
        return _SlowDecodeIter(X_data, Y_data, batch, decode_s)

    prior = os.environ.get("MXNET_GUARD_READBACK_LAG")

    def set_lag(v):
        if v:
            os.environ["MXNET_GUARD_READBACK_LAG"] = str(v)
        else:
            os.environ.pop("MXNET_GUARD_READBACK_LAG", None)

    try:
        # -- calibrate Y: the serial loop at ZERO decode time --------
        # Y here is everything the serial consumer pays per step
        # besides the simulated decode: the iterator's host batch
        # conversion + the guarded step with its synchronous readback.
        # Calibrating on the REAL loop (not a warm reused batch, whose
        # puts are elided) makes X track what the machine actually
        # does under its current CPU shares.
        set_lag(0)
        mod = build()
        it0 = fresh_iter(0.0)
        for _ in range(3):
            mod.forward_backward_update(it0.next())   # compile + settle
        ys = []
        for _ in range(7):
            t0 = time.perf_counter()
            mod.forward_backward_update(it0.next())
            ys.append(time.perf_counter() - t0)
        step_s = sorted(ys)[len(ys) // 2]
        # X ≈ 1.3Y: the sleep dominates the producer's period (its
        # conversion work contends with XLA's compute threads on
        # small-core hosts), while max(X,Y)/(X+Y) stays near its 0.5
        # floor; the 10 ms floor keeps scheduler jitter second-order
        decode_s = max(1.3 * step_s, 0.010)

        # -- serial: decode + put + blocking readback per step -------
        mod = build()
        it = fresh_iter(decode_s)
        for _ in range(3):
            mod.forward_backward_update(it.next())   # compile + settle
        t0 = time.perf_counter()
        for _ in range(steps):
            mod.forward_backward_update(it.next())
        serial_dt = time.perf_counter() - t0         # guard drains each

        # -- pipelined: device ring + bounded-lag readback -----------
        set_lag(lag)
        mod = build()
        pf = DevicePrefetcher(fresh_iter(decode_s), depth=depth)
        try:
            for _ in range(3 + depth):               # compile + fill ring
                mod.forward_backward_update(pf.next())
            wait_hist = _obs_metrics.REGISTRY.get("input_wait_seconds")
            wait0 = wait_hist.sum
            t0 = time.perf_counter()
            for _ in range(steps):
                mod.forward_backward_update(pf.next())
            # the timed window is only honest once the in-flight lag
            # steps have drained on-device
            mod.drain_guard_readbacks()
            pipe_dt = time.perf_counter() - t0
            stall_share = (wait_hist.sum - wait0) / pipe_dt
        finally:
            pf.close()
    finally:
        if prior is None:
            os.environ.pop("MXNET_GUARD_READBACK_LAG", None)
        else:
            os.environ["MXNET_GUARD_READBACK_LAG"] = prior

    serial_per = serial_dt / steps
    pipe_per = pipe_dt / steps
    out = {
        "metric": "input_pipeline_overlap",
        "value": round(steps / pipe_dt, 2),
        "unit": "steps/sec",
        "serial_steps_per_s": round(steps / serial_dt, 2),
        "pipelined_steps_per_s": round(steps / pipe_dt, 2),
        "speedup": round(serial_per / pipe_per, 3),
        "decode_ms": round(decode_s * 1e3, 3),
        "step_ms": round(step_s * 1e3, 3),
        "serial_ms_per_step": round(serial_per * 1e3, 3),
        "pipelined_ms_per_step": round(pipe_per * 1e3, 3),
        "input_stall_share": round(stall_share, 4),
        "prefetch_depth": depth,
        "guard_readback_lag": lag,
        "batch_size": batch,
        # serial ≈ X+Y, pipelined steady state ≈ max(X,Y): the overlap
        # proof the CI smoke stage asserts
        "overlap_ok": pipe_per < 0.7 * serial_per,
    }
    print(json.dumps(out))
    return out


def _percentile(sorted_vals, q):
    """Nearest-rank percentile of an ascending list (exact — serving
    SLOs are quoted on real request latencies, not histogram bounds)."""
    if not sorted_vals:
        return None
    idx = min(len(sorted_vals) - 1,
              max(0, math.ceil(q / 100.0 * len(sorted_vals)) - 1))
    return sorted_vals[idx]


def serve_bench(hidden=256, dim=64, classes=16,
                closed_threads=8, closed_requests=40,
                open_rate=150.0, open_seconds=2.0, max_wait_ms=1.0,
                record_trace=None, trace=None, quantize=None):
    """``--serve``: load test of the compiled serving subsystem
    (mxnet_tpu/serve): one warm-compiled model behind the dynamic
    batcher, driven closed-loop (N threads, back-to-back requests —
    the throughput ceiling) and open-loop (fixed arrival rate — the
    latency distribution under load, which closed-loop hides by
    coordinated omission).  Mixed request sizes (1-4 rows) exercise
    the coalescing + padding path.  Prints ONE BENCH-schema JSON line
    with p50/p99 latency and throughput and returns the dict.

    ``--record-trace PATH`` serializes the open-loop arrival schedule
    (request sizes + offsets) as an autotune trace;
    ``--trace PATH`` replays a recorded trace as the open loop
    instead of the synthetic grid — the same load the autotuner
    scored, so bench numbers and tuning artifacts are comparable.
    When a ``MXNET_TUNING_STORE`` entry exists for model "bench", the
    hand-picked ladder/window defaults are NOT passed, so the tuned
    config applies (precedence: env > store > default) and the
    ``tuning`` field reports what was picked up."""
    import threading

    import mxnet_tpu as mx
    from mxnet_tpu import serve, sym
    from mxnet_tpu.autotune import trace as _at
    from mxnet_tpu.autotune.store import lookup as _at_lookup

    tr = None
    if trace is not None:
        tr = _at.Trace.load(trace)
        if tr.kind != "serve":
            raise ValueError("bench --serve needs a serve trace, got "
                             "kind=%r" % tr.kind)
        dim = int(tr.meta.get("dim", dim))

    data = sym.var("data")
    net = sym.FullyConnected(data, num_hidden=hidden, name="sfc1")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=classes, name="sfc2")
    net = sym.softmax(net)
    rs = np.random.RandomState(0)
    arg_shapes, _, _ = net.infer_shape(data=(1, dim))
    params = {n: mx.nd.array(rs.randn(*s).astype(np.float32) * 0.05)
              for n, s in zip(net.list_arguments(), arg_shapes)
              if n != "data"}

    registry = serve.ModelRegistry()
    # with a tuned-store entry for "bench", leave ladder/window unset
    # so the tuning applies (env > store > default); otherwise use the
    # bench's hand-picked defaults
    tuned = _at_lookup("bench", "serve")
    ladder = None if tuned else \
        serve.BucketLadder(batches=(1, 2, 4, 8, 16))
    # --quantize int8|int8-weight-only: serve the post-training-
    # quantized model instead (calibrated on traffic-shaped batches,
    # accuracy-gated at load — docs/quantization.md); the bench line
    # then reports the quantization section next to the latencies so
    # fp32 and int8 artifacts are comparable at a glance
    quant_kw = {}
    if quantize:
        quant_kw = {"quantize": quantize,
                    "calib_batches": [rs.randn(4, dim).astype(np.float32)
                                      for _ in range(8)]}
    t0 = time.perf_counter()
    pred = registry.load("bench", net, params,
                         data_shapes={"data": (1, dim)}, ladder=ladder,
                         **quant_kw)
    warm_s = time.perf_counter() - t0
    batcher = registry.batcher(
        "bench", max_wait_ms=None if tuned else max_wait_ms)
    compiles_after_warm = pred.compile_count

    reqs = [rs.randn(rs.randint(1, 5), dim).astype(np.float32)
            for _ in range(64)]

    # -- closed loop: threads issue back-to-back ------------------------
    lat_closed = []
    worker_errors = []
    lat_lock = threading.Lock()

    def worker(tid):
        mine = []
        try:
            for i in range(closed_requests):
                x = reqs[(tid * closed_requests + i) % len(reqs)]
                t0 = time.monotonic()
                batcher.submit(x).result(60)
                mine.append(time.monotonic() - t0)
        except Exception as exc:
            with lat_lock:
                worker_errors.append("worker %d: %r" % (tid, exc))
        finally:
            with lat_lock:
                lat_closed.extend(mine)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(closed_threads)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    closed_dt = time.monotonic() - t0
    if worker_errors:
        # a failed/timed-out request would silently skew the report
        raise RuntimeError("serve bench closed loop failed: %s"
                           % "; ".join(worker_errors[:3]))
    closed_n = closed_threads * closed_requests

    # -- open loop: fixed arrival rate ----------------------------------
    if tr is not None:
        # replay the recorded trace — identical offsets + request
        # sizes the autotuner scored, payloads rematerialized from
        # the trace seed
        records, open_dt = _at.replay(
            tr, lambda x, _i: batcher.submit(x))
        for _slot, _t_sub, fut in records:
            fut.result(60)
        lat_open = [fut._t_resolved - t_sub
                    for _slot, t_sub, fut in records]
        n_open = len(records)
        open_rate = round((n_open - 1) / max(tr.duration(), 1e-9), 2)
    else:
        futures = []
        period = 1.0 / open_rate
        t_start = time.monotonic()
        n_open = int(open_rate * open_seconds)
        for i in range(n_open):
            slot = t_start + i * period
            delay = slot - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            x = reqs[i % len(reqs)]
            futures.append((time.monotonic(), batcher.submit(x)))
        for _, fut in futures:
            fut.result(60)
        open_dt = time.monotonic() - t_start
        # each future stamps its own resolution time — submit->resolve
        # is the true per-request latency even though collection is
        # serial
        lat_open = [fut._t_resolved - t_sub for t_sub, fut in futures]

    if record_trace:
        rec = tr if tr is not None else _at.Trace(
            "serve",
            [{"t": round(i / open_rate, 6),
              "rows": int(reqs[i % len(reqs)].shape[0])}
             for i in range(n_open)],
            {"dim": dim, "rate": open_rate}, seed=0)
        rec.save(record_trace)

    lat_closed.sort()
    lat_open.sort()
    out = {
        "metric": "serve_load",
        "value": round(closed_n / closed_dt, 2),
        "unit": "requests/sec",
        "model": {"hidden": hidden, "dim": dim,
                  "buckets": list(pred.ladder.batches)},
        "tuning": (pred.tuning or {}).get("config"),
        "quantization": ({"mode": pred.quantization["mode"],
                          "calib_sha": pred.quantization["calib_sha"],
                          "covered": pred.quantization["covered"],
                          "total": pred.quantization["total"]}
                         if pred.quantization else None),
        "trace": tr.summary() if tr is not None else None,
        "warm_compile_seconds": round(warm_s, 3),
        "programs_compiled": compiles_after_warm,
        "request_path_compiles": pred.compile_count - compiles_after_warm,
        "closed_loop": {
            "threads": closed_threads,
            "requests": closed_n,
            "throughput_rps": round(closed_n / closed_dt, 2),
            "p50_ms": round(_percentile(lat_closed, 50) * 1e3, 3),
            "p99_ms": round(_percentile(lat_closed, 99) * 1e3, 3),
        },
        "open_loop": {
            "offered_rps": open_rate,
            "requests": n_open,
            "achieved_rps": round(len(lat_open) / open_dt, 2),
            "p50_ms": round(_percentile(lat_open, 50) * 1e3, 3)
            if lat_open else None,
            "p99_ms": round(_percentile(lat_open, 99) * 1e3, 3)
            if lat_open else None,
        },
        "batches": batcher.batch_count,
        "requests": batcher.request_count,
    }
    registry.close()
    print(json.dumps(out))
    return out


def compare_quant_paths(hidden=256, dim=64, classes=16, rungs=(1, 2, 4, 8),
                        threads=6, requests=30):
    """``--compare-quant-paths``: fp32 vs post-training-int8 serving
    A/B on the same model, ladder and traffic — a relative
    measurement, so it ALWAYS runs on CPU.  Proves, per rung, from the
    lowered StableHLO via the costs.py per-op table, that the quantized
    program moves >= 2x fewer weight+activation bytes through its
    compute ops (dot/conv); and measures what int8 costs in accuracy
    (max rel err + top-1 agreement vs the fp32 path on identical
    inputs) and buys/costs in latency under identical closed-loop
    traffic.  On CPU the byte reduction is the honest headline — XLA's
    CPU int8 GEMMs are not the MXU path, so wall-clock parity, not
    speedup, is expected (docs/quantization.md).  Asserts zero
    request-path compiles on BOTH paths.  Prints ONE BENCH-schema
    JSON line and returns the dict."""
    import threading

    import mxnet_tpu as mx
    from mxnet_tpu import serve, sym
    from mxnet_tpu.observability import costs
    from mxnet_tpu.quantize import calibrate, hlo_has_int8_compute

    data = sym.var("data")
    net = sym.FullyConnected(data, num_hidden=hidden, name="sfc1")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=classes, name="sfc2")
    net = sym.softmax(net)
    rs = np.random.RandomState(0)
    arg_shapes, _, _ = net.infer_shape(data=(1, dim))
    params = {n: mx.nd.array(rs.randn(*s).astype(np.float32) * 0.05)
              for n, s in zip(net.list_arguments(), arg_shapes)
              if n != "data"}

    calib = calibrate(
        net, params,
        [rs.randn(4, dim).astype(np.float32) for _ in range(8)],
        name="bench")

    registry = serve.ModelRegistry()
    preds = {}
    try:
        for path, kw in (("fp32", {}),
                         ("int8", {"quantize": "int8", "calib": calib})):
            t0 = time.perf_counter()
            preds[path] = (registry.load(
                "bench-" + path, net, params,
                data_shapes={"data": (1, dim)},
                ladder=serve.BucketLadder(batches=rungs), **kw),
                time.perf_counter() - t0)

        # -- per-rung compute-op byte accounting from the lowered HLO --
        per_rung = {}
        byte_ratios = []
        for b in rungs:
            row = {}
            for path, (pred, _) in preds.items():
                text = pred.lowered_text(pred.rung_shapes(b))
                if path == "int8" and not hlo_has_int8_compute(text):
                    raise RuntimeError(
                        "rung %d of the quantized path lowered with no "
                        "int8 dot/conv" % b)
                row[path] = sum(
                    r["bytes"] for r in costs.parse_hlo_ops(text)
                    if r["op"] in ("dot_general", "dot", "convolution"))
            ratio = row["fp32"] / max(row["int8"], 1.0)
            byte_ratios.append(ratio)
            per_rung[b] = {
                "fp32_compute_bytes": int(row["fp32"]),
                "int8_compute_bytes": int(row["int8"]),
                "byte_reduction_x": round(ratio, 2),
            }

        # -- accuracy on identical inputs at every rung ----------------
        # rel err is gated per rung; top-1 agreement is pooled over
        # every sample (a per-rung min at rung 1 would let a single
        # near-tie argmax flip read as 0% agreement)
        worst_err = 0.0
        agree, total = 0, 0
        for b in list(rungs) + [max(rungs)] * 16:
            x = rs.randn(b, dim).astype(np.float32)
            ref = preds["fp32"][0].predict(x)[0].asnumpy()
            out = preds["int8"][0].predict(x)[0].asnumpy()
            worst_err = max(worst_err, float(
                np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-20)))
            agree += int((out.argmax(-1) == ref.argmax(-1)).sum())
            total += ref.shape[0]
        worst_top1 = agree / total

        # -- identical closed-loop traffic through both batchers -------
        reqs = [rs.randn(rs.randint(1, 5), dim).astype(np.float32)
                for _ in range(64)]
        perf = {}
        for path, (pred, warm_s) in preds.items():
            batcher = registry.batcher("bench-" + path, max_wait_ms=1.0)
            warm = pred.compile_count
            lats, errors = [], []
            lock = threading.Lock()

            def worker(tid, batcher=batcher, lats=lats, errors=errors,
                       lock=lock):
                mine = []
                try:
                    for i in range(requests):
                        x = reqs[(tid * requests + i) % len(reqs)]
                        t0 = time.monotonic()
                        batcher.submit(x).result(60)
                        mine.append(time.monotonic() - t0)
                except Exception as exc:
                    with lock:
                        errors.append("worker %d: %r" % (tid, exc))
                finally:
                    with lock:
                        lats.extend(mine)

            ths = [threading.Thread(target=worker, args=(t,))
                   for t in range(threads)]
            t0 = time.monotonic()
            for t in ths:
                t.start()
            for t in ths:
                t.join()
            dt = time.monotonic() - t0
            if errors:
                raise RuntimeError("quant A/B %s loop failed: %s"
                                   % (path, "; ".join(errors[:3])))
            lats.sort()
            perf[path] = {
                "warm_compile_seconds": round(warm_s, 3),
                "throughput_rps": round(threads * requests / dt, 2),
                "p50_ms": round(_percentile(lats, 50) * 1e3, 3),
                "p99_ms": round(_percentile(lats, 99) * 1e3, 3),
                "request_path_compiles": pred.compile_count - warm,
            }
        qreport = preds["int8"][0].quantization
    finally:
        registry.close()

    min_ratio = min(byte_ratios)
    out = {
        "metric": "quant_paths",
        "value": round(min_ratio, 2),
        "unit": "x fewer compute-op bytes (worst rung)",
        "model": {"hidden": hidden, "dim": dim, "classes": classes,
                  "rungs": list(rungs)},
        "quantization": {"mode": qreport["mode"],
                         "calib_sha": qreport["calib_sha"],
                         "covered": qreport["covered"],
                         "total": qreport["total"]},
        "per_rung": per_rung,
        "max_rel_err": round(worst_err, 5),
        "top1_agreement": round(worst_top1, 4),
        "fp32": perf["fp32"],
        "int8": perf["int8"],
        "quant_ok": (min_ratio >= 2.0 and worst_err <= 0.1
                     and worst_top1 >= 0.95
                     and perf["fp32"]["request_path_compiles"] == 0
                     and perf["int8"]["request_path_compiles"] == 0),
    }
    print(json.dumps(out))
    return out


def serve_fleet_bench(chips, hidden=64, dim=16, classes=8, open_rate=60.0,
                      open_seconds=2.0, replicas=3, pool=16):
    """``--serve-fleet``: open-loop load through the multi-replica
    serving fleet's router at 1 vs N replicas — REAL replica
    processes (mxnet_tpu.serve.replica) sharing one persistent XLA
    compile cache, so replicas 2..N warm from disk.  Per-request
    latency is measured from the request's SCHEDULED arrival slot
    (queue wait included — no coordinated omission).  Prints ONE
    BENCH-schema JSON line with per-stage p50/p99 + throughput and
    asserts zero request-path compiles on every replica.  *chips* is
    the host's chip list (``_fleet_parent_off_chip``): each replica
    owns one, so N is capped at their number."""
    import queue as _queue
    import tempfile
    import threading

    import mxnet_tpu as mx
    from mxnet_tpu import model as model_mod, serve, sym

    data = sym.var("data")
    net = sym.FullyConnected(data, num_hidden=hidden, name="ffc1")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=classes, name="ffc2")
    net = sym.softmax(net)
    rs = np.random.RandomState(0)
    arg_shapes, _, _ = net.infer_shape(data=(1, dim))
    params = {n: mx.nd.array(rs.randn(*s).astype(np.float32) * 0.05)
              for n, s in zip(net.list_arguments(), arg_shapes)
              if n != "data"}
    if chips:
        replicas = min(replicas, len(chips))
    tmp = tempfile.mkdtemp(prefix="bench_fleet_")
    prefix = os.path.join(tmp, "m")
    model_mod.save_checkpoint(prefix, 1, net, params, {})
    spec = [{"name": "m", "prefix": prefix, "epoch": 1,
             "data_shapes": {"data": [1, dim]},
             "batches": [1, 2, 4, 8]}]
    reqs = [rs.randn(rs.randint(1, 5), dim).astype(np.float32)
            for _ in range(64)]

    def run_stage(fleet, n_replicas):
        compiles_before = {k: fleet.stats(k)["compile_count"]
                           for k in fleet.keys()}
        n = int(open_rate * open_seconds)
        slots = _queue.Queue()
        t_start = time.monotonic() + 0.2
        for i in range(n):
            slots.put((t_start + i / open_rate, i))
        lat = []
        errors = []
        lock = threading.Lock()

        def worker():
            while True:
                try:
                    slot, i = slots.get_nowait()
                except _queue.Empty:
                    return
                delay = slot - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                try:
                    fleet.router.predict("m",
                                         {"data": reqs[i % len(reqs)]})
                except Exception as exc:
                    with lock:
                        errors.append(repr(exc))
                    return
                with lock:
                    # latency from the SCHEDULED arrival: a backed-up
                    # fleet pays its queue wait here instead of
                    # silently slowing the offered rate
                    lat.append(time.monotonic() - slot)

        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(pool)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.monotonic() - t0
        if errors:
            raise RuntimeError("fleet bench stage failed: %s"
                               % "; ".join(errors[:3]))
        request_path = 0
        for k in fleet.keys():
            if fleet.stats(k)["compile_count"] != \
                    compiles_before.get(k, {}):
                request_path += 1
        lat.sort()
        return {
            "replicas": n_replicas,
            "offered_rps": open_rate,
            "requests": len(lat),
            "achieved_rps": round(len(lat) / dt, 2),
            "p50_ms": round(_percentile(lat, 50) * 1e3, 3),
            "p99_ms": round(_percentile(lat, 99) * 1e3, 3),
            "request_path_compiles": request_path,
        }

    fleet = serve.Fleet(spec, replicas=1, workdir=tmp, max_wait_ms=1.0,
                        router_kwargs={"probe_interval": 0.2})
    try:
        t0 = time.monotonic()
        fleet.start()
        first_up = time.monotonic() - t0
        stage1 = run_stage(fleet, 1)
        t0 = time.monotonic()
        for _ in range(replicas - 1):
            fleet._spawn()
        fleet.wait_routable(count=replicas)
        scale_out = time.monotonic() - t0
        stageN = run_stage(fleet, replicas) if replicas > 1 else stage1
        cache_entries = len(os.listdir(fleet.compile_cache_dir))
    finally:
        fleet.stop()
    request_path = stage1["request_path_compiles"] + \
        stageN["request_path_compiles"]
    out = {
        "metric": "serve_fleet",
        "value": stageN["achieved_rps"],
        "unit": "requests/sec",
        "model": {"hidden": hidden, "dim": dim},
        "first_replica_up_seconds": round(first_up, 2),
        "scale_out_seconds": round(scale_out, 2),
        "compile_cache_entries": cache_entries,
        "request_path_compiles": request_path,
        "stages": [stage1, stageN],
    }
    print(json.dumps(out))
    if request_path:
        raise RuntimeError(
            "fleet bench: %d replica(s) compiled in the request path"
            % request_path)
    return out


def _decode_toy(vocab=48, dim=24, seed=0):
    from mxnet_tpu.test_utils import tiny_attention_lm
    return tiny_attention_lm(vocab=vocab, dim=dim, seed=seed)


def compare_decode_paths(sessions=16, prompt_len=16, new_tokens=32,
                         block_size=8, vocab=48, dim=16):
    """``--compare-decode-paths``: batched decode ticks (paged pool,
    one dispatch serves every session's next token) vs SERIAL
    per-session dense decode (the PR-9 DecodeSession discipline: one
    dense worst-case cache and one dispatch per session per token).
    Both paths run the SAME step function and their token streams are
    checked bit-equal, so the speedup is pure dispatch/batching, not
    a different model.  Prints ONE BENCH-schema JSON line with
    aggregate tokens/sec for both paths and the speedup."""
    import warnings

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.serve.decode import DecodeBatcher, DecodeEngine

    params, step_fn, prefill_fn, token_spec, input_spec = _decode_toy(
        vocab=vocab, dim=dim)
    max_len = prompt_len + new_tokens + 1
    rs = np.random.RandomState(3)
    prompts = [rs.randint(0, vocab, size=prompt_len).astype(np.int32)
               for _ in range(sessions)]

    # -- serial baseline: one dense program, per-session caches, one
    # dispatch per session per token (prompt fed token by token — the
    # dense path has no prefill program) -------------------------------
    padded_len = -(-max_len // block_size) * block_size
    dense = jax.jit(step_fn)
    cache_zero = {"k": jnp.zeros((1, padded_len, dim), jnp.float32),
                  "v": jnp.zeros((1, padded_len, dim), jnp.float32)}
    lowered = dense.lower(
        jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params),
        jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            cache_zero),
        {"tok": jax.ShapeDtypeStruct((1,), jnp.int32)},
        jax.ShapeDtypeStruct((1,), jnp.int32))
    dense_prog = lowered.compile()
    del lowered

    def serial_decode(prompt):
        cache = dict(cache_zero)
        stream = []
        cur = None
        t = 0
        for tok in prompt:
            out, cache = dense_prog(
                params, cache, {"tok": np.asarray([tok], np.int32)},
                np.asarray([t], np.int32))
            t += 1
            cur = int(np.asarray(out)[0])   # d2h readback per token
        for _ in range(new_tokens):
            stream.append(cur)
            if len(stream) >= new_tokens:
                break
            out, cache = dense_prog(
                params, cache, {"tok": np.asarray([cur], np.int32)},
                np.asarray([t], np.int32))
            t += 1
            cur = int(np.asarray(out)[0])
        return stream

    t0 = time.monotonic()
    serial_streams = [serial_decode(p) for p in prompts]
    serial_dt = time.monotonic() - t0
    total_tokens = sessions * new_tokens
    serial_tps = total_tokens / serial_dt

    # -- batched ticks over the paged pool ------------------------------
    rungs = [1]
    while rungs[-1] < sessions:
        rungs.append(rungs[-1] * 2)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")     # CPU ignores donation
        engine = DecodeEngine(
            step_fn, prefill_fn, token_spec, input_spec, params=params,
            max_len=max_len, block_size=block_size,
            num_blocks=sessions * (-(-max_len // block_size)) + 2,
            session_rungs=rungs, donate=True, label="bench")
        warm_compiles = engine.compile_count
        batcher = DecodeBatcher(engine, max_wait_ms=1.0)
        t0 = time.monotonic()
        sess = [batcher.start({"tok": p}, max_new_tokens=new_tokens)
                for p in prompts]
        batched_streams = [[int(o) for o in s.result(120)]
                           for s in sess]
        batched_dt = time.monotonic() - t0
        request_path_compiles = engine.compile_count - warm_compiles
        ticks = batcher.tick_count
        batcher.close()
        engine.close()
    batched_tps = total_tokens / batched_dt

    if batched_streams != serial_streams:
        raise RuntimeError(
            "decode bench: batched token streams are not bit-equal "
            "to the serial dense decode — the comparison is void")

    speedup = batched_tps / serial_tps
    out = {
        "metric": "serve_decode_compare",
        "value": round(speedup, 3),
        "unit": "x_tokens_per_sec",
        "sessions": sessions,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "total_tokens": total_tokens,
        "serial_tokens_per_sec": round(serial_tps, 2),
        "batched_tokens_per_sec": round(batched_tps, 2),
        "serial_seconds": round(serial_dt, 4),
        "batched_seconds": round(batched_dt, 4),
        "decode_ticks": ticks,
        "request_path_compiles": request_path_compiles,
        "streams_bit_equal": True,
        # the acceptance bar: batched ticks must at least double the
        # aggregate token throughput at >= 8 concurrent sessions
        "speedup_ok": speedup >= 2.0 and request_path_compiles == 0,
    }
    print(json.dumps(out))
    return out


def serve_decode_bench(rate=12.0, seconds=3.0, prompt_lo=4,
                       prompt_hi=24, new_tokens=24, vocab=48, dim=24,
                       block_size=8, record_trace=None, trace=None):
    """``--serve-decode``: open-loop many-session decode load — new
    sessions arrive on a fixed schedule (no coordinated omission: the
    arrival grid never waits for the system), each decodes
    *new_tokens* greedily through the continuous-batching tick loop.
    Per-token latencies come from the batcher's delivery stamps (each
    token is stamped when its tick resolves, not when the client gets
    scheduled).  Prints ONE BENCH-schema JSON line with p50/p99 token
    latency, p50/p99 time-to-first-token, aggregate tokens/sec and
    request_path_compiles.

    ``--record-trace PATH`` serializes the session-arrival schedule
    (prompt lengths + offsets) as an autotune trace; ``--trace PATH``
    replays one instead of the synthetic grid.  A tuned-store entry
    for model "bench-open" (workload decode) overrides the
    hand-picked block size / session rungs / tick window."""
    import warnings

    from mxnet_tpu.autotune import trace as _at
    from mxnet_tpu.autotune.store import lookup as _at_lookup
    from mxnet_tpu.serve.decode import DecodeBatcher, DecodeEngine

    tr = None
    if trace is not None:
        tr = _at.Trace.load(trace)
        if tr.kind != "decode":
            raise ValueError("bench --serve-decode needs a decode "
                             "trace, got kind=%r" % tr.kind)
        vocab = int(tr.meta.get("vocab", vocab))
        new_tokens = int(tr.meta.get("new_tokens", new_tokens))
        prompts = tr.payloads()
        prompt_hi = max(p.shape[0] for p in prompts)
        n_sessions = len(prompts)
        rate = round((n_sessions - 1) / max(tr.duration(), 1e-9), 2)
    else:
        n_sessions = int(rate * seconds)
        rs = np.random.RandomState(5)
        prompts = [rs.randint(0, vocab,
                              size=rs.randint(prompt_lo,
                                              prompt_hi + 1))
                   .astype(np.int32) for _ in range(n_sessions)]
    if record_trace:
        rec = tr if tr is not None else _at.Trace(
            "decode",
            [{"t": round(i / rate, 6), "prompt_len": int(p.shape[0])}
             for i, p in enumerate(prompts)],
            {"vocab": vocab, "new_tokens": new_tokens, "rate": rate},
            seed=5)
        rec.save(record_trace)

    params, step_fn, prefill_fn, token_spec, input_spec = _decode_toy(
        vocab=vocab, dim=dim)
    max_len = prompt_hi + new_tokens + 1
    # tuned-store pickup (docs/autotuning.md): an entry for
    # ("bench-open", decode) replaces the hand-picked knobs
    tuned = _at_lookup("bench-open", "decode")
    tcfg = (tuned or {}).get("config") or {}
    if tuned:
        block_size = int(tcfg.get("MXNET_SERVE_KV_BLOCK_SIZE")
                         or block_size)
    session_rungs = tuple(tcfg.get("ladder") or (1, 2, 4, 8, 16, 32))

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        engine = DecodeEngine(
            step_fn, prefill_fn, token_spec, input_spec, params=params,
            max_len=max_len, block_size=block_size,
            num_blocks=n_sessions * (-(-max_len // block_size)) + 2,
            session_rungs=session_rungs, donate=True,
            label="bench-open")
        warm_compiles = engine.compile_count
        batcher = DecodeBatcher(
            engine, max_wait_ms=None if tuned else 1.0)

        shed_box = [0]

        def _start(prompt, _i):
            try:
                return batcher.start({"tok": prompt},
                                     max_new_tokens=new_tokens)
            except Exception:
                shed_box[0] += 1
                return None

        t_start = time.monotonic()
        if tr is not None:
            records, _replay_wall = _at.replay(tr, _start)
        else:
            period = 1.0 / rate
            records = []
            for i in range(n_sessions):
                slot = t_start + i * period
                delay = slot - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                t_sub = time.monotonic()
                records.append((i * period, t_sub,
                                _start(prompts[i], i)))
        arrivals = [(t_sub, s) for _slot, t_sub, s in records
                    if s is not None]
        shed = shed_box[0]
        for _, s in arrivals:
            s.result(120)
        wall = time.monotonic() - t_start
        request_path_compiles = engine.compile_count - warm_compiles
        ticks = batcher.tick_count
        batcher.close()
        engine.close()

    ttft, token_lat = [], []
    total_tokens = 0
    for t_sub, s in arrivals:
        stamps = s.stamps()
        total_tokens += len(stamps)
        if not stamps:
            continue
        ttft.append(stamps[0] - t_sub)
        token_lat.append(stamps[0] - t_sub)
        token_lat.extend(b - a for a, b in zip(stamps, stamps[1:]))
    token_lat.sort()
    ttft.sort()
    out = {
        "metric": "serve_decode_load",
        "value": round(total_tokens / wall, 2),
        "unit": "tokens/sec",
        "offered_sessions_per_sec": rate,
        "sessions": len(arrivals),
        "sessions_shed": shed,
        "new_tokens": new_tokens,
        "total_tokens": total_tokens,
        "decode_ticks": ticks,
        "token_p50_ms": round(_percentile(token_lat, 50) * 1e3, 3)
        if token_lat else None,
        "token_p99_ms": round(_percentile(token_lat, 99) * 1e3, 3)
        if token_lat else None,
        "ttft_p50_ms": round(_percentile(ttft, 50) * 1e3, 3)
        if ttft else None,
        "ttft_p99_ms": round(_percentile(ttft, 99) * 1e3, 3)
        if ttft else None,
        "request_path_compiles": request_path_compiles,
        "tuning": tcfg or None,
        "trace": tr.summary() if tr is not None else None,
    }
    print(json.dumps(out))
    return out


def serve_decode_failover_bench(streams=6, new_tokens=48, replicas=2,
                                vocab=32, dim=16, seed=5, kill_at=30,
                                block_size=4, max_len=64):
    """``--serve-decode --failover``: the decode fault-tolerance path
    measured, not just gated — N wire decode streams through the
    fleet router while one replica is armed to hard-kill mid-run
    (``replica_kill_decode_at``), so the streams it was serving fail
    over to a survivor and resume from the router journal.
    Consumers stamp every delivered token client-side.  Prints ONE
    BENCH-schema JSON line: resume latency p50/p99 out of
    ``DecodeStream.resume_stamps`` (kill detection → resumed and
    serving), steady vs dip tokens/sec (best vs worst interior 50 ms
    delivery window — the dip is what the kill costs the fleet), full
    bit-equality of every stream to the solo dense decode, and
    request_path_compiles=0 on the survivors.  Failover needs a
    survivor: on a TPU host the fleet asks for two chips."""
    import tempfile
    import threading

    from mxnet_tpu import serve
    from mxnet_tpu.test_utils import (dense_decode_reference,
                                      tiny_attention_lm)

    prompt = np.array([3, 1, 2], dtype=np.int32)
    blocks_per = -(-max_len // block_size)
    spec = [{"name": "lm", "kind": "decode_lm", "vocab": vocab,
             "dim": dim, "seed": seed, "dtype": "float32",
             "max_len": max_len, "block_size": block_size,
             "num_blocks": streams * blocks_per + 8,
             "rungs": [1, 2, 4]}]
    dparams, dstep, _, _, _ = tiny_attention_lm(vocab=vocab, dim=dim,
                                                seed=seed)
    ref = dense_decode_reference(dparams, dstep, list(prompt),
                                 new_tokens, max_len, dim)

    tmp = tempfile.mkdtemp(prefix="bench_decode_fo_")
    fleet = serve.Fleet(spec, replicas=replicas, workdir=tmp,
                        max_wait_ms=1.0,
                        router_kwargs={"probe_interval": 0.2,
                                       "retries": 4})
    stamps = []                       # (t_mono, stream_seq) per token
    errors = []
    lock = threading.Lock()

    def consume(s):
        while True:
            try:
                s.next_output(timeout=120)
            except StopIteration:
                return
            except Exception as exc:
                with lock:
                    errors.append("stream %d: %r" % (s.seq, exc))
                return
            with lock:
                stamps.append((time.monotonic(), s.seq))

    try:
        fleet.start()
        armed = fleet.replace(fleet.keys()[0], extra_env={
            "MXNET_CHAOS": "replica_kill_decode_at=%d" % kill_at})
        fleet.wait_routable(count=replicas, model="lm")
        survivors = [k for k in fleet.keys() if k != armed]
        warm = {k: fleet.stats(k)["decode"]["lm"]["compile_count"]
                for k in survivors}
        t0 = time.monotonic()
        opened = [fleet.router.decode_open("lm", {"tok": prompt},
                                           max_new_tokens=new_tokens)
                  for _ in range(streams)]
        threads = [threading.Thread(target=consume, args=(s,),
                                    daemon=True) for s in opened]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        wall = time.monotonic() - t0
        rec = fleet.record(armed)
        deadline = time.monotonic() + 30
        while rec["proc"].poll() is None and \
                time.monotonic() < deadline:
            time.sleep(0.1)
        kill_rc = rec["proc"].poll()
        bit_equal = True
        for s in opened:
            got = [int(np.asarray(t)) for t in s.tokens()]
            if got != ref:
                bit_equal = False
                errors.append("stream %d not bit-equal" % s.seq)
        moved = [s for s in opened if s.failover_count >= 1]
        resume_lat = sorted(b - a for s in moved
                            for a, b in s.resume_stamps)
        request_path = sum(
            fleet.stats(k)["decode"]["lm"]["compile_count"] - warm[k]
            for k in survivors)
        for s in opened:
            s.close()
    finally:
        fleet.stop()

    # interior 50 ms delivery windows: steady = best, dip = worst —
    # the first/last windows are ramp and tail, not the kill's cost
    win = 0.05
    rates = []
    if stamps:
        times = sorted(t for t, _ in stamps)
        t_lo, t_hi = times[0], times[-1]
        n_win = max(1, int((t_hi - t_lo) / win))
        counts = [0] * n_win
        for t in times:
            counts[min(n_win - 1, int((t - t_lo) / win))] += 1
        rates = [c / win for c in counts[1:-1]] or \
            [c / win for c in counts]
    total_tokens = len(stamps)
    out = {
        "metric": "serve_decode_failover",
        "value": round(resume_lat[-1] * 1e3, 3) if resume_lat
        else None,
        "unit": "ms_worst_resume",
        "streams": streams,
        "new_tokens": new_tokens,
        "replicas": replicas,
        "total_tokens": total_tokens,
        "tokens_per_sec": round(total_tokens / wall, 2),
        "failed_over_streams": len(moved),
        "resumes": len(resume_lat),
        "resume_p50_ms": round(
            _percentile(resume_lat, 50) * 1e3, 3)
        if resume_lat else None,
        "resume_p99_ms": round(
            _percentile(resume_lat, 99) * 1e3, 3)
        if resume_lat else None,
        "tokens_per_sec_steady": round(max(rates), 2)
        if rates else None,
        "tokens_per_sec_dip": round(min(rates), 2) if rates else None,
        "dip_ratio": round(min(rates) / max(rates), 3)
        if rates and max(rates) else None,
        "bit_equal": bit_equal,
        "kill_rc": kill_rc,
        "request_path_compiles": request_path,
        "errors": errors or None,
    }
    print(json.dumps(out))
    if errors or not moved or kill_rc != 137 or request_path:
        raise RuntimeError(
            "decode failover bench failed: moved=%d rc=%r "
            "request_path_compiles=%d errors=%s"
            % (len(moved), kill_rc, request_path, errors[:3]))
    return out


def decompose_main():
    """``--decompose``: lower the north-star train step, attribute its
    cost per op against probed roofline peaks, print the human table
    to stderr and ONE JSON line (BENCH schema: metric=mfu_decompose)
    to stdout.  Runs on whatever platform ``_ensure_platform``
    admits — CPU (BENCH_ALLOW_CPU=1) uses a small config, so CI can
    smoke the whole decompose path in seconds."""
    _ensure_platform()
    import jax
    from mxnet_tpu.observability import costs as _costs

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    batch = 128 if on_tpu else 8
    image = 224 if on_tpu else 32
    peak = _probe_peak_flops() if on_tpu else \
        _probe_peak_flops(iters=8, n=1024)
    bw = _probe_peak_bw() if on_tpu else _probe_peak_bw(mb=32)
    r = timed_resnet_train(
        batch, image, remat=None, iters=4 if on_tpu else 2,
        scan_n=2, warmup=1, optimizer="lbsgd" if on_tpu else "sgd",
        multi_precision=on_tpu)
    if not r.get("hlo_text"):
        print("bench: could not lower the train step for decompose",
              file=sys.stderr)
        return 1
    table = _costs.cost_table(text=r["hlo_text"], peak_flops=peak,
                              peak_bytes_s=bw, top=20)
    print(_costs.format_table(table, limit=24), file=sys.stderr)
    out = {
        "metric": "mfu_decompose",
        "batch_size": batch,
        "image_size": image,
        "device": getattr(dev, "device_kind", str(dev)),
        "peak_flops_probe": peak,
        "peak_bw_probe": bw,
        "machine_balance": table["machine_balance"],
        "total_flops": table["total_flops"],
        "total_bytes": table["total_bytes"],
        "flops_vs_xla": table.get("flops_vs_xla"),
        "ms_per_step": round(r["dt"] / r["iters"] * 1e3, 2),
        "rows": table["rows"],
    }
    print(json.dumps(out))
    return 0


def audit_main():
    """``--audit``: lower the graftir representative AOT program set,
    run rules GI001-GI005, diff per-program flops/bytes/sha against
    the committed manifest, print the human diff table to stderr and
    ONE JSON line (BENCH schema: metric=ir_audit) to stdout.  A
    static measurement over lowered text — nothing executes, so it
    ALWAYS runs on CPU (the committed manifest shas are CPU lowers)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    from tools.graftir import (audit_programs, diff as manifest_diff,
                               format_diff_table, load as manifest_load,
                               DEFAULT_MANIFEST)
    from tools.graftir.programs import build_representative_set

    programs = build_representative_set()
    engine, findings = audit_programs(programs)
    rows, violations = manifest_diff(programs,
                                     manifest_load(DEFAULT_MANIFEST))
    print(format_diff_table(rows), file=sys.stderr)
    for v in violations:
        print("bench: audit: %s" % v, file=sys.stderr)
    out = {
        "metric": "ir_audit",
        "programs": len(programs),
        "findings": len(findings),
        "new_findings": engine.stats["new"],
        "violations": len(violations),
        "flops_total": round(sum(r["flops"] or 0.0 for r in rows), 1),
        "bytes_total": round(sum(r["bytes"] or 0.0 for r in rows), 1),
        "rows": rows,
    }
    print(json.dumps(out))
    return 1 if (engine.stats["new"] or violations) else 0


def _argv_path(flag):
    """Value of ``flag PATH`` in sys.argv, or None (bench's dispatch
    is flag-sniffing, not argparse — keep trace flags the same)."""
    if flag not in sys.argv:
        return None
    i = sys.argv.index(flag)
    if i + 1 >= len(sys.argv) or sys.argv[i + 1].startswith("--"):
        raise SystemExit("bench: %s needs a path" % flag)
    return sys.argv[i + 1]


def main():
    if "--serve" in sys.argv:
        # serving load test: throughput + latency of the compiled
        # inference subsystem under concurrent traffic.  Platform
        # rules match the training bench (_ensure_platform).
        _ensure_platform()
        serve_bench(record_trace=_argv_path("--record-trace"),
                    trace=_argv_path("--trace"),
                    quantize=_argv_path("--quantize"))
        return
    if "--compare-quant-paths" in sys.argv:
        # fp32 vs post-training-int8 serving on the same ladder and
        # traffic — a relative measurement (HLO byte accounting +
        # accuracy + latency deltas), so it ALWAYS runs on CPU
        os.environ["JAX_PLATFORMS"] = "cpu"
        out = compare_quant_paths()
        if not out["quant_ok"]:
            print("bench: quantized path failed the bar (%.2fx fewer "
                  "compute-op bytes at the worst rung, rel err %.4f, "
                  "top-1 %.3f, request_path_compiles fp32=%d int8=%d "
                  "— want >= 2x, <= 0.1, >= 0.95, 0, 0)"
                  % (out["value"], out["max_rel_err"],
                     out["top1_agreement"],
                     out["fp32"]["request_path_compiles"],
                     out["int8"]["request_path_compiles"]),
                  file=sys.stderr)
            return 1
        return 0
    if "--decompose" in sys.argv:
        return decompose_main()
    if "--audit" in sys.argv:
        return audit_main()
    if "--compare-decode-paths" in sys.argv:
        # batched decode ticks vs serial per-session dense decode — a
        # relative dispatch-count measurement, so it ALWAYS runs on
        # CPU
        os.environ["JAX_PLATFORMS"] = "cpu"
        out = compare_decode_paths()
        if not out["speedup_ok"]:
            print("bench: batched decode failed the bar (%.2fx "
                  "tokens/sec vs serial at %d sessions, "
                  "request_path_compiles=%d — want >= 2x with 0)"
                  % (out["value"], out["sessions"],
                     out["request_path_compiles"]), file=sys.stderr)
            return 1
        return 0
    if "--serve-decode" in sys.argv:
        # open-loop many-session continuous-batching decode load;
        # latency distribution + aggregate tokens/sec.  --failover
        # instead measures the fault-tolerance path: resume latency
        # and the tokens/sec dip around a seeded mid-run replica kill
        if "--failover" in sys.argv:
            _fleet_parent_off_chip()
            serve_decode_failover_bench()
            return
        _ensure_platform()
        serve_decode_bench(record_trace=_argv_path("--record-trace"),
                           trace=_argv_path("--trace"))
        return
    if "--serve-fleet" in sys.argv:
        # open-loop load through the multi-replica fleet router at
        # 1 vs N replica processes (request_path_compiles=0 asserted)
        serve_fleet_bench(_fleet_parent_off_chip())
        return
    if "--compare-input-paths" in sys.argv:
        # serial vs device-prefetched input path — a host/device
        # overlap measurement, so it ALWAYS runs on CPU
        os.environ["JAX_PLATFORMS"] = "cpu"
        out = compare_input_paths()
        if not out["overlap_ok"]:
            print("bench: input pipelining failed the overlap bar "
                  "(pipelined %.2f ms/step vs serial %.2f — want "
                  "< 0.7x)" % (out["pipelined_ms_per_step"],
                               out["serial_ms_per_step"]),
                  file=sys.stderr)
            return 1
        return 0
    if "--compare-update-paths" in sys.argv:
        # explicit A/B of the two update paths — a relative dispatch-
        # overhead measurement, so it ALWAYS runs on CPU
        os.environ["JAX_PLATFORMS"] = "cpu"
        compare_update_paths()
        return
    _ensure_platform()
    import jax

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    batch = 128 if on_tpu else 8
    image = 224 if on_tpu else 32
    warmup, iters = (4, 20) if on_tpu else (2, 10)
    # 10-deep scan: one dispatch covers ten steps, which keeps the
    # per-dispatch host latency out of the window (CPU keeps a short
    # scan — it multiplies compile time)
    scan_n = 10 if on_tpu else 2

    # input-stall accounting across the timed window: share of wall
    # time the step loop spent blocked on the input pipeline
    # (input_wait_seconds histogram — 0.0 here because the bench feeds
    # a device-resident batch, the pipelined ideal the real input path
    # is measured against via --compare-input-paths)
    from mxnet_tpu.observability import metrics as _obs_metrics
    _wait_hist = _obs_metrics.REGISTRY.get("input_wait_seconds")
    _wait0 = _wait_hist.sum if _wait_hist is not None else 0.0

    r = timed_resnet_train(
        batch, image,
        # BENCH_REMAT=dots|full selects a jax.checkpoint policy for the
        # step (HBM-pressure experiments on hardware)
        remat=os.environ.get("BENCH_REMAT") or None,
        iters=iters, scan_n=scan_n, warmup=warmup,
        optimizer="lbsgd" if on_tpu else "sgd",
        multi_precision=on_tpu)
    img_s, dt, iters = r["img_s"], r["dt"], r["iters"]
    flops, final_loss = r["flops_per_step"], r["final_loss"]
    input_stall_share = round(
        ((_wait_hist.sum - _wait0) if _wait_hist is not None else 0.0)
        / dt, 4)

    peak_probe = _probe_peak_flops() if on_tpu else None
    sustained = flops * iters / dt
    mfu = sustained / peak_probe if peak_probe else None
    mfu_error = None
    if mfu is not None and not 0.0 < mfu <= 1.0:
        # a broken probe must
        # not crash the WHOLE bench run and lose the throughput
        # number with it: record mfu=null + a structured warning and
        # keep going (the round artifact stays parseable)
        mfu_error = (
            "MFU %.4f outside (0, 1] — measurement or probe is broken "
            "(sustained %.1f TF/s, probe %.1f TF/s)"
            % (mfu, sustained / 1e12, peak_probe / 1e12))
        print("bench: " + mfu_error, file=sys.stderr)
        from mxnet_tpu.observability import events as _obs_events
        _obs_events.emit("warning", kind="mfu_probe_broken",
                         mfu=round(mfu, 4), sustained_flops=sustained,
                         peak_flops_probe=peak_probe)
        mfu = None

    # per-op cost attribution of the exact step just timed (rows name
    # the op a round-over-round MFU regression blames; see
    # docs/observability.md and bench --decompose for the full table)
    decompose = None
    if r.get("hlo_text"):
        try:
            from mxnet_tpu.observability import costs as _costs
            peak_bw = _probe_peak_bw() if on_tpu else None
            table = _costs.cost_table(text=r["hlo_text"],
                                      peak_flops=peak_probe,
                                      peak_bytes_s=peak_bw, top=12)
            decompose = {
                "machine_balance": table["machine_balance"],
                "total_flops": table["total_flops"],
                "total_bytes": table["total_bytes"],
                "rows": table["rows"],
            }
        except Exception as e:
            print("bench: decompose failed (%r)" % e, file=sys.stderr)

    out = {
        "metric": "resnet50_train_throughput",
        "value": round(img_s, 2),
        "unit": "images/sec",
        "vs_baseline": round(img_s / BASELINE_IMG_S, 3),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "peak_flops_probe": peak_probe,
        "peak_flops_datasheet": _datasheet_peak(dev) if on_tpu else None,
        "sustained_flops": sustained,
        "batch_size_per_chip": batch,
        "image_size": image,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "flops_per_step": flops,
        "final_loss": final_loss,
        "mfu_error": mfu_error,
        "input_stall_share": input_stall_share,
        "decompose": decompose,
    }
    print(json.dumps(out))


if __name__ == "__main__":
    sys.exit(main())
