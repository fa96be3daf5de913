"""Sanitizer-enabled CI smoke train step (ci/run_tests.sh stage).

Runs a short real training loop — fused train step + DevicePrefetcher
data path + a local kvstore multi-device trainer — with ALL FOUR
graftsan components on (the stage exports MXNET_SAN=all), then fails
on:

* any sanitizer report (race/lockset, lock-order, recompile,
  donation, transfer),
* a broken one-program-per-step contract (fused_step dispatches must
  equal the step count; compiles must stay at warmup's one), on both
  the full-fused and the partial-fused (tree_apply) paths.

The point is drift protection: a new lock added without discipline, a
per-step recompile, or a hot-path host sync shows up HERE, in seconds,
with stacks — not as a flaky multi-process drill three PRs later.
"""

import os
import sys

os.environ.setdefault("MXNET_SAN", "all")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# two virtual CPU devices: the partial-fused (multi-device tree
# update) path only engages with >1 executor
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") +
        " --xla_force_host_platform_device_count=2").strip()

import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import nd, sym  # noqa: E402
from mxnet_tpu import profiler  # noqa: E402
from mxnet_tpu.io import DevicePrefetcher, NDArrayIter  # noqa: E402
import tools.graftsan as graftsan  # noqa: E402

STEPS = 12


def build_module(contexts=None, kvstore=None):
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    net = sym.FullyConnected(data, num_hidden=32, name="fc1")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = sym.SoftmaxOutput(net, label, name="softmax")
    mod = mx.mod.Module(net, context=contexts or mx.cpu())
    mod.bind([("data", (16, 8))], [("softmax_label", (16,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(kvstore=kvstore, optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    return mod


def main():
    rng = np.random.RandomState(0)
    x = rng.randn(64, 8).astype(np.float32)
    y = rng.randint(0, 4, 64).astype(np.float32)
    failures = []

    # threaded data path: DevicePrefetcher (a PrefetchingIter whose
    # producer thread also does the device_put into a depth-2 ring)
    # runs under the instrumented queue/event/thread wrappers
    it = DevicePrefetcher(NDArrayIter(x, y, batch_size=16,
                                      last_batch_handle="discard"),
                          depth=2)

    # -- phase 1: full-fused path (single device, no kvstore) ---------
    mod = build_module()
    profiler.reset_counters()
    steps = 0
    while steps < STEPS:
        for batch in it:
            mod.forward_backward_update(batch)
            steps += 1
            if steps >= STEPS:
                break
        it.reset()
    dispatches = profiler.counter_value("fused_step_dispatches")
    compiles = profiler.counter_value("fused_step_compiles")
    if dispatches != STEPS:
        failures.append(
            "one-program-per-step broken: %d fused dispatches for %d "
            "steps (legacy fallback engaged?)" % (dispatches, STEPS))
    if compiles != 1:
        failures.append(
            "one-program-per-step broken: %d fused compiles (want "
            "exactly 1 warmup compile for %d steps)"
            % (compiles, STEPS))

    # -- phase 2: local kvstore push/pull + partial-fused path --------
    kv = mx.kv.create("local")
    kv.init("smoke", nd.ones((4,)))
    kv.push("smoke", nd.ones((4,)) * 2)
    out = nd.zeros((4,))
    kv.pull("smoke", out=out)
    assert out.asnumpy().tolist() == [2.0] * 4

    profiler.reset_counters()
    # multi-device, locally-reduced grads -> the jitted tree_apply
    # partial fusion (a local kvstore with update_on_kvstore would put
    # the updater store-side and fall back to the legacy loop)
    mod2 = build_module(contexts=[mx.cpu(0), mx.cpu(1)])
    it.reset()
    p_steps = 0
    for batch in it:
        mod2.forward_backward_update(batch)
        p_steps += 1
    tree_dispatches = profiler.counter_value("tree_apply_dispatches")
    tree_compiles = profiler.counter_value("tree_apply_compiles")
    if tree_dispatches != p_steps:
        failures.append(
            "partial-fused path broken: %d tree_apply dispatches for "
            "%d steps" % (tree_dispatches, p_steps))
    if tree_compiles != 1:
        failures.append(
            "partial-fused path recompiles: %d tree_apply compiles "
            "(want 1)" % tree_compiles)

    reports = graftsan.reports()
    for r in reports:
        failures.append(graftsan.format_report(r))

    # -- phase 3: donation drill ---------------------------------------
    # The CPU backend never donates, so without forcing the declared
    # donation this component would be INERT in CPU CI — force it and
    # prove a stale alias of a donated buffer raises at the touch
    # site.  Runs last: the deliberate trip adds a report.
    import warnings
    from mxnet_tpu.ops import registry as _registry
    from tools.graftsan.donation import UseAfterDonateError
    real_supports = _registry.supports_donation
    _registry.supports_donation = lambda: True
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")   # cpu ignores donation
            mod3 = build_module()
            it.reset()
            batch = next(iter(it))
            mod3.forward_backward_update(batch)
            ex3 = mod3._exec_group.execs[0]
            stale = mx.nd.NDArray(ex3.arg_dict["fc1_weight"]._data)
            mod3.forward_backward_update(batch)
        try:
            stale.asnumpy()
            failures.append("donation sanitizer inert: stale alias of "
                            "a donated buffer was readable")
        except UseAfterDonateError:
            pass
        if ex3.arg_dict["fc1_weight"].asnumpy().shape != (32, 8):
            failures.append("donation poison hit a LIVE rebound handle")
    finally:
        _registry.supports_donation = real_supports
        it.close()      # joins the producer: teardown is audited too
    deliberate = [r for r in graftsan.reports()[len(reports):]]
    if [r for r in deliberate if r.component != "donation"]:
        failures.extend(graftsan.format_report(r) for r in deliberate
                        if r.component != "donation")

    print("graftsan smoke: full_steps=%d dispatches=%d compiles=%d | "
          "partial_steps=%d tree_dispatches=%d tree_compiles=%d | "
          "donation drill tripped | reports=%d"
          % (steps, dispatches, compiles, p_steps, tree_dispatches,
             tree_compiles, len(reports)))
    if failures:
        print("\n".join(failures), file=sys.stderr)
        print("graftsan smoke: FAIL", file=sys.stderr)
        return 1
    print("graftsan smoke: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
