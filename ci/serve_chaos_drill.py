#!/usr/bin/env python
"""Serve-chaos drill: the serving request path driven through every
injected fault class (ci/run_tests.sh stage).

The injections live at the PRODUCTION choke points (see
mxnet_tpu/resilience/servechaos.py and docs/serving.md "Serving fault
tolerance"): the batcher's dispatcher consults ``on_dispatch`` before
every coalesced batch, the predictor consults ``on_warm`` before
every AOT program build.  Scenarios:

  overload    slow dispatches (armed through the MXNET_CHAOS env
              spec, the production wire format) back the queue up
              against a small request cap: submits past it shed with
              a typed OverloadError, every ACCEPTED request still
              completes bit-equal — overload never OOMs and never
              strands a caller
  expiry      the dispatcher is wedged (dispatch_hang_at) while a
              deadlined request waits: the request expires with a
              typed DeadlineExceededError and its payload provably
              NEVER reaches a dispatch; the un-deadlined request
              queued behind it completes
  crash       dispatch_raise_at escapes the dispatcher loop:
              supervision fails exactly the failing batch's futures,
              restarts the thread (jittered backoff), and the next
              batch serves normally
  unhealthy   crashes past the restart budget: the batcher goes
              unhealthy, submits shed typed, readiness and liveness
              probes flip false, and teardown still works
  liveness    a wedged dispatch with work queued goes stale on the
              health surface (Registry.live() false), recovers when
              released, and both requests land correct
  drain       unload(drain=True) under concurrent submit load with
              slow dispatches: every accepted request completes
              bit-equal to the eager forward at some rung, later
              submits shed typed, nothing hangs
  warm        reject_warm_at fails a load mid-warm: the model never
              half-registers (no name, no health entry), and the
              retried load serves

Cross-cutting asserts: ZERO stranded futures (every future any
scenario accepted resolves with a result or a typed error), and the
health state machine walked its full cycle in events.jsonl
(loading -> warming -> ready -> draining, plus ready -> unhealthy).

Deterministic counter-armed injections; the only sleeps are the
injected delays/hangs.  Scrapeable last stdout line::

    servechaos: faults=N recovered=M ok
"""

import os
import sys
import tempfile
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("MXNET_OBS", "serve")
# the overload/drain scenarios shed thousands of typed submits, each
# a serve event — uncap the rate so the control-trail assertions
# (drain / unhealthy / health transitions) cannot be rate-dropped
os.environ.setdefault("MXNET_OBS_RATE", "0")
os.environ.setdefault(
    "MXNET_OBS_PATH",
    os.path.join(tempfile.mkdtemp(prefix="serve_chaos_"),
                 "events.jsonl"))

import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import sym  # noqa: E402
from mxnet_tpu.observability import events as obs_events  # noqa: E402
from mxnet_tpu.observability import metrics as obs_metrics  # noqa: E402
from mxnet_tpu.resilience import chaos, servechaos  # noqa: E402
from mxnet_tpu.serve import (BucketLadder, CompiledPredictor,  # noqa: E402
                             DeadlineExceededError, DynamicBatcher,
                             ModelRegistry, OverloadError, ServeError)

DIM = 12
BUCKETS = (1, 2, 4)

failures = []       # human-readable assertion failures
all_futures = []    # every future any scenario accepted (strand sweep)
faults = 0          # injections actually fired
recovered = 0       # scenarios that fully recovered


def check(ok, msg):
    if not ok:
        failures.append(msg)
    return ok


def build_model(seed):
    data = sym.var("data")
    net = sym.FullyConnected(data, num_hidden=16, name="h")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=4, name="o")
    rs = np.random.RandomState(seed)
    arg_shapes, _, _ = net.infer_shape(data=(1, DIM))
    params = {n: mx.nd.array(rs.randn(*s).astype(np.float32) * 0.1)
              for n, s in zip(net.list_arguments(), arg_shapes)
              if n != "data"}
    return net, params


class RungRefs:
    """Bit-exact references: the request's rows zero-padded through
    the EAGER executor at every rung the batch could have landed on
    (tests/test_serve.py proves pad-invariance separately, so only
    the rung can change the bits)."""

    def __init__(self, net, params):
        self._net, self._params, self._execs = net, params, {}

    def refs(self, x):
        out = []
        for b in BUCKETS:
            if b < x.shape[0]:
                continue
            ex = self._execs.get(b)
            if ex is None:
                args = dict(self._params)
                args["data"] = mx.nd.array(np.zeros((b, DIM), np.float32))
                ex = self._net.bind(mx.cpu(), args)
                self._execs[b] = ex
            padded = np.zeros((b, DIM), np.float32)
            padded[:x.shape[0]] = x
            ex.arg_dict["data"][:] = mx.nd.array(padded)
            out.append(ex.forward()[0].asnumpy()[:x.shape[0]].copy())
        return out

    def matches(self, out, x):
        return any(np.array_equal(out, r) for r in self.refs(x))


def wait_for(pred, timeout, what):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.005)   # don't busy-spin against the threads under test
    failures.append("timed out after %ss waiting for %s" % (timeout, what))
    return False


def counter_value(name):
    snap = obs_metrics.snapshot().get(name)
    return snap["value"] if snap else 0


def scenario_overload(pred, refs):
    """Slow dispatches (armed via the MXNET_CHAOS ENV spec — the
    production wire format) + a 3-request queue cap: overload sheds
    typed at submit, every accepted request completes bit-equal."""
    global faults, recovered
    chaos.reset()
    os.environ["MXNET_CHAOS"] = "slow_dispatch_ms=30"
    b = DynamicBatcher(pred, max_wait_ms=1, max_queue=3,
                       name="overload")
    try:
        shed_before = counter_value("serve_requests_shed_total")
        rs = np.random.RandomState(1)
        accepted, sheds = [], 0
        for _ in range(24):
            x = rs.randn(1, DIM).astype(np.float32)
            try:
                accepted.append((x, b.submit(x)))
            except OverloadError:
                sheds += 1
        all_futures.extend(f for _, f in accepted)
        check(sheds > 0, "overload: queue cap never shed (24 submits, "
                         "cap 3, 30ms dispatches)")
        check(b.queue_depth <= 3, "overload: queue depth %d exceeded "
                                  "its cap" % b.queue_depth)
        ok = True
        for x, fut in accepted:
            try:
                out = fut.result(30)[0]
            except Exception as e:
                ok = check(False, "overload: accepted request failed: "
                                  "%r" % (e,))
                continue
            if not refs.matches(out, x):
                ok = check(False, "overload: accepted request not "
                                  "bit-equal at any rung")
        shed_delta = counter_value("serve_requests_shed_total") \
            - shed_before
        check(shed_delta == sheds,
              "overload: serve_requests_shed_total moved %d for %d "
              "typed sheds" % (shed_delta, sheds))
        # every slowed dispatch was an injection through the env spec
        faults += b.batch_count
        if ok and sheds > 0:
            recovered += 1
    finally:
        b.close()
        del os.environ["MXNET_CHAOS"]
        chaos.reset()


def scenario_expiry(pred, refs):
    """A wedged dispatcher (dispatch_hang_at) holds the queue while a
    deadlined request expires: typed DeadlineExceededError, and the
    expired payload provably never dispatched."""
    global faults, recovered
    chaos.configure(dispatch_hang_at=1)
    servechaos.reset_hangs()
    dispatched_tags = []
    real = pred.predict

    def spy(data, key=None, **kw):
        arr = data["data"] if isinstance(data, dict) else data
        dispatched_tags.extend(np.asarray(arr)[:, 0].tolist())
        return real(data, key=key, **kw)

    pred.predict = spy
    b = DynamicBatcher(pred, max_wait_ms=1, name="expiry")
    try:
        expired_before = counter_value("serve_requests_expired_total")

        def tagged(tag):
            x = np.zeros((1, DIM), np.float32)
            x[0, 0] = tag
            return x

        filler = tagged(111.0)
        f_filler = b.submit(filler)
        all_futures.append(f_filler)
        if not wait_for(lambda: chaos.fired("dispatch_hang_at") == 1,
                        10, "the dispatcher to wedge"):
            return
        doomed = tagged(222.0)
        f_doomed = b.submit(doomed, deadline_ms=60)
        survivor = tagged(333.0)
        f_survivor = b.submit(survivor)
        all_futures.extend([f_doomed, f_survivor])
        time.sleep(0.12)                # the deadline passes, wedged
        servechaos.release_hangs()
        ok = True
        try:
            f_doomed.result(10)
            ok = check(False, "expiry: the deadlined request resolved "
                              "with a result instead of expiring")
        except DeadlineExceededError:
            pass
        except Exception as e:
            ok = check(False, "expiry: wrong error type %r" % (e,))
        for x, fut, who in ((filler, f_filler, "filler"),
                            (survivor, f_survivor, "survivor")):
            try:
                out = fut.result(10)[0]
                if not refs.matches(out, x):
                    ok = check(False, "expiry: %s not bit-equal" % who)
            except Exception as e:
                ok = check(False, "expiry: %s failed: %r" % (who, e))
        if 222.0 in dispatched_tags:
            ok = check(False, "expiry: the EXPIRED request's payload "
                              "reached a dispatch: %s" % dispatched_tags)
        check(111.0 in dispatched_tags and 333.0 in dispatched_tags,
              "expiry: expected payloads missing from dispatches: %s"
              % dispatched_tags)
        expired_delta = counter_value("serve_requests_expired_total") \
            - expired_before
        check(expired_delta == 1,
              "expiry: serve_requests_expired_total moved %d, want 1"
              % expired_delta)
        faults += chaos.fired("dispatch_hang_at")
        if ok:
            recovered += 1
    finally:
        servechaos.release_hangs()
        servechaos.reset_hangs()
        pred.predict = real
        b.close()
        chaos.reset()


def scenario_crash(pred, refs):
    """dispatch_raise_at escapes the loop: exactly the failing
    batch's futures get the error, the dispatcher restarts, the next
    batch serves."""
    global faults, recovered
    chaos.configure(dispatch_raise_at=2)
    b = DynamicBatcher(pred, max_wait_ms=1, name="crash")
    try:
        restarts_before = counter_value("serve_dispatcher_restarts_total")
        rs = np.random.RandomState(2)
        x1 = rs.randn(1, DIM).astype(np.float32)
        f1 = b.submit(x1)
        all_futures.append(f1)
        ok = True
        try:
            if not refs.matches(f1.result(30)[0], x1):
                ok = check(False, "crash: pre-crash batch not bit-equal")
        except Exception as e:
            ok = check(False, "crash: pre-crash batch failed: %r" % (e,))
        x2 = rs.randn(1, DIM).astype(np.float32)
        f2 = b.submit(x2)
        all_futures.append(f2)
        try:
            f2.result(30)
            ok = check(False, "crash: the crashing batch resolved with "
                              "a result")
        except RuntimeError as e:
            if "servechaos" not in str(e):
                ok = check(False, "crash: wrong error %r" % (e,))
        except Exception as e:
            ok = check(False, "crash: wrong error type %r" % (e,))
        if not wait_for(lambda: b.dispatcher_alive(), 10,
                        "the dispatcher to restart"):
            return
        check(b.restart_count == 1,
              "crash: restart_count %d, want 1" % b.restart_count)
        x3 = rs.randn(2, DIM).astype(np.float32)
        f3 = b.submit(x3)
        all_futures.append(f3)
        try:
            if not refs.matches(f3.result(30)[0], x3):
                ok = check(False, "crash: post-restart batch not "
                                  "bit-equal")
        except Exception as e:
            ok = check(False, "crash: post-restart batch failed: %r"
                       % (e,))
        restarts_delta = \
            counter_value("serve_dispatcher_restarts_total") \
            - restarts_before
        check(restarts_delta == 1,
              "crash: serve_dispatcher_restarts_total moved %d, want 1"
              % restarts_delta)
        faults += chaos.fired("dispatch_raise_at")
        if ok:
            recovered += 1
    finally:
        b.close()
        chaos.reset()


def scenario_unhealthy(reg):
    """Crashes past the restart budget: unhealthy, typed sheds,
    probes flip false, teardown still works."""
    global faults, recovered
    net, params = build_model(seed=3)
    reg.load("crashy", net, params, data_shapes={"data": (1, DIM)},
             ladder=BucketLadder(batches=BUCKETS))
    chaos.configure(dispatch_raise_at=1, dispatch_raise_for=10)
    b = reg.batcher("crashy", max_wait_ms=1, max_restarts=1)
    try:
        x = np.ones((1, DIM), np.float32)
        f1 = reg.submit("crashy", x)
        all_futures.append(f1)
        ok = True
        try:
            f1.result(30)
            ok = check(False, "unhealthy: crashing batch resolved")
        except (RuntimeError, ServeError):
            pass
        if not wait_for(lambda: b.restart_count >= 1 and
                        b.dispatcher_alive(), 10,
                        "the first crash-restart"):
            return
        # the restarted dispatcher crashes again on the next batch —
        # past the 1-restart budget, the batcher goes unhealthy
        f2 = reg.submit("crashy", x)
        all_futures.append(f2)
        try:
            f2.result(30)
            ok = check(False, "unhealthy: post-budget submit "
                              "resolved with a result")
        except (RuntimeError, ServeError):
            pass
        if not wait_for(lambda: b.unhealthy, 10,
                        "the batcher to exhaust its restart budget"):
            return
        try:
            reg.submit("crashy", x)
            ok = check(False, "unhealthy: submit to an unhealthy "
                              "batcher did not shed")
        except ServeError:
            pass
        check(b.health_state() == "unhealthy",
              "unhealthy: health_state %r" % b.health_state())
        check(reg.health("crashy")["state"] == "unhealthy",
              "unhealthy: registry health %r"
              % reg.health("crashy")["state"])
        check(reg.ready("crashy") is False,
              "unhealthy: ready() still true")
        check(reg.live() is False, "unhealthy: live() still true")
        faults += chaos.fired("dispatch_raise_at")
        reg.unload("crashy", drain=False)
        check(reg.live() is True,
              "unhealthy: live() still false after unload")
        if ok:
            recovered += 1
    finally:
        chaos.reset()
        if "crashy" in reg.names():
            reg.unload("crashy", drain=False)


def scenario_liveness(reg):
    """A wedged dispatch with work queued goes stale on the health
    surface; releasing it recovers, and both requests land."""
    global faults, recovered
    net, params = build_model(seed=4)
    refs = RungRefs(net, params)
    reg.load("hangy", net, params, data_shapes={"data": (1, DIM)},
             ladder=BucketLadder(batches=BUCKETS))
    chaos.configure(dispatch_hang_at=1)
    servechaos.reset_hangs()
    reg.batcher("hangy", max_wait_ms=1)
    try:
        rs = np.random.RandomState(5)
        x1 = rs.randn(1, DIM).astype(np.float32)
        f1 = reg.submit("hangy", x1)
        all_futures.append(f1)
        if not wait_for(lambda: chaos.fired("dispatch_hang_at") == 1,
                        10, "the dispatcher to wedge"):
            return
        x2 = rs.randn(1, DIM).astype(np.float32)
        f2 = reg.submit("hangy", x2)      # queued behind the wedge
        all_futures.append(f2)
        time.sleep(0.25)
        ok = check(reg.live(max_tick_age=0.2) is False,
                   "liveness: a wedged dispatcher with queued work "
                   "still probes live")
        health = reg.health("hangy")
        check(health["queue_depth"] >= 1,
              "liveness: queue_depth %d with a request queued behind "
              "the wedge" % health["queue_depth"])
        servechaos.release_hangs()
        for x, fut, who in ((x1, f1, "wedged"), (x2, f2, "queued")):
            try:
                out = fut.result(30)[0]
                if not refs.matches(out, x):
                    ok = check(False, "liveness: %s request not "
                                      "bit-equal" % who)
            except Exception as e:
                ok = check(False, "liveness: %s request failed: %r"
                           % (who, e))
        if not wait_for(lambda: reg.live(max_tick_age=5.0), 10,
                        "liveness to recover after release"):
            return
        faults += chaos.fired("dispatch_hang_at")
        if ok:
            recovered += 1
    finally:
        servechaos.release_hangs()
        servechaos.reset_hangs()
        chaos.reset()
        reg.unload("hangy", drain=False)


def scenario_drain(reg):
    """unload(drain=True) under concurrent submit load with slow
    dispatches: every ACCEPTED request completes bit-equal at some
    rung, later submits shed typed, nothing hangs."""
    global faults, recovered
    net, params = build_model(seed=6)
    refs = RungRefs(net, params)
    reg.load("prime", net, params, data_shapes={"data": (1, DIM)},
             ladder=BucketLadder(batches=BUCKETS))
    chaos.configure(slow_dispatch_ms=20)
    b = reg.batcher("prime", max_wait_ms=1)
    drains_before = counter_value("serve_drains_total")
    rs = np.random.RandomState(7)
    pool = [rs.randn(1, DIM).astype(np.float32) for _ in range(8)]
    accepted, untyped = [], []
    stop = threading.Event()

    def writer(tid):
        i = 0
        while not stop.is_set():
            x = pool[(tid + i) % len(pool)]
            i += 1
            try:
                accepted.append((x, reg.submit("prime", x)))
            except ServeError:
                pass                    # draining / unloaded: typed
            except Exception as e:
                untyped.append(repr(e))
                return

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(3)]
    for t in threads:
        t.start()
    try:
        time.sleep(0.15)                # queue backs up behind 20ms
        reg.unload("prime")             # drain=True default
        stop.set()
        for t in threads:
            t.join(10)
            check(not t.is_alive(), "drain: a writer thread hung")
        all_futures.extend(f for _, f in accepted)
        check(untyped == [], "drain: untyped writer errors: %s"
              % untyped[:3])
        ok = True
        completed = 0
        for x, fut in accepted:
            try:
                out = fut.result(10)[0]
            except ServeError:
                continue                # shed/closed: typed is fine
            except Exception as e:
                ok = check(False, "drain: untyped failure %r" % (e,))
                continue
            completed += 1
            if not refs.matches(out, x):
                ok = check(False, "drain: accepted request not "
                                  "bit-equal at any rung")
        check(completed >= 1, "drain: no request completed (%d "
                              "accepted)" % len(accepted))
        drains_delta = counter_value("serve_drains_total") \
            - drains_before
        check(drains_delta == 1,
              "drain: serve_drains_total moved %d, want 1"
              % drains_delta)
        faults += b.batch_count         # every dispatch was slowed
        if ok and completed >= 1:
            recovered += 1
    finally:
        stop.set()
        chaos.reset()
        if "prime" in reg.names():
            reg.unload("prime", drain=False)


def scenario_warm(reg):
    """reject_warm_at fails a load mid-warm: the model never
    half-registers; the retried load serves."""
    global faults, recovered
    net, params = build_model(seed=8)
    chaos.configure(reject_warm_at=2)   # the 2nd program build dies
    ok = True
    try:
        reg.load("flaky", net, params, data_shapes={"data": (1, DIM)},
                 ladder=BucketLadder(batches=BUCKETS))
        ok = check(False, "warm: injected warm failure did not raise")
    except ServeError:
        pass
    check("flaky" not in reg.names(),
          "warm: a failed load half-registered the model")
    check(reg.ready("flaky") is False,
          "warm: a failed load left a health entry")
    faults += chaos.fired("reject_warm_at")
    chaos.reset()
    reg.load("flaky", net, params, data_shapes={"data": (1, DIM)},
             ladder=BucketLadder(batches=BUCKETS))
    refs = RungRefs(net, params)
    x = np.random.RandomState(9).randn(1, DIM).astype(np.float32)
    fut = reg.submit("flaky", x)
    all_futures.append(fut)
    try:
        if not refs.matches(fut.result(30)[0], x):
            ok = check(False, "warm: retried load serves wrong bits")
    except Exception as e:
        ok = check(False, "warm: retried load failed to serve: %r"
                   % (e,))
    check(reg.ready("flaky") is True, "warm: retried load not ready")
    reg.unload("flaky", drain=False)
    if ok:
        recovered += 1


def check_health_trail():
    """The state machine walked its full cycle, replayable from
    events.jsonl."""
    evs = obs_events.read_events()
    trails = {}
    for e in evs:
        if e.get("ev") == "serve" and e.get("kind") == "health":
            trails.setdefault(e["model"], []).append(e["state"])
    prime = trails.get("prime", [])
    for a, b in (("loading", "warming"), ("warming", "ready"),
                 ("ready", "draining")):
        if not (a in prime and b in prime and
                prime.index(a) < prime.index(b)):
            failures.append("health trail for 'prime' lacks %s->%s: %s"
                            % (a, b, prime))
    crashy = trails.get("crashy", [])
    if "unhealthy" not in crashy:
        failures.append("health trail for 'crashy' lacks unhealthy: %s"
                        % crashy)
    kinds = {e.get("kind") for e in evs if e.get("ev") == "serve"}
    for kind in ("shed", "expired", "dispatcher_restart", "unhealthy",
                 "drain", "load_failed", "health"):
        if kind not in kinds:
            failures.append("serve event kind %r never recorded "
                            "(have %s)" % (kind, sorted(kinds)))


def check_no_stranded():
    """Every future any scenario accepted resolved — with a result or
    a typed error, never a hang."""
    stranded = 0
    for fut in all_futures:
        if not fut._event.wait(5):
            stranded += 1
    if stranded:
        failures.append("%d of %d accepted futures never resolved"
                        % (stranded, len(all_futures)))


def main():
    t0 = time.monotonic()
    obs_events.configure(path=os.environ["MXNET_OBS_PATH"])
    net, params = build_model(seed=0)
    pred = CompiledPredictor(net, params,
                             data_shapes={"data": (1, DIM)},
                             ladder=BucketLadder(batches=BUCKETS),
                             name="shared")
    pred.warm()
    refs = RungRefs(net, params)
    reg = ModelRegistry()
    try:
        scenario_overload(pred, refs)
        scenario_expiry(pred, refs)
        scenario_crash(pred, refs)
        scenario_unhealthy(reg)
        scenario_liveness(reg)
        scenario_drain(reg)
        scenario_warm(reg)
    finally:
        chaos.reset()
        reg.close()
    check_no_stranded()
    check_health_trail()
    for f in failures:
        print("serve chaos FAILURE: %s" % f, file=sys.stderr)
    print("servechaos: faults=%d recovered=%d/7 futures=%d %.1fs %s"
          % (faults, recovered, len(all_futures),
             time.monotonic() - t0, "FAIL" if failures else "ok"))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
