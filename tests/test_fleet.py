"""Serving fleet tests — replica RPC surface, router failover,
circuit breaker, hedging, rolling deploy, compile-cache warm start.

Everything here runs in-process (real ReplicaServers on ephemeral
ports, scripted fake replicas for the transport-fault drills);
ci/fleet_chaos_drill.py is the real multi-process counterpart."""

import json
import os
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import model as model_mod
from mxnet_tpu import sym
from mxnet_tpu._kvstore_impl import (_connect_retry, _frame_bytes,
                                     _recv_frame, _send_frame)
from mxnet_tpu.observability import events as obs_events
from mxnet_tpu.serve import (BucketLadder, CircuitBreaker, ModelRegistry,
                             ReplicaDraining, ReplicaServer, Router,
                             ServeError)
from mxnet_tpu.serve import replica as replica_mod
from mxnet_tpu.serve.fleet import parse_exposition
from mxnet_tpu.serve.replica import (MSG_CANCEL, MSG_DRAIN, MSG_LOAD,
                                     MSG_PREDICT, MSG_REPLY, MSG_STATS)

DIM = 6
BATCHES = (1, 2)


def _mlp(hidden=8):
    data = sym.var("data")
    net = sym.FullyConnected(data, num_hidden=hidden, name="h")
    return sym.softmax(net)


def _params_for(net, seed=0):
    rs = np.random.RandomState(seed)
    arg_shapes, _, _ = net.infer_shape(data=(1, DIM))
    return {n: mx.nd.array(rs.randn(*s).astype(np.float32) * 0.1)
            for n, s in zip(net.list_arguments(), arg_shapes)
            if n != "data"}


def _eager_refs(net, params, x):
    """x's rows zero-padded through the eager forward at every rung
    they could have been coalesced onto (the test_serve discipline)."""
    refs = []
    rows = x.shape[0]
    for b in BATCHES:
        if b < rows:
            continue
        padded = np.zeros((b, DIM), x.dtype)
        padded[:rows] = x
        args = dict(params)
        args["data"] = mx.nd.array(padded)
        ex = net.bind(mx.cpu(), args)
        refs.append(ex.forward()[0].asnumpy()[:rows])
    return refs


def _matches(out, refs):
    return any(np.array_equal(out, r) for r in refs)


def _rpc(sock, kind, meta, tensors=()):
    _send_frame(sock, kind, meta, tensors)
    k, m, t = _recv_frame(sock)
    assert k == MSG_REPLY
    return m, [np.array(x) for x in t]


def _connect(port):
    s = _connect_retry("127.0.0.1", port, time.monotonic() + 10)
    s.settimeout(30)
    return s


def _dead_port():
    """A port with nothing listening (dead-at-connect)."""
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class FakeReplica:
    """Scripted wire-level replica for transport-fault drills:
    ``dead_mid_reply`` reads the request then closes;
    ``torn_reply`` sends a half frame then closes;
    ``slow_ok`` answers PREDICT with canned tensors after a delay
    (and everything else with a bare ok) — the hedging straggler."""

    def __init__(self, behavior, reply=None, delay=0.0):
        self.behavior = behavior
        self.reply = reply
        self.delay = delay
        self.kinds = []         # every message kind received
        self._stop = threading.Event()
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("127.0.0.1", 0))
        self.port = self.sock.getsockname()[1]
        self.sock.listen(8)
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        self.sock.settimeout(0.2)
        while not self._stop.is_set():
            try:
                conn, _ = self.sock.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            t = threading.Thread(target=self._serve, args=(conn,),
                                 daemon=True)
            t.start()

    def _serve(self, conn):
        try:
            while True:
                kind, meta, tensors = _recv_frame(conn)
                self.kinds.append(kind)
                if self.behavior == "dead_mid_reply":
                    conn.close()
                    return
                if self.behavior == "torn_reply":
                    frame = _frame_bytes(
                        MSG_REPLY, {"status": "ok", "outputs": 1},
                        [np.zeros((1, DIM), np.float32)])
                    conn.sendall(frame[:12])
                    conn.close()
                    return
                # slow_ok
                if kind == MSG_PREDICT:
                    time.sleep(self.delay)
                    conn.sendall(_frame_bytes(
                        MSG_REPLY, {"status": "ok", "outputs": 1},
                        [self.reply]))
                else:
                    conn.sendall(_frame_bytes(MSG_REPLY,
                                              {"status": "ok"}, ()))
        except (ConnectionError, OSError, ValueError):
            return

    def stop(self):
        self._stop.set()
        try:
            self.sock.close()
        except OSError:
            pass


# ---------------------------------------------------------------------------
# shared in-process replica (read-mostly tests reuse it; tests that
# drain/stop things build their own)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def kit(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("fleet_kit")
    net = _mlp()
    params_v1 = _params_for(net, seed=0)
    params_v2 = _params_for(net, seed=1)
    prefix = str(tmp / "m")
    model_mod.save_checkpoint(prefix, 1, net, params_v1, {})
    model_mod.save_checkpoint(prefix, 2, net, params_v2, {})
    return {"net": net, "params_v1": params_v1, "params_v2": params_v2,
            "prefix": prefix, "tmp": tmp}


@pytest.fixture(scope="module")
def live_replica(kit):
    registry = ModelRegistry()
    registry.load("m", kit["net"], kit["params_v1"],
                  data_shapes={"data": (1, DIM)},
                  ladder=BucketLadder(batches=BATCHES))
    registry.batcher("m", max_wait_ms=1.0)
    rep = ReplicaServer(registry, http_port=0).start()
    yield rep
    rep.stop()
    registry.close()


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------

class TestCircuitBreaker:
    def test_open_half_open_close_cycle(self):
        clk = [0.0]
        b = CircuitBreaker(failures=2, cooldown=1.0,
                           clock=lambda: clk[0])
        assert b.state == "closed" and b.allow()
        b.record_failure()
        assert b.state == "closed" and b.allow()
        b.record_failure()
        assert b.state == "open"
        assert not b.allow()
        clk[0] += 0.5
        assert not b.allow()            # still cooling
        clk[0] += 0.6
        assert b.state == "half_open"
        assert b.allow()                # the ONE trial
        assert not b.allow()            # trial in flight
        b.record_success()
        assert b.state == "closed" and b.allow()

    def test_half_open_failure_reopens(self):
        clk = [0.0]
        b = CircuitBreaker(failures=1, cooldown=1.0,
                           clock=lambda: clk[0])
        b.record_failure()
        assert b.state == "open"
        clk[0] += 1.1
        assert b.allow()
        b.record_failure()              # trial failed
        assert b.state == "open"
        assert not b.allow()
        clk[0] += 1.1
        assert b.allow()
        b.record_success()
        assert b.state == "closed"

    def test_force_open_ejection(self):
        clk = [0.0]
        b = CircuitBreaker(failures=5, cooldown=1.0,
                           clock=lambda: clk[0])
        b.force_open()
        assert b.state == "open" and not b.allow()
        clk[0] += 1.1
        assert b.allow()                # half-open rejoin trial


# ---------------------------------------------------------------------------
# replica RPC surface
# ---------------------------------------------------------------------------

class TestReplicaRPC:
    def test_predict_roundtrip_bit_equal(self, kit, live_replica):
        rs = np.random.RandomState(7)
        x = rs.randn(2, DIM).astype(np.float32)
        refs = _eager_refs(kit["net"], kit["params_v1"], x)
        s = _connect(live_replica.port)
        try:
            meta, outs = _rpc(s, MSG_PREDICT,
                              {"model": "m", "inputs": ["data"],
                               "req": ["t-rt", 1, 1]}, [x])
        finally:
            s.close()
        assert meta["status"] == "ok"
        assert _matches(outs[0], refs)

    def test_idempotent_retry_exactly_once(self, live_replica):
        rs = np.random.RandomState(8)
        x = rs.randn(1, DIM).astype(np.float32)
        meta = {"model": "m", "inputs": ["data"],
                "req": ["t-idem", 1, 1]}
        s = _connect(live_replica.port)
        try:
            m1, o1 = _rpc(s, MSG_PREDICT, meta, [x])
            before = live_replica.predicts_dispatched
            m2, o2 = _rpc(s, MSG_PREDICT, meta, [x])    # retried id
        finally:
            s.close()
        assert m1["status"] == "ok" and m2["status"] == "ok"
        assert m2.get("dup") is True and "dup" not in m1
        # exactly-once: the duplicate answered from the window, the
        # dispatch counter did not move, and the bits are identical
        assert live_replica.predicts_dispatched == before
        assert np.array_equal(o1[0], o2[0])

    def test_retry_on_fresh_connection_still_dedups(self, live_replica):
        rs = np.random.RandomState(9)
        x = rs.randn(1, DIM).astype(np.float32)
        meta = {"model": "m", "inputs": ["data"],
                "req": ["t-idem2", 5, 3]}
        s1 = _connect(live_replica.port)
        try:
            m1, o1 = _rpc(s1, MSG_PREDICT, meta, [x])
        finally:
            s1.close()      # the router reconnects on retry
        before = live_replica.predicts_dispatched
        s2 = _connect(live_replica.port)
        try:
            m2, o2 = _rpc(s2, MSG_PREDICT, meta, [x])
        finally:
            s2.close()
        assert m2.get("dup") is True
        assert live_replica.predicts_dispatched == before
        assert np.array_equal(o1[0], o2[0])

    def test_cancel_pins_window(self, live_replica):
        """A CANCEL for an id that never arrived pins the window: a
        LATE arrival of that id answers 'cancelled' from cache and is
        never dispatched (the hedge-loser contract)."""
        rs = np.random.RandomState(10)
        x = rs.randn(1, DIM).astype(np.float32)
        req = ["t-cancel", 1, 1]
        s = _connect(live_replica.port)
        try:
            m, _ = _rpc(s, MSG_CANCEL, {"req": req})
            assert m["status"] == "ok"
            before = live_replica.predicts_dispatched
            m2, _ = _rpc(s, MSG_PREDICT,
                         {"model": "m", "inputs": ["data"],
                          "req": req}, [x])
        finally:
            s.close()
        assert m2["status"] == "err" and m2["code"] == "cancelled"
        assert live_replica.predicts_dispatched == before

    def test_stats_rpc(self, live_replica):
        s = _connect(live_replica.port)
        try:
            m, _ = _rpc(s, MSG_STATS, {})
        finally:
            s.close()
        assert m["status"] == "ok"
        assert m["predicts_dispatched"] >= 1
        assert m["compile_count"] == {"m": len(BATCHES)}

    def test_unknown_model_typed(self, live_replica):
        s = _connect(live_replica.port)
        try:
            m, _ = _rpc(s, MSG_PREDICT,
                        {"model": "ghost", "inputs": ["data"],
                         "req": ["t-ghost", 1, 1]},
                        [np.zeros((1, DIM), np.float32)])
        finally:
            s.close()
        assert m["status"] == "err" and m["code"] == "serve"


# ---------------------------------------------------------------------------
# HTTP probe endpoint
# ---------------------------------------------------------------------------

class TestHttpProbe:
    def _get(self, port, path):
        try:
            with urllib.request.urlopen(
                    "http://127.0.0.1:%d%s" % (port, path),
                    timeout=10) as r:
                return r.status, r.read().decode()
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode()

    def test_metrics_exposition(self, live_replica):
        status, body = self._get(live_replica.http_port, "/metrics")
        assert status == 200
        parsed = parse_exposition(body)
        assert "mxnet_serve_requests_total" in parsed
        assert "mxnet_fleet_replica_requests_total" in parsed

    def test_healthz_readyz(self, live_replica):
        status, body = self._get(live_replica.http_port, "/healthz")
        assert status == 200 and json.loads(body)["live"] is True
        status, body = self._get(live_replica.http_port, "/readyz")
        assert status == 200
        payload = json.loads(body)
        assert payload["ready"] is True
        assert payload["models"] == {"m": "ready"}

    def test_unknown_path_404(self, live_replica):
        status, _ = self._get(live_replica.http_port, "/nope")
        assert status == 404


# ---------------------------------------------------------------------------
# router failover
# ---------------------------------------------------------------------------

class TestRouterFailover:
    def test_dead_at_connect(self, kit, live_replica):
        router = Router([("127.0.0.1", _dead_port()),
                         ("127.0.0.1", live_replica.port)],
                        probe=False, retries=3)
        try:
            rs = np.random.RandomState(11)
            x = rs.randn(1, DIM).astype(np.float32)
            out = router.predict("m", {"data": x})
            assert _matches(out[0], _eager_refs(kit["net"],
                                                kit["params_v1"], x))
        finally:
            router.close()

    def test_dead_mid_reply(self, kit, live_replica):
        fake = FakeReplica("dead_mid_reply")
        router = Router([("127.0.0.1", fake.port),
                         ("127.0.0.1", live_replica.port)],
                        probe=False, retries=3)
        try:
            rs = np.random.RandomState(12)
            x = rs.randn(2, DIM).astype(np.float32)
            out = router.predict("m", {"data": x})
            assert _matches(out[0], _eager_refs(kit["net"],
                                                kit["params_v1"], x))
            assert MSG_PREDICT in fake.kinds    # it really was tried
        finally:
            router.close()
            fake.stop()

    def test_torn_reply_frame(self, kit, live_replica):
        fake = FakeReplica("torn_reply")
        router = Router([("127.0.0.1", fake.port),
                         ("127.0.0.1", live_replica.port)],
                        probe=False, retries=3)
        try:
            rs = np.random.RandomState(13)
            x = rs.randn(1, DIM).astype(np.float32)
            out = router.predict("m", {"data": x})
            assert _matches(out[0], _eager_refs(kit["net"],
                                                kit["params_v1"], x))
        finally:
            router.close()
            fake.stop()

    def test_all_dead_typed_error(self):
        router = Router([("127.0.0.1", _dead_port()),
                         ("127.0.0.1", _dead_port())],
                        probe=False, retries=3)
        try:
            with pytest.raises(ServeError):
                router.predict("m", np.zeros((1, DIM), np.float32))
        finally:
            router.close()

    def test_breaker_opens_after_repeated_failures(self, live_replica):
        dead = ("127.0.0.1", _dead_port())
        router = Router([dead, ("127.0.0.1", live_replica.port)],
                        probe=False, retries=2)
        try:
            rs = np.random.RandomState(14)
            # round-robin only offers the dead replica every other
            # request; 6 predicts guarantee >= 3 transport failures
            for _ in range(6):
                router.predict("m", rs.randn(1, DIM).astype(np.float32))
            handles = router.replicas()
            dead_handle = handles["%s:%d" % dead]
            assert dead_handle.breaker.state in ("open", "half_open")
        finally:
            router.close()


# ---------------------------------------------------------------------------
# heartbeat ejection / rejoin
# ---------------------------------------------------------------------------

class TestEjectRejoin:
    def test_eject_on_staleness_then_rejoin(self, live_replica):
        # second server over the SAME (warm) registry — stopping it
        # does not touch the module fixture
        rep2 = ReplicaServer(live_replica.registry, http_port=0).start()
        router = Router([("127.0.0.1", rep2.port)], probe=False,
                        eject_timeout=0.2, probe_interval=0.05)
        try:
            router.probe_once()
            handle = next(iter(router.replicas().values()))
            assert handle.eligible("m")
            port = rep2.port
            rep2.stop()
            time.sleep(0.3)
            router.probe_once()     # stale past the eject timeout
            assert handle.ejected and not handle.eligible("m")
            assert handle.breaker.state in ("open", "half_open")
            # same port comes back (the replica process restarted)
            rep3 = ReplicaServer(live_replica.registry,
                                 port=port, http_port=0).start()
            try:
                deadline = time.monotonic() + 5
                while handle.ejected and time.monotonic() < deadline:
                    router.probe_once()
                    time.sleep(0.05)
                assert not handle.ejected
                assert handle.eligible("m")
            finally:
                rep3.stop()
        finally:
            router.close()


# ---------------------------------------------------------------------------
# hedging
# ---------------------------------------------------------------------------

class TestHedging:
    def test_hedge_wins_and_loser_cancelled(self, kit, live_replica):
        """Primary is a straggler: the hedge fires after
        MXNET_SERVE_HEDGE_MS, the fast secondary's typed answer wins,
        the loser gets a CANCEL through the idempotency window, and
        each replica saw the request AT MOST once."""
        canned = np.full((1, DIM), 99.0, np.float32)
        fake = FakeReplica("slow_ok", reply=canned, delay=1.0)
        router = Router([("127.0.0.1", fake.port),
                         ("127.0.0.1", live_replica.port)],
                        probe=False, hedge_ms=40, retries=3)
        try:
            before_real = live_replica.requests_received
            rs = np.random.RandomState(15)
            x = rs.randn(1, DIM).astype(np.float32)
            out = router.predict("m", {"data": x})
            # the REAL replica's answer won, not the straggler's
            assert _matches(out[0], _eager_refs(kit["net"],
                                                kit["params_v1"], x))
            assert not np.array_equal(out[0], canned)
            from mxnet_tpu.observability import metrics as obs_metrics
            assert obs_metrics.snapshot()[
                "fleet_requests_hedged_total"]["value"] >= 1
            # at most one dispatch per replica
            assert live_replica.requests_received == before_real + 1
            assert fake.kinds.count(MSG_PREDICT) == 1
            # the loser is cancelled through the window (best-effort
            # async — wait for it)
            deadline = time.monotonic() + 5
            while MSG_CANCEL not in fake.kinds and \
                    time.monotonic() < deadline:
                time.sleep(0.02)
            assert MSG_CANCEL in fake.kinds
        finally:
            router.close()
            fake.stop()

    def test_no_hedge_when_primary_fast(self, live_replica):
        fake = FakeReplica("slow_ok",
                           reply=np.zeros((1, DIM), np.float32),
                           delay=1.0)
        # live replica first: it answers well inside the hedge delay,
        # so the straggler never sees the request
        router = Router([("127.0.0.1", live_replica.port),
                         ("127.0.0.1", fake.port)],
                        probe=False, hedge_ms=5000, retries=2)
        try:
            from mxnet_tpu.observability import metrics as obs_metrics
            before = obs_metrics.snapshot()[
                "fleet_requests_hedged_total"]["value"]
            rs = np.random.RandomState(16)
            router.predict("m", rs.randn(1, DIM).astype(np.float32))
            assert obs_metrics.snapshot()[
                "fleet_requests_hedged_total"]["value"] == before
            assert MSG_PREDICT not in fake.kinds
        finally:
            router.close()
            fake.stop()


# ---------------------------------------------------------------------------
# rolling deploy (in-process): zero dropped requests under load
# ---------------------------------------------------------------------------

class TestRollingDeploy:
    def test_zero_drop_with_concurrent_submitters(self, kit):
        regs = []
        reps = []
        for _ in range(2):
            reg = ModelRegistry()
            reg.load("m", kit["net"], kit["params_v1"],
                     data_shapes={"data": (1, DIM)},
                     ladder=BucketLadder(batches=BATCHES))
            reg.batcher("m", max_wait_ms=1.0)
            rep = ReplicaServer(reg).start()
            regs.append(reg)
            reps.append(rep)
        router = Router([("127.0.0.1", r.port) for r in reps],
                        probe=False, retries=4)
        rs = np.random.RandomState(17)
        xs = [rs.randn(rs.randint(1, 3), DIM).astype(np.float32)
              for _ in range(8)]
        refs = {i: (_eager_refs(kit["net"], kit["params_v1"], x)
                    + _eager_refs(kit["net"], kit["params_v2"], x))
                for i, x in enumerate(xs)}
        stop = threading.Event()
        failures = []
        answered = [0]
        lock = threading.Lock()

        def submitter(tid):
            n = 0
            while not stop.is_set():
                i = (tid + n) % len(xs)
                n += 1
                try:
                    out = router.predict("m", {"data": xs[i]})
                except Exception as exc:    # noqa: BLE001 - recorded
                    with lock:
                        failures.append("submitter %d: %r" % (tid, exc))
                    return
                if not _matches(out[0], refs[i]):
                    with lock:
                        failures.append(
                            "submitter %d: request %d not bit-equal "
                            "to v1 or v2 at any rung" % (tid, i))
                    return
                with lock:
                    answered[0] += 1

        threads = [threading.Thread(target=submitter, args=(t,),
                                    daemon=True) for t in range(4)]
        try:
            for t in threads:
                t.start()
            time.sleep(0.3)     # traffic flowing
            # rolling deploy: drain -> swap to epoch 2 -> readmit,
            # one replica at a time
            for key in sorted(router.replicas()):
                router.set_draining(key, True)
                stats, _ = router.control(key, MSG_DRAIN,
                                          {"timeout": 10})
                assert stats["timed_out"] is False
                assert stats["waited_requests"] >= 0
                rmeta, _ = router.control(
                    key, MSG_LOAD,
                    {"model": "m", "prefix": kit["prefix"],
                     "epoch": 2, "data_shapes": {"data": [1, DIM]},
                     "batches": list(BATCHES)})
                assert rmeta["status"] == "ok"
                router.set_draining(key, False)
                router.probe_once()
            time.sleep(0.3)     # post-deploy traffic
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=30)
            router.close()
            for rep in reps:
                rep.stop()
            for reg in regs:
                reg.close()
        assert not failures, failures
        assert answered[0] > 20

    def test_draining_replica_rerouted_not_errored(self, kit,
                                                   live_replica):
        """A submit racing the drain gets the distinct 'draining'
        shed code and the router reroutes it instead of surfacing a
        typed error — only when EVERY replica drains does the caller
        see ReplicaDraining."""
        reg2 = ModelRegistry()
        reg2.load("m", kit["net"], kit["params_v1"],
                  data_shapes={"data": (1, DIM)},
                  ladder=BucketLadder(batches=BATCHES))
        reg2.batcher("m", max_wait_ms=1.0)
        rep2 = ReplicaServer(reg2).start()
        router = Router([("127.0.0.1", rep2.port),
                         ("127.0.0.1", live_replica.port)],
                        probe=False, retries=3)
        try:
            router.control("127.0.0.1:%d" % rep2.port, MSG_DRAIN,
                           {"timeout": 5})
            rs = np.random.RandomState(18)
            x = rs.randn(1, DIM).astype(np.float32)
            out = router.predict("m", {"data": x})   # rerouted
            assert _matches(out[0], _eager_refs(kit["net"],
                                                kit["params_v1"], x))
        finally:
            router.close()
            rep2.stop()
            reg2.close()

    def test_drain_resume_returns_replica_to_service(self, kit):
        """The aborted-deploy recovery path: a drained replica
        resumed via DRAIN{resume} serves again (board ready, batcher
        admissions open, replica flag cleared) instead of shedding
        for the rest of its life."""
        reg = ModelRegistry()
        reg.load("m", kit["net"], kit["params_v1"],
                 data_shapes={"data": (1, DIM)},
                 ladder=BucketLadder(batches=BATCHES))
        reg.batcher("m", max_wait_ms=1.0)
        rep = ReplicaServer(reg).start()
        router = Router([("127.0.0.1", rep.port)], probe=False,
                        retries=2)
        try:
            key = "127.0.0.1:%d" % rep.port
            stats, _ = router.control(key, MSG_DRAIN, {"timeout": 5})
            assert stats["timed_out"] is False
            with pytest.raises(ReplicaDraining):
                router.predict("m", np.zeros((1, DIM), np.float32))
            rmeta, _ = router.control(key, MSG_DRAIN, {"resume": True})
            assert rmeta["resumed"] == ["m"]
            assert rep.draining is False
            rs = np.random.RandomState(19)
            x = rs.randn(1, DIM).astype(np.float32)
            out = router.predict("m", {"data": x})
            assert _matches(out[0], _eager_refs(kit["net"],
                                                kit["params_v1"], x))
            assert reg.health("m")["state"] == "ready"
        finally:
            router.close()
            rep.stop()
            reg.close()

    def test_all_draining_surfaces_typed(self, kit):
        reg = ModelRegistry()
        reg.load("m", kit["net"], kit["params_v1"],
                 data_shapes={"data": (1, DIM)},
                 ladder=BucketLadder(batches=BATCHES))
        reg.batcher("m", max_wait_ms=1.0)
        rep = ReplicaServer(reg).start()
        router = Router([("127.0.0.1", rep.port)], probe=False,
                        retries=2)
        try:
            router.control("127.0.0.1:%d" % rep.port, MSG_DRAIN,
                           {"timeout": 5})
            with pytest.raises(ReplicaDraining):
                router.predict("m", np.zeros((1, DIM), np.float32))
        finally:
            router.close()
            rep.stop()
            reg.close()


# ---------------------------------------------------------------------------
# compile-cache warm start
# ---------------------------------------------------------------------------

class TestWarmStart:
    def test_second_load_compiles_zero_programs(self, kit, tmp_path,
                                                monkeypatch):
        """With the shared persistent XLA compile cache, the second
        replica's load hits disk for every program: zero NEW cache
        entries (the fleet's seconds-not-minutes scale-out claim)."""
        cache_dir = str(tmp_path / "cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache_dir)
        from mxnet_tpu.config import enable_compile_cache
        assert enable_compile_cache() == cache_dir
        try:
            reg1 = ModelRegistry()
            reg1.load("wm", kit["net"], kit["params_v1"],
                      data_shapes={"data": (1, DIM)},
                      ladder=BucketLadder(batches=BATCHES))
            first = len(os.listdir(cache_dir))
            assert first > 0        # the first load populated it
            reg2 = ModelRegistry()
            pred2 = reg2.load("wm", kit["net"], kit["params_v1"],
                              data_shapes={"data": (1, DIM)},
                              ladder=BucketLadder(batches=BATCHES))
            assert len(os.listdir(cache_dir)) == first
            assert pred2.compile_count == len(BATCHES)
            reg1.close()
            reg2.close()
        finally:
            monkeypatch.undo()
            enable_compile_cache()      # back to the session's cache


# ---------------------------------------------------------------------------
# drain event satellite (machine-readable drain record)
# ---------------------------------------------------------------------------

class TestDrainEvent:
    def test_drain_complete_event_carries_counts(self, kit, tmp_path,
                                                 monkeypatch):
        path = str(tmp_path / "events.jsonl")
        monkeypatch.setenv("MXNET_OBS", "serve")
        monkeypatch.setenv("MXNET_OBS_PATH", path)
        obs_events.configure()
        try:
            reg = ModelRegistry()
            # two rungs: a 1-row submit does NOT fill the top rung,
            # so the long coalescing window provably parks it in the
            # queue until drain() flips the batcher to dispatch-now
            reg.load("m", kit["net"], kit["params_v1"],
                     data_shapes={"data": (1, DIM)},
                     ladder=BucketLadder(batches=BATCHES))
            reg.batcher("m", max_wait_ms=500.0)
            fut = reg.submit("m", np.zeros((1, DIM), np.float32))
            assert reg.drain("m", timeout=10) is True
            fut.result(10)
            reg.unload("m", drain=True)
            evs = obs_events.read_events(path)
        finally:
            obs_events.configure()
        completes = [e for e in evs if e.get("ev") == "serve"
                     and e.get("kind") == "drain_complete"]
        assert len(completes) == 2      # drain() + unload(drain=True)
        drain_ev = completes[0]
        assert drain_ev["mode"] == "drain"
        assert drain_ev["waited_requests"] == 1
        assert drain_ev["timed_out"] is False
        unload_ev = completes[1]
        assert unload_ev["mode"] == "unload"
        assert unload_ev["timed_out"] is False

    def test_batcher_drain_stats_surface(self, kit):
        reg = ModelRegistry()
        reg.load("m", kit["net"], kit["params_v1"],
                 data_shapes={"data": (1, DIM)},
                 ladder=BucketLadder(batches=(1,)))
        b = reg.batcher("m", max_wait_ms=1.0)
        assert b.last_drain_stats is None
        assert b.drain(timeout=5)
        assert b.last_drain_stats == {"waited_requests": 0,
                                      "timed_out": False}
        reg.unload("m", drain=False)


# ---------------------------------------------------------------------------
# misc plumbing
# ---------------------------------------------------------------------------

def test_parse_exposition():
    text = ("# HELP mxnet_a help\n"
            "# TYPE mxnet_a counter\n"
            "mxnet_a 3\n"
            "mxnet_b 1.5\n"
            "mxnet_h_bucket{le=\"0.1\"} 2\n")
    parsed = parse_exposition(text)
    assert parsed["mxnet_a"] == 3.0
    assert parsed["mxnet_b"] == 1.5


def test_fleet_event_category_registered():
    assert "fleet" in obs_events._CATEGORIES


def test_error_code_mapping():
    from mxnet_tpu.serve.buckets import (DeadlineExceededError,
                                         OverloadError)
    assert replica_mod.error_code(OverloadError("x")) == "overload"
    assert replica_mod.error_code(ReplicaDraining("x")) == "draining"
    assert replica_mod.error_code(
        DeadlineExceededError("x")) == "deadline"
    assert replica_mod.error_code(ValueError("x")) == "internal"
    assert replica_mod.error_class("overload") is OverloadError
    assert replica_mod.error_class("draining") is ReplicaDraining
