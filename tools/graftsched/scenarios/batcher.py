"""DynamicBatcher scenario: submit / cancel / drain / close.

Two submitter threads race the dispatcher and a cancel; the root then
drains and closes.  Invariants checked after every schedule:

* every future resolved (result or typed error) — none left pending
* a successful cancel() implies a RequestCancelled resolution
* queue accounting returns to zero (rows/bytes/pending/inflight)
* close() reports clean (the dispatcher joined)
"""

from __future__ import annotations

import numpy as _np


class _Out:
    def __init__(self, data):
        self._data = data


class _Ladder:
    def __init__(self, top):
        self.max_batch = top

    def batch_for(self, rows):
        return rows


class _FakePredictor:
    """The DynamicBatcher-facing surface of CompiledPredictor, with
    the XLA boundary replaced by numpy (a controlled thread must
    never block in a real device dispatch)."""

    def __init__(self):
        self.name = "sched-batcher"
        self._data_shapes = {"data": (1, 2)}
        self._bucket_inputs = {"data"}
        self.ladder = _Ladder(4)
        self.tuning = None

    def predict(self, feed, trim=True):
        rows = int(feed["data"].shape[0])
        return [_Out(_np.full((rows, 2), 7.0, _np.float32))]


class BatcherScenario:
    name = "batcher"
    budget = 80

    def run(self):
        from mxnet_tpu import sanitizer as _san
        from mxnet_tpu.serve.batcher import DynamicBatcher

        b = DynamicBatcher(_FakePredictor(), max_wait_ms=0, max_batch=0,
                           max_queue=0, max_queue_bytes=0,
                           default_deadline_ms=0, max_restarts=0,
                           tuning={})
        state = {"batcher": b, "outcomes": {}}

        def submit_and_wait(key):
            fut = b.submit(_np.ones((1, 2), _np.float32))
            try:
                res = fut.result(None)
                state["outcomes"][key] = ("ok", res[0].shape)
            except Exception as exc:  # typed shed/cancel — recorded
                state["outcomes"][key] = ("err", type(exc).__name__)

        def submit_and_cancel(key):
            fut = b.submit(_np.ones((1, 2), _np.float32))
            reclaimed = fut.cancel()
            try:
                res = fut.result(None)
                state["outcomes"][key] = ("ok", res[0].shape, reclaimed)
            except Exception as exc:
                state["outcomes"][key] = ("err", type(exc).__name__,
                                          reclaimed)

        t1 = _san.thread(target=submit_and_wait, args=("s1",),
                         name="submit")
        t2 = _san.thread(target=submit_and_cancel, args=("s2",),
                         name="cancel")
        t1.start()
        t2.start()
        t1.join()
        t2.join()
        state["drained"] = b.drain(timeout=30.0)
        state["closed_clean"] = b.close(timeout=30.0)
        return state

    def check(self, state):
        b = state["batcher"]
        out = state["outcomes"]
        assert set(out) == {"s1", "s2"}, out
        # s1 never cancels: it must land (the drain waits for it)
        assert out["s1"][0] == "ok", out
        assert out["s1"][1] == (1, 2), out
        # s2: a successful cancel implies the typed cancelled error;
        # a failed cancel means the request dispatched and resolved ok
        kind = out["s2"][0]
        reclaimed = out["s2"][2]
        if reclaimed:
            assert kind == "err" and out["s2"][1] == "RequestCancelled", \
                out
        else:
            assert kind == "ok", out
        assert state["drained"] is True, state
        assert state["closed_clean"] is True, state
        assert b._rows_pending == 0, b._rows_pending
        assert b._bytes_pending == 0, b._bytes_pending
        assert not b._pending, b._pending
        assert b._inflight == (), b._inflight
