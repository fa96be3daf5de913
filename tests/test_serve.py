"""mxnet_tpu.serve — compiled inference subsystem tests.

Covers the bucket ladder, AOT-per-bucket CompiledPredictor (padded
outputs bit-equal to unpadded eager predict, fp32 + bf16; pad
invariance; one-compile-per-bucket pinning), the donated KV-cache
decode path, the dynamic batcher's coalescing/deadline/error/close
semantics, the multi-model registry, the C-ABI thin client and the
persistent-compilation-cache knob."""

import os
import threading
import time
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serve, sym
from mxnet_tpu.resilience import chaos
from mxnet_tpu.serve import (BucketLadder, CompiledPredictor,
                             DeadlineExceededError, DynamicBatcher,
                             HealthBoard, ModelRegistry, OverloadError,
                             RequestCancelled, ServeError, ServeFuture)


def _mlp(dim=12, hidden=32, classes=4, batchnorm=False):
    data = sym.var("data")
    net = sym.FullyConnected(data, num_hidden=hidden, name="h")
    net = sym.Activation(net, act_type="relu")
    if batchnorm:
        net = sym.BatchNorm(net, name="bn")
    net = sym.FullyConnected(net, num_hidden=classes, name="o")
    return sym.softmax(net)


def _params_for(net, dim, dtype="float32", seed=0, batch=1):
    rs = np.random.RandomState(seed)
    arg_shapes, _, aux_shapes = net.infer_shape(data=(batch, dim))
    params = {n: mx.nd.array(rs.randn(*s).astype(np.float32) * 0.1)
              .astype(dtype)
              for n, s in zip(net.list_arguments(), arg_shapes)
              if n != "data"}
    aux = {n: mx.nd.array(np.abs(rs.randn(*s)).astype(np.float32))
           .astype(dtype)
           for n, s in zip(net.list_auxiliary_states(), aux_shapes)}
    return params, aux


def _eager(net, params, aux, x_nd):
    args = dict(params)
    args["data"] = x_nd
    ex = net.bind(mx.cpu(), args, aux_states=aux or None)
    return ex.forward()[0]


def _rung_refs(net, params, aux, x, batches=(1, 2, 4, 8)):
    """Bit-exact references for a request under dynamic batching: the
    request's rows zero-padded through the eager forward at every rung
    it could have been coalesced onto.  Pad-invariance is proven
    separately, so only the rung (XLA program) can change the bits."""
    rows = x.shape[0]
    refs = []
    for b in batches:
        if b < rows:
            continue
        padded = np.zeros((b,) + x.shape[1:], x.dtype)
        padded[:rows] = x
        refs.append(
            _eager(net, params, aux, mx.nd.array(padded)).asnumpy()[:rows])
    return refs


# ---------------------------------------------------------------------------
# bucket ladder
# ---------------------------------------------------------------------------

class TestBucketLadder:
    def test_batch_for(self):
        lad = BucketLadder(batches=(1, 2, 4, 8))
        assert [lad.batch_for(n) for n in (1, 2, 3, 5, 8)] == \
            [1, 2, 4, 8, 8]

    def test_batch_over_top_rung_raises(self):
        with pytest.raises(ServeError, match="top rung"):
            BucketLadder(batches=(1, 2)).batch_for(3)

    def test_pad_shape_rounds_seq_axes(self):
        lad = BucketLadder(batches=(2, 4), seq_axes={1: 16})
        assert lad.pad_shape((3, 17, 5)) == (4, 32, 5)
        assert lad.pad_shape((2, 16, 5)) == (2, 16, 5)

    def test_seq_max_cap(self):
        lad = BucketLadder(batches=(1,), seq_axes={1: 8},
                           seq_max={1: 16})
        assert lad.pad_shape((1, 9)) == (1, 16)
        with pytest.raises(ServeError, match="cap"):
            lad.pad_shape((1, 17))

    def test_bad_config_raises(self):
        with pytest.raises(ServeError):
            BucketLadder(batches=())
        with pytest.raises(ServeError):
            BucketLadder(batches=(0, 2))
        with pytest.raises(ServeError):
            BucketLadder(seq_axes={0: 8})

    def test_bucket_key_canonical(self):
        lad = BucketLadder()
        k1 = lad.bucket_key({"a": (1, 2), "b": (1, 3)})
        k2 = lad.bucket_key({"b": (1, 3), "a": (1, 2)})
        assert k1 == k2 and hash(k1) == hash(k2)


# ---------------------------------------------------------------------------
# compiled predictor — bucketing correctness
# ---------------------------------------------------------------------------

class TestCompiledPredictor:
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("n", [1, 2, 3, 5, 7, 8])
    def test_padded_bit_equal_unpadded_eager(self, dtype, n):
        """The tentpole contract: predict on inputs padded up to the
        bucket is BIT-identical to the unpadded eager forward at the
        natural batch — across dtypes, through BatchNorm aux."""
        import jax.numpy as jnp
        net = _mlp(batchnorm=True)
        params, aux = _params_for(net, 12, dtype=dtype)
        pred = CompiledPredictor(
            net, params, aux_params=aux, data_shapes={"data": (1, 12)},
            ladder=BucketLadder(batches=(1, 2, 4, 8)),
            data_dtypes={"data": dtype})
        rs = np.random.RandomState(n)
        x = jnp.asarray(rs.randn(n, 12).astype(np.float32)).astype(dtype)
        ref = _eager(net, params, aux, mx.nd.NDArray(x))
        out = pred.predict(np.asarray(x))[0]
        assert tuple(out.shape) == tuple(ref.shape)
        assert bool(jnp.array_equal(out._data, ref._data))

    def test_pad_invariance(self):
        """Mask-off is exact: the co-batch content (zero padding vs
        other requests' garbage rows) cannot change a row's result at
        a fixed bucket."""
        net = _mlp()
        params, aux = _params_for(net, 12)
        pred = CompiledPredictor(
            net, params, aux_params=aux, data_shapes={"data": (1, 12)},
            ladder=BucketLadder(batches=(8,)))
        rs = np.random.RandomState(3)
        x = rs.randn(3, 12).astype(np.float32)
        alone = pred.predict(x)[0].asnumpy()
        stacked = np.concatenate(
            [x, 100.0 * rs.randn(5, 12).astype(np.float32)], axis=0)
        together = pred.predict(stacked)[0].asnumpy()[:3]
        assert np.array_equal(alone, together)

    def test_one_compile_per_bucket_pinned(self):
        net = _mlp()
        params, aux = _params_for(net, 12)
        pred = CompiledPredictor(
            net, params, aux_params=aux, data_shapes={"data": (1, 12)},
            ladder=BucketLadder(batches=(1, 2, 4)))
        assert pred.warm() == 3
        assert pred.compile_count == 3
        rs = np.random.RandomState(0)
        for n in (1, 2, 3, 4, 1, 3, 2, 4):
            pred.predict(rs.randn(n, 12).astype(np.float32))
        assert pred.compile_count == 3          # request path never compiles
        assert pred.jit_cache_size() == 0       # nothing ever traced a call
        assert pred.dispatch_count == 8

    def test_unplanned_seq_shape_compiles_once_on_demand(self):
        net = _mlp()
        params, aux = _params_for(net, 12)
        # no warm: every bucket is demand-compiled, but only ONCE each
        pred = CompiledPredictor(
            net, params, aux_params=aux, data_shapes={"data": (1, 12)},
            ladder=BucketLadder(batches=(2,)))
        rs = np.random.RandomState(0)
        pred.predict(rs.randn(2, 12).astype(np.float32))
        pred.predict(rs.randn(1, 12).astype(np.float32))
        assert pred.compile_count == 1

    def test_seq_axis_bucketing(self):
        """Variable-length axis rounds to its multiple; the padded
        program is bit-identical to the eager forward of the same
        zero-padded input (zero rows are identity for sum-of-relu —
        only the numerically-equivalent reduction order could differ,
        and it must not), values match numpy up to float reassociation,
        and the program count is one per (batch, seq) bucket."""
        data = sym.var("data")
        net = sym.sum(sym.Activation(data, act_type="relu"), axis=1)
        lad = BucketLadder(batches=(2,), seq_axes={1: 4})
        pred = CompiledPredictor(
            net, {}, data_shapes={"data": (1, 4, 6)}, ladder=lad)
        rs = np.random.RandomState(0)
        for seq in (3, 4, 6, 7):
            x = rs.randn(2, seq, 6).astype(np.float32)
            out = pred.predict(x)[0].asnumpy()
            buf = np.zeros((2, lad.round_axis(1, seq), 6), np.float32)
            buf[:, :seq] = x
            ref = _eager(net, {}, {}, mx.nd.array(buf)).asnumpy()
            assert np.array_equal(out, ref)
            assert np.allclose(out, np.maximum(x, 0).sum(axis=1),
                               rtol=1e-6, atol=1e-6)
        # seq 3,4 -> bucket 4; seq 6,7 -> bucket 8: two programs
        assert pred.compile_count == 2

    def test_input_validation(self):
        net = _mlp()
        params, aux = _params_for(net, 12)
        pred = CompiledPredictor(
            net, params, aux_params=aux, data_shapes={"data": (1, 12)},
            ladder=BucketLadder(batches=(2,)))
        with pytest.raises(ServeError, match="rank"):
            pred.predict(np.zeros((1, 1, 12), np.float32))
        with pytest.raises(ServeError, match="top rung"):
            pred.predict(np.zeros((3, 12), np.float32))
        single = pred.predict(np.zeros((12,), np.float32))[0]
        assert single.shape == (1, 4)           # example -> batch of 1

    def test_fixed_shape_inputs_not_bucketed(self):
        """bucket_inputs: inputs left out are fixed-shape — no batch
        padding, exact-match enforced — so multi-input models whose
        inputs do not share a leading dim still serve (the C-ABI
        client's contract)."""
        data = sym.var("data")
        scale = sym.var("scale")
        net = sym.broadcast_mul(data, scale)
        pred = CompiledPredictor(
            net, {}, data_shapes={"data": (1, 4), "scale": (1, 4)},
            ladder=BucketLadder(batches=(1, 2, 4)),
            bucket_inputs=("data",))
        rs = np.random.RandomState(0)
        x = rs.randn(3, 4).astype(np.float32)
        s = rs.randn(1, 4).astype(np.float32)
        out = pred.predict({"data": x, "scale": s})[0].asnumpy()
        assert out.shape == (3, 4)              # trimmed from rung 4
        assert np.array_equal(out, x * s)
        assert pred.compile_count == 1
        with pytest.raises(ServeError, match="fixed-shape"):
            pred.predict({"data": x,
                          "scale": np.ones((2, 4), np.float32)})
        with pytest.raises(ServeError, match="fixed-shape"):
            DynamicBatcher(pred)                # cannot coalesce these
        with pytest.raises(ServeError, match="not data inputs"):
            CompiledPredictor(
                net, {}, data_shapes={"data": (1, 4), "scale": (1, 4)},
                bucket_inputs=("ghost",))

    def test_missing_param_raises(self):
        net = _mlp()
        with pytest.raises(ServeError, match="neither data inputs"):
            CompiledPredictor(net, {}, data_shapes={"data": (1, 12)})

    def test_set_params_refreshes_without_recompile(self):
        import jax.numpy as jnp
        net = _mlp()
        params, aux = _params_for(net, 12)
        pred = CompiledPredictor(
            net, params, aux_params=aux, data_shapes={"data": (1, 12)},
            ladder=BucketLadder(batches=(2,)))
        pred.warm()
        x = np.ones((2, 12), np.float32)
        before = pred.predict(x)[0].asnumpy()
        params2, _ = _params_for(net, 12, seed=9)
        pred.set_params(params2)
        after = pred.predict(x)[0].asnumpy()
        assert pred.compile_count == 1
        assert not np.array_equal(before, after)
        ref = _eager(net, params2, aux, mx.nd.array(x))
        assert bool(jnp.array_equal(pred.predict(x)[0]._data, ref._data))
        with pytest.raises(ServeError, match="shape-specialized"):
            pred.set_params({"h_weight": mx.nd.zeros((2, 2))})


# ---------------------------------------------------------------------------
# donated decode
# ---------------------------------------------------------------------------

def _decode_pred():
    net = _mlp()
    params, aux = _params_for(net, 12)
    return CompiledPredictor(
        net, params, aux_params=aux, data_shapes={"data": (1, 12)},
        ladder=BucketLadder(batches=(1,)))


def _append_step(p, cache, inputs, t):
    """Toy KV-cache decode: write this step's token column, emit the
    running row sums."""
    import jax
    import jax.numpy as jnp
    new = jax.lax.dynamic_update_slice(
        cache["kv"], inputs["tok"][:, None], (0, t))
    return jnp.sum(new, axis=1), {"kv": new}


class TestDecode:
    def test_decode_matches_eager_loop_cache_never_copied(self):
        import jax.numpy as jnp
        pred = _decode_pred()
        steps = 6
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")     # cpu ignores donation
            sess = pred.make_decoder(
                _append_step, {"kv": jnp.zeros((2, steps), jnp.float32)},
                {"tok": (2,)}, donate=True)
            compiles = pred.compile_count
            ref = np.zeros((2, steps), np.float32)
            for t in range(steps):
                tok = np.full((2,), float(t + 1), np.float32)
                out = np.asarray(sess.step({"tok": tok}))
                ref[:, t] = tok
                assert np.array_equal(out, ref.sum(axis=1))
        assert sess.step_count == steps
        assert pred.compile_count == compiles   # one program, N steps
        assert np.array_equal(np.asarray(sess.cache["kv"]), ref)

    def test_decode_donation_declared_in_program(self):
        import jax.numpy as jnp
        pred = _decode_pred()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            sess = pred.make_decoder(
                _append_step, {"kv": jnp.zeros((1, 4), jnp.float32)},
                {"tok": (1,)}, donate=True)
        txt = sess.lowered_text()
        assert "jax.buffer_donor" in txt or "tf.aliasing_output" in txt
        sess_off = pred.make_decoder(
            _append_step, {"kv": jnp.zeros((1, 4), jnp.float32)},
            {"tok": (1,)}, donate=False)
        txt_off = sess_off.lowered_text()
        assert "jax.buffer_donor" not in txt_off

    def test_decode_stale_cache_alias_poisoned(self, monkeypatch):
        """The fused-step donation discipline applies: with the
        graftsan donation component on, an NDArray still aliasing a
        donated cache buffer raises at the touch site."""
        import jax.numpy as jnp
        from tools.graftsan.donation import UseAfterDonateError
        import tools.graftsan as graftsan
        pred = _decode_pred()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            sess = pred.make_decoder(
                _append_step, {"kv": jnp.zeros((1, 4), jnp.float32)},
                {"tok": (1,)}, donate=True)
            monkeypatch.setenv("MXNET_SAN", "donation")
            stale = mx.nd.NDArray(sess.cache["kv"])
            sess.step({"tok": np.ones((1,), np.float32)})
            with pytest.raises(UseAfterDonateError):
                stale.asnumpy()
        # drop the deliberate report so later tests see a clean slate
        graftsan.clear()

    def test_decode_shape_validation(self):
        import jax.numpy as jnp
        pred = _decode_pred()
        sess = pred.make_decoder(
            _append_step, {"kv": jnp.zeros((1, 4), jnp.float32)},
            {"tok": (1,)}, donate=False)
        with pytest.raises(ServeError, match="fixed-shape"):
            sess.step({"tok": np.ones((2,), np.float32)})
        with pytest.raises(ServeError, match="missing input"):
            sess.step({})


# ---------------------------------------------------------------------------
# dynamic batcher
# ---------------------------------------------------------------------------

def _batcher_pred(batches=(1, 2, 4, 8)):
    net = _mlp()
    params, aux = _params_for(net, 12)
    pred = CompiledPredictor(
        net, params, aux_params=aux, data_shapes={"data": (1, 12)},
        ladder=BucketLadder(batches=batches))
    pred.warm()
    return net, params, aux, pred


class TestDynamicBatcher:
    def test_coalesces_and_splits_bit_exact(self):
        net, params, aux, pred = _batcher_pred()
        b = DynamicBatcher(pred, max_wait_ms=250)
        try:
            rs = np.random.RandomState(0)
            xs = [rs.randn(n, 12).astype(np.float32) for n in (1, 2, 1)]
            futs = [b.submit(x) for x in xs]
            outs = [f.result(30)[0] for f in futs]
            assert b.batch_count == 1           # one dispatch, 3 callers
            # 4 rows coalesced -> rung 4: the exact reference is the
            # eager forward of the stacked batch at that rung
            stacked = np.concatenate(xs, axis=0)
            ref = _eager(net, params, aux,
                         mx.nd.array(stacked)).asnumpy()
            got = np.concatenate(outs, axis=0)
            assert np.array_equal(got, ref)
        finally:
            b.close()

    def test_padded_batches_compile_nothing_at_all(self):
        """Rows that do not fill their rung are trimmed on the HOST: a
        device-side trim compiled one tiny slice program per distinct
        (rung, rows) pair on first use — in the request path, unseen
        by ``compile_count`` (found on the chip, PR 21)."""
        import jax
        net, params, aux, pred = _batcher_pred(batches=(1, 2, 4, 8))
        compiled = []
        watching = [False]

        def listener(event, secs, **kw):
            if watching[0] and \
                    event == "/jax/core/compile/backend_compile_duration":
                compiled.append(kw.get("fun_name"))

        jax.monitoring.register_event_duration_secs_listener(listener)
        b = DynamicBatcher(pred, max_wait_ms=0)
        try:
            watching[0] = True
            for rows in (3, 5, 6, 7):       # every one pads its rung
                x = np.ones((rows, 12), np.float32)
                out = b.submit(x).result(30)[0]
                assert out.shape == (rows, 4)
                ref = _eager(net, params, aux, mx.nd.array(x)).asnumpy()
                np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-6)
            watching[0] = False
        finally:
            watching[0] = False
            b.close()
        assert compiled == [] or all(
            "dynamic_slice" not in str(n) for n in compiled), compiled

    def test_full_batch_dispatches_before_deadline(self):
        _, _, _, pred = _batcher_pred(batches=(1, 2, 4))
        b = DynamicBatcher(pred, max_wait_ms=30000, max_batch=4)
        try:
            t0 = time.monotonic()
            fut = b.submit(np.zeros((4, 12), np.float32))
            fut.result(10)
            assert time.monotonic() - t0 < 5.0  # did not sit out 30s
        finally:
            b.close()

    def test_single_request_resolves_after_deadline(self):
        _, _, _, pred = _batcher_pred(batches=(1, 2))
        b = DynamicBatcher(pred, max_wait_ms=50)
        try:
            out = b(np.zeros((1, 12), np.float32), timeout=10)
            assert out[0].shape == (1, 4)
        finally:
            b.close()

    def test_submit_validation(self):
        _, _, _, pred = _batcher_pred(batches=(1, 2))
        b = DynamicBatcher(pred, max_wait_ms=1)
        try:
            with pytest.raises(ServeError, match="cap"):
                b.submit(np.zeros((3, 12), np.float32))
            with pytest.raises(ServeError, match="rank"):
                b.submit(np.zeros((1, 1, 12), np.float32))
            with pytest.raises(ServeError, match="no rows"):
                b.submit(np.zeros((0, 12), np.float32))
        finally:
            b.close()

    def test_dispatch_error_fails_only_that_batch(self):
        _, _, _, pred = _batcher_pred(batches=(1, 2))
        b = DynamicBatcher(pred, max_wait_ms=20)
        try:
            real = pred.predict
            boom = {"armed": True}

            def flaky(data, key=None, **kw):
                if boom.pop("armed", False):
                    raise RuntimeError("injected dispatch failure")
                return real(data, key=key, **kw)

            pred.predict = flaky
            with pytest.raises(RuntimeError, match="injected"):
                b(np.zeros((1, 12), np.float32), timeout=10)
            out = b(np.zeros((1, 12), np.float32), timeout=10)
            assert out[0].shape == (1, 4)
        finally:
            pred.predict = real
            b.close()

    def test_close_fails_pending_and_rejects_new(self):
        _, _, _, pred = _batcher_pred(batches=(1,))
        b = DynamicBatcher(pred, max_wait_ms=60000, max_batch=1)
        # saturate: first request dispatches, hold the queue with more
        real = pred.predict

        def slow(data, key=None, **kw):
            time.sleep(0.2)
            return real(data, key=key, **kw)

        pred.predict = slow
        try:
            futs = [b.submit(np.zeros((1, 12), np.float32))
                    for _ in range(3)]
            b.close()
            with pytest.raises(ServeError, match="closed"):
                b.submit(np.zeros((1, 12), np.float32))
            failures = 0
            for f in futs:
                try:
                    f.result(10)
                except ServeError:
                    failures += 1
            assert failures >= 1                # undispatched ones failed
        finally:
            pred.predict = real

    def test_future_timeout(self):
        fut = ServeFuture()
        with pytest.raises(TimeoutError):
            fut.result(0.05)

    def test_metrics_accounting(self):
        from mxnet_tpu.observability import metrics as obs_metrics
        _, _, _, pred = _batcher_pred(batches=(1, 2))
        b = DynamicBatcher(pred, max_wait_ms=10)
        try:
            before = obs_metrics.snapshot()
            for _ in range(4):
                b(np.zeros((1, 12), np.float32), timeout=10)
            after = obs_metrics.snapshot()
            delta = (after["serve_requests_total"]["value"]
                     - before["serve_requests_total"]["value"])
            assert delta == 4
            assert after["serve_request_seconds"]["count"] >= \
                before["serve_request_seconds"]["count"] + 4
            assert after["serve_queue_depth"]["value"] == 0
        finally:
            b.close()


# ---------------------------------------------------------------------------
# admission control & load shedding
# ---------------------------------------------------------------------------

def _counter_value(name):
    from mxnet_tpu.observability import metrics as obs_metrics
    snap = obs_metrics.snapshot().get(name)
    return snap["value"] if snap else 0


class TestAdmissionControl:
    def test_queue_request_cap_sheds_typed(self):
        _, _, _, pred = _batcher_pred()
        # a 60s window keeps submissions queued while we overfill
        b = DynamicBatcher(pred, max_wait_ms=60000, max_queue=2)
        try:
            before = _counter_value("serve_requests_shed_total")
            futs = [b.submit(np.zeros((1, 12), np.float32))
                    for _ in range(2)]
            with pytest.raises(OverloadError, match="full"):
                b.submit(np.zeros((1, 12), np.float32))
            assert isinstance(OverloadError("x"), ServeError)
            assert _counter_value("serve_requests_shed_total") == \
                before + 1
            assert b.queue_depth == 2 and len(futs) == 2
        finally:
            b.close()

    def test_queue_byte_cap_sheds_typed(self):
        _, _, _, pred = _batcher_pred()
        # one row is 12 float32 = 48 bytes; cap admits two rows only
        b = DynamicBatcher(pred, max_wait_ms=60000, max_queue_bytes=100)
        try:
            b.submit(np.zeros((1, 12), np.float32))
            b.submit(np.zeros((1, 12), np.float32))
            with pytest.raises(OverloadError, match="byte cap"):
                b.submit(np.zeros((1, 12), np.float32))
        finally:
            b.close()

    def test_accepted_requests_still_complete_under_shedding(self):
        net, params, aux, pred = _batcher_pred()
        b = DynamicBatcher(pred, max_wait_ms=60000, max_queue=1)
        try:
            x = np.random.RandomState(0).randn(1, 12).astype(np.float32)
            fut = b.submit(x)
            with pytest.raises(OverloadError):
                b.submit(x)
            # draining releases the accepted request for dispatch
            assert b.drain(timeout=30) is True
            out = fut.result(10)[0]
            ref = _eager(net, params, aux, mx.nd.array(x)).asnumpy()
            assert np.array_equal(out, ref)
        finally:
            b.close()


# ---------------------------------------------------------------------------
# deadlines
# ---------------------------------------------------------------------------

def _wait_queue_taken(b, timeout=5.0):
    """Poll until the dispatcher has taken everything queued (it is
    now inside a dispatch — with slow-dispatch chaos armed, wedged in
    the injected sleep)."""
    deadline = time.monotonic() + timeout
    while b.queue_depth and time.monotonic() < deadline:
        time.sleep(0.005)
    assert b.queue_depth == 0


class TestDeadlines:
    def test_deadline_cuts_the_coalescing_window(self):
        # an idle dispatcher never holds a head past its deadline: the
        # 60s coalescing window is cut short and the request dispatches
        # BEFORE the 500ms deadline instead of expiring at it
        net, params, aux, pred = _batcher_pred()
        b = DynamicBatcher(pred, max_wait_ms=60000)
        try:
            x = np.random.RandomState(0).randn(1, 12).astype(np.float32)
            t0 = time.monotonic()
            out = b.submit(x, deadline_ms=500).result(10)[0]
            took = time.monotonic() - t0
            ref = _eager(net, params, aux, mx.nd.array(x)).asnumpy()
            assert np.array_equal(out, ref)
            assert took < 0.6, "window was not cut by the deadline"
        finally:
            b.close()

    def test_expired_request_shed_before_dispatch(self):
        # the dispatcher is wedged in a slow dispatch (chaos) when the
        # victim's deadline passes: shed BEFORE padding/dispatch
        _, _, _, pred = _batcher_pred()
        b = DynamicBatcher(pred, max_wait_ms=5)
        try:
            before = _counter_value("serve_requests_expired_total")
            chaos.configure(slow_dispatch_ms=600)
            filler = b.submit(np.zeros((1, 12), np.float32))
            _wait_queue_taken(b)
            assert pred.dispatch_count == 0     # still in the sleep
            victim = b.submit(np.zeros((1, 12), np.float32),
                              deadline_ms=100)
            with pytest.raises(DeadlineExceededError, match="expired"):
                victim.result(10)
            assert filler.result(10)[0].shape == (1, 4)
            chaos.reset()
            assert b.drain(timeout=10) is True
            # the victim's row provably never rode through XLA
            assert pred.dispatch_count == 1
            assert _counter_value("serve_requests_expired_total") == \
                before + 1
        finally:
            chaos.reset()
            b.close()

    def test_default_deadline_knob_applies(self):
        _, _, _, pred = _batcher_pred()
        b = DynamicBatcher(pred, max_wait_ms=5,
                           default_deadline_ms=100)
        try:
            chaos.configure(slow_dispatch_ms=600)
            filler = b.submit(np.zeros((1, 12), np.float32))
            _wait_queue_taken(b)
            victim = b.submit(np.zeros((1, 12), np.float32))
            with pytest.raises(DeadlineExceededError):
                victim.result(10)
            assert filler.result(10)[0].shape == (1, 4)
        finally:
            chaos.reset()
            b.close()

    def test_deadline_met_dispatches_normally(self):
        _, _, _, pred = _batcher_pred()
        b = DynamicBatcher(pred, max_wait_ms=5)
        try:
            out = b.submit(np.zeros((1, 12), np.float32),
                           deadline_ms=10000).result(10)
            assert out[0].shape == (1, 4)
        finally:
            b.close()

    def test_nonpositive_deadline_rejected(self):
        _, _, _, pred = _batcher_pred()
        b = DynamicBatcher(pred, max_wait_ms=5)
        try:
            with pytest.raises(ServeError, match="deadline_ms"):
                b.submit(np.zeros((1, 12), np.float32), deadline_ms=0)
        finally:
            b.close()

    def test_expired_head_does_not_starve_successor(self):
        # doomed expires while the dispatcher is wedged behind it;
        # when the dispatcher comes back it sheds doomed and serves
        # live in the same take — no starvation
        net, params, aux, pred = _batcher_pred()
        b = DynamicBatcher(pred, max_wait_ms=5)
        try:
            chaos.configure(slow_dispatch_ms=600)
            filler = b.submit(np.zeros((1, 12), np.float32))
            _wait_queue_taken(b)
            doomed = b.submit(np.zeros((1, 12), np.float32),
                              deadline_ms=100)
            x = np.random.RandomState(1).randn(1, 12).astype(np.float32)
            live = b.submit(x, deadline_ms=30000)
            with pytest.raises(DeadlineExceededError):
                doomed.result(10)
            out = live.result(10)[0]
            ref = _eager(net, params, aux, mx.nd.array(x)).asnumpy()
            assert np.array_equal(out, ref)
            assert filler.result(1)[0].shape == (1, 4)
        finally:
            chaos.reset()
            b.close()


# ---------------------------------------------------------------------------
# caller-side cancellation (abandoned slots are reclaimed)
# ---------------------------------------------------------------------------

class TestCancel:
    def test_cancelled_row_never_reaches_dispatch(self):
        """Regression: a caller that times out used to leave its
        request queued — it was padded, dispatched and resolved into
        rows nobody read.  cancel() reclaims the slot."""
        _, _, _, pred = _batcher_pred()
        b = DynamicBatcher(pred, max_wait_ms=60000)
        try:
            fut = b.submit(np.zeros((1, 12), np.float32))
            with pytest.raises(TimeoutError):
                fut.result(0.02)
            assert fut.cancel() is True
            with pytest.raises(RequestCancelled):
                fut.result(1)
            assert b.queue_depth == 0
            # dispatcher finds nothing to run: the row never dispatched
            assert b.drain(timeout=10) is True
            assert pred.dispatch_count == 0
            assert b.batch_count == 0
        finally:
            b.close()

    def test_deadline_behind_lenient_head_dispatches(self):
        """Regression: the coalescing window honored only the HEAD's
        deadline — a tight-deadline request queued behind a
        deadline-less head expired spuriously on an idle server
        (resolved only when the head's full max-wait elapsed)."""
        net, params, aux, pred = _batcher_pred()
        b = DynamicBatcher(pred, max_wait_ms=60000)
        try:
            x0 = np.zeros((1, 12), np.float32)
            slack = b.submit(x0)                        # no deadline
            x1 = np.random.RandomState(11).randn(1, 12) \
                   .astype(np.float32)
            tight = b.submit(x1, deadline_ms=500)
            out = tight.result(10)[0]   # well before the 60s window
            # the two rows coalesce: reference is the stacked eager
            stacked = np.concatenate([x0, x1], axis=0)
            ref = _eager(net, params, aux,
                         mx.nd.array(stacked)).asnumpy()[1:2]
            assert np.array_equal(out, ref)
            slack.result(10)
        finally:
            b.close()

    def test_cancelled_head_hands_window_to_successor(self):
        net, params, aux, pred = _batcher_pred()
        b = DynamicBatcher(pred, max_wait_ms=60000)
        try:
            doomed = b.submit(np.zeros((1, 12), np.float32))
            x = np.random.RandomState(2).randn(1, 12).astype(np.float32)
            live = b.submit(x, deadline_ms=1500)
            assert doomed.cancel() is True
            # the successor's own deadline now bounds the window (60s
            # max-wait): live dispatches before 1.5s, not never
            out = live.result(10)[0]
            ref = _eager(net, params, aux, mx.nd.array(x)).asnumpy()
            assert np.array_equal(out, ref)
        finally:
            b.close()

    def test_cancel_after_resolution_returns_false(self):
        _, _, _, pred = _batcher_pred()
        b = DynamicBatcher(pred, max_wait_ms=5)
        try:
            fut = b.submit(np.zeros((1, 12), np.float32))
            fut.result(10)
            assert fut.cancel() is False
            assert fut.result(1)[0].shape == (1, 4)  # result survives
        finally:
            b.close()

    def test_unbound_future_cancel_is_false(self):
        assert ServeFuture().cancel() is False

    def test_resolved_future_releases_cancel_closure(self):
        """Regression: the cancel closure pins the request payload and
        the batcher (cycling through req.future) — _resolve must drop
        it, and submit must wire it under the lock so a fast dispatch
        cannot re-install it afterwards."""
        _, _, _, pred = _batcher_pred()
        b = DynamicBatcher(pred, max_wait_ms=5)
        try:
            fut = b.submit(np.zeros((1, 12), np.float32))
            fut.result(10)
            assert fut._cancel_cb is None
        finally:
            b.close()

    def test_cancel_racing_expiry_does_not_double_account(self):
        """Regression: _take_locked popped an expired request without
        marking it taken, so a cancel() landing in the window before
        the dispatcher resolved it re-decremented the rows/bytes/depth
        accounting (permanently loosening the byte-cap admission
        check) and double-resolved the future."""
        from mxnet_tpu.serve.batcher import _Request, _QUEUE_DEPTH
        _, _, _, pred = _batcher_pred()
        b = DynamicBatcher(pred, max_wait_ms=60000)
        b.close()               # stop the dispatcher: drive _take_locked by hand
        data = {"data": np.zeros((1, 12), np.float32)}
        fut = ServeFuture()
        req = _Request(data, 1, data["data"].nbytes,
                       deadline=time.monotonic() - 1.0, dispatch_by=None,
                       future=fut)
        fut._cancel_cb = lambda: b._cancel(req)
        with b._lock:
            b._pending.append(req)
            b._rows_pending += req.rows
            b._bytes_pending += req.nbytes
            _QUEUE_DEPTH.inc()
        with b._lock:
            taken, _, expired = b._take_locked()
        assert taken == [] and expired == [req]
        assert req.taken        # off the queue, accounting settled
        # the caller gives up exactly now — before the dispatcher has
        # resolved the expired future.  The slot must not be reclaimed
        # a second time, and resolution stays with the dispatcher.
        assert fut.cancel() is False
        assert b._rows_pending == 0 and b._bytes_pending == 0
        assert not fut.done()

    def test_cancel_after_close_orphaning_does_not_double_account(self):
        """Same hole via close(): orphaned requests are failed outside
        the lock — a racing cancel() must see them as taken."""
        _, _, _, pred = _batcher_pred()
        real = pred.predict
        release = threading.Event()

        def wedged(data, key=None, **kw):
            release.wait(10)
            return real(data, key=key, **kw)

        pred.predict = wedged
        b = DynamicBatcher(pred, max_wait_ms=1)
        try:
            b.submit(np.zeros((1, 12), np.float32))
            time.sleep(0.1)             # dispatcher wedges on batch 1
            queued = b.submit(np.zeros((1, 12), np.float32))
            b.close(timeout=0.05)       # orphans the queued request
            assert queued.cancel() is False
            assert b._rows_pending == 0 and b._bytes_pending == 0
            with pytest.raises(ServeError, match="closed before"):
                queued.result(10)
        finally:
            release.set()
            pred.predict = real


# ---------------------------------------------------------------------------
# dispatcher supervision
# ---------------------------------------------------------------------------

class TestDispatcherSupervision:
    def test_crash_loses_exactly_the_failing_batch_then_restarts(self):
        _, _, _, pred = _batcher_pred()
        b = DynamicBatcher(pred, max_wait_ms=5)
        b._restart_sleep = lambda s: None
        try:
            before = _counter_value("serve_dispatcher_restarts_total")
            chaos.configure(dispatch_raise_at=1)
            fut = b.submit(np.zeros((1, 12), np.float32))
            with pytest.raises(RuntimeError, match="servechaos"):
                fut.result(10)
            chaos.reset()
            # the restarted dispatcher serves the next request
            out = b.submit(np.zeros((1, 12), np.float32)).result(10)
            assert out[0].shape == (1, 4)
            assert b.restart_count == 1
            assert not b.unhealthy
            assert _counter_value("serve_dispatcher_restarts_total") \
                == before + 1
        finally:
            chaos.reset()
            b.close()

    def test_budget_exhausted_goes_unhealthy_and_fails_queued(self):
        _, _, _, pred = _batcher_pred()
        b = DynamicBatcher(pred, max_wait_ms=60000, max_batch=1,
                           max_restarts=1)
        b._restart_sleep = lambda s: None
        try:
            chaos.configure(dispatch_raise_at=1, dispatch_raise_for=5)
            futs = [b.submit(np.zeros((1, 12), np.float32))
                    for _ in range(3)]
            # f1 crashes the loop (restart 1), f2 crashes it again
            # (budget exhausted) — f3 must fail LOUDLY, not hang
            with pytest.raises(RuntimeError, match="servechaos"):
                futs[0].result(10)
            with pytest.raises(RuntimeError, match="servechaos"):
                futs[1].result(10)
            with pytest.raises(ServeError, match="unhealthy"):
                futs[2].result(10)
            assert b.unhealthy
            assert b.health_state() == "unhealthy"
            assert not b.dispatcher_alive()
            with pytest.raises(ServeError, match="unhealthy"):
                b.submit(np.zeros((1, 12), np.float32))
        finally:
            chaos.reset()
            b.close()

    def test_per_batch_dispatch_error_consumes_no_restart(self):
        _, _, _, pred = _batcher_pred(batches=(1, 2))
        b = DynamicBatcher(pred, max_wait_ms=20)
        try:
            real = pred.predict
            boom = {"armed": True}

            def flaky(data, key=None, **kw):
                if boom.pop("armed", False):
                    raise RuntimeError("injected dispatch failure")
                return real(data, key=key, **kw)

            pred.predict = flaky
            with pytest.raises(RuntimeError, match="injected"):
                b(np.zeros((1, 12), np.float32), timeout=10)
            assert b.restart_count == 0     # isolation, not a crash
            assert b(np.zeros((1, 12), np.float32),
                     timeout=10)[0].shape == (1, 4)
        finally:
            pred.predict = real
            b.close()


# ---------------------------------------------------------------------------
# graceful drain + dirty close
# ---------------------------------------------------------------------------

class TestDrain:
    def test_drain_completes_accepted_then_rejects(self):
        net, params, aux, pred = _batcher_pred()
        b = DynamicBatcher(pred, max_wait_ms=60000)
        try:
            rs = np.random.RandomState(3)
            xs = [rs.randn(1, 12).astype(np.float32) for _ in range(4)]
            futs = [b.submit(x) for x in xs]
            assert b.drain(timeout=30) is True
            assert b.draining and b.health_state() == "draining"
            outs = [f.result(10)[0] for f in futs]
            # the 4 rows coalesce into one rung-4 dispatch: the exact
            # reference is the eager forward of the stacked batch
            stacked = np.concatenate(xs, axis=0)
            ref = _eager(net, params, aux, mx.nd.array(stacked)).asnumpy()
            assert np.array_equal(np.concatenate(outs, axis=0), ref)
            with pytest.raises(ServeError, match="draining"):
                b.submit(xs[0])
            assert b.drain(timeout=5) is True   # idempotent
        finally:
            b.close()

    def test_drain_timeout_reports_false(self):
        _, _, _, pred = _batcher_pred()
        real = pred.predict

        def slow(data, key=None, **kw):
            time.sleep(0.5)
            return real(data, key=key, **kw)

        pred.predict = slow
        b = DynamicBatcher(pred, max_wait_ms=1)
        try:
            b.submit(np.zeros((1, 12), np.float32))
            time.sleep(0.05)                # let the dispatch start
            assert b.drain(timeout=0.05) is False
        finally:
            pred.predict = real
            b.close()

    def test_drain_wakes_when_backlog_expires(self):
        """Regression: a shed-only dispatcher round (every queued
        request expired, nothing taken) emptied the queue without
        notifying, so a concurrent drain() slept out its entire
        timeout instead of returning the moment the queue died."""
        _, _, _, pred = _batcher_pred()
        real = pred.predict

        def slow(data, key=None, **kw):
            time.sleep(0.8)
            return real(data, key=key, **kw)

        pred.predict = slow
        b = DynamicBatcher(pred, max_wait_ms=5, max_batch=1)
        try:
            first = b.submit(np.zeros((1, 12), np.float32))
            time.sleep(0.1)     # dispatcher takes it into the slow dispatch
            doomed = b.submit(np.zeros((1, 12), np.float32),
                              deadline_ms=100)
            res = {}
            done = threading.Event()

            def run():
                t0 = time.monotonic()
                res["ok"] = b.drain(timeout=30)
                res["s"] = time.monotonic() - t0
                done.set()

            threading.Thread(target=run, daemon=True).start()
            assert first.result(10)[0].shape == (1, 4)
            with pytest.raises(DeadlineExceededError):
                doomed.result(10)
            assert done.wait(10)
            assert res["ok"] is True
            assert res["s"] < 8     # woke on the expiry, not the 30s cap
        finally:
            pred.predict = real
            b.close()

    def test_close_join_timeout_surfaces_dirty(self):
        """Satellite: close used to ignore a join that timed out and
        return as if clean — a wedged dispatcher must surface."""
        _, _, _, pred = _batcher_pred()
        real = pred.predict
        release = threading.Event()

        def wedged(data, key=None, **kw):
            release.wait(10)
            return real(data, key=key, **kw)

        pred.predict = wedged
        b = DynamicBatcher(pred, max_wait_ms=1)
        try:
            before = _counter_value("serve_batcher_dirty_closes_total")
            fut = b.submit(np.zeros((1, 12), np.float32))
            time.sleep(0.1)                 # dispatcher takes the batch
            assert b.close(timeout=0.1) is False
            assert b.closed_dirty
            assert _counter_value("serve_batcher_dirty_closes_total") \
                == before + 1
            release.set()
            assert fut.result(10)[0].shape == (1, 4)  # in-flight lands
        finally:
            release.set()
            pred.predict = real


# ---------------------------------------------------------------------------
# health surface
# ---------------------------------------------------------------------------

class TestHealth:
    def test_board_transitions_and_gauges(self):
        from mxnet_tpu.observability import metrics as obs_metrics
        board = HealthBoard()
        ready = obs_metrics.REGISTRY.get("serve_models_ready")
        draining = obs_metrics.REGISTRY.get("serve_models_draining")
        r0, d0 = ready.value, draining.value
        assert board.transition("m", "loading") is None
        assert board.transition("m", "warming") == "loading"
        board.transition("m", "ready")
        assert ready.value == r0 + 1
        board.transition("m", "draining")
        assert ready.value == r0 and draining.value == d0 + 1
        assert board.state("m") == "draining"
        assert board.drop("m") == "draining"
        assert draining.value == d0 and board.state("m") is None
        with pytest.raises(ServeError, match="unknown serving state"):
            board.transition("m", "bogus")

    def test_registry_health_view_and_probes(self):
        reg = ModelRegistry()
        try:
            net = _mlp()
            params, aux = _params_for(net, 12)
            reg.load("hm", net, params, aux_params=aux,
                     data_shapes={"data": (1, 12)},
                     ladder=BucketLadder(batches=(1, 2)))
            assert reg.ready("hm")
            info = reg.health("hm")
            assert info["state"] == "ready"
            assert info["programs"] == 2
            assert info["dispatcher_alive"] is None  # no batcher yet
            reg.submit("hm", np.zeros((1, 12), np.float32)).result(10)
            info = reg.health("hm")
            assert info["dispatcher_alive"] is True
            assert info["tick_age_s"] < 5.0
            assert info["requests"] == 1 and info["batches"] == 1
            assert info["closed_dirty"] is False
            assert reg.live()
            reg.drain("hm", timeout=10)
            assert reg.health("hm")["state"] == "draining"
            assert not reg.ready("hm")
            assert "hm" in reg.health()         # all-models view
            reg.unload("hm")
            with pytest.raises(ServeError, match="no model"):
                reg.health("hm")
            assert reg.ready("hm") is False
        finally:
            reg.close()

    def test_drain_before_any_traffic_still_stops_admissions(self):
        """Regression: drain() on a model that never saw traffic (no
        batcher yet) marked it draining on the board, but a later
        submit created a fresh ACCEPTING batcher — traffic admitted
        behind the health surface's back."""
        reg = ModelRegistry()
        try:
            net = _mlp()
            params, aux = _params_for(net, 12)
            reg.load("dv", net, params, aux_params=aux,
                     data_shapes={"data": (1, 12)},
                     ladder=BucketLadder(batches=(1,)))
            assert reg.drain("dv", timeout=5) is True
            assert reg.health("dv")["state"] == "draining"
            with pytest.raises(ServeError, match="draining"):
                reg.submit("dv", np.zeros((1, 12), np.float32))
            assert reg.health("dv")["state"] == "draining"
        finally:
            reg.close()

    def test_fleet_health_skips_model_unloaded_mid_view(self):
        """Regression: the aggregate health() view raced unload — a
        model deleted between the name snapshot and its per-model read
        failed the whole fleet view with ServeError, exactly when a
        deploy made the probe matter most."""
        reg = ModelRegistry()
        try:
            net = _mlp()
            params, aux = _params_for(net, 12)
            reg.load("hv", net, params, aux_params=aux,
                     data_shapes={"data": (1, 12)},
                     ladder=BucketLadder(batches=(1,)))
            orig = reg._board.snapshot
            reg._board.snapshot = \
                lambda: dict(orig(), ghost="ready")  # mid-view unload
            view = reg.health()
            assert "hv" in view and "ghost" not in view
            with pytest.raises(ServeError, match="no model"):
                reg.health("ghost")     # by-name stays a typed error
        finally:
            reg.close()

    def test_unhealthy_batcher_reaches_registry_state(self):
        reg = ModelRegistry()
        try:
            net = _mlp()
            params, aux = _params_for(net, 12)
            reg.load("uh", net, params, aux_params=aux,
                     data_shapes={"data": (1, 12)},
                     ladder=BucketLadder(batches=(1, 2)))
            b = reg.batcher("uh", max_restarts=0, max_wait_ms=5)
            b._restart_sleep = lambda s: None
            chaos.configure(dispatch_raise_at=1, dispatch_raise_for=3)
            fut = reg.submit("uh", np.zeros((1, 12), np.float32))
            with pytest.raises(RuntimeError, match="servechaos"):
                fut.result(10)
            chaos.reset()
            assert reg.health("uh")["state"] == "unhealthy"
            assert not reg.live()
        finally:
            chaos.reset()
            reg.close()


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

class TestModelRegistry:
    def _load(self, reg, name, seed=0):
        net = _mlp()
        params, aux = _params_for(net, 12, seed=seed)
        pred = reg.load(name, net, params, aux_params=aux,
                        data_shapes={"data": (1, 12)},
                        ladder=BucketLadder(batches=(1, 2)))
        return net, params, aux, pred

    def test_load_get_alias_unload(self):
        reg = ModelRegistry()
        try:
            _, _, _, pred = self._load(reg, "m1")
            assert reg.get("m1") is pred
            reg.alias("prod", "m1")
            assert reg.get("prod") is pred
            self._load(reg, "m2", seed=5)
            reg.alias("prod", "m2")             # traffic cutover
            assert reg.get("prod") is reg.get("m2")
            reg.unload("m2")
            assert reg.names() == ["m1"]
            with pytest.raises(ServeError, match="no model"):
                reg.get("prod")                 # alias died with m2
            with pytest.raises(ServeError, match="no model"):
                reg.get("m2")
        finally:
            reg.close()

    def test_alias_and_name_collisions(self):
        reg = ModelRegistry()
        try:
            self._load(reg, "m1")
            reg.alias("a", "m1")
            with pytest.raises(ServeError, match="alias"):
                self._load(reg, "a")
            with pytest.raises(ServeError, match="unknown model"):
                reg.alias("b", "ghost")
            with pytest.raises(ServeError, match="loaded model"):
                reg.alias("m1", "m1")
            reg.unload("a")                     # unalias only
            assert reg.names() == ["m1"]
        finally:
            reg.close()

    def test_submit_routes_through_batcher_and_unload_closes(self):
        reg = ModelRegistry()
        try:
            net, params, aux, _ = self._load(reg, "m1")
            x = np.ones((1, 12), np.float32)
            out = reg.submit("m1", x).result(10)[0]
            ref = _eager(net, params, aux, mx.nd.array(x)).asnumpy()
            assert np.array_equal(out, ref)
            batcher = reg.batcher("m1")
            reg.unload("m1")
            with pytest.raises(ServeError, match="closed"):
                batcher.submit(x)
        finally:
            reg.close()

    def test_replaced_batcher_hook_detached(self):
        """Regression: a displaced batcher's on_state hook stayed
        wired to the board — a crash-past-budget while draining its
        leftovers marked the healthy REPLACEMENT unhealthy."""
        reg = ModelRegistry()
        try:
            self._load(reg, "rp")
            b1 = reg.batcher("rp")
            self._load(reg, "rp", seed=5)       # deploy replaces it
            assert b1._on_state is None
            assert reg.health("rp")["state"] == "ready"
            b2 = reg.batcher("rp")
            assert b2 is not b1 and b2._on_state is not None
        finally:
            reg.close()

    def test_unload_losing_race_to_load_heals_board(self):
        """Regression: unload racing a concurrent load could stamp
        'draining' over the freshly-deployed replacement and leave it
        permanently unready (its next batcher created pre-drained)."""
        reg = ModelRegistry()
        try:
            self._load(reg, "rl")
            reg.submit("rl", np.zeros((1, 12), np.float32)).result(10)
            orig_tr = reg._board.transition
            raced = threading.Event()

            def tr(name, state):
                if state == "draining" and not raced.is_set():
                    raced.set()
                    # the concurrent deploy lands BEFORE our draining
                    # mark goes on the board — the classic interleave
                    self._load(reg, "rl", seed=7)
                return orig_tr(name, state)

            reg._board.transition = tr
            try:
                reg.unload("rl", drain=True)
            finally:
                reg._board.transition = orig_tr
            assert raced.is_set()
            # the replacement must be serving, not stuck draining
            assert reg.health("rl")["state"] == "ready"
            out = reg.submit(
                "rl", np.zeros((1, 12), np.float32)).result(10)
            assert out[0].shape == (1, 4)
        finally:
            reg.close()

    def test_load_checkpoint(self, tmp_path):
        from mxnet_tpu import model as model_mod
        net = _mlp()
        params, aux = _params_for(net, 12)
        prefix = str(tmp_path / "ckpt")
        model_mod.save_checkpoint(
            prefix, 3, net,
            {k: v for k, v in params.items()}, dict(aux))
        reg = ModelRegistry()
        try:
            reg.load_checkpoint("ck", prefix, 3,
                                data_shapes={"data": (1, 12)},
                                ladder=BucketLadder(batches=(2,)))
            x = np.ones((2, 12), np.float32)
            out = reg.predict("ck", x)[0].asnumpy()
            ref = _eager(net, params, aux, mx.nd.array(x)).asnumpy()
            assert np.array_equal(out, ref)
        finally:
            reg.close()

    def test_serve_events_emitted(self, tmp_path, monkeypatch):
        from mxnet_tpu.observability import events as obs_events
        monkeypatch.setenv("MXNET_OBS", "serve")
        obs_events.configure(path=str(tmp_path / "events.jsonl"))
        try:
            reg = ModelRegistry()
            self._load(reg, "evm")
            reg.alias("ev-alias", "evm")
            reg.unload("evm")
            evs = obs_events.read_events()
            kinds = [e.get("kind") for e in evs if e["ev"] == "serve"]
            assert "load" in kinds and "alias" in kinds and \
                "unload" in kinds
            assert kinds.count("compile") == 2  # one per bucket rung
        finally:
            obs_events.configure()


# ---------------------------------------------------------------------------
# registry graceful teardown + concurrent lifecycle drills
# ---------------------------------------------------------------------------

class TestRegistryDrainAndCutover:
    def _load(self, reg, name, seed=0):
        net = _mlp()
        params, aux = _params_for(net, 12, seed=seed)
        pred = reg.load(name, net, params, aux_params=aux,
                        data_shapes={"data": (1, 12)},
                        ladder=BucketLadder(batches=(1, 2, 4, 8)))
        return net, params, aux, pred

    def test_unload_drain_completes_accepted(self):
        reg = ModelRegistry()
        try:
            net, params, aux, _ = self._load(reg, "dm")
            reg.batcher("dm", max_wait_ms=60000)  # 60s window: queued
            rs = np.random.RandomState(4)
            xs = [rs.randn(1, 12).astype(np.float32) for _ in range(5)]
            futs = [reg.submit("dm", x) for x in xs]
            reg.unload("dm")                    # drain=True default
            for x, fut in zip(xs, futs):
                out = fut.result(10)[0]
                refs = _rung_refs(net, params, aux, x)
                assert any(np.array_equal(out, r) for r in refs)
            assert reg.names() == []
        finally:
            reg.close()

    def test_unload_without_drain_fails_queued_typed(self):
        reg = ModelRegistry()
        try:
            self._load(reg, "fm")
            reg.batcher("fm", max_wait_ms=60000)
            fut = reg.submit("fm", np.zeros((1, 12), np.float32))
            reg.unload("fm", drain=False)
            with pytest.raises(ServeError, match="closed"):
                fut.result(10)
        finally:
            reg.close()

    def test_alias_cutover_flushes_old_target(self):
        reg = ModelRegistry()
        try:
            net, params, aux, _ = self._load(reg, "v1")
            self._load(reg, "v2", seed=9)
            reg.alias("prod", "v1")
            reg.batcher("v1", max_wait_ms=60000)
            x = np.random.RandomState(5).randn(1, 12).astype(np.float32)
            fut = reg.submit("prod", x)         # accepted by v1
            assert not fut.done()
            reg.alias("prod", "v2")             # cutover flushes v1
            # the flush horizon forces v1's accepted work to dispatch
            # promptly instead of waiting out the 60s window — by the
            # time the cutover returns, the request has landed
            assert fut.done()
            out = fut.result(1)[0]
            ref = _eager(net, params, aux, mx.nd.array(x)).asnumpy()
            assert np.array_equal(out, ref)     # computed by v1, not v2
        finally:
            reg.close()

    def test_concurrent_unload_vs_submit_never_hangs(self):
        """Satellite drill: unload racing in-flight submit traffic —
        every accepted request completes bit-equal or fails with a
        typed ServeError; nothing hangs."""
        reg = ModelRegistry()
        try:
            net, params, aux, _ = self._load(reg, "race")
            reg.batcher("race", max_wait_ms=2)
            rs = np.random.RandomState(6)
            pool = [rs.randn(1, 12).astype(np.float32)
                    for _ in range(8)]
            refs = [_rung_refs(net, params, aux, x) for x in pool]
            accepted, errors = [], []
            stop = threading.Event()

            def writer(tid):
                i = 0
                while not stop.is_set():
                    k = (tid + i) % len(pool)
                    i += 1
                    try:
                        accepted.append((k, reg.submit("race", pool[k])))
                    except ServeError:
                        errors.append("serve")
                    except Exception as e:      # anything untyped fails
                        errors.append("UNTYPED %r" % (e,))
                        return

            threads = [threading.Thread(target=writer, args=(t,))
                       for t in range(3)]
            for t in threads:
                t.start()
            time.sleep(0.15)
            reg.unload("race")                  # drain=True under load
            stop.set()
            for t in threads:
                t.join(10)
                assert not t.is_alive()
            untyped = [e for e in errors if e != "serve"]
            assert untyped == []
            completed = failed = 0
            for k, fut in accepted:
                try:
                    out = fut.result(10)[0]     # bounded: never hangs
                    assert any(np.array_equal(out, r) for r in refs[k])
                    completed += 1
                except ServeError:
                    failed += 1
            assert completed + failed == len(accepted)
            assert completed >= 1               # traffic actually flowed
        finally:
            reg.close()

    def test_concurrent_alias_repoint_vs_submit_bit_equal(self):
        """Satellite drill: alias cutover racing submit traffic.  Both
        targets share parameters, so every successful result must be
        bit-equal to the shared eager forward no matter which side of
        the cutover served it."""
        reg = ModelRegistry()
        try:
            net, params, aux, _ = self._load(reg, "blue", seed=7)
            self._load(reg, "green", seed=7)    # identical params
            reg.alias("prod", "blue")
            reg.batcher("blue", max_wait_ms=2)
            reg.batcher("green", max_wait_ms=2)
            x = np.random.RandomState(8).randn(1, 12).astype(np.float32)
            refs = _rung_refs(net, params, aux, x)
            results, errors = [], []
            stop = threading.Event()

            def writer():
                while not stop.is_set():
                    try:
                        results.append(reg.submit("prod", x))
                    except ServeError:
                        pass
                    except Exception as e:
                        errors.append(e)
                        return

            threads = [threading.Thread(target=writer)
                       for _ in range(3)]
            for t in threads:
                t.start()
            for target in ("green", "blue", "green"):
                time.sleep(0.05)
                reg.alias("prod", target)
            stop.set()
            for t in threads:
                t.join(10)
                assert not t.is_alive()
            assert errors == []
            done = 0
            for fut in results:
                try:
                    out = fut.result(10)[0]
                    assert any(np.array_equal(out, r) for r in refs)
                    done += 1
                except ServeError:
                    pass
            assert done >= 1
        finally:
            reg.close()


# ---------------------------------------------------------------------------
# C-ABI thin client
# ---------------------------------------------------------------------------

class TestCApiBridgeServes:
    def test_predictor_routes_through_registry(self):
        from mxnet_tpu import capi_bridge
        net = _mlp()
        params, _ = _params_for(net, 12)
        x = np.random.RandomState(0).randn(2, 12).astype(np.float32)
        save = {"arg:%s" % k: v for k, v in params.items()}
        param_bytes = mx.nd.save_bytes(save) \
            if hasattr(mx.nd, "save_bytes") else None
        if param_bytes is None:
            import tempfile
            with tempfile.NamedTemporaryFile(suffix=".params") as f:
                mx.nd.save(f.name, save)
                param_bytes = open(f.name, "rb").read()
        handle = capi_bridge.create(net.tojson(), param_bytes, 1, 0,
                                    ["data"], [(2, 12)])
        reg = serve.c_registry()
        assert handle._name in reg.names()
        handle.set_input("data", x.astype(np.float32).tobytes(), (2, 12))
        handle.forward()
        got = np.frombuffer(handle.get_output(0),
                            np.float32).reshape(handle.get_output_shape(0))
        ref = _eager(net, params, {}, mx.nd.array(x)).asnumpy()
        np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-7)
        name = handle._name
        handle.close()
        assert name not in reg.names()
        handle.close()                          # double free is safe

    def test_multi_input_distinct_leading_dims(self):
        """Reference bind semantics preserved: a C predictor whose
        inputs do not share a leading dim (data batch 4, a (1, 6)
        broadcast vector) still creates and forwards — the non-batch
        input rides as fixed-shape outside the bucket ladder."""
        from mxnet_tpu import capi_bridge
        data = sym.var("data")
        wvec = sym.var("wvec")
        net = sym.broadcast_mul(data, wvec)
        handle = capi_bridge.Predictor(net.tojson(), b"", 1, 0,
                                       ["data", "wvec"],
                                       [(4, 6), (1, 6)])
        try:
            rs = np.random.RandomState(1)
            x = rs.randn(4, 6).astype(np.float32)
            v = rs.randn(1, 6).astype(np.float32)
            handle.set_input("data", x.tobytes(), (4, 6))
            handle.set_input("wvec", v.tobytes(), (1, 6))
            handle.forward()
            got = np.frombuffer(handle.get_output(0), np.float32) \
                .reshape(handle.get_output_shape(0))
            assert np.array_equal(got, x * v)
        finally:
            handle.close()

    def test_set_input_shape_mismatch_raises(self):
        from mxnet_tpu import capi_bridge
        net = _mlp()
        params, _ = _params_for(net, 12)
        handle = capi_bridge.Predictor(net.tojson(), b"", 1, 0,
                                       ["data"], [(2, 12)])
        try:
            with pytest.raises(ValueError, match="shape-specialized"):
                handle.set_input("data",
                                 np.zeros((3, 12), np.float32).tobytes(),
                                 (3, 12))
        finally:
            handle.close()


