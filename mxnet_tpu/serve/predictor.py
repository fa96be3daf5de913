"""CompiledPredictor — AOT-compiled inference programs per padding bucket.

Training got its one-donated-program-per-step treatment in PR 1; this
is the inference twin.  A predictor owns:

* the model's pure inference graph (``executor._build_eval`` over the
  bound Symbol, ``training=False``);
* device-committed parameter/aux trees;
* one **ahead-of-time compiled** XLA executable per bucket of the
  :class:`~mxnet_tpu.serve.buckets.BucketLadder` — built via
  ``jit(fn).lower(avals).compile()`` at load/warm time, NEVER in the
  request path.  A compiled executable rejects a mismatched shape with
  a TypeError instead of silently retracing, which is exactly the
  contract serving wants: after warmup the request path cannot compile,
  by construction.

Requests at a natural shape are zero-padded up to their bucket and the
outputs trimmed back (mask-off), proven bit-equal to the unpadded
eager forward in tests/test_serve.py.

Autoregressive decode gets the fused-train-step donation discipline:
:meth:`CompiledPredictor.make_decoder` AOT-compiles a step function
whose KV-cache-style state tree is donated (``donate_argnums``) and
re-donated every step — the cache never copies, and stale host aliases
of donated buffers are poisoned through the graftsan bridge just like
the fused step's weights.
"""

from __future__ import annotations

import time as _time

import numpy as _np

from .buckets import BucketLadder, ServeError
from .. import iraudit as _iraudit
from .. import sanitizer as _san
from ..observability import events as _obs_events
from ..observability import metrics as _obs_metrics
from ..resilience import servechaos as _servechaos

__all__ = ["CompiledPredictor", "DecodeSession"]

# module-level instrument refs (hot path: no registry lookup per call)
_DISPATCH_SECONDS = _obs_metrics.histogram(
    "serve_dispatch_seconds",
    "host-side latency of one compiled-program serve dispatch")
_COMPILES_TOTAL = _obs_metrics.counter(
    "serve_compiles_total",
    "AOT program builds (bucket warmups + decode steps); flat after "
    "warmup or the request path is compiling")
_PADDED_ROWS = _obs_metrics.counter(
    "serve_padded_rows_total",
    "zero-padded rows dispatched (bucket size minus real rows)")
_DEVICE_PUT_ELIDED = _obs_metrics.counter(
    "device_put_elided_total",
    "host->device transfers skipped because the array was already "
    "committed to its target device/sharding (device-resident input)")


def _as_jnp(x):
    """Incoming request array (numpy / NDArray / jax) -> host numpy
    (serving requests originate host-side; the compiled call does the
    single h2d transfer)."""
    data = getattr(x, "_data", None)
    if data is not None:
        return _np.asarray(data)
    return _np.asarray(x)


def _device_resident(arr, dev):
    """AOT-dispatch flavor of ``ndarray._already_placed``: a compiled
    executable has no trace cache, so an input's committedness cannot
    flip a jit cache key here — any live jax array already on *dev*
    may skip the host round trip.  (Compiled-program outputs on CPU
    come back uncommitted, which is exactly the chained-decode case.)
    Deleted/donated buffers fall through to the normal path so the
    real use-after-donate error surfaces at the transfer site."""
    import jax
    if not isinstance(arr, jax.Array):
        return False
    try:
        return arr.devices() == {dev}
    except (RuntimeError, TypeError, AttributeError):
        return False


class CompiledPredictor:
    """AOT-bucketed inference programs for one model.

    Parameters
    ----------
    symbol : Symbol
        The inference graph.
    arg_params : dict name -> array
        Every non-data argument of *symbol*.  Committed to the target
        device at construction.
    aux_params : dict name -> array, optional
        Auxiliary states (BatchNorm running stats, ...).
    data_shapes : dict name -> full shape
        The natural full shape (including a nominal batch dim) of each
        data input — the trailing dims seed :meth:`warm`, and the key
        set defines which symbol arguments are request inputs.
    ladder : BucketLadder, optional
        Defaults to the power-of-two batch ladder.
    data_dtypes : dict name -> dtype, optional
        Request input dtypes (default float32); inputs are cast.
    ctx : Context, optional
        Target device (default: current context).
    name : str
        Model name used in events/errors.
    bucket_inputs : iterable of str, optional
        The data inputs whose leading dim is a batch axis subject to
        the ladder (default: all of them).  Inputs left out are
        **fixed-shape**: requests must match their declared shape
        exactly — no padding, no rung replacement (the C-ABI client
        uses this for multi-input models whose inputs do not share a
        leading dim).
    """

    def __init__(self, symbol, arg_params, aux_params=None,
                 data_shapes=None, ladder=None, data_dtypes=None,
                 ctx=None, name="model", bucket_inputs=None):
        import jax
        import jax.numpy as jnp
        from ..context import current_context
        from ..executor import _build_eval

        if not data_shapes:
            raise ServeError("CompiledPredictor needs data_shapes "
                             "({input name: full shape})")
        self.name = name
        self._symbol = symbol
        self._ctx = ctx or current_context()
        self._dev = self._ctx.jax_device
        self.ladder = ladder or BucketLadder()
        self._data_shapes = {n: tuple(int(d) for d in s)
                             for n, s in data_shapes.items()}
        self._data_dtypes = {
            n: jnp.dtype((data_dtypes or {}).get(n, "float32"))
            for n in self._data_shapes}
        if bucket_inputs is None:
            self._bucket_inputs = frozenset(self._data_shapes)
        else:
            self._bucket_inputs = frozenset(bucket_inputs)
            bad = self._bucket_inputs - set(self._data_shapes)
            if bad:
                raise ServeError(
                    "model %r: bucket_inputs %s are not data inputs"
                    % (name, sorted(bad)))

        arg_names = symbol.list_arguments()
        missing = [n for n in arg_names
                   if n not in self._data_shapes
                   and n not in (arg_params or {})]
        if missing:
            raise ServeError(
                "model %r: arguments %s are neither data inputs nor in "
                "arg_params" % (name, missing))
        unknown = [n for n in self._data_shapes if n not in arg_names]
        if unknown:
            raise ServeError(
                "model %r: data inputs %s are not arguments of the "
                "symbol" % (name, unknown))

        put = lambda a: jax.device_put(
            getattr(a, "_data", None) if getattr(a, "_data", None)
            is not None else jnp.asarray(a), self._dev)
        self._params = {n: put(v) for n, v in (arg_params or {}).items()
                        if n in arg_names and n not in self._data_shapes}
        aux_names = symbol.list_auxiliary_states()
        aux_params = aux_params or {}
        missing_aux = [n for n in aux_names if n not in aux_params]
        if missing_aux:
            raise ServeError("model %r: missing auxiliary states %s"
                             % (name, missing_aux))
        self._aux = {n: put(aux_params[n]) for n in aux_names}
        # fixed base key: inference ops that structurally need rng
        # (none in eval mode for the shipped op set) stay deterministic
        self._key = jax.device_put(jax.random.PRNGKey(0), self._dev)

        self._eval = _build_eval(symbol, False)

        def _predict(params, aux, data, key):
            amap = dict(params)
            amap.update(data)
            outs, _ = self._eval(amap, aux, key)
            return outs

        # the jitted object exists ONLY as the .lower() entry point —
        # its call cache must stay empty (asserted in CI: a non-zero
        # cache size means something traced in the request path)
        self._jit = jax.jit(_predict)
        self._programs = {}        # bucket key -> compiled executable
        self._lock = _san.lock(label="serve.predictor.%s" % name)
        self._compiles = 0
        self._dispatches = 0
        # paged decode engines attached via make_paged_decoder: the
        # registry drains/closes them on unload and alias cutover
        self._decode_engines = []
        # the TuningStore entry the registry attached at load time
        # (None = untuned); DynamicBatcher reads its scalar knobs,
        # health() surfaces it (docs/autotuning.md)
        self.tuning = None
        # quantization report the registry attached at load time
        # (None = fp32): mode, calib sha, per-layer coverage, gate
        # results — surfaced by health() (docs/quantization.md)
        self.quantization = None

    # -- introspection -----------------------------------------------------
    @property
    def compile_count(self):
        """AOT programs built so far (buckets + decoders).  Pinned
        after warmup — a growing count means request-path compiles."""
        return self._compiles

    @property
    def dispatch_count(self):
        return self._dispatches

    def jit_cache_size(self):
        """Size of the traced-call cache of the underlying jit — 0 by
        contract (serving only ever calls AOT executables)."""
        size_of = getattr(self._jit, "_cache_size", None)
        return size_of() if size_of else 0

    def program_keys(self):
        return sorted(self._programs)

    def output_shapes(self, n):
        """Output shapes for a natural batch of *n* rows (trimmed)."""
        shapes = {nm: ((n,) + self._data_shapes[nm][1:])
                  if nm in self._bucket_inputs else self._data_shapes[nm]
                  for nm in self._data_shapes}
        _, out_shapes, _ = self._symbol.infer_shape(**shapes)
        return [tuple(s) for s in out_shapes]

    # -- program cache -----------------------------------------------------
    def _avals(self, shapes):
        import jax
        param_avals = {n: jax.ShapeDtypeStruct(v.shape, v.dtype)
                       for n, v in self._params.items()}
        aux_avals = {n: jax.ShapeDtypeStruct(v.shape, v.dtype)
                     for n, v in self._aux.items()}
        data_avals = {n: jax.ShapeDtypeStruct(tuple(s),
                                              self._data_dtypes[n])
                      for n, s in shapes.items()}
        key_aval = jax.ShapeDtypeStruct(self._key.shape,
                                        self._key.dtype)
        return param_avals, aux_avals, data_avals, key_aval

    def _bucket_shapes(self, natural_shapes):
        """{name: padded full shape} for a request's natural shapes —
        batch dims must agree across the bucketed inputs; fixed-shape
        inputs must match their declared shape exactly."""
        batches = {s[0] for n, s in natural_shapes.items()
                   if s and n in self._bucket_inputs}
        if len(batches) > 1:
            raise ServeError(
                "model %r: inputs disagree on batch size (%s)"
                % (self.name, sorted(batches)))
        out = {}
        for n, s in natural_shapes.items():
            if n in self._bucket_inputs:
                out[n] = self.ladder.pad_shape(s)
            elif tuple(s) != self._data_shapes[n]:
                raise ServeError(
                    "model %r fixed-shape input %r: %s does not match "
                    "the declared %s (it is outside bucket_inputs — "
                    "no padding applies)"
                    % (self.name, n, tuple(s), self._data_shapes[n]))
            else:
                out[n] = tuple(s)
        return out

    def ensure_program(self, shapes):
        """Get-or-build the compiled executable for a {name: padded
        full shape} bucket.  Builds are serialized, timed, counted and
        evented (``serve`` category, compile-blame = the bucket key);
        the hit path is one lock-free dict read."""
        key = self.ladder.bucket_key(shapes)
        prog = self._programs.get(key)
        if prog is not None:
            return prog
        with self._lock:
            prog = self._programs.get(key)
            if prog is not None:
                return prog
            # chaos choke point (reject_warm_at): a failed build must
            # propagate as a typed error, never half-register a model
            _servechaos.on_warm(self.name)
            pa, aa, da, ka = self._avals(shapes)
            t0 = _time.perf_counter()
            lowered = self._jit.lower(pa, aa, da, ka)
            if _iraudit.enabled():
                self._audit_rung(key, shapes, lowered.as_text())
            prog = lowered.compile()
            dt = _time.perf_counter() - t0
            self._programs[key] = prog
            self._compiles += 1
            _COMPILES_TOTAL.inc()
            _obs_events.emit(
                "serve", kind="compile", model=self.name,
                bucket=[list(s) for _, s in key],
                seconds=round(dt, 4), programs=len(self._programs))
            return prog

    def _audit_rung(self, key, shapes, text):
        """MXNET_IR_AUDIT hook: register this bucket program with the
        graftir auditor, declaring the rung geometry (GI004 pad-waste:
        the worst natural batch this rung serves is one past the rung
        below) and the ladder size as the program budget (GI005: a
        request-path compile past the warm set is budget growth)."""
        rows = next((shapes[n][0] for n in sorted(self._bucket_inputs)
                     if shapes[n]), None)
        natural = None
        if rows is not None:
            below = [b for b in self.ladder.batches if b < rows]
            natural = (max(below) + 1) if below else 1
        qmode = (self.quantization or {}).get("mode") \
            if isinstance(self.quantization, dict) else None
        _iraudit.audit(
            "serve", "predict/b%s" % rows, text, model=self.name,
            hot_path=True, dtype_policy=qmode,
            bucket_rows=rows, natural_rows=natural,
            budget=len(self.ladder.batches))

    def rung_shapes(self, b):
        """The padded input shapes of the rung that serves a natural
        batch of *b* rows (construction data shapes, bucket-rounded)."""
        return {n: ((self.ladder.batch_for(b),) + tuple(
            self.ladder.round_axis(ax, d)
            for ax, d in enumerate(s[1:], start=1)))
            if n in self._bucket_inputs else s
            for n, s in self._data_shapes.items()}

    def lowered_text(self, shapes):
        """StableHLO of the program for *shapes* (lower only, no
        compile) — what the quantization gate greps for int8 compute
        and costs.py prices."""
        pa, aa, da, ka = self._avals(shapes)
        return self._jit.lower(pa, aa, da, ka).as_text()

    def warm(self, batches=None):
        """Pre-compile one program per batch rung (at the construction
        data shapes) so the request path starts hot, and PRIME each
        with one zero-input execution — first executions pay one-time
        runtime setup that must not land on the first real request.
        Returns the number of programs built."""
        before = self._compiles
        for b in (batches or self.ladder.batches):
            shapes = self.rung_shapes(b)
            prog = self.ensure_program(shapes)
            zeros = {n: _np.zeros(s, self._data_dtypes[n])
                     for n, s in shapes.items()}
            for out in prog(self._params, self._aux, zeros, self._key):
                out.block_until_ready()
        return self._compiles - before

    # -- request path ------------------------------------------------------
    def predict(self, data, key=None, trim=True):
        """Run one padded-bucket dispatch.

        *data*: {input name: array} (numpy / NDArray / jax), or a
        single array when the model has exactly one input.  An array
        missing the batch dim (ndim == example ndim - 1) counts as a
        single example.  Returns the outputs as NDArrays, trimmed to
        the natural batch size.

        ``trim=False`` returns the padded rung's rows instead.  A
        device-side trim dispatches — and on first use COMPILES — one
        tiny slice program per distinct (rung, rows) pair, which no
        warm-up covers; a caller that reads the outputs back to the
        host anyway (the batcher) trims there.
        """
        from ..ndarray import NDArray

        if not isinstance(data, dict):
            if len(self._data_shapes) != 1:
                raise ServeError(
                    "model %r has %d inputs — pass a dict"
                    % (self.name, len(self._data_shapes)))
            data = {next(iter(self._data_shapes)): data}
        arrays = {}
        for n in self._data_shapes:
            if n not in data:
                raise ServeError("model %r: request is missing input %r"
                                 % (self.name, n))
            a = _as_jnp(data[n])
            if a.ndim == len(self._data_shapes[n]) - 1:
                a = a[None]    # single example -> batch of one
            if a.ndim != len(self._data_shapes[n]):
                raise ServeError(
                    "model %r input %r: rank %d does not match the "
                    "bound example rank %d"
                    % (self.name, n, a.ndim, len(self._data_shapes[n])))
            arrays[n] = a
        natural = {n: a.shape for n, a in arrays.items()}
        bucketed = [n for n in natural if n in self._bucket_inputs]
        rows = natural[bucketed[0]][0] if bucketed else None
        shapes = self._bucket_shapes(natural)
        prog = self.ensure_program(shapes)

        padded = {}
        for n, a in arrays.items():
            target = shapes[n]
            dt = self._data_dtypes[n]
            if tuple(a.shape) == target and a.dtype == dt:
                padded[n] = a
                continue
            buf = _np.zeros(target, dt)
            buf[tuple(slice(0, s) for s in a.shape)] = a
            padded[n] = buf
        bucket_rows = shapes[bucketed[0]][0] if bucketed else None
        if bucketed and bucket_rows > rows:
            _PADDED_ROWS.inc(bucket_rows - rows)

        t0 = _time.perf_counter()
        with _san.transfer_guard("serve dispatch (%s)" % self.name):
            outs = prog(self._params, self._aux, padded,
                        key if key is not None else self._key)
        _DISPATCH_SECONDS.observe(_time.perf_counter() - t0)
        with self._lock:
            self._dispatches += 1
        trimmed = []
        for o in outs:
            if trim and bucketed and rows != bucket_rows and \
                    getattr(o, "shape", None) and o.shape and \
                    o.shape[0] == bucket_rows:
                o = o[:rows]
            trimmed.append(NDArray(o))
        return trimmed

    # -- parameter refresh -------------------------------------------------
    def set_params(self, arg_params, aux_params=None):
        """Swap in new parameter values WITHOUT recompiling — shapes
        and dtypes must match the compiled avals (a changed shape
        raises; that is a new model, load it under a new name)."""
        import jax
        import jax.numpy as jnp
        for n, v in (arg_params or {}).items():
            if n not in self._params:
                raise ServeError("model %r has no parameter %r"
                                 % (self.name, n))
            cur = self._params[n]
            arr = getattr(v, "_data", None)
            arr = arr if arr is not None else jnp.asarray(v)
            if tuple(arr.shape) != tuple(cur.shape) or \
                    arr.dtype != cur.dtype:
                raise ServeError(
                    "parameter %r changed shape/dtype (%s %s -> %s %s) "
                    "— compiled programs are shape-specialized"
                    % (n, cur.shape, cur.dtype, arr.shape, arr.dtype))
            self._params[n] = jax.device_put(arr, self._dev)
        for n, v in (aux_params or {}).items():
            if n not in self._aux:
                raise ServeError("model %r has no aux state %r"
                                 % (self.name, n))
            arr = getattr(v, "_data", None)
            arr = arr if arr is not None else jnp.asarray(v)
            self._aux[n] = jax.device_put(arr, self._dev)

    # -- autoregressive decode ---------------------------------------------
    def make_decoder(self, step_fn, cache, input_shapes,
                     input_dtypes=None, donate=None, label="decode"):
        """AOT-compile an autoregressive step and return a
        :class:`DecodeSession` that threads its donated state.

        *step_fn(params, cache, inputs, step)* must be pure and return
        ``(outputs, new_cache)`` with ``new_cache`` matching *cache*'s
        tree structure/avals exactly (the donation contract: every
        step's outputs become the next step's donated inputs, like the
        fused train step's weights).  *step* is an int32 scalar the
        session advances — fold it into a key in-graph for stochastic
        decode, never host-side.

        *donate* defaults to ``ops.registry.supports_donation()`` (CPU
        XLA ignores donation and would warn per call); pass ``True``
        to force the declaration — the graftsan donation component
        checks DECLARED donation, so CI exercises the discipline on
        CPU.
        """
        import jax
        import jax.numpy as jnp
        from ..ops.registry import supports_donation

        if donate is None:
            donate = supports_donation()
        cache = jax.tree_util.tree_map(
            lambda a: jax.device_put(
                getattr(a, "_data", None)
                if getattr(a, "_data", None) is not None
                else jnp.asarray(a), self._dev), cache)
        jitted = jax.jit(step_fn,
                         donate_argnums=(1,) if donate else ())
        pa = {n: jax.ShapeDtypeStruct(v.shape, v.dtype)
              for n, v in self._params.items()}
        ca = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), cache)
        dtypes = input_dtypes or {}
        ia = {n: jax.ShapeDtypeStruct(
            tuple(int(d) for d in s),
            jnp.dtype(dtypes.get(n, "float32")))
            for n, s in input_shapes.items()}
        step_aval = jax.ShapeDtypeStruct((), jnp.int32)
        t0 = _time.perf_counter()
        lowered = jitted.lower(pa, ca, ia, step_aval)
        # materialize the StableHLO once at build time (tests check the
        # donation declaration) instead of pinning the whole Lowered
        # object for the life of a long-running decode session
        lowered_text = lowered.as_text()
        compiled = lowered.compile()
        del lowered
        dt = _time.perf_counter() - t0
        with self._lock:
            self._compiles += 1
        _COMPILES_TOTAL.inc()
        _obs_events.emit("serve", kind="compile", model=self.name,
                         decoder=label, donated=bool(donate),
                         seconds=round(dt, 4))
        return DecodeSession(self, compiled, cache, ia, donate, label,
                             lowered_text=lowered_text)

    def make_paged_decoder(self, step_fn, prefill_fn=None,
                           token_spec=None, input_spec=None, **kwargs):
        """Build a continuously-batched paged-KV decode engine bound
        to this model: shares its parameters/device/compile
        accounting, and the registry's unload/alias-cutover drains it
        with the model (docs/serving.md "Continuous-batching
        decode").  See :class:`~mxnet_tpu.serve.decode.DecodeEngine`
        for the step/prefill contract and knobs."""
        from .decode import DecodeEngine
        kwargs.setdefault("label", "%s.decode" % self.name)
        return DecodeEngine(step_fn, prefill_fn=prefill_fn,
                            token_spec=token_spec,
                            input_spec=input_spec,
                            predictor=self, **kwargs)


class DecodeSession:
    """One live autoregressive decode: holds the donated cache tree
    and threads it through the compiled step — the serve-side mirror
    of the fused train step's state discipline (cache buffers are
    donated every step and never copied; stale aliases are poisoned
    when the graftsan donation component is on)."""

    def __init__(self, predictor, compiled, cache, input_avals, donate,
                 label, lowered_text=None):
        self._predictor = predictor
        self._compiled = compiled
        self._cache = cache
        self._input_avals = input_avals
        self._donate = donate
        self._label = label
        self._lowered_text = lowered_text
        self._t = 0

    @property
    def step_count(self):
        return self._t

    @property
    def cache(self):
        """The live cache tree (the CURRENT buffers; yesterday's were
        donated — do not keep references across steps)."""
        return self._cache

    def lowered_text(self):
        """StableHLO of the step program (tests check the donation
        declaration survived AOT compilation)."""
        return self._lowered_text or ""

    def step(self, inputs):
        """Run one decode step; returns the step outputs and advances
        the donated cache in place."""
        import jax
        import numpy as np

        pred = self._predictor
        data = {}
        for n, aval in self._input_avals.items():
            if n not in inputs:
                raise ServeError("decode %r: missing input %r"
                                 % (self._label, n))
            raw = inputs[n]
            raw = getattr(raw, "_data", None) \
                if getattr(raw, "_data", None) is not None else raw
            if _device_resident(raw, pred._dev):
                # the previous step's output fed back as this step's
                # input: already committed to the target device — the
                # old np.asarray round trip forced a full d2h readback
                # of every output per token.  Elide it (the PR-11
                # committedness rule) and count the avoided transfer.
                a = raw
                _DEVICE_PUT_ELIDED.inc()
            else:
                a = _as_jnp(raw)
            if tuple(a.shape) != tuple(aval.shape):
                raise ServeError(
                    "decode %r input %r: shape %s does not match the "
                    "compiled %s (decode programs are fixed-shape; "
                    "pad upstream)" % (self._label, n,
                                       tuple(a.shape),
                                       tuple(aval.shape)))
            data[n] = a.astype(aval.dtype) if a.dtype != aval.dtype \
                else a
        old_leaves = jax.tree_util.tree_leaves(self._cache) \
            if self._donate and _san.enabled("donation") else None
        t0 = _time.perf_counter()
        with _san.transfer_guard("serve decode step (%s)" % self._label):
            outs, new_cache = self._compiled(
                pred._params, self._cache, data, np.int32(self._t))
        _DISPATCH_SECONDS.observe(_time.perf_counter() - t0)
        with pred._lock:
            pred._dispatches += 1
        self._cache = new_cache
        self._t += 1
        if old_leaves is not None:
            # every framework-visible container now points at the new
            # buffers; anything still aliasing the donated cache is
            # stale — same poison rule as the fused step's weights
            _san.poison_donated(
                old_leaves, "serve decode step %d (%s)"
                % (self._t - 1, self._label))
        return outs
