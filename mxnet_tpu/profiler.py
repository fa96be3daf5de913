"""Profiler — chrome://tracing output + aggregate op stats.

Reference capability: `src/profiler/profiler.h:87-108,256` (chrome-trace
JSON writer, mode bitmask, per-op stats) with the Python surface
`python/mxnet/profiler.py:33-151` (set_config/set_state/dump/dumps +
scriptable Task/Frame/Event/Counter/Marker objects).

TPU-native design: host-side spans are collected in-process (op dispatch
in `ops/registry.invoke`, executor forward/backward, API scopes); when
profiling is on, op calls block on their results so spans measure real
execution, not async dispatch (the reference's engine profiles the
worker thread for the same reason).  Device-side timelines come from the
XLA profiler: ``set_config(profile_device=True)`` starts a
``jax.profiler`` trace whose TensorBoard-loadable output lands next to
the chrome-trace file.

Spans: :class:`scope` is the one span API and ``_spans`` the one store, a
bounded ring read with :func:`spans`.  The program's own ``mx.*`` scopes
(docs/observability.md "Spans") are always on; each is mirrored as a
``jax.profiler.TraceAnnotation``, so while a device trace is taken it
also lies on the profiler's host plane, on the device trace's clock.
"""

from __future__ import annotations

import collections
import gc
import itertools
import json
import logging
import os
import threading
import time
import zlib

import jax.monitoring
from jax.profiler import TraceAnnotation

from . import sanitizer as _san
from .observability import costs as _costs
from .observability import metrics as _metrics

__all__ = ["set_config", "set_state", "pause", "resume", "dump", "dumps",
           "profiler_set_config", "profiler_set_state",
           "Domain", "Task", "Frame", "Event", "Counter", "Marker",
           "scope", "spans", "Span", "scope_map", "set_scope_map",
           "cost_map", "cost_totals",
           "compile_seconds", "bump_counter", "counter_value", "counters",
           "reset_counters", "collect_step_stats", "emit_step_stat",
           "register_step_stat", "fold_step_stats"]

_lock = _san.rlock(label="profiler._lock")
_marks = []             # chrome trace counter ('C') and marker ('i') dicts
_agg = {}               # name -> [count, total_us, min_us, max_us]

# -- the span store -----------------------------------------------------------
#: one finished host span: times on `time.perf_counter`, `thread` the
#: ident of the thread it ran on, `parent` the id of the span that was
#: open on that thread when it began (None at the top)
Span = collections.namedtuple(
    "Span", "id name cat start end thread parent args")

SPAN_RING = 65536       # spans kept; the oldest leave first
_spans = collections.deque(maxlen=SPAN_RING)
_ids = itertools.count(1)
_open = threading.local()   # .stack: ids of the spans open on this thread


def _stack():
    try:
        return _open.stack
    except AttributeError:
        _open.stack = []
        return _open.stack


def _store(name, cat, start, end, thread=None, parent=None, args=None,
           span_id=None):
    """Append one finished span to the ring (lock-free: a deque append is
    atomic) and, while profiling runs, to the aggregate table."""
    _spans.append(Span(span_id or next(_ids), name, cat, start, end,
                       thread or threading.get_ident(), parent, args))
    if is_running():
        dur_us = (end - start) * 1e6
        with _lock:
            st = _agg.setdefault(name, [0, 0.0, float("inf"), 0.0])
            st[0] += 1
            st[1] += dur_us
            st[2] = min(st[2], dur_us)
            st[3] = max(st[3], dur_us)


def spans(since=None):
    """The finished spans still in the ring, oldest first; with *since*
    (a `time.perf_counter` reading) those that ended at or after it."""
    while True:
        try:
            out = list(_spans)
            break
        except RuntimeError:    # another thread (or a collection) appended
            continue
    if since is not None:
        out = [s for s in out if s.end >= since]
    return out


# -- the compiled program: optimized-HLO instruction -> op_name, and its cost --
# `jax.named_scope` names ("mx.loss", "<op>:<node>", "mx.flash.fwd") reach
# the compiled program as each instruction's `op_name` metadata; a device
# trace names its events by instruction, so the map from the one to the
# other is what lets a reader of the trace say which phase, operator or
# kernel an event belongs to.  The same text prints every instruction's
# shapes, so it also says what each moves and multiplies: `cost_map`.
# What is kept is the text, packed, until a map is first asked for: one
# parse (`observability/costs.py`) gives the scope map and is then priced
# when a cost is first asked for.  Bytes, plain strings and numbers only,
# so that no trainer and no array is kept alive; nothing is parsed in a
# run that asks for no map, and the scope map depends on the parse alone:
# a text that the pricing cannot read leaves the costs None, as a program
# from before the cost map has none.
class _CompiledText:
    __slots__ = ("packed", "xla", "parsed", "scopes", "costs", "totals")

    def __init__(self, hlo_text):
        self.packed = zlib.compress(hlo_text.encode(), 1)
        self.xla = self.parsed = self.scopes = None
        self.costs = self.totals = None

    def parse(self):
        """The text parsed, once: the work is done outside the
        profiler's lock (a span on another thread does not wait for it)
        and only the result is put in place under it."""
        packed = self.packed
        if packed is not None:
            parsed = _costs.parse_optimized_hlo(
                zlib.decompress(packed).decode())
            scopes = _costs.hlo_op_names(parsed)
            with _lock:
                if self.packed is not None:
                    self.parsed, self.scopes, self.packed = \
                        parsed, scopes, None
        return self

    def price(self):
        """The parse priced, once, and then let go of."""
        parsed = self.parse().parsed
        if parsed is not None:
            try:
                costs, totals = _costs.price_optimized_hlo(parsed)
            except Exception:   # a text some other XLA prints differently
                logging.getLogger(__name__).warning(
                    "the compiled text could not be priced", exc_info=True)
                costs = totals = None
            with _lock:
                if self.parsed is not None:
                    self.costs, self.totals, self.parsed = \
                        costs, totals, None
        return self


_compiled = {}


def set_scope_map(program, hlo_text, cost_analysis=None):
    """Keep *program*'s optimized HLO text (`Compiled.as_text()`), packed,
    for `scope_map`, `cost_map` and `cost_totals`, with XLA's own
    `Compiled.cost_analysis()` where the caller has it at hand: its
    `bytes accessed` and `flops` are the two numbers kept of it."""
    _compiled[program] = kept = _CompiledText(hlo_text)
    if isinstance(cost_analysis, (list, tuple)):
        cost_analysis = cost_analysis[0] if cost_analysis else None
    if cost_analysis:
        kept.xla = {"bytes_accessed": float(
                        cost_analysis.get("bytes accessed", 0.0)),
                    "flops": float(cost_analysis.get("flops", 0.0))}


def scope_map(program):
    """``{instruction name: op_name}`` of *program* ("parallel_step": the
    `ParallelTrainer` step), or None before its first call."""
    kept = _compiled.get(program)
    return None if kept is None else kept.parse().scopes


def cost_map(program):
    """``{instruction name: record}`` for every instruction of *program*'s
    optimized HLO that can run as a device event, or None before its
    first call.  A record says what the instruction is (`op_name`,
    `opcode`, the `computation` it stands in, a fusion's `kind`, a custom
    call's `target` and `kernel`),
    the logical bytes it reads and writes (`bytes_read`, `bytes_written`;
    by memory `hbm_bytes_*` and `onchip_bytes_*`), its `mxu_flops` (None
    for a kernel that states none) and `bytes_by_scope`, its HBM bytes by
    the op_name they belong to.  Priced on the first request;
    docs/observability.md "What the compiled step costs"."""
    kept = _compiled.get(program)
    return None if kept is None else kept.price().costs


def cost_totals(program):
    """The cost map's sums over *program* as XLA adds a module up (a
    loop's body once, a conditional's dearest branch), the ENTRY
    computation's name under ``entry`` and, under ``xla``, the
    `bytes_accessed` and `flops` of XLA's own `cost_analysis()` (None
    where the caller of `set_scope_map` had none); None before the
    program's first call."""
    kept = _compiled.get(program)
    totals = None if kept is None else kept.price().totals
    return None if totals is None else dict(totals, xla=kept.xla)


# -- what a program's first call spends, by jax.monitoring event --------------
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        "jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration":
        "backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec":
        "cache_retrieval_time_sec",
}
_compile_seconds = {
    short: _metrics.counter(
        "jax_%s_seconds" % short.replace("_sec", ""),
        "seconds JAX reported under its monitoring event %s" % event)
    for event, short in _COMPILE_EVENTS.items()}


def _on_duration(event, seconds, **_):
    short = _COMPILE_EVENTS.get(event)
    if short is not None and seconds > 0:
        _compile_seconds[short].inc(seconds)


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_seconds():
    """Seconds so far under each of JAX's compile events: tracing to a
    jaxpr, lowering to MLIR, the backend's compile, and loading from the
    persistent cache.  A difference of two readings says what a span
    spent on each."""
    return {short: c.value for short, c in _compile_seconds.items()}


# -- full collections of Python's heap ----------------------------------------
_gc_started = [None]


def _on_gc(phase, info):
    """`gc.callbacks` entry: one `mx.gc` span per generation-2 collection,
    on the thread it stopped (a candidate for a stalled step that nothing
    else records)."""
    if info["generation"] != 2:
        return
    if phase == "start":
        _gc_started[0] = time.perf_counter()
    elif _gc_started[0] is not None:
        stack = _stack()
        _store("mx.gc", "gc", _gc_started[0], time.perf_counter(), None,
               stack[-1] if stack else None,
               {"collected": info.get("collected", 0)})
        _gc_started[0] = None


gc.callbacks.append(_on_gc)


# -- dispatch / compile counters --------------------------------------------
# Always-on, like the `mx.*` spans: these are the
# observable for the fused-train-step contract — "after warmup, one
# training step is exactly ONE jitted dispatch and ZERO compiles" —
# and tests must be able to assert it without turning tracing on.
# Sites:  eager_dispatches       ops/registry.invoke (per eager op)
#         executor_dispatches    LOGICAL executor-level calls
#                                (forward/train_step); a group2ctx
#                                segment-chained step counts ONCE even
#                                though it issues one program per
#                                segment — the counter's contract is
#                                the fused-step assertion, which never
#                                applies to grouped executors
#         fused_step_dispatches  Module full-fused step invocations
#         fused_step_compiles    fused-step trace-time (bumped inside the
#                                traced body, so cached executions add 0)
#         tree_apply_dispatches  Module partial-fused (multi-device)
#                                tree-update invocations
#         tree_apply_compiles    tree-update trace-time
#         parallel_step_dispatches / parallel_step_compiles
#                                ParallelTrainer fit_batch step
#
# Historically these lived in a private lock-free dict here; they are
# now Counter instruments in observability.metrics.REGISTRY (one
# uncontended per-counter lock — built from the sanitizer factories,
# so graftsan audits it — instead of the contended profiler RLock this
# comment used to justify avoiding), and this module keeps the
# original bump/value/snapshot surface as the compatibility layer.
# The same numbers the fused-step tests assert are what a scraper
# reads from metrics.exposition().

#: names bumped through this layer (so counters()/reset_counters keep
#: their historical "only the dispatch counters" scope even though the
#: registry also holds latency histograms and subsystem instruments)
_count_names = set()
_instruments = {}           # name -> Counter (lookup-free hot path)


def bump_counter(name, n=1):
    """Increment a named dispatch/compile counter (registry-backed)."""
    inst = _instruments.get(name)
    if inst is None:
        inst = _instruments[name] = _metrics.counter(
            name, "profiler dispatch/compile counter")
        _count_names.add(name)
    inst.inc(n)


def counter_value(name):
    inst = _instruments.get(name)
    return inst.value if inst is not None else 0


def counters():
    """Snapshot of all dispatch/compile counters."""
    return {name: _instruments[name].value
            for name in list(_count_names)}


def reset_counters():
    for name in list(_count_names):
        _instruments[name]._reset()


# -- statistics that leave a compiled step ------------------------------------
# An op that counts something inside a compiled step (tokens routed to
# each expert) hands the small device array to `emit_step_stat` while it
# is traced.  The program that traces the step collects them
# (`collect_step_stats`) and returns them beside its results;
# `ParallelTrainer` keeps them until they are ready and then folds them
# into counters on the host (`fold_step_stats`), through the function
# registered for the name.  Outside a collection the value is dropped:
# no callback, no readback, no extra dispatch.
_collecting = threading.local()     # .open: the dicts being collected
_stat_folds = {}                    # name -> fold(stacked numpy array)


class collect_step_stats:
    """While open on this thread, `emit_step_stat` values land in the
    dict this yields: ``name -> [arrays, in the order emitted]``."""

    def __enter__(self):
        self._stats = {}
        if not hasattr(_collecting, "open"):
            _collecting.open = []
        _collecting.open.append(self._stats)
        return self._stats

    def __exit__(self, *exc):
        _collecting.open.pop()


def emit_step_stat(name, value):
    """Hand *value* (a small array, traced or not) to the innermost open
    collection under *name*; dropped where none is open."""
    open_ = getattr(_collecting, "open", None)
    if open_:
        open_[-1].setdefault(name, []).append(value)


def register_step_stat(name, fold):
    """``fold(values)`` turns one step's stacked *name* values (a numpy
    array, one row per emission) into counters."""
    _stat_folds[name] = fold


def fold_step_stats(stats):
    """Fold one step's ``name -> numpy array`` into the counters."""
    for name, values in stats.items():
        fold = _stat_folds.get(name)
        if fold is not None:
            fold(values)
_config = {
    "filename": "profile.json",
    "profile_all": False,
    "profile_symbolic": True,
    "profile_imperative": True,
    "profile_api": False,
    "profile_memory": False,
    "profile_device": False,
    "aggregate_stats": False,
}
_state = {"running": False, "paused": False, "jax_trace": None}


def is_running():
    return _state["running"] and not _state["paused"]


# rank-0 worker can drive the profiler running inside kvstore SERVER
# processes (reference: include/mxnet/kvstore.h:43-56 profiler commands,
# python/mxnet/profiler.py profile_process='server',
# tests/nightly/test_server_profiling.py)
_kvstore_handle = None


def set_kvstore_handle(kv):
    """Register the dist kvstore used to route 'server' profiler
    commands (reference: profiler.py set_kvstore_handle)."""
    global _kvstore_handle
    _kvstore_handle = kv


def _to_server(head, body):
    if _kvstore_handle is None:
        raise ValueError(
            "profile_process='server' needs a dist kvstore (create one "
            "first; it registers itself)")
    _kvstore_handle._send_command_to_servers(head, body)


def _check_process(profile_process):
    if profile_process not in ("worker", "server"):
        raise ValueError("profile_process must be 'worker' or 'server', "
                         "got %r" % (profile_process,))
    return profile_process == "server"


def set_config(profile_process="worker", **kwargs):
    """Configure (reference: profiler.py set_config:33).  Accepts the
    reference's kwargs; unknown keys are rejected.
    ``profile_process='server'`` configures the profiler inside every
    kvstore server process instead."""
    if _check_process(profile_process):
        _to_server("profiler:set_config", kwargs)
        return
    for k, v in kwargs.items():
        if k not in _config:
            raise ValueError("unknown profiler option %r (known: %s)"
                             % (k, sorted(_config)))
        _config[k] = v


def set_state(state="stop", profile_process="worker"):
    """'run' starts collection, 'stop' ends it
    (reference: profiler.py set_state:89)."""
    if state not in ("run", "stop"):
        raise ValueError("state must be 'run' or 'stop'")
    if _check_process(profile_process):
        _to_server("profiler:set_state", state)
        return
    if state == "run" and not _state["running"]:
        _state["running"] = True
        _state["paused"] = False
        if _config["profile_device"]:
            import jax
            trace_dir = os.path.splitext(_config["filename"])[0] + \
                "_device"
            try:
                jax.profiler.start_trace(trace_dir)
                _state["jax_trace"] = trace_dir
            except Exception as e:
                _state["jax_trace"] = None
                logging.getLogger(__name__).warning(
                    "profile_device=True, but no device trace is being "
                    "taken: jax.profiler.start_trace(%r) failed: %s: %s",
                    trace_dir, type(e).__name__, e)
    elif state == "stop" and _state["running"]:
        _state["running"] = False
        if _state["jax_trace"]:
            import jax
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            _state["jax_trace"] = None


def pause():
    _state["paused"] = True


def resume():
    _state["paused"] = False


def record_span(name, cat, t0_s, t1_s, tid=0, args=None):
    """Add one complete span, only while profiling runs; timestamps in
    seconds on `time.perf_counter`."""
    if not is_running():
        return
    stack = _stack()
    _store(name, cat, t0_s, t1_s, tid, stack[-1] if stack else None, args)


def record_counter(name, value):
    # perf_counter, NOT time.time(): spans are stamped on the
    # monotonic base (record_span t0/t1 come from perf_counter), and a
    # trace mixing clock bases scatters counters decades away from the
    # spans in Perfetto
    if not is_running():
        return
    with _lock:
        _marks.append({"name": name, "ph": "C",
                       "ts": time.perf_counter() * 1e6,
                       "pid": os.getpid(), "tid": 0,
                       "args": {name: value}})


def record_marker(name, cat="marker"):
    if not is_running():
        return
    with _lock:
        _marks.append({"name": name, "cat": cat, "ph": "i",
                       "ts": time.perf_counter() * 1e6,
                       "pid": os.getpid(), "tid": 0, "s": "p"})


def dump(finished=True, profile_process="worker"):
    """Write the chrome-trace JSON (reference: profiler.py dump:122);
    load it at chrome://tracing or ui.perfetto.dev."""
    if _check_process(profile_process):
        _to_server("profiler:dump", bool(finished))
        return None
    if finished:
        set_state("stop")
    # flush the metrics-registry instruments as chrome-trace Counter
    # ('C') events at dump time, so ONE trace file carries both the
    # spans and the final instrument values (histograms flatten to
    # their count/sum pair — enough to spot "4000 host transfers
    # inside this window" next to the spans that caused them).
    # perf_counter base to land ON the spans' timeline (see
    # record_counter)
    now_us = time.perf_counter() * 1e6
    pid = os.getpid()
    counter_events = []
    for name, snap in _metrics.snapshot().items():
        if snap["kind"] == "histogram":
            args = {"count": snap["count"], "sum": snap["sum"]}
        else:
            args = {name: snap["value"]}
        counter_events.append({"name": "metrics/" + name, "ph": "C",
                               "ts": now_us, "pid": pid, "tid": 0,
                               "args": args})
    span_events = [
        {"name": s.name, "cat": s.cat, "ph": "X", "ts": s.start * 1e6,
         "dur": (s.end - s.start) * 1e6, "pid": pid, "tid": s.thread,
         **({"args": s.args} if s.args else {})}
        for s in spans()]
    with _lock:
        data = {"traceEvents": span_events + list(_marks) + counter_events,
                "displayTimeUnit": "ms"}
        with open(_config["filename"], "w") as f:
            json.dump(data, f)
    return _config["filename"]


def dumps(reset=False):
    """Aggregate per-op stats table (reference: aggregate_stats.cc /
    profiler.dumps)."""
    with _lock:
        lines = ["%-40s %8s %12s %12s %12s %12s" % (
            "Name", "Calls", "Total(us)", "Avg(us)", "Min(us)",
            "Max(us)")]
        for name, (cnt, tot, mn, mx) in sorted(
                _agg.items(), key=lambda kv: -kv[1][1]):
            lines.append("%-40s %8d %12.1f %12.1f %12.1f %12.1f" % (
                name[:40], cnt, tot, tot / max(cnt, 1), mn, mx))
        if reset:
            _agg.clear()
    return "\n".join(lines)


def reset():
    with _lock:
        _spans.clear()
        _marks.clear()
        _agg.clear()


# reference aliases
profiler_set_config = set_config
profiler_set_state = set_state


class scope:
    """Context manager timing a named host-side span: always recorded
    (two clock reads, a ring append, and a `TraceAnnotation` that is a
    flag test while no device trace runs), closed on an exception too.
    `start` and `end` are its `time.perf_counter` readings; `args`, set
    any time before it closes, travels with the span."""

    __slots__ = ("name", "cat", "args", "start", "end", "_id", "_parent",
                 "_ann")

    def __init__(self, name, cat="user"):
        self.name = name
        self.cat = cat
        self.args = None

    def __enter__(self):
        stack = _stack()
        self._parent = stack[-1] if stack else None
        self._id = next(_ids)
        stack.append(self._id)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.end = time.perf_counter()
        self._ann.__exit__(*exc)
        _stack().pop()
        _store(self.name, self.cat, self.start, self.end, None,
               self._parent, self.args, self._id)


class Domain:
    """Grouping namespace for user objects (reference: Domain)."""

    def __init__(self, name):
        self.name = name

    def __repr__(self):
        return "Domain(%s)" % self.name


class _Span:
    def __init__(self, name, domain=None):
        self.name = name if domain is None else \
            "%s::%s" % (domain.name, name)
        self._t0 = None

    def start(self):
        self._t0 = time.perf_counter()

    def stop(self):
        if self._t0 is not None:
            record_span(self.name, self._cat, self._t0,
                        time.perf_counter())
            self._t0 = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()


class Task(_Span):
    _cat = "task"


class Frame(_Span):
    _cat = "frame"


class Event(_Span):
    _cat = "event"


class Marker:
    def __init__(self, name, domain=None):
        self.name = name if domain is None else \
            "%s::%s" % (domain.name, name)

    def mark(self, scope="process"):
        record_marker(self.name)


class Counter:
    """User counter (reference: ProfileCounter)."""

    def __init__(self, name, domain=None, value=0):
        self.name = name if domain is None else \
            "%s::%s" % (domain.name, name)
        self._value = value
        record_counter(self.name, value)

    def set_value(self, value):
        self._value = value
        record_counter(self.name, value)

    def increment(self, delta=1):
        self.set_value(self._value + delta)

    def decrement(self, delta=1):
        self.set_value(self._value - delta)

    def __iadd__(self, delta):
        self.increment(delta)
        return self

    def __isub__(self, delta):
        self.decrement(delta)
        return self
