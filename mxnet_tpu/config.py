"""Environment-knob registry (reference: §5.6 config system —
~32 documented ``MXNET_*`` vars in docs/faq/env_var.md read through
``dmlc::GetEnv`` at singleton init).

One typed, documented registry instead of scattered ``os.environ`` reads:
every knob this framework consults is declared here with type, default,
and doc; ``describe()`` prints the env-var reference table the way
docs/faq/env_var.md documents the reference's.  Values are read at call
time (not import time) so tests can monkeypatch the environment.

Three layers resolve every read, in precedence order
(docs/autotuning.md):

1. **explicit env** — the variable is exported in ``os.environ``;
   an operator's export always wins,
2. **tuned override** — a value installed by :func:`tuned_override`
   (the autotuner's ``TuningStore`` applies winning configs here),
3. **registered default** — the ``register_env`` declaration.
"""

from __future__ import annotations

import os

__all__ = ["register_env", "get_env", "list_env", "describe",
           "tuned_override", "tuned_overrides", "clear_tuned",
           "resolve_env", "env_is_set", "compile_cache_dir",
           "enable_compile_cache"]

_REGISTRY = {}

# the tuned-override layer: knob name -> typed value.  Sits BETWEEN
# the environment and the registered default — get_env consults it
# only when the env var is not exported, so a tuned store can never
# shadow an operator's explicit setting.
_TUNED = {}


class _Knob(object):
    __slots__ = ("name", "type", "default", "doc")

    def __init__(self, name, typ, default, doc):
        self.name = name
        self.type = typ
        self.default = default
        self.doc = doc


def register_env(name, typ, default, doc):
    """Declare an environment knob (type in {int, float, str, bool})."""
    _REGISTRY[name] = _Knob(name, typ, default, doc)
    return _REGISTRY[name]


def _coerce(knob, value):
    if knob.type is bool and isinstance(value, str):
        return value.lower() not in ("0", "false", "off", "")
    try:
        return knob.type(value)
    except (TypeError, ValueError):
        raise ValueError("env %s=%r is not a valid %s"
                         % (knob.name, value, knob.type.__name__))


def get_env(name):
    """Read a registered knob: explicit env > tuned override >
    registered default (typed at every layer)."""
    return resolve_env(name)


def resolve_env(name, tuned=None):
    """Read a registered knob with an explicit per-call tuned value.

    Precedence: exported env var > *tuned* argument > the process-wide
    :func:`tuned_override` layer > registered default.  The *tuned*
    argument is how per-model tuning records (a registry consulting
    the ``TuningStore`` for one model) participate without mutating
    process-wide state; ``None`` means "no per-call tuning"."""
    knob = _REGISTRY[name]
    raw = os.environ.get(name)
    if raw is not None:
        return _coerce(knob, raw)
    if tuned is not None:
        return _coerce(knob, tuned)
    if name in _TUNED:
        return _TUNED[name]
    return knob.default


def env_is_set(name):
    """Is the knob's variable explicitly exported?  (The predicate a
    store-consulting call site uses to honor env-wins precedence.)"""
    return os.environ.get(name) is not None


def tuned_override(name, value):
    """Install a tuned value for a registered knob.  It applies to
    every subsequent :func:`get_env` read UNLESS the env var is
    exported — explicit env always wins (regression-tested in
    tests/test_autotune.py).  Returns the typed value installed."""
    knob = _REGISTRY[name]
    _TUNED[name] = _coerce(knob, value)
    return _TUNED[name]


def tuned_overrides():
    """The currently installed tuned layer (copy)."""
    return dict(_TUNED)


def clear_tuned(name=None):
    """Drop one tuned override (or all of them with no argument)."""
    if name is None:
        _TUNED.clear()
    else:
        _TUNED.pop(name, None)


def list_env():
    return sorted(_REGISTRY)


def describe():
    """The env-var reference table (reference: docs/faq/env_var.md)."""
    lines = []
    for name in list_env():
        k = _REGISTRY[name]
        lines.append("%-40s %-6s default=%-12r %s"
                     % (name, k.type.__name__, k.default, k.doc))
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Knob declarations — every env var the framework consults.
# ---------------------------------------------------------------------------

register_env("MXNET_ENGINE_TYPE", str, "XLAAsync",
             "Engine selection; 'NaiveEngine' forces synchronous "
             "execution after every op (reference: engine.cc:32-48)")
register_env("MXNET_EXEC_BULK_EXEC_TRAIN", bool, True,
             "Compile the whole training graph as one XLA program; off "
             "= per-node execution for debugging/monitoring "
             "(reference: graph_executor.cc:1187 bulk segments)")
register_env("MXNET_EXEC_BULK_EXEC_INFERENCE", bool, True,
             "Same as MXNET_EXEC_BULK_EXEC_TRAIN for inference graphs")
register_env("MXNET_KVSTORE_SYNC_TIMEOUT", float, 120.0,
             "Seconds a dist_sync server waits for all workers' pushes "
             "or barrier arrivals before raising")
register_env("MXNET_KVSTORE_HEARTBEAT_INTERVAL", float, 1.0,
             "Seconds between worker heartbeats feeding dead-node "
             "detection (reference: ps-lite heartbeats)")
register_env("MXNET_KVSTORE_CONNECT_TIMEOUT", float, 120.0,
             "Seconds a dist worker retries connecting to its servers "
             "(fresh socket per attempt) before raising — covers "
             "server-process spin-up, which includes a full package "
             "import")
register_env("MXNET_KVSTORE_RPC_TIMEOUT", float, 150.0,
             "Per-call socket timeout (seconds) on dist bulk RPC "
             "sockets: a server that dies mid-reply surfaces as a "
             "typed RPCTimeoutError instead of hanging the worker "
             "forever in recv; must exceed MXNET_KVSTORE_SYNC_TIMEOUT "
             "(sync pushes block server-side until the round "
             "completes); 0 = no timeout (legacy hang behavior)")
register_env("MXNET_KVSTORE_RPC_RETRIES", int, 5,
             "Transport attempts per dist bulk RPC: a timed-out or "
             "connection-broken call reconnects and resends the SAME "
             "(rank, seq) request id with jittered backoff; the "
             "server dedup window makes retried mutations apply "
             "exactly once")
register_env("MXNET_KVSTORE_DEDUP_WINDOW", int, 256,
             "Per-rank server-side idempotency window: how many "
             "recent mutating request ids (push/init/barrier) the "
             "server remembers so a retried RPC is answered from "
             "cache instead of re-applied")
register_env("MXNET_KVSTORE_EVICT_TIMEOUT", float, 10.0,
             "Seconds without a heartbeat before a sync-mode server "
             "treats a missing contributor as provably dead on "
             "sync/barrier deadline expiry and evicts it (survivors "
             "make progress); an alive-but-slow laggard instead "
             "raises a loud SyncTimeoutError naming it")
register_env("MXNET_KVSTORE_SNAPSHOT_PREFIX", str, "",
             "Checkpoint prefix for periodic KVStore server state "
             "snapshots (store + optimizer state + dedup window via "
             "resilience.CheckpointManager); a restarted server "
             "restores the snapshot so worker rejoin pulls resume "
             "from committed state; empty = snapshots off; server s "
             "of a group appends '-s<id>'")
register_env("MXNET_KVSTORE_SNAPSHOT_EVERY", int, 1,
             "Applies between server state snapshots (counter-based, "
             "deterministic); only consulted when "
             "MXNET_KVSTORE_SNAPSHOT_PREFIX is set; 0 = never")
register_env("MXNET_KVSTORE_JOIN_TIMEOUT", float, 120.0,
             "Seconds a joining/rejoining worker's wait_admission() "
             "polls for its admission to the expected-contributor set "
             "(admission happens at sync-round boundaries, so a "
             "stalled job admits nobody) before raising")
register_env("MXNET_KVSTORE_ADMIT_POLL", float, 0.2,
             "Poll interval (seconds) of wait_admission() and the "
             "joiner-side job-metadata fetch during mid-epoch "
             "admission")
register_env("MXNET_SAN", str, "",
             "graftsan runtime sanitizer components to enable: comma "
             "list of race,recompile,donation,transfer, or 'all'; "
             "empty = off (zero overhead; see docs/sanitizers.md)")
register_env("MXNET_IR_AUDIT", str, "",
             "Audit every AOT program's lowered StableHLO with the "
             "graftir rules (tools/graftir) as it is built: findings "
             "are logged, counted and evented ('iraudit' category); "
             "empty = off (zero overhead; see docs/ir_audit.md)")
register_env("MXNET_OBS", str, "",
             "Structured run-event categories to record to "
             "events.jsonl: comma list of compile,guard,chaos,"
             "checkpoint,preempt,retry,respawn,warning,kvstore,"
             "membership,supervisor,watchdog,serve,decode,fleet,"
             "autotune,iraudit, or 'all'; "
             "empty = off (no file, zero per-event cost; see "
             "docs/observability.md)")
register_env("MXNET_OBS_PATH", str, "events.jsonl",
             "Path of the structured run-event log (created lazily on "
             "the first recorded event)")
register_env("MXNET_OBS_RATE", int, 200,
             "Max run events recorded per second; excess events are "
             "counted and surfaced as 'dropped' on the next admitted "
             "event (0 = uncapped)")
register_env("MXNET_KVSTORE_BIGARRAY_BOUND", int, 1000000,
             "Arrays above this many elements shard across all servers "
             "(reference: kvstore_dist.h:58)")
register_env("MXNET_KVSTORE_TYPE", str, "local",
             "Default kvstore type for examples/launchers")
register_env("MXNET_SUBGRAPH_BACKEND", str, "",
             "Subgraph property applied at bind time "
             "(reference: partition_graph.cc; see mxnet_tpu.subgraph)")
register_env("MXNET_TPU_MATMUL_PRECISION", str, "",
             "Override jax matmul precision: bfloat16 | float32 | "
             "tensorfloat32 (TPU-native knob)")
register_env("MXNET_MODULE_FUSED_STEP", bool, True,
             "Module.forward_backward_update fuses forward + backward + "
             "gradient reduction + optimizer update into one donated "
             "XLA program when eligible; off = always run the legacy "
             "per-parameter Updater loop (TPU-native knob)")
register_env("MXNET_GUARD_NONFINITE", bool, False,
             "Skip optimizer updates whose loss/gradients contain "
             "NaN/Inf: one in-graph isfinite reduction inside the "
             "fused train step selects the unchanged params/state, so "
             "a diverged step costs no extra dispatch and no "
             "recompile (TPU-native knob; see docs/resilience.md)")
register_env("MXNET_GUARD_READBACK_LAG", int, 0,
             "Async non-finite-guard accounting on the FULL-fused "
             "step: defer the guard counter's scalar device->host "
             "readback by up to this many steps, so the host "
             "dispatches step N+1 while the device still runs step N "
             "(params/opt-state/aux stay protected in-graph by the "
             "where-select regardless).  Deferred readbacks resolve "
             "FIFO, so max_consecutive divergence actions fire within "
             "this many steps of the real divergence; the backlog is "
             "drained at epoch end, on preemption, and whenever job "
             "state is captured.  0 = synchronous (legacy, one "
             "blocking readback per step); see "
             "docs/perf_input_pipeline.md")
register_env("MXNET_DEVICE_PREFETCH", int, 0,
             "Ring depth for the fit()-level DevicePrefetcher wrap: "
             "training loops wrap their data iterator so host decode "
             "AND jax.device_put run on a background thread into a "
             "ring of this many device-resident batches (device "
             "memory: depth x batch bytes); 0 = off; "
             "fit(device_prefetch=...) overrides in both directions "
             "(see docs/perf_input_pipeline.md)")
register_env("MXNET_GUARD_MAX_BAD_STEPS", int, 0,
             "With the non-finite guard on, this many CONSECUTIVE "
             "skipped steps trigger the divergence action (raise, or "
             "rollback via Module.set_nonfinite_guard); 0 = count "
             "and skip only")
register_env("MXNET_CHAOS", str, "",
             "Fault-injection spec for the resilience chaos harness, "
             "e.g. 'fail_file_writes=2,nan_grads_at_step=3'; 'on' "
             "enables the harness with nothing armed; empty = off "
             "(see mxnet_tpu/resilience/chaos.py)")
register_env("MXNET_CHECKPOINT_KEEP_LAST", int, 0,
             "Default keep-last-K rotation for CheckpointManager "
             "(older epochs' files are deleted once unreferenced); "
             "0 = keep every checkpoint")
register_env("MXNET_WATCHDOG_TIMEOUT", float, 300.0,
             "Seconds the supervisor's watchdog tolerates a stalled "
             "heartbeat (no batch-boundary tick) from a live child "
             "before declaring it HUNG — wedged collective, "
             "deadlocked dataloader — dumping a flight record, and "
             "killing/restarting it; measured on the monotonic clock")
register_env("MXNET_SUPERVISOR_RESTARTS", int, 3,
             "Restart budget of resilience.supervisor: how many child "
             "deaths + hang-kills are restarted (with jittered "
             "backoff) from the latest checkpoint before the "
             "supervisor gives up and surfaces the failure")
register_env("MXNET_HEARTBEAT_FILE", str, "",
             "Path of the supervised-job heartbeat file; set by the "
             "supervisor for its child — when present, fit()-style "
             "training loops tick it once per batch (empty = "
             "unsupervised, zero overhead)")
register_env("MXNET_FLIGHT_STACKS", str, "",
             "Path where a supervised child's faulthandler dumps "
             "all-thread stacks on SIGUSR1 (set by the supervisor; "
             "part of the hang flight record)")
register_env("MXNET_FLIGHT_SNAPSHOT", str, "",
             "Path where a supervised child writes a metrics "
             "snapshot on SIGUSR2 (best-effort: Python-level handler, "
             "so only sleep-style hangs can honor it)")
register_env("MXNET_OPTSTATE_MISMATCH", str, "raise",
             "What load_optimizer_states does when the blob was "
             "written by a different optimizer class or hyper-param "
             "signature: 'raise' (typed StateMismatchError) or "
             "'reinit' (warn and start from fresh optimizer state)")
register_env("MXNET_DATALOADER_RESPAWNS", int, 2,
             "How many crashed DataLoader worker processes are "
             "respawned (with backoff, lost batches resubmitted) "
             "before the loader gives up and raises")
register_env("MXNET_UPDATE_ON_KVSTORE", bool, True,
             "Run the optimizer on the kvstore server (dist) / store "
             "(local) instead of locally (reference: module/trainer)")
register_env("MXNET_CPU_WORKER_NTHREADS", int, 0,
             "Host-side worker threads for the data pipeline; 0 = "
             "library default (reference: "
             "threaded_engine_perdevice.cc:79)")
register_env("MXNET_USE_NATIVE_RECORDIO", bool, True,
             "Read .rec files through the native C++ reader "
             "(src/io/recordio_reader.cc) when built; off = pure Python")
register_env("MXNET_ENGINE_INFO", bool, False,
             "Verbose engine scheduling debug output "
             "(reference: threaded_engine.h:302)")
register_env("MXNET_SERVE_MAX_WAIT_MS", float, 2.0,
             "How long the serve DynamicBatcher holds a non-full "
             "batch open for more arrivals, measured from the oldest "
             "queued request (milliseconds, monotonic clock); 0 = "
             "dispatch immediately, no coalescing window")
register_env("MXNET_SERVE_MAX_BATCH", int, 0,
             "Row cap per coalesced serve batch; 0 = the model's "
             "bucket-ladder top rung")
register_env("MXNET_SERVE_MAX_QUEUE", int, 1024,
             "Admission control: max requests waiting in one serve "
             "DynamicBatcher — submit past the cap raises a typed "
             "OverloadError (load shedding) instead of queueing "
             "unboundedly; 0 = unbounded (legacy)")
register_env("MXNET_SERVE_MAX_QUEUE_BYTES", int, 1 << 28,
             "Admission control: max payload bytes waiting in one "
             "serve DynamicBatcher (the byte-sided overload cap "
             "alongside MXNET_SERVE_MAX_QUEUE); 0 = unbounded")
register_env("MXNET_SERVE_DEFAULT_DEADLINE_MS", float, 0.0,
             "Default per-request serving deadline (milliseconds, "
             "monotonic clock) applied when submit() passes none: an "
             "expired request is shed BEFORE padding/dispatch and its "
             "future resolves with a typed DeadlineExceededError; "
             "0 = no deadline")
register_env("MXNET_SERVE_DISPATCHER_RESTARTS", int, 3,
             "How many serve dispatcher-thread crashes (an exception "
             "escaping the batching loop, not a per-batch dispatch "
             "failure) are restarted with jittered backoff before the "
             "batcher declares itself unhealthy and fails every "
             "queued future loudly")
register_env("MXNET_SERVE_DRAIN_TIMEOUT", float, 30.0,
             "Default bound (seconds) on graceful drain: how long "
             "Registry.drain / unload(drain=True) / an alias-cutover "
             "flush waits for accepted serve requests to finish "
             "before proceeding anyway")
register_env("MXNET_SERVE_KV_BLOCK_SIZE", int, 16,
             "Tokens per paged KV-cache block (serve.kvpool): the "
             "granularity decode sessions allocate cache memory at — "
             "smaller blocks waste less tail memory per session, "
             "larger blocks mean fewer scatter rows per tick")
register_env("MXNET_SERVE_KV_BLOCKS", int, 256,
             "Paged KV pool capacity in blocks (per decode engine, "
             "including the reserved null block): bounds TOTAL cache "
             "memory across every concurrent decode session; an "
             "admission that cannot get its blocks sheds with a "
             "typed KVPoolExhausted")
register_env("MXNET_SERVE_DECODE_MAX_WAIT_MS", float, 2.0,
             "How long an IDLE decode batcher holds its first tick "
             "open for more sessions to arrive (milliseconds, "
             "monotonic clock) so co-arriving sessions share one "
             "session-count rung from the start; once decoding, "
             "ticks run back-to-back and joins land between ticks")
register_env("MXNET_SERVE_HTTP_PORT", int, 0,
             "Per-replica HTTP probe port (serve.replica): serves "
             "/metrics (Prometheus exposition of the process metrics "
             "registry), /healthz (liveness) and /readyz (readiness "
             "+ per-model health JSON) over stdlib http.server so "
             "the fleet router and any external orchestrator can "
             "scrape it; 0 = probe server off (the fleet passes an "
             "explicit port when it spawns replicas)")
register_env("MXNET_SERVE_HEDGE_MS", float, 0.0,
             "Router-side request hedging: after this many "
             "milliseconds without an answer, re-issue the still-"
             "pending predict (SAME request id) to a second replica "
             "— first typed answer wins, the loser is cancelled "
             "through the replica's idempotency window so no request "
             "is ever dispatched twice on one replica or answered "
             "twice; 0 = hedging off")
register_env("MXNET_SERVE_RPC_TIMEOUT", float, 60.0,
             "Per-call socket timeout (seconds) on router->replica "
             "RPCs: a replica that dies mid-reply surfaces as a "
             "transport failure the router fails over, instead of "
             "hanging the caller; 0 = no timeout")
register_env("MXNET_SERVE_ROUTER_RETRIES", int, 3,
             "Total transport attempts per routed request (first "
             "try + failovers): a connection failure retries the "
             "SAME (client, seq, incarnation) request id on the "
             "next eligible replica — wrapping around to an "
             "already-tried replica only when no fresh one is left, "
             "where the dedup window answers a retried id from "
             "cache instead of re-dispatching")
register_env("MXNET_SERVE_BREAKER_FAILURES", int, 3,
             "Consecutive transport failures that open one "
             "replica's router-side circuit breaker (no requests "
             "routed while open)")
register_env("MXNET_SERVE_BREAKER_COOLDOWN", float, 1.0,
             "Seconds an open circuit breaker waits before letting "
             "ONE half-open trial request through; success closes "
             "the breaker, failure re-opens it for another cooldown")
register_env("MXNET_SERVE_FLEET_HEARTBEAT", float, 0.5,
             "Router health-probe cadence (seconds): each replica "
             "is probed with a HEALTH RPC this often, feeding "
             "readiness-aware routing and heartbeat-staleness "
             "ejection")
register_env("MXNET_SERVE_EJECT_TIMEOUT", float, 5.0,
             "Seconds without a successful health probe before the "
             "router ejects a replica from the rotation (breaker "
             "forced open); the next successful probe rejoins it")
register_env("MXNET_TUNING_STORE", str, "",
             "Path of the autotuner's JSON TuningStore "
             "(tools/autotune.py output).  When set, ModelRegistry."
             "load / DynamicBatcher / DecodeEngine consult it for the "
             "winning config keyed (model_name, device_kind, "
             "workload); an exported env var still beats a stored "
             "tuning (see docs/autotuning.md); empty = no store")
register_env("MXNET_SERVE_DEDUP_WINDOW", int, 256,
             "Per-client replica-side idempotency window: how many "
             "recent predict request ids each replica remembers so "
             "a retried or hedged RPC is answered from cache "
             "instead of re-dispatched (in-flight entries are "
             "never trimmed)")
register_env("MXNET_SERVE_DECODE_REBUILDS", int, 2,
             "How many decode tick-loop crashes a DecodeBatcher "
             "survives by quarantine-and-rebuild: the suspect KVPool "
             "is quarantined, a fresh same-shape pool is allocated "
             "against the already-warm tick/prefill programs (zero "
             "new compiles) and journaled sessions are re-admitted "
             "via re-prefill + replayed ticks; past the budget the "
             "batcher degrades to unhealthy typed-fail")


# Where compiled programs persist when JAX_COMPILATION_CACHE_DIR is not
# set: one fixed path beside the package.  The directory is part of the
# cache key, so a path built from a temporary name never hits.
_DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def compile_cache_dir():
    """The persistent XLA compilation cache directory of this process
    and of every child it starts: ``JAX_COMPILATION_CACHE_DIR`` where
    the environment sets it, else the fixed in-checkout default."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        _DEFAULT_COMPILE_CACHE


def enable_compile_cache():
    """Turn jax's persistent compilation cache on at
    :func:`compile_cache_dir`, so every process sharing it — a serving
    fleet, the multi-process dist drills, supervisor-restarted jobs, the
    next run — pays each distinct program's compile once.  Called at
    package import; does not initialize a backend.  Safe to call again
    after mutating the environment (tests)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as _cc
    path = compile_cache_dir()
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
        # jax latches cache initialization on the FIRST compile: drop
        # the latch so the next compile initializes against this dir
        _cc.reset_cache()
    # serving ladders are many small, fast-compiling programs: cache
    # them all unless the environment says otherwise
    for name in ("jax_persistent_cache_min_compile_time_secs",
                 "jax_persistent_cache_min_entry_size_bytes"):
        if name.upper() not in os.environ:
            jax.config.update(name, 0)
    return path
