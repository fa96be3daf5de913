#!/usr/bin/env bash
# CI entry point (reference: ci/docker/runtime_functions.sh sanity + unit
# test functions).  Runs the full suite on the virtual 8-device CPU mesh,
# byte-compiles the package as a lint floor, and builds the C predict ABI.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== sanity: byte-compile =="
python -m compileall -q mxnet_tpu tools examples

echo "== sanity: graftlint static analysis =="
# Pure-stdlib AST pass (no jax import, no accelerator needed, <10s):
# tracer leaks, donation misuse, recompile hazards, registry contract.
# Exits nonzero on any finding not in tools/graftlint/baseline.json;
# the last stdout line is the scrapeable summary ("graftlint: ...").
python -m tools.graftlint mxnet_tpu

echo "== graftir: StableHLO program audit + manifest gate =="
# Lowers the representative AOT program set (fused step, serve rungs,
# decode tick/prefill, quantized rung) on CPU avals and audits the
# StableHLO text: rules GI001-GI005 (donation coverage, dtype policy,
# host round-trips, pad-waste, program budgets) against the committed
# baseline, plus the committed per-program cost manifest
# (tools/graftir/manifest.json — >10% flops/bytes growth or program-
# count drift fails; --update-manifest to accept an intended change).
# The smoke also proves the auditor still CATCHES seeded regressions
# (2x cost, stripped donation, injected f64).  Seconds, CPU-only
# (docs/ir_audit.md).  Last stdout line is the scrapeable summary
# ("graftir: programs=.. findings=.. ok").
MXNET_SAN=all python ci/graftir_smoke.py
python -m tools.graftir --check

echo "== graftsan: sanitizer-enabled smoke train step =="
# Fused + partial-fused train steps, the DevicePrefetcher ring and its
# producer thread, local kvstore, with ALL FOUR runtime sanitizers on
# (race/lockset + lock-order, recompile-blame, use-after-donate poison,
# host-transfer guard).
# Fails on any sanitizer report or a broken one-program-per-step
# contract.  Seconds, CPU-only (docs/sanitizers.md).
MXNET_SAN=all python ci/graftsan_smoke.py

echo "== graftsched: deterministic schedule exploration drill =="
# Serializing-scheduler model check of the threaded serving/kvstore
# subsystems: every shipped scenario explores its bounded schedule
# set (preemption bounding + DPOR pruning) with zero findings, the
# seeded PR-19 stop() double-teardown is re-found and its trace
# replays bit-exactly, and the graftsched counters move.  Seconds,
# CPU-only (docs/sanitizers.md "Schedule exploration").  Last stdout
# line: "graftsched: scenarios=.. schedules=.. findings=0 ok".
MXNET_SAN=sched python ci/sched_drill.py

echo "== observability: telemetry smoke train step =="
# Short fused-step run with MXNET_OBS=all: asserts the expected
# instruments exist with sane values, events.jsonl is well-formed
# (gapless seq, compile event present), and profiler.dump() carries
# the registry counters next to its spans.  Seconds, CPU-only; last
# stdout line is the scrapeable summary ("obs: instruments=.. ...").
MXNET_OBS=all python ci/obs_smoke.py

echo "== serve: compiled-inference smoke (registry + dynamic batcher) =="
# Two-model registry under concurrent mixed-size traffic through the
# dynamic batcher, sanitizers on: asserts one AOT compile per bucket
# and ZERO compiles/traces in the request path, every caller's rows
# bit-equal to the eager forward at some rung, p50/p99 emitted from
# the request histogram, and no graftsan reports from the batcher's
# locks/threads.  Seconds, CPU-only (docs/serving.md).  Last stdout
# line is the scrapeable summary ("serve: reqs=.. batches=.. ...").
MXNET_SAN=all python ci/serve_smoke.py

echo "== serve: continuous-batching decode drill (paged KV pool) =="
# Sixteen staggered decode sessions through the paged KV pool and the
# continuous-batching tick loop, sanitizers on: every session's token
# stream bit-equal to its SOLO dense-cache decode (block-table
# gather/scatter, co-tenant garbage, rung padding and join/leave
# churn invisible in the tokens), one AOT compile per tick/prefill
# rung and ZERO in the request path, a mid-decode cancel keeping its
# accepted tokens, typed KVPoolExhausted shedding + recovery, a
# chaos-armed tick crash surviving quarantine-and-rebuild (fresh pool
# against warm programs, journaled sessions re-admitted bit-equal,
# past-budget crash failing typed), zero leaked blocks, zero graftsan
# reports (docs/serving.md).  Last stdout line:
# "decode: sessions=.. ticks=.. compiles=.. rebuilds=.. ok".
MXNET_SAN=all python ci/decode_smoke.py

echo "== perf: autotune smoke (measured search + store pickup) =="
# A real successive-halving search over the serve knob space against
# a short synthetic trace (tiny FC model, ~8 candidates, analytic-
# prior pruning), sanitizers on: asserts the search completes, the
# winner is never worse than the measured default on the same trace
# (baseline guard), zero request-path compiles in every replay, the
# TuningStore round-trips with the trace identity + measurement
# artifact, and a fresh registry under MXNET_TUNING_STORE applies
# the winning config and serves the same trace with zero request-
# path compiles (docs/autotuning.md).  Last stdout line is the
# scrapeable summary ("autotune: trials=.. pruned=.. ...").
MXNET_SAN=all python ci/autotune_smoke.py

echo "== perf: quantized-serving smoke (calibrate/lower/gate/serve) =="
# The int8 post-training quantization pipeline end to end, sanitizers
# on: calibrate a conv+FC model on synthetic batches, atomic calib-
# table round-trip (a corrupted table fails the load typed), quantize
# and load through ModelRegistry with the accuracy gate enforced at
# every rung (an impossible threshold fails typed), int8 dot/conv ops
# asserted present in every rung's lowered StableHLO, concurrent
# mixed-size traffic through a real DynamicBatcher with zero request-
# path compiles, balanced quantize events, instruments moving, zero
# graftsan reports (docs/quantization.md).  Last stdout line:
# "quant: layers=.. covered=.. acc_ok compiles=0 ok".
MXNET_SAN=all python ci/quant_smoke.py

echo "== serve: request-path chaos drill (shedding/supervision/drain) =="
# The serving request path through every injected fault class —
# overload (slow dispatches vs a bounded queue), deadline expiry
# under a wedged dispatcher, dispatcher crash + restart, restart-
# budget exhaustion to unhealthy, stale-liveness detection, drain-
# under-load, and a failed warm compile: asserts typed errors only,
# zero stranded futures, expired payloads provably never dispatched,
# drained requests bit-equal to eager at some rung, and the health
# state machine replayable from events.jsonl (docs/serving.md).
# Deterministic counter-armed injections; the only sleeps are the
# injected delays/hangs.  Last stdout line is the scrapeable summary
# ("servechaos: faults=.. recovered=.. ok").
python ci/serve_chaos_drill.py

echo "== serve: fleet chaos drill (3 replicas, kill/deploy/partition) =="
# Three REAL replica processes behind the router under concurrent
# load: a replica hard-killed mid-request (router failover, same
# request id, dedup window), a drain-aware rolling deploy to a new
# checkpoint (zero dropped accepted requests, successors warm from
# the shared persistent XLA compile cache with zero new entries and
# zero request-path compiles), and a router<->replica partition
# (breaker opens, staleness ejects, healing rejoins).  Every accepted
# request is answered bit-equal to the eager forward at some
# rung/version or fails typed — never lost, never hung; bounded
# child-process cleanup on failure (docs/serving.md "Serving
# fleet").  Last stdout line is the scrapeable summary
# ("fleet: replicas=.. faults=.. recovered=.. ok").
MXNET_SAN=all python ci/fleet_chaos_drill.py

echo "== resilience: chaos-injected fault drills =="
# The resilience suite under the chaos harness: kill-mid-save,
# corrupt-checkpoint, NaN-step, and preemption drills against the REAL
# checkpoint/guard/fit code paths.  Deterministic counters + injected
# backoff clocks — no sleeps, seconds not minutes (docs/resilience.md).
MXNET_CHAOS=on python -m pytest tests/test_resilience.py -q \
    -p no:cacheprovider

echo "== resilience: network chaos drill (dist kvstore) =="
# Real 2-worker x 2-server dist_sync jobs through every injected
# network fault class — drop / delay / duplicate / torn-frame /
# partition / server-kill / dead-worker: asserts convergence-
# equivalent pulls, exactly-once apply counters, snapshot-restore
# after a hard kill, and eviction unblocking the survivors.
# Deterministic counter-armed injections; the only sleeps are the
# injected delays (docs/resilience.md).  The elastic scenarios follow
# (grow/shrink/evict+replace/3->2->4 resize chain under load:
# exactly-once coverage, zero lost accepted pushes, convergence
# equivalence vs the fixed-size baseline — docs/resilience.md
# "Elastic training").  Last stdout lines are the scrapeable
# summaries ("elastic: resizes=.. joins=.. evictions=.. ok" then
# "netchaos: faults=.. recovered=.. ok").
python ci/netchaos_drill.py

echo "== resilience: crash-anywhere drill (supervisor + watchdog) =="
# A supervised training job hard-killed at seeded ARBITRARY steps
# (plus one injected hang the watchdog must catch and flight-record)
# auto-resumes from per-batch job-state checkpoints and finishes
# BIT-IDENTICAL to an uninterrupted run — params, optimizer state,
# metric — with zero replayed or skipped batches (per-batch sequence
# log), and events.jsonl keeps a monotone seq across every restart.
# Last stdout line: "crash_anywhere: kills=.. hangs=.. ... ok".
python ci/crash_anywhere_drill.py

echo "== native: C predict ABI + RecordIO reader =="
if command -v g++ >/dev/null; then
    make -C src/capi
    make -C src/io
else
    echo "g++ not found — skipping native build"
fi

echo "== unit tests (virtual 8-device CPU mesh) =="
python -m pytest tests/ -q "$@"
