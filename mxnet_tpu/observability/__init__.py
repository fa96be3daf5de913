"""Unified telemetry for the TPU-native framework.

Three parts (docs/observability.md):

* :mod:`.metrics` — an always-on, thread-safe instrument registry
  (counters / gauges / histograms) with JSON snapshots and
  Prometheus-style text exposition.  The profiler's historical
  ``bump_counter``/``counters`` dispatch-and-compile counter surface
  is a compatibility layer over this registry, so every number a test
  asserted before this subsystem existed still comes from the same
  place a fleet scraper reads.

* :mod:`.events` — an opt-in structured run-event log
  (``events.jsonl``; ``MXNET_OBS`` env knob, off by default with zero
  per-event cost) recording compiles with blame, non-finite-guard
  trips, chaos injections, preemptions, retries, worker respawns and
  checkpoint commits, so a failed run is diagnosable post-mortem from
  one file.

* :mod:`.costs` — per-op HLO cost attribution: an analytic
  flops/bytes model over a lowered program plus roofline
  classification against the peaks it is given, turning a single MFU
  number into a per-op optimization queue (``tools/graftir`` prices
  programs with it).

Import discipline: this package depends only on the stdlib,
``..sanitizer`` (lock factories, so graftsan can audit instrument
locking) and ``..config`` — it must stay importable from every
subsystem (ndarray, io, kvstore, resilience) without cycles.
"""

from __future__ import annotations

from . import metrics
from . import events
from . import costs

__all__ = ["metrics", "events", "costs"]
