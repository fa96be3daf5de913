"""The data-driven part of the benchmark: `BENCHMARK.json` names a cell's
configuration, traffic mix and per-layer metrics; everything else is found
by those names under `benchmarks/`, so a later PR adds files and entries
and edits nothing that is here.

    configs/<config>.json          sizes as run, with source/reduced/assumed
    traffic/<traffic>.json         parameters of a mix; `kind` names the loop
    kinds/<kind>.py                run(cell) -> Outcome: the loop of a kind
    models/<family>.py             the program's model of a family
    reference/<family>.py          its plain float32 reference
    layer_metrics/<name>.py        read(outcome) -> value or None

A PR that adds a cell appends a configuration, a workload and its
per-layer entries (last in `per_layer`, each with a `workloads` list), and
adds `configs/<config>.json`, `models/` + `reference/<family>.py` for a new
family, a counts file beside `moe_counts.py`, and a `layer_metrics/<name>.py`
an entry (`LAYER`, `UNIT`, `MOVES`, `BETTER`, `SOURCE` as the entry says).
`tests/benchmark_suite/` finds entries by name: nothing that exists is edited.
"""

from __future__ import annotations

import contextlib
import importlib
import json
import os
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Cell:
    """One entry of `workloads` with its files loaded."""

    def __init__(self, name, seed, seconds, trace, started, root=ROOT):
        self.root = root
        here = os.path.join(root, "benchmarks")
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)
        entry = [w for w in self.spec["workloads"] if w["name"] == name]
        if not entry:
            raise SystemExit("no workload %r in BENCHMARK.json (have %s)" % (
                name, ", ".join(w["name"] for w in self.spec["workloads"])))
        self.entry = entry[0]
        self.name, self.chips = name, int(self.entry["chips"])
        self.seed, self.seconds, self.trace = int(seed), float(seconds), \
            bool(trace)
        self.started = started
        conf = [c for c in self.spec["configs"]
                if c["name"] == self.entry["config"]][0]
        with open(os.path.join(root, conf["file"])) as f:
            self.config = json.load(f)
        with open(os.path.join(here, "traffic",
                               self.entry["traffic"] + ".json")) as f:
            self.traffic = json.load(f)
        with open(os.path.join(here, "peaks.json")) as f:
            self.peaks = json.load(f)
        self.trace_dir = os.path.join(root, ".bench_out",
                                      "trace-" + self.name)

    def kind(self):
        return importlib.import_module(
            "benchmarks.kinds." + self.traffic["kind"])

    def family(self):
        return importlib.import_module(
            "benchmarks.models." + self.config["family"])

    def metric_names(self, group):
        """The metrics of *group* (`end_to_end` or `per_layer`) that this
        cell reports."""
        return [m for m in self.spec[group]
                if self.name in m.get("workloads", [self.name])]

    def peak(self, kind, key):
        if kind not in self.peaks:
            raise KeyError("no published peaks for device kind %r in "
                           "benchmarks/peaks.json" % kind)
        return self.peaks[kind][key]


class Spans:
    """Host spans of the benchmark's own loop: kept in memory as
    ``(name, start, end)`` on `time.perf_counter`, and written into the
    profiler's trace while one is being taken."""

    def __init__(self):
        self.records = []
        self.annotate = False

    @contextlib.contextmanager
    def __call__(self, name):
        import jax
        ann = jax.profiler.TraceAnnotation(name) if self.annotate \
            else contextlib.nullcontext()
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def seconds(self, name, lo=float("-inf"), hi=float("inf")):
        return sum(b - a for n, a, b in self.records
                   if n == name and a >= lo and b <= hi)


class CompileCounter:
    """Persistent-cache misses and hits and backend compiles, as
    `chip_smoke.py` counts them: a `jax.monitoring` listener.
    `requests` moves whenever a program had to be compiled or loaded."""

    def __init__(self):
        import jax
        self.misses = self.hits = self.compiles = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_misses":
            self.misses += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, event, _secs, **__):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    @property
    def requests(self):
        return self.misses + self.hits + self.compiles


def device_line(devices, memory_peak_bytes):
    d = devices[0]
    import jax
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count(),
            "memory_peak_bytes": int(memory_peak_bytes)}


def memory_peak(devices):
    """Peak bytes held on the fullest device, 0 where the backend does not
    report it (the CPU fixtures).  On the TPU the runtime counts live
    arrays under `peak_bytes_in_use` and the compiled programs' scratch
    under `peak_bytes_reserved` (my chip run, PR 23: 2.30 GB and 8.35 GB
    for a step whose `memory_analysis()` gives 0.33 GB of arguments and
    8.39 GB of temporaries), so the peak is their sum."""
    def held(stats):
        return stats.get("peak_bytes_in_use", 0) \
            + stats.get("peak_bytes_reserved", 0)
    return max(held(d.memory_stats() or {}) for d in devices)


def per_layer_metrics(cell, outcome):
    """Each declared per-layer metric of the cell through its own reader;
    a reader that finds nothing to read leaves its metric out."""
    out = {}
    for m in cell.metric_names("per_layer"):
        reader = importlib.import_module(
            "benchmarks.layer_metrics." + m["name"].replace("-", "_"))
        value = reader.read(outcome)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(cell, outcome, devices):
    """The contract's last line.  Off the TPU (the tests' fixtures) only
    counts are kept: a time, a rate or a share from a CPU is no
    measurement."""
    on_tpu = devices[0].platform == "tpu"
    if cell.trace:
        metrics = per_layer_metrics(cell, outcome)
    else:
        metrics = {m["name"]: {"value": float(outcome.end_to_end[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.metric_names("end_to_end")
                   if m["name"] in outcome.end_to_end}
    if not on_tpu:
        metrics = {k: v for k, v in metrics.items() if v["unit"] == "count"}
    line = {"correct": bool(outcome.correct),
            "attempted": int(outcome.attempted),
            "failed": int(outcome.failed),
            "metrics": metrics,
            "device": device_line(devices, outcome.memory_peak_bytes)}
    if cell.trace and outcome.trace is not None and on_tpu:
        line["device"]["busy_s"] = outcome.trace.busy_s
        line["device"]["window_s"] = outcome.trace.window_s
        line["breakdown"] = {"device_ops": outcome.trace.top_ops(10),
                             "idle_gaps": outcome.trace.idle_gaps(10)}
    return line


class Outcome:
    """What a kind's loop hands back: the end-to-end numbers, whether the
    output was correct, and whatever its per-layer readers read."""

    def __init__(self, cell):
        self.cell = cell
        self.correct = False
        self.attempted = self.failed = 0
        self.end_to_end = {}
        self.memory_peak_bytes = 0
        self.trace = None           # benchmarks.trace.Trace, traced runs
        self.spans = None           # Spans
        self.facts = {}             # anything else a reader may want
