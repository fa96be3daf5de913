"""What the per-layer readers share that read from inside the program: its
host spans (`mxnet_tpu.profiler.spans()`, names `mx.*`, on
`time.perf_counter`) and the scope map of its step (`profiler.scope_map`:
optimized-HLO instruction name -> `op_name`, which carries the
`jax.named_scope` names `mx.loss`, `mx.optimizer`, `<op>:<node>`,
`mx.flash.*`).  docs/observability.md "Spans" is the list of names.

A program from before the span store (a parent commit) has neither: every
function here then returns None, and the readers leave their metric out.
"""

from __future__ import annotations

import re
import statistics

from . import trace as trace_mod

STEP_PROGRAM = "parallel_step"
# an op_name is scoped if it lies under one of the program's own names: an
# `mx.` scope, or a graph node's `<op>:<node>`
_SCOPED = re.compile(r"(?:^|[/(])(?:mx\.\w|\w+:[^/()])")
CLOCK_FIT_LIMIT_S = 1e-4


# -- host spans ---------------------------------------------------------------
def _from_program(outcome, key, read):
    """``read(mxnet_tpu.profiler)``, once per outcome (kept under *key*
    of its facts); None where the program has no such thing to read."""
    f = outcome.facts
    if key not in f:
        try:
            from mxnet_tpu import profiler
            f[key] = read(profiler)
        except (ImportError, AttributeError):
            f[key] = None
    return f[key]


def records(outcome):
    """The program's finished spans."""
    return _from_program(outcome, "program_spans", lambda p: p.spans())


def named(outcome, names, lo=float("-inf"), hi=float("inf")):
    """The spans called one of *names* that lie within [lo, hi]; None
    without a span store."""
    spans = records(outcome)
    if spans is None:
        return None
    return [s for s in spans
            if s.name in names and s.start >= lo and s.end <= hi]


def seconds(spans):
    """Seconds covered by *spans*, a time under two of them counted once
    (one parameter's `mx.initialize` inside another span of the list)."""
    return trace_mod.total(trace_mod.union(
        (s.start, s.end) for s in spans)) if spans else 0.0


def self_seconds(outcome, name, lo, hi):
    """Seconds inside the spans called *name* and outside their children:
    a span's own Python."""
    mine = named(outcome, (name,), lo, hi)
    if mine is None:
        return None
    ids = {s.id for s in mine}
    inside = sum(s.end - s.start for s in records(outcome)
                 if s.parent in ids)
    return sum(s.end - s.start for s in mine) - inside


def untraced(outcome):
    """``(lo, hi, steps, blocks)`` of the window's untraced blocks, the
    ones `train_samples_per_s` is read from; None where the kind keeps no
    such window."""
    f = outcome.facts
    if "untraced_span" not in f:
        return None
    steps = f["steps"] - f["traced_blocks"] * f["steps_per_block"]
    lo, hi = f["untraced_span"]
    return lo, hi, steps, steps // f["steps_per_block"]


def setup_seconds(outcome, names):
    """Seconds of set-up (process start to the window's first block)
    under the spans called one of *names*."""
    if "setup_s" not in outcome.end_to_end:
        return None
    spans = named(outcome, names,
                  hi=outcome.cell.started + outcome.end_to_end["setup_s"])
    return None if spans is None else seconds(spans)


def per_untraced_step_ms(outcome, name):
    """Milliseconds a step under the spans called *name*, over the
    untraced blocks."""
    win = untraced(outcome)
    if win is None:
        return None
    spans = named(outcome, (name,), win[0], win[1])
    return None if spans is None else 1e3 * seconds(spans) / win[2]


def say_once(outcome, key, text):
    """Print *text* once per outcome (several readers share a table)."""
    said = outcome.facts.setdefault("program_spans_said", set())
    if key not in said:
        said.add(key)
        print(text, flush=True)


# -- device scopes ------------------------------------------------------------
def scopes(outcome):
    """The step's ``{instruction name: op_name}``."""
    return _from_program(outcome, "program_scope_map",
                         lambda p: p.scope_map(STEP_PROGRAM))


def instruction(event_name):
    """A device event is named by its HLO instruction's text:
    ``%fusion.12 = bf16[..] fusion(..)`` -> ``fusion.12``."""
    return event_name.split(" = ")[0].strip().lstrip("%")


def phase(op_name):
    """``forward``, ``backward`` or ``optimizer`` (the update, gradient
    clipping with it) from an instruction's op_name; None where it lies
    under no scope of the program."""
    if not op_name or not _SCOPED.search(op_name):
        return None
    if "/mx.optimizer" in op_name or "/mx.grad_clip" in op_name:
        return "optimizer"
    return "backward" if "transpose(" in op_name else "forward"


def device_seconds(outcome, pred):
    """Device seconds of the traced blocks (`Trace.seconds_where`) under
    the instructions whose op_name (None without an entry) satisfies
    *pred*; None without a trace or a scope map."""
    scope_of = scopes(outcome)
    if outcome.trace is None or scope_of is None:
        return None
    return outcome.trace.seconds_where(
        lambda name: pred(scope_of.get(instruction(name))))


def device_ms_per_step(outcome, pred):
    s = device_seconds(outcome, pred)
    f = outcome.facts
    return None if s is None else \
        1e3 * s / (f["traced_blocks"] * f["steps_per_block"])


def phase_ms_per_step(outcome, which):
    return device_ms_per_step(outcome, lambda op: phase(op) == which)


def scope_ms_per_step(outcome, pattern):
    """Device milliseconds a step under the instructions whose op_name
    matches the regular expression *pattern*; None where none does."""
    found = re.compile(pattern).search
    ms = device_ms_per_step(outcome, lambda op: bool(op and found(op)))
    return ms or None


# -- the program's spans on the device trace's clock --------------------------
def clock_offset(outcome):
    """Seconds to add to a `time.perf_counter` reading to land on the
    trace's clock.  The benchmark's own spans exist on both
    (`outcome.spans.records`, `outcome.trace.spans`): the traced ones are
    the window's first, in order.  The offset is the median over the
    pairs, so that one pair split by a thread switch moves nothing; if
    the pairs' typical distance from it is over 0.1 ms the clocks do not
    agree and nothing may be placed: an error."""
    on_trace = outcome.trace.spans
    on_host = sorted(outcome.spans.records, key=lambda r: r[1])
    on_host = on_host[:len(on_trace)]
    if len(on_host) < len(on_trace) or any(
            h[0] != t[2] for h, t in zip(on_host, on_trace)):
        raise ValueError(
            "the trace's %d bench.* spans are not the window's first: "
            "no pairs to fit the clocks from" % len(on_trace))
    gaps = [t[0] * 1e-9 - h[1] for h, t in zip(on_host, on_trace)] + \
        [t[1] * 1e-9 - h[2] for h, t in zip(on_host, on_trace)]
    offset = statistics.median(gaps)
    residual = statistics.median(abs(g - offset) for g in gaps)
    if residual > CLOCK_FIT_LIMIT_S:
        raise ValueError(
            "the host's clock and the trace's do not fit: the pairs lie "
            "%.3f ms from their median offset (limit %.1f ms)"
            % (residual * 1e3, CLOCK_FIT_LIMIT_S * 1e3))
    return offset


def idle_by_program_span(outcome):
    """``{span name: idle seconds}`` of the first device in the traced
    window, each idle gap under the innermost `mx.*` span that the loop's
    thread (the one `mx.fit_batch` runs on) was in at the gap's middle,
    ``(outside)`` under none; None without a trace or spans."""
    spans = records(outcome)
    if outcome.trace is None or spans is None:
        return None
    t = outcome.trace
    offset = clock_offset(outcome)
    loop = {s.thread for s in spans if s.name == "mx.fit_batch"}
    placed = [((s.start + offset) * 1e9, (s.end + offset) * 1e9, s.name)
              for s in spans
              if s.thread in loop and s.name.startswith("mx.")]
    placed = [p for p in placed if p[1] > t.lo and p[0] < t.hi]
    device = min(t.devices)
    by = {}
    for a, b in trace_mod.subtract([(t.lo, t.hi)], t.busy(device)):
        mid = (a + b) / 2
        inner = [p for p in placed if p[0] <= mid < p[1]]
        name = min(inner, key=lambda p: p[1] - p[0])[2] if inner \
            else "(outside)"
        by[name] = by.get(name, 0.0) + (b - a) * 1e-9
    return by
