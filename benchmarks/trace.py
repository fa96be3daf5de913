"""From a profiler trace to numbers: the one reduction every PR shares.

`load_events` flattens the profiler's ``.xplane.pb`` (read with
`jax.profiler.ProfileData`, nothing else) into plain records
``{"plane", "line", "name", "start_ns", "dur_ns"}``; everything below
works on such records, so the tests drive it from a small recorded list.

Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line is one
event per executed HLO operation.  Host spans written with
`jax.profiler.TraceAnnotation` under names starting ``bench.`` land on the
host plane's thread lines, on the same clock.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
# operations that only wrap others on the ops line: their time is their
# children's (a `lax.cond` is on the ops line as `cond.N` or `cond.N.clone`)
_WRAPPERS = re.compile(r"^(while|conditional|cond|call)([.\d]|$)")


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return found[-1]


def load_events(xplane_path):
    """Device operations and ``bench.`` host spans of one trace file."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        device = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(SPAN_PREFIX):
                    continue
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "start_ns": int(ev.start_ns),
                            "dur_ns": int(ev.duration_ns)})
    return out


def op_family(name):
    """``fusion.123`` -> ``fusion``; ``%all-gather-start.4`` ->
    ``all-gather-start``: names that survive a recompile."""
    return re.sub(r"[.\d]+$", "", name.lstrip("%").split(" ")[0]) or name


def union(intervals):
    """Sorted, merged copy of ``[(start, end), ...]``."""
    merged = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if merged and lo <= merged[-1][1]:
            if hi > merged[-1][1]:
                merged[-1] = (merged[-1][0], hi)
        else:
            merged.append((lo, hi))
    return merged


def total(intervals):
    return sum(hi - lo for lo, hi in intervals)


def subtract(intervals, holes):
    """The part of merged *intervals* not covered by merged *holes*."""
    out = []
    j = 0
    for lo, hi in intervals:
        while j < len(holes) and holes[j][1] <= lo:
            j += 1
        k, at = j, lo
        while k < len(holes) and holes[k][0] < hi:
            if holes[k][0] > at:
                out.append((at, holes[k][0]))
            at = max(at, holes[k][1])
            k += 1
        if at < hi:
            out.append((at, hi))
    return out


class Trace:
    """The reduction of one traced window.

    The window runs from the start of the first ``bench.`` span to the end
    of the last; device events are clipped to it."""

    def __init__(self, events):
        spans = [e for e in events if e["name"].startswith(SPAN_PREFIX)]
        if not spans:
            raise ValueError("the trace holds no %s* host span"
                             % SPAN_PREFIX)
        self.lo = min(e["start_ns"] for e in spans)
        self.hi = max(e["start_ns"] + e["dur_ns"] for e in spans)
        self.spans = sorted(
            (e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"])
            for e in spans)
        self.devices = {}
        for e in events:
            m = DEVICE_PLANE.match(e["plane"])
            if not m or _WRAPPERS.match(op_family(e["name"])):
                continue
            a, b = e["start_ns"], e["start_ns"] + e["dur_ns"]
            if min(b, self.hi) > max(a, self.lo):
                self.devices.setdefault(int(m.group(1)), []).append(
                    (max(a, self.lo), min(b, self.hi), e["name"]))
        if not self.devices:
            raise ValueError("no operation ran on a device in the window")

    @property
    def window_s(self):
        return (self.hi - self.lo) * 1e-9

    def busy(self, device):
        return union((a, b) for a, b, _ in self.devices[device])

    @property
    def busy_s(self):
        """Seconds an operation ran, averaged over the devices used."""
        return sum(total(self.busy(d)) for d in self.devices) * 1e-9 \
            / len(self.devices)

    def seconds_where(self, pred):
        """Device seconds (union per device, averaged) of the operations
        whose event name (the HLO instruction's text) satisfies *pred*."""
        return sum(
            total(union((a, b) for a, b, n in ev if pred(n)))
            for ev in self.devices.values()) * 1e-9 / len(self.devices)

    def top_ops(self, n=10):
        """``[[family, seconds], ...]``: device seconds by operation
        family, summed over the devices and divided by their number."""
        by = {}
        for ev in self.devices.values():
            for a, b, name in ev:
                fam = op_family(name)
                by[fam] = by.get(fam, 0) + (b - a)
        k = 1e-9 / len(self.devices)
        return [[f, s * k] for f, s in
                sorted(by.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n=10, device=None):
        """``[[host span, seconds], ...]``: the idle time of one device in
        the window, by the ``bench.`` span the host was in at the middle
        of each gap (``(none)`` between spans), largest first."""
        device = min(self.devices) if device is None else device
        gaps = subtract([(self.lo, self.hi)], self.busy(device))
        by = {}
        for a, b in gaps:
            mid = (a + b) / 2
            inner = [s for s in self.spans if s[0] <= mid < s[1]]
            # the innermost span that holds the gap's middle
            name = min(inner, key=lambda s: s[1] - s[0])[2] \
                if inner else "(none)"
            by[name] = by.get(name, 0) + (b - a)
        return [[k, v * 1e-9] for k, v in
                sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def describe(xplane_path, limit=12):
    """A printable outline of a trace file, for reading one by hand."""
    from jax.profiler import ProfileData

    lines = []
    for plane in ProfileData.from_file(xplane_path).planes:
        lines.append("plane %r" % plane.name)
        for line in plane.lines:
            evs = list(line.events)
            lines.append("  line %r: %d events" % (line.name, len(evs)))
            for ev in evs[:limit]:
                lines.append("    %r start=%d dur=%d" % (
                    ev.name, ev.start_ns, ev.duration_ns))
    return "\n".join(lines)


if __name__ == "__main__":
    import sys
    print(describe(find_xplane(sys.argv[1])))
