"""What the per-layer readers share that price the traced steps: the cost
map of the compiled step (`mxnet_tpu.profiler.cost_map`: for every
optimized-HLO instruction that can run as a device event, the logical
bytes it reads and writes, by memory, its MXU FLOPs, and its HBM bytes by
the op_name they belong to; docs/observability.md "What the compiled step
costs") laid over the device events of the traced blocks.  Every event is
priced by its instruction (`program_spans.instruction`), so a loop's body
and a `cond`'s branch count as often as they ran; an event whose
instruction the map lacks (the small programs beside the step: the
batch's cast, the key split) is left out, and its time is printed as not
priced.

These are counts from the compiled text laid beside measured times, not
measurements of traffic: tile padding, what a kernel reads twice and what
XLA's placement in the on-chip memory spares are not in them.  A floor is
the larger of an instruction's MXU FLOPs over the published bf16 peak and
its HBM bytes over the published HBM bandwidth (`peaks.json`), summed over
the events.

A program from before the cost map (a parent commit) has none: every
function here then returns None, and the readers leave their metric out.
"""

from __future__ import annotations

import re

from . import program_spans

_NODE = re.compile(r"(\w+):[^/()]+")
_MX = re.compile(r"mx\.[\w.]+")
TABLE_ROWS = 12


def cost_map(outcome):
    """The step's ``{instruction name: record}``."""
    return program_spans._from_program(
        outcome, "program_cost_map",
        lambda p: p.cost_map(program_spans.STEP_PROGRAM))


def totals(outcome):
    """The map's sums beside XLA's own (`profiler.cost_totals`)."""
    return program_spans._from_program(
        outcome, "program_cost_totals",
        lambda p: p.cost_totals(program_spans.STEP_PROGRAM))


def scope_key(op_name):
    """The row of the table an op_name belongs to: the update, or the
    operator (``<op>`` of its ``<op>:<node>``, every node of one kind
    together) with the innermost `mx.*` scope inside it, forward and
    backward apart."""
    phase = program_spans.phase(op_name)
    if phase is None:
        return "(unscoped)"
    if phase == "optimizer":
        return "mx.grad_clip" if "/mx.grad_clip" in op_name \
            else "mx.optimizer"
    node = _NODE.search(op_name)
    inner = [m for m in _MX.findall(op_name) if m != "mx.loss"]
    name = "/".join(([node.group(1)] if node else [])
                    + inner[-1:]) or "mx.loss"
    return "%s %s" % (name, phase)


class Priced:
    """The traced blocks' device events, each priced by its instruction.

    The host's count of traced steps does not say how many times the
    device ran each instruction inside the window (the device runs a step
    or two behind, and the window's ends cut through a step), so the
    events are counted over WHOLE CYCLES of the step: between the first
    and the last start of a marker, the instruction of the step's ENTRY
    computation (`cost_totals`' ``entry``) with the most time.  `rows` is
    ``{instruction: [events, seconds, record]}`` and `unpriced`
    ``{instruction: seconds}`` (the events the map lacks) over those
    cycles on every device: divide by `per_step`, the cycles counted.
    Every reader of this module divides counts and times by that one
    number.

    An instruction of the ENTRY computation runs once a cycle.  Where the
    cycles hold more events under its name, the shortest of them are not
    the step's (a small program beside the step holds an `add_add_fusion`
    of its own): they are counted in `beside`, and their time goes to
    `unpriced` under ``<name> (beside the step)``, so that an over-count
    of any other cause shows there too."""

    def __init__(self, outcome, costs, entry):
        f = outcome.facts
        kind = f["device_kind"]
        self.flops_per_s = outcome.cell.peak(kind, "bf16_flops_per_s")
        self.bytes_per_s = outcome.cell.peak(kind, "hbm_bytes_per_s")
        steps = f["traced_blocks"] * f["steps_per_block"]
        self.rows, self.unpriced = {}, {}
        self.per_step = self.beside = 0
        for events in outcome.trace.devices.values():
            events = [(a, b, program_spans.instruction(name))
                      for a, b, name in events]
            lo, hi, cycles = self._whole_cycles(events, costs, entry, steps)
            self.per_step += cycles
            here = {}
            for a, b, inst in events:
                if not lo <= a < hi:
                    continue
                if inst not in costs:
                    self.unpriced[inst] = self.unpriced.get(inst, 0.0) \
                        + (b - a) * 1e-9
                    continue
                here.setdefault(inst, []).append((b - a) * 1e-9)
            for inst, seconds in here.items():
                rec = costs[inst]
                over = len(seconds) - cycles
                if over > 0 and rec.get("computation") == entry \
                        and lo > float("-inf"):     # whole cycles alone
                    seconds.sort()
                    self.beside += over
                    self.unpriced[inst + " (beside the step)"] = \
                        self.unpriced.get(inst + " (beside the step)", 0.0) \
                        + sum(seconds[:over])
                    seconds = seconds[over:]
                row = self.rows.setdefault(inst, [0, 0.0, rec])
                row[0] += len(seconds)
                row[1] += sum(seconds)

    @staticmethod
    def _whole_cycles(events, costs, entry, steps):
        """``(from, to, cycles)``: the span between the first and the last
        start of the marker and the marker's starts in it but one; all of
        the events and the host's *steps* where the ENTRY computation is
        not known or the marker ran less than twice."""
        seconds = {}
        for a, b, inst in events:
            rec = costs.get(inst)
            if rec is not None and entry is not None \
                    and rec.get("computation") == entry:
                seconds[inst] = seconds.get(inst, 0) + b - a
        if seconds:
            marker = max(seconds, key=seconds.get)
            starts = sorted(a for a, _, inst in events if inst == marker)
            if len(starts) > 1:
                return starts[0], starts[-1], len(starts) - 1
        return float("-inf"), float("inf"), steps

    def floors(self, rec):
        """``(MXU seconds, HBM seconds)`` of one record at the peaks."""
        return ((rec["mxu_flops"] or 0.0) / self.flops_per_s,
                (rec["hbm_bytes_read"] + rec["hbm_bytes_written"])
                / self.bytes_per_s)

    def sum(self, value):
        """``value(record)`` over the events, a step."""
        return sum(n * value(rec) for n, _, rec in self.rows.values()) \
            / self.per_step

    def device_ms(self):
        """The cycles' own device time, a step: every event's, priced or
        not, added up."""
        return 1e3 * (sum(s for _, s, _ in self.rows.values())
                      + sum(self.unpriced.values())) / self.per_step


def priced(outcome):
    """The `Priced` events of the traced blocks, once per outcome; None
    without a trace or a cost map."""
    f = outcome.facts
    if "program_costs_priced" not in f:
        costs = cost_map(outcome) if outcome.trace is not None else None
        f["program_costs_priced"] = None if costs is None else Priced(
            outcome, costs, (totals(outcome) or {}).get("entry"))
    return f["program_costs_priced"]


def bound(rec, p):
    """What bounds an instruction at the peaks: ``bytes`` where its HBM
    floor exceeds its MXU floor, ``mxu`` the other way round, ``kernel``
    for a kernel that states no FLOPs (it cannot be told), ``nothing``
    for one that moves and multiplies nothing (the end of an async
    pair)."""
    if rec["mxu_flops"] is None:
        return "kernel"
    mxu, hbm = p.floors(rec)
    return "bytes" if hbm > mxu else "mxu" if mxu else "nothing"


def device_ms_by_bound(p):
    """``{bound: device milliseconds a step}`` of the priced events: their
    own times added up over the whole cycles, as the counts are."""
    out = dict.fromkeys(("bytes", "mxu", "kernel", "nothing"), 0.0)
    for _, seconds, rec in p.rows.values():
        out[bound(rec, p)] += 1e3 * seconds / p.per_step
    return out


def say_not_priced(outcome, p):
    lacking = sum(p.unpriced.values())
    busy = sum(s for _, s, _ in p.rows.values()) + lacking
    worst = sorted(p.unpriced.items(), key=lambda kv: -kv[1])[:3]
    program_spans.say_once(
        outcome, "costs-not-priced",
        "bench: cost map prices %d instructions of the traced steps over "
        "%d whole cycles; not priced %.3f ms a step, %.2f%% of the events' "
        "time%s; of it %d events beside the step under a name of the "
        "step's own" % (
            len(p.rows), p.per_step, 1e3 * lacking / p.per_step,
            100.0 * lacking / busy if busy else 0.0,
            "".join(" [%s %.3f]" % (k, 1e3 * v / p.per_step)
                    for k, v in worst), p.beside))


def say_table(outcome, p):
    """The scopes with the most milliseconds a step over their floor."""
    by = {}
    for n, s, rec in p.rows.values():
        row = by.setdefault(scope_key(rec["op_name"]), [0.0] * 6)
        mxu, hbm = p.floors(rec)
        row[0] += s
        row[1] += n * mxu
        row[2] += n * hbm
        row[3] += n * max(mxu, hbm)
        row[4] += n * (rec["hbm_bytes_read"] + rec["hbm_bytes_written"])
        row[5] += rec["mxu_flops"] is None
    lines = ["bench: scopes by ms a step over their floor (measured ms: "
             "the events' own times added up; floor: each instruction's "
             "larger of MXU and HBM time at the peaks; kernels that state "
             "no FLOPs have a bytes floor alone)",
             "bench:   %-58s %9s %9s %9s %9s %8s %-5s %s" % (
                 "scope", "ms", "floor", "mxu_ms", "hbm_ms", "hbm_GB",
                 "bound", "kernels_without_flops")]
    k = 1e3 / p.per_step
    ranked = sorted(by.items(), key=lambda kv: kv[1][3] - kv[1][0])
    for key, row in ranked[:TABLE_ROWS]:
        lines.append("bench:   %-58s %9.3f %9.3f %9.3f %9.3f %8.3f %-5s %d"
                     % (key[:58], row[0] * k, row[3] * k, row[1] * k,
                        row[2] * k, row[4] / p.per_step / 1e9,
                        "bytes" if row[2] > row[1] else "mxu", row[5]))
    program_spans.say_once(outcome, "costs-table", "\n".join(lines))


def say_credit(outcome, p, rows=8):
    """Whose HBM bytes a step's instructions move: by the scope they
    belong to (`bytes_by_scope`), beside the bytes of the instructions
    NAMED for that scope (a fusion bears its root's name)."""
    credited, named = {}, {}
    for n, _, rec in p.rows.values():
        key = scope_key(rec["op_name"])
        named[key] = named.get(key, 0.0) + n * (
            rec["hbm_bytes_read"] + rec["hbm_bytes_written"])
        for op, b in rec["bytes_by_scope"].items():
            key = scope_key(op)
            credited[key] = credited.get(key, 0.0) + n * b
    k = 1e-9 / p.per_step
    lines = ["bench: HBM GB a step by the scope the bytes belong to "
             "(beside it: in instructions named for that scope)"]
    for key, b in sorted(credited.items(), key=lambda kv: -kv[1])[:rows]:
        lines.append("bench:   %-58s %8.3f %8.3f" % (
            key[:58], b * k, named.get(key, 0.0) * k))
    program_spans.say_once(outcome, "costs-credit", "\n".join(lines))


def say_totals(outcome):
    t = totals(outcome)
    if not t or not t.get("xla") or not t["xla"]["bytes_accessed"]:
        return
    moved = t["bytes_read"] + t["bytes_written"]
    xla = t["xla"]["bytes_accessed"]
    program_spans.say_once(
        outcome, "costs-totals",
        "bench: cost map %.3f GB (HBM %.3f + on-chip %.3f; a loop's body "
        "once, a cond's dearest branch), XLA %.3f GB, ratio %.3f (XLA "
        "counts an async pair at both ends, a kernel at its own "
        "cost_estimate and a gather's fused instructions one by one: "
        "PERF.md section 6 PR 37); MXU %.3f TFLOP, XLA's flops %.3f" % (
            moved / 1e9,
            (t["hbm_bytes_read"] + t["hbm_bytes_written"]) / 1e9,
            (t["onchip_bytes_read"] + t["onchip_bytes_written"]) / 1e9,
            xla / 1e9, moved / xla,
            t["mxu_flops"] / 1e12, t["xla"]["flops"] / 1e12))
