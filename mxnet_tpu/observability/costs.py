"""Per-op HLO cost attribution — the MFU decompose engine.

``jit(...).lower(...).compile().cost_analysis()`` answers "how many
flops does the whole program do", which is enough for ONE MFU number
but not for an optimization queue: a 4.9%-MFU step needs to say
*which op* sits on the roofline's memory-bound floor.  XLA does not
expose per-instruction costs, so this module walks the lowered
StableHLO text with an analytic cost model (TVM/Glow-style: exact
flop formulas for the contraction ops, element-count estimates for
the rest, operand+result bytes for traffic) and classifies every op
group against the machine balance point::

    intensity = flops / bytes          (arithmetic intensity)
    balance   = peak_flops / peak_bytes_per_s
    class     = compute-bound if intensity >= balance else memory-bound

The estimated time share of a group is the roofline time
``max(flops/peak_flops, bytes/peak_bw)`` normalized over the program —
the number that makes an MFU regression attributable to a named op
(the table is plain JSON; ``tools/graftir`` keeps its totals per
program in its manifest).

Totals are cross-checked against ``compiled.cost_analysis()`` when
available: the analytic model counts the UNOPTIMIZED program (before
fusion folds ops away), so ``flops_vs_xla`` near 1.0 means the model
is trustworthy and >1 quantifies how much XLA fused away.
"""

from __future__ import annotations

import collections
import functools
import re

__all__ = ["parse_hlo_ops", "cost_table", "format_table",
           "parse_optimized_hlo", "hlo_op_names", "price_optimized_hlo"]

# dtype byte widths, under StableHLO's names (tensor<...x DTYPE>) and
# under HLO's (`s32[..]`, `u8[..]`); a sub-byte type counts a byte
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e5m2": 1, "f8e4m3fn": 1,
    "f8e4m3": 1, "f8e4m3fnuz": 1, "f8e5m2fnuz": 1, "f8e4m3b11fnuz": 1,
    "i64": 8, "ui64": 8, "i32": 4, "ui32": 4, "i16": 2, "ui16": 2,
    "i8": 1, "ui8": 1, "i4": 1, "ui4": 1, "i1": 1, "pred": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "c64": 8, "c128": 16,
}


def dot_flops(result_count, contracted):
    """A contraction's multiply-adds as FLOPs: two for every element of
    the result and every contracted position."""
    return 2.0 * result_count * contracted


def conv_flops(result_count, positions, in_per_group):
    """A convolution's FLOPs: two for every element of the result, every
    kernel position that contributes to it (*positions*: the window's
    size, or the mean count of taps that land on the input where the
    caller knows padding and dilation) and every input feature of its
    group (the kernel's own `i` dimension, which is already the input's
    features over `feature_group_count`)."""
    return 2.0 * result_count * positions * in_per_group

_TENSOR_RE = re.compile(r"tensor<([^>]*)>")
_OP_RE = re.compile(r'=\s+"?(?:stablehlo|mhlo|chlo)\.([a-zA-Z0-9_]+)"?')
_CONTRACT_RE = re.compile(r"contracting_dims\s*=\s*\[([0-9,\s]*)\]")
_BATCH_RE = re.compile(r"batching_dims\s*=\s*\[([0-9,\s]*)\]")
_KERNEL_SPEC_RE = re.compile(r"x\[([^\]]*)\]->")

# ops that are pure data movement / bookkeeping: zero flops, and for
# the shape-only ones zero meaningful traffic either.  Control-flow
# headers (while/if/case) are free too: their cost is their REGION
# bodies, which parse_hlo_ops charges with the loop multiplier.
_FREE_OPS = frozenset([
    "constant", "iota", "reshape", "bitcast_convert", "transpose",
    "broadcast_in_dim", "broadcast", "slice", "dynamic_slice",
    "dynamic_update_slice", "concatenate", "pad", "reverse",
    "get_tuple_element", "tuple", "optimization_barrier", "copy",
    "convert", "custom_call", "after_all", "create_token",
    "while", "if", "case", "return",
])

# one-flop-per-element ops get 1; costlier elementwise ops get a
# weight approximating their scalar op count (transcendentals)
_ELEMENTWISE_WEIGHT = {
    "tanh": 8, "exponential": 8, "log": 8, "logistic": 8, "power": 8,
    "sine": 8, "cosine": 8, "rsqrt": 4, "sqrt": 4, "divide": 4,
    "erf": 8, "atan2": 10, "expm1": 8, "log_plus_one": 8,
    "cbrt": 8, "tan": 10,
}


def _parse_tensor(spec):
    """'16x32xf32' / 'f32' -> (shape tuple, dtype, bytes)."""
    parts = spec.strip().split("x")
    if len(parts) == 1:
        dtype = parts[0]
        shape = ()
    else:
        dtype = parts[-1]
        try:
            shape = tuple(int(p) for p in parts[:-1])
        except ValueError:
            # dynamic dim ('?') or complex spec — treat unknown as 1
            shape = tuple(int(p) if p.isdigit() else 1
                          for p in parts[:-1])
    n = 1
    for s in shape:
        n *= s
    return shape, dtype, n * _DTYPE_BYTES.get(dtype, 4)


def _prod(seq):
    n = 1
    for s in seq:
        n *= s
    return n


def _int_list(raw):
    return [int(p) for p in raw.replace(" ", "").split(",") if p]


def _op_flops(op, line, operands, result):
    """Analytic flop count for one instruction.

    *operands*/*result* are (shape, dtype, bytes) triples; the result
    triple is the first result for multi-result ops."""
    rshape = result[0]
    rcount = _prod(rshape)
    if op == "dot_general" or op == "dot":
        # 2 * prod(result) * K, K = product of the lhs contracting dims
        m = _CONTRACT_RE.search(line)
        lhs_shape = operands[0][0] if operands else ()
        if m:
            dims = _int_list(m.group(1))
            k = _prod(lhs_shape[d] for d in dims
                      if d < len(lhs_shape))
        elif len(lhs_shape) >= 1:
            k = lhs_shape[-1]        # plain dot default
        else:
            k = 1
        return dot_flops(rcount, k)
    if op == "convolution":
        # 2 * prod(out) * (kernel spatial) * (the kernel's `i`, which is
        # the input's channels over feature_group_count already)
        if len(operands) < 2:
            return 2.0 * rcount
        kshape = operands[1][0]
        spec = _KERNEL_SPEC_RE.search(line)
        if spec:
            labels = [p.strip() for p in spec.group(1).split(",")]
            spatial = _prod(kshape[i] for i, l in enumerate(labels)
                            if l not in ("i", "o") and i < len(kshape))
            try:
                in_ch = kshape[labels.index("i")]
            except (ValueError, IndexError):
                in_ch = 1
        else:
            # HWIO fallback: all but the last two dims are spatial
            spatial = _prod(kshape[:-2]) if len(kshape) >= 2 else 1
            in_ch = kshape[-2] if len(kshape) >= 2 else 1
        return conv_flops(rcount, spatial, in_ch)
    if op in ("reduce", "reduce_window", "select_and_scatter"):
        # one combine per input element
        return float(_prod(operands[0][0])) if operands else float(rcount)
    if op in ("rng", "rng_bit_generator"):
        return 8.0 * rcount
    if op in ("sort",):
        n = _prod(operands[0][0]) if operands else rcount
        return 4.0 * n                  # ~n log n, flattened estimate
    if op in ("gather", "scatter", "select", "clamp", "compare",
              "maximum", "minimum", "and", "or", "xor", "not"):
        return float(rcount)
    return float(rcount) * _ELEMENTWISE_WEIGHT.get(op, 1)


_FUNC_RE = re.compile(r"func\.func\s+(?:(public|private)\s+)?@([\w$.\-]+)")
_CALL_RE = re.compile(r"(?:func\.)?call\s+@([\w$.\-]+)")
_INT_CONST_RE = re.compile(
    r"(%[\w#]+)\s*=\s*stablehlo\.constant\s+dense<(-?\d+)>\s*:"
    r"\s*tensor<(?:i32|i64|ui32|ui64)>")
_ITER_INIT_RE = re.compile(r"(%[\w#]+)\s*=\s*(%[\w#]+)")
_WHILE_CMP_RE = re.compile(
    r"stablehlo\.compare\s+(LT|LE),\s*(%[\w#]+),\s*(%[\w#]+)")


def _cost_row(line, op_match):
    """One {op, flops, bytes, shapes} row for an instruction line, or
    None when the line carries no tensor types."""
    op = op_match.group(1)
    tensors = [_parse_tensor(t) for t in _TENSOR_RE.findall(line)]
    if not tensors:
        return None
    # pretty form: "... : (operand types) -> result" or
    # "... : type" (every operand AND the result share the one
    # printed type — so count the %-operand refs, or a binary
    # add would be charged 2x tensor bytes instead of 3x and its
    # arithmetic intensity inflated 1.5x)
    if "->" in line.split(" : ")[-1] and len(tensors) >= 2:
        operands, results = tensors[:-1], tensors[-1:]
    else:
        seg = line[op_match.end():line.rfind(" : ")]
        n_operands = max(1, seg.count("%"))
        operands = [tensors[-1]] * n_operands
        results = tensors[-1:]
    flops = _op_flops(op, line, operands, results[0])
    byts = sum(t[2] for t in operands) + sum(t[2] for t in results)
    return {
        "op": op,
        "flops": flops,
        "bytes": float(byts),
        "shapes": "%s->%s" % (
            ",".join("x".join(map(str, t[0])) or "scalar"
                     for t in operands[:2]),
            "x".join(map(str, results[0][0])) or "scalar"),
    }


def _parse_functions(text):
    """Split StableHLO text into per-function op lists with LOOP
    multipliers resolved.

    Returns ``{fname: {"public": bool, "rows": [(row, mult)],
    "calls": [(callee, mult)]}}``.  *mult* is the product of the trip
    counts of the enclosing ``stablehlo.while`` regions: jax lowers
    ``lax.scan``/``fori_loop`` to a while whose cond compares the
    induction iterArg LT/LE a constant bound, with the body outlined
    into a ``func.func private`` reached via ``func.call`` — so a
    scanned matmul must charge trip_count x body, not 1x.  A while
    whose trip count is not statically visible multiplies by 1
    (conservative)."""
    funcs = {}
    cur = None            # current function record
    consts = {}           # %name -> int (scalar int constants, SSA)
    # scope stack: [depth_at_open, multiplier] for each open while
    # region; current multiplier = product over the stack
    scopes = []
    depth = 0
    pending_while = None  # iterArg -> init operand, for the next cond
    cond_scope = None     # scope collecting the cond of pending_while

    for line in text.splitlines():
        stripped = line.strip()
        fm = _FUNC_RE.search(line)
        if fm:
            cur = {"public": fm.group(1) != "private",
                   "rows": [], "calls": []}
            funcs[fm.group(2)] = cur
            consts = {}
            scopes = []
            depth = line.count("{") - line.count("}")
            pending_while = None
            cond_scope = None
            continue
        if cur is None:
            # bare op text with no func.func wrapper (tests, snippets):
            # treat everything before the first signature as an
            # implicit entry function
            if not _OP_RE.search(line):
                continue
            cur = {"public": True, "rows": [], "calls": []}
            funcs["<toplevel>"] = cur

        cm = _INT_CONST_RE.search(line)
        if cm:
            consts[cm.group(1)] = int(cm.group(2))

        if "stablehlo.while" in line and "=" in line:
            inside = line[line.find("(") + 1:line.rfind(")")] \
                if "(" in line else ""
            pending_while = dict(_ITER_INIT_RE.findall(inside))

        mult = 1
        for s in scopes:
            mult *= s[1]

        if pending_while is not None and stripped.startswith("cond"):
            # the cond region: runs trip+1 times, but holds only the
            # bound compare — charge it with the body multiplier once
            # the trip count is known (scope mult patched at "} do {")
            cond_scope = [depth + 1, 1, pending_while]
            scopes.append(cond_scope)
            depth += line.count("{") - line.count("}")
            continue
        if cond_scope is not None and stripped.startswith("}") \
                and "do" in stripped and "{" in stripped:
            # "} do {": close the cond scope, open the body scope with
            # the trip count inferred from the cond's compare
            trip = cond_scope[1] if cond_scope[1] > 1 else 1
            scopes.pop()
            scopes.append([depth, trip])
            pending_while = None
            cond_scope = None
            depth += line.count("{") - line.count("}")
            continue

        if cond_scope is not None:
            wm = _WHILE_CMP_RE.search(line)
            if wm:
                direction, it, bound = wm.groups()
                limit = consts.get(bound)
                init = consts.get(cond_scope[2].get(it, ""), 0)
                if limit is not None:
                    trip = limit - init + (1 if direction == "LE" else 0)
                    if trip > 0:
                        cond_scope[1] = trip

        om = _OP_RE.search(line)
        if om and om.group(1) not in _FREE_OPS:
            row = _cost_row(line, om)
            if row is not None:
                cur["rows"].append((row, mult))
        else:
            km = _CALL_RE.search(line)
            if km:
                cur["calls"].append((km.group(1), mult))

        depth += line.count("{") - line.count("}")
        while scopes and depth < scopes[-1][0]:
            scopes.pop()
            if scopes is not None and cond_scope is not None and \
                    (not scopes or cond_scope not in scopes):
                cond_scope = None
                pending_while = None
    return funcs


def parse_hlo_ops(text):
    """Walk lowered StableHLO/MHLO text; one cost row per
    instruction: ``{op, flops, bytes, shapes, count}``.  Lines that
    are not instructions (signatures, regions, returns) are skipped.

    Nested regions are priced honestly: ops inside a
    ``stablehlo.while`` body (and in functions the body calls — jax
    outlines scan/fori bodies into ``func.func private``) are
    multiplied by the statically-inferred trip count, so a scanned
    matmul costs trip_count x body flops, not 1x."""
    funcs = _parse_functions(text)
    if not funcs:
        return []

    # function multiplier: how many times each function runs per
    # program execution.  Public functions are entry points (1x);
    # private ones run once per call site times the caller's own
    # multiplier.  MLIR functions cannot recurse, so plain memoized
    # recursion over the caller edges terminates.
    callers = {}
    for fname, rec in funcs.items():
        for callee, mult in rec["calls"]:
            callers.setdefault(callee, []).append((fname, mult))

    memo = {}

    def fmult(fname):
        if fname in memo:
            return memo[fname]
        rec = funcs.get(fname)
        if rec is None:
            return 0
        if rec["public"]:
            memo[fname] = 1
            return 1
        edges = callers.get(fname)
        if not edges:
            # unreferenced private function: price it once rather
            # than silently dropping it (unusual dialect output)
            memo[fname] = 1
            return 1
        memo[fname] = 0            # break accidental cycles at 0
        total = sum(fmult(c) * m for c, m in edges)
        memo[fname] = total if total > 0 else 1
        return memo[fname]

    rows = []
    for fname, rec in funcs.items():
        fm = fmult(fname)
        if fm <= 0:
            continue
        for row, mult in rec["rows"]:
            n = fm * mult
            if n == 1:
                rows.append(dict(row, count=1))
            else:
                rows.append({
                    "op": row["op"],
                    "flops": row["flops"] * n,
                    "bytes": row["bytes"] * n,
                    "shapes": row["shapes"],
                    "count": n,
                })
    return rows


def cost_table(lowered=None, text=None, compiled=None, peak_flops=None,
               peak_bytes_s=None, top=None):
    """Build the per-op cost table for a lowered program.

    Pass a ``jax.stages.Lowered`` (``jit(f).lower(...)``), or raw
    StableHLO *text*.  With *peak_flops* and *peak_bytes_s* (probed or
    datasheet), each op group gets a roofline class and an estimated
    share of step time; without them only flops/bytes shares are
    filled.  Groups are keyed by (op kind, shape signature) so "the
    7x7 stem conv" and "the 1x1 bottleneck convs" stay separate rows.
    """
    if text is None:
        if lowered is None:
            raise ValueError("need a lowered program or HLO text")
        text = lowered.as_text()
        if compiled is None:
            try:
                compiled = lowered.compile()
            except Exception:
                compiled = None
    rows = parse_hlo_ops(text)

    groups = {}
    for r in rows:
        key = (r["op"], r["shapes"])
        g = groups.setdefault(key, {"op": r["op"], "shapes": r["shapes"],
                                    "count": 0, "flops": 0.0,
                                    "bytes": 0.0})
        g["count"] += 1
        g["flops"] += r["flops"]
        g["bytes"] += r["bytes"]

    total_flops = sum(g["flops"] for g in groups.values()) or 1.0
    total_bytes = sum(g["bytes"] for g in groups.values()) or 1.0
    balance = (peak_flops / peak_bytes_s
               if peak_flops and peak_bytes_s else None)

    out_rows = []
    total_time = 0.0
    for g in groups.values():
        intensity = g["flops"] / g["bytes"] if g["bytes"] else 0.0
        row = dict(g)
        row["intensity"] = round(intensity, 3)
        row["pct_flops"] = round(100.0 * g["flops"] / total_flops, 2)
        if balance is not None:
            row["class"] = ("compute-bound" if intensity >= balance
                            else "memory-bound")
            row["roofline_s"] = max(g["flops"] / peak_flops,
                                    g["bytes"] / peak_bytes_s)
            total_time += row["roofline_s"]
        out_rows.append(row)
    if total_time > 0:
        for row in out_rows:
            row["pct_time"] = round(100.0 * row.pop("roofline_s")
                                    / total_time, 2)
        out_rows.sort(key=lambda r: -r["pct_time"])
    else:
        out_rows.sort(key=lambda r: -r["pct_flops"])
    if top:
        dropped = out_rows[top:]
        if dropped:
            rest = {"op": "(other %d groups)" % len(dropped),
                    "shapes": "", "count": sum(d["count"] for d in dropped),
                    "flops": sum(d["flops"] for d in dropped),
                    "bytes": sum(d["bytes"] for d in dropped),
                    "intensity": 0.0,
                    "pct_flops": round(sum(d["pct_flops"]
                                           for d in dropped), 2)}
            if "pct_time" in (dropped[0] if dropped else {}):
                rest["pct_time"] = round(sum(d["pct_time"]
                                             for d in dropped), 2)
                rest["class"] = "-"
            out_rows = out_rows[:top] + [rest]

    table = {
        "rows": out_rows,
        "total_flops": total_flops,
        "total_bytes": total_bytes,
        "machine_balance": round(balance, 3) if balance else None,
        "peak_flops": peak_flops,
        "peak_bytes_s": peak_bytes_s,
    }
    if compiled is not None:
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0]
            if ca:
                table["xla_cost_analysis"] = {
                    k: float(v) for k, v in ca.items()
                    if isinstance(v, (int, float)) and "{" not in k}
                xf = table["xla_cost_analysis"].get("flops")
                if xf:
                    table["flops_vs_xla"] = round(total_flops / xf, 3)
        except Exception:
            pass
    return table


def format_table(table, limit=20):
    """Human-readable text rendering of :func:`cost_table`."""
    have_time = any("pct_time" in r for r in table["rows"])
    hdr = "%-18s %-34s %5s %12s %12s %9s %6s" % (
        "op", "shapes", "n", "gflops", "MB", "int.", "%fl")
    if have_time:
        hdr += " %6s %-14s" % ("%time", "roofline")
    lines = [hdr, "-" * len(hdr)]
    for r in table["rows"][:limit]:
        line = "%-18s %-34s %5d %12.3f %12.2f %9.1f %6.2f" % (
            r["op"], r["shapes"][:34], r["count"], r["flops"] / 1e9,
            r["bytes"] / 1e6, r.get("intensity", 0.0), r["pct_flops"])
        if have_time:
            line += " %6.2f %-14s" % (r.get("pct_time", 0.0),
                                      r.get("class", "-"))
        lines.append(line)
    lines.append("total: %.3f gflops, %.2f MB analytic%s" % (
        table["total_flops"] / 1e9, table["total_bytes"] / 1e6,
        ", %.2fx of XLA's %.3f gflops" % (
            table["flops_vs_xla"],
            table["xla_cost_analysis"]["flops"] / 1e9)
        if table.get("flops_vs_xla") else ""))
    return "\n".join(lines)



# -- the compiled program: optimized HLO (`Compiled.as_text()`) ---------------
# One parse gives the profiler both of its maps: `scope_map` is the
# `op_name` column, `cost_map` the pricing below.  What is counted:
#
# * bytes: the LOGICAL size of every array an instruction reads and writes
#   (tuples flattened; this JAX prints no shapes at a call site, so an
#   operand's shape is its defining instruction's).  Tile padding is not
#   counted, nor what a kernel reads twice.  As `HloCostAnalysis` has it
#   where that is cheap: a fusion parameter that the fused computation only
#   slices counts at the slices' size, a `dynamic-update-slice` at the
#   update's, an async pair once (at its `-start`; the `-done` is free), an
#   array that is an instruction's operand twice over once, and
#   `bitcast`, `tuple`, `get-tuple-element`, `parameter`, `constant`, the
#   custom calls that only name something (`_FREE_TARGETS`) and the
#   control-flow wrappers nothing (their bodies are priced);
# * where the bytes live: a layout with a memory-space mark (`S(1)`: the
#   on-chip memory XLA prefetches into) is on chip, one without is HBM;
# * MXU work: `2 x result x contracted` of every `dot` and `convolution`
#   at top level or anywhere under a fusion's `calls=`; for a custom call
#   the FLOPs of the kernel's own `cost_estimate` where it states one,
#   else None (a kernel's FLOPs stay with its counts file).  Elementwise
#   work is not counted: the floor of such an instruction is its bytes;
# * `bytes_by_scope`: an instruction's HBM bytes by the `op_name` they
#   belong to.  Inside a fusion each parameter goes to the fused
#   instruction that consumes it and each output to the one that produces
#   it (through a nested fusion, a `bitcast` or a `convert` that has no
#   `op_name` to the first instruction that has one; consumers under two
#   scopes split a parameter evenly); an instruction with no `op_name` of
#   its own (a prefetch into the on-chip memory) goes where its result's
#   bytes go.  Exact in bytes; it says nothing about time.

HloInstr = collections.namedtuple(
    "HloInstr", "name shape opcode operands arg attrs op_name root")

_HLO_LINE = re.compile(r"^\s*(ROOT\s+)?%?([^\s=]+) = ")
_HLO_HEADER = re.compile(r"^(ENTRY\s+)?%?([^\s(]+)\s*\(.*->.*\{\s*$")
_HLO_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_HLO_ARRAY = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\](?:\{([^}]*)\})?")
_PARENS = re.compile(r"[()]")
_CALLEE = re.compile(
    r"\b(calls|body|condition|to_apply|true_computation|"
    r"false_computation)=%?([^\s,}]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_DIM_LABELS = re.compile(r"\bdim_labels=(\w+)_(\w+)->(\w+)")
_WINDOW = re.compile(r"\bwindow=\{([^}]*)\}")
_LHS_CONTRACT = re.compile(r"\blhs_contracting_dims=\{([0-9,]*)\}")
_TARGET = re.compile(r'\bcustom_call_target="([^"]*)"')
_KIND = re.compile(r"\bkind=(\w+)")
# what a Pallas kernel says of itself (`pallas_call(cost_estimate=...)`:
# the installed JAX's grouped matmuls do; no kernel of `ops/` does)
_ESTIMATE = re.compile(
    r'"cost_estimate":\{"flops":"(\d+)"[^}]*?"bytes_accessed":"(\d+)"')

# instructions that move nothing: names for what is there already, and
# the wrappers whose bodies are priced in their place
_FREE = frozenset([
    "parameter", "constant", "bitcast", "tuple", "get-tuple-element",
    "after-all", "partition-id", "replica-id", "opt-barrier",
    "while", "conditional", "call"])
# custom calls that only name or promise something: adjacent allocations
# read as one array, a buffer reserved, indices declared in bounds
_FREE_TARGETS = frozenset(["ConcatBitcast", "AllocateBuffer",
                           "AssumeGatherIndicesInBound"])
_SLICING = frozenset(["slice", "dynamic-slice", "gather"])
_RENAMING = frozenset(["bitcast", "reshape", "get-tuple-element"])
COST_SUMS = ("bytes_read", "bytes_written", "hbm_bytes_read",
             "hbm_bytes_written", "onchip_bytes_read",
             "onchip_bytes_written", "mxu_flops")


def _close(text, start):
    """Index of the parenthesis that closes the one at *start*."""
    depth = 0
    for m in _PARENS.finditer(text, start):
        depth += 1 if m.group() == "(" else -1
        if not depth:
            return m.start()
    raise ValueError("unbalanced parentheses in %r" % text[start:start + 80])


def _instruction(line, m):
    """The `HloInstr` of one instruction's line, *m* its `_HLO_LINE`
    match; of a line that is not laid out as XLA prints one, the name and
    the op_name alone."""
    at = m.end()
    try:
        end = _close(line, at) + 1 if line[at] == "(" \
            else line.index(" ", at)
        paren = line.index("(", end)
        close = _close(line, paren)
    except (ValueError, IndexError):
        op = _HLO_OP_NAME.search(line, at)
        return HloInstr(m.group(2), "", "", (), "", "",
                        op.group(1) if op else None, bool(m.group(1)))
    cut = line.find(", backend_config=", close)
    attrs = line[close + 1:cut if cut >= 0 else len(line)]
    opcode = line[end + 1:paren]
    if opcode == "custom-call" and cut >= 0:
        estimate = _ESTIMATE.search(line, cut)
        if estimate:
            attrs += ", " + estimate.group()
    op = _HLO_OP_NAME.search(attrs)
    inner = line[paren + 1:close]
    named = "%" in inner
    return HloInstr(
        m.group(2), line[at:end], opcode,
        tuple(part.split()[-1].lstrip("%")
              for part in inner.split(", ")) if named else (),
        "" if named else inner[:32], attrs,
        op.group(1) if op else None, bool(m.group(1)))


def parse_optimized_hlo(text):
    """``(entry, {computation: [HloInstr, ...]})`` of an optimized-HLO
    module's text.  `shape` is the result's shape as printed (layout and
    memory space with it), `operands` the operands' names, `arg` what the
    parentheses hold where that names no operand (a parameter's number),
    `attrs` what follows them up to `backend_config` (and a kernel's own
    `cost_estimate` from inside it, where it states one)."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        m = _HLO_LINE.match(line)
        if m is None:
            h = _HLO_HEADER.match(line)
            if h:
                cur = comps[h.group(2)] = []
                if h.group(1):
                    entry = h.group(2)
            elif line.startswith("}"):
                cur = None
            continue
        if cur is None:             # a bare instruction, under no header
            cur = comps.setdefault("", [])
        cur.append(_instruction(line, m))
    return entry, comps


def hlo_op_names(parsed):
    """``{instruction: op_name}`` over every computation of a parsed
    module, the fused ones with the rest, in the text's order."""
    shared = {}
    return {i.name: shared.setdefault(i.op_name, i.op_name)
            for comp in parsed[1].values() for i in comp
            if i.op_name is not None}


@functools.lru_cache(maxsize=16384)
def _leaves(shape):
    """``((dims, bytes, on_chip), ...)`` of the arrays in a printed shape
    (a tuple's, flattened; a token has none)."""
    out = []
    for dtype, dims, layout in _HLO_ARRAY.findall(shape):
        width = _DTYPE_BYTES.get(dtype)
        if width is None:               # token[], opaque[]
            continue
        dims = tuple(int(d) for d in dims.split(",") if d)
        out.append((dims, _prod(dims) * width, "S(" in layout))
    return tuple(out)


def _size(shape):
    return sum(b for _, b, _ in _leaves(shape))


def _on_chip(shape):
    leaves = _leaves(shape)
    return bool(leaves) and all(chip for _, _, chip in leaves)


def _scope(op_name):
    """An op_name without its last part, the primitive's own name."""
    return op_name.rpartition("/")[0]


def _one_a_scope(op_names):
    """The first of *op_names* under each scope, in order."""
    first = {}
    for name in op_names:
        first.setdefault(_scope(name), name)
    return list(first.values())


class _Computation:
    """One computation's instructions by name, with who uses each."""

    def __init__(self, instrs):
        self.instrs = instrs
        self.by_name = {i.name: i for i in instrs}
        self.users = {}
        self.params = {}
        for i in instrs:
            for k, o in enumerate(i.operands):
                self.users.setdefault(o, []).append((i, k))
            if i.opcode == "parameter" and i.arg.isdigit():
                self.params[int(i.arg)] = i
        self.root = next((i for i in instrs if i.root),
                         instrs[-1] if instrs else None)

    def shape_of(self, name):
        d = self.by_name.get(name)
        return d.shape if d else ""


def _called(instr):
    """``{attribute: computation}`` of what *instr* calls, a
    conditional's branches under ``branches``."""
    out = dict(_CALLEE.findall(instr.attrs))
    b = _BRANCHES.search(instr.attrs)
    if b:
        out["branches"] = [n.strip().lstrip("%")
                           for n in b.group(1).split(",") if n.strip()]
    elif "true_computation" in out:
        out["branches"] = [out["true_computation"],
                           out["false_computation"]]
    return out


def _fused(instr):
    """Name of the computation a fusion calls."""
    return _called(instr).get("calls", "")


@functools.lru_cache(maxsize=1024)
def _taps(size, kernel, out, stride, pad, lhs_dilate, rhs_dilate):
    """How many (kernel position, output position) pairs of one spatial
    axis land on an element of the input, padding and the holes of a
    dilated input left out: what `HloCostAnalysis` counts."""
    n = 0
    for k in range(kernel):
        for o in range(out):
            at = o * stride - pad + k * rhs_dilate
            if at >= 0 and at % lhs_dilate == 0 and at // lhs_dilate < size:
                n += 1
    return n


def _window(attrs, n):
    """``{field: [one number a spatial axis]}`` of a `window={...}`."""
    out = {"stride": [1] * n, "pad": [0] * n, "lhs_dilate": [1] * n,
           "rhs_dilate": [1] * n}
    w = _WINDOW.search(attrs)
    for field in (w.group(1).split() if w else ()):
        key, _, value = field.partition("=")
        if key in out:
            out[key] = [int(v.split("_")[0]) for v in value.split("x")]
    return out


def _instruction_flops(i, comp):
    """MXU FLOPs of one `dot` or `convolution` (0 for anything else)."""
    if i.opcode not in ("dot", "convolution") or len(i.operands) < 2:
        return 0.0
    leaves = _leaves(i.shape)
    lhs = _leaves(comp.shape_of(i.operands[0]))
    rhs = _leaves(comp.shape_of(i.operands[1]))
    if not (leaves and lhs and rhs):
        return 0.0
    out_dims, lhs_dims, rhs_dims = leaves[0][0], lhs[0][0], rhs[0][0]
    if i.opcode == "dot":
        c = _LHS_CONTRACT.search(i.attrs)
        dims = _int_list(c.group(1)) if c else []
        return dot_flops(_prod(out_dims), _prod(lhs_dims[d] for d in dims))
    labels = _DIM_LABELS.search(i.attrs)
    if not labels:
        return 0.0
    l, r, o = labels.groups()
    axes = sorted(ch for ch in r if ch.isdigit())
    w = _window(i.attrs, len(axes))
    positions = 1.0
    for n, ax in enumerate(axes):
        out = out_dims[o.index(ax)]
        positions *= _taps(lhs_dims[l.index(ax)], rhs_dims[r.index(ax)],
                           out, w["stride"][n], w["pad"][n],
                           w["lhs_dilate"][n], w["rhs_dilate"][n]) \
            / max(out, 1)
    return conv_flops(_prod(out_dims), positions, rhs_dims[r.index("i")])


class _Pricing:
    """The records of one parsed module."""

    def __init__(self, parsed):
        self.entry, self.raw = parsed
        self.comps = {}
        self.flops_memo = {}
        self.records = {}
        self.operand_scopes = {}    # fusion -> its operands' op_names
        self.walked = set()

    def comp(self, name):
        if name not in self.comps:
            self.comps[name] = _Computation(self.raw.get(name, []))
        return self.comps[name]

    # -- MXU work -------------------------------------------------------------
    def flops(self, name):
        """MXU FLOPs of computation *name*, nested fusions with it."""
        if name not in self.flops_memo:
            self.flops_memo[name] = 0.0     # (no recursion in HLO)
            c = self.comp(name)
            self.flops_memo[name] = sum(
                self.flops(_fused(i))
                if i.opcode == "fusion" else _instruction_flops(i, c)
                for i in c.instrs)
        return self.flops_memo[name]

    # -- bytes inside a fusion ------------------------------------------------
    def read_bytes(self, c, name, full):
        """Bytes of the array *name* (*full* of them) that computation
        *c* reads: the slices' where it only slices it, none of a buffer
        it only updates in place."""
        total = 0
        for u, k in c.users.get(name, ()):
            if u.opcode in _SLICING and k == 0:
                total += _size(u.shape)
            elif u.opcode == "dynamic-update-slice" and k == 0:
                continue
            elif u.opcode in _RENAMING and u is not c.root:
                total += self.read_bytes(c, u.name, full)
            elif u.opcode == "fusion":
                inner = self.comp(_fused(u))
                p = inner.params.get(k)
                if p is None:
                    return full
                if all(i.opcode in ("parameter", "bitcast")
                       for i in inner.instrs):
                    # a `bitcast_fusion` renames it: who reads that?
                    total += self.read_bytes(c, u.name, full)
                else:
                    total += self.read_bytes(inner, p.name, full)
            else:
                return full
            if total >= full:
                return full
        return total

    def written_bytes(self, c, name, full):
        """Bytes written of output *name* of computation *c*: the update's
        where it is a `dynamic-update-slice` of a buffer."""
        i = c.by_name.get(name)
        while i is not None and i.opcode == "bitcast" and i.operands:
            i = c.by_name.get(i.operands[0])
        if i is None:
            return full
        if i.opcode == "dynamic-update-slice" and len(i.operands) > 1:
            return min(full, _size(c.shape_of(i.operands[1])))
        if i.opcode == "fusion":
            inner = self.comp(_fused(i))
            if inner.root is not None and inner.root.opcode != "tuple":
                return self.written_bytes(inner, inner.root.name, full)
        return full

    def consumers(self, c, name, out, depth=0):
        """op_names of the first instructions with one that consume
        *name* in *c*, into *out*."""
        for u, _ in c.users.get(name, ()):
            if u.op_name:
                out.append(u.op_name)
            elif depth < 16:
                self.consumers(c, u.name, out, depth + 1)
        return out

    def producer(self, c, name):
        """op_name of the first instruction with one on the way back from
        *name* along first operands."""
        i = c.by_name.get(name)
        for _ in range(32):
            if i is None or i.op_name:
                break
            i = c.by_name.get(i.operands[0]) if i.operands else None
        return i.op_name if i is not None else None

    # -- one instruction ------------------------------------------------------
    def fusion_traffic(self, i, c, inner, own):
        """``(reads, their op_names, writes, their op_names)`` of fusion
        *i* of computation *c*, *inner* its fused computation: a read or
        a write is ``(bytes, on chip)``."""
        reads, read_to, writes, write_to = [], [], [], []
        for k, o in enumerate(i.operands):
            shape = c.shape_of(o)
            full = _size(shape)
            p = inner.params.get(k)
            reads.append((self.read_bytes(inner, p.name, full)
                          if p else full, _on_chip(shape)))
            read_to.append(_one_a_scope(
                self.consumers(inner, p.name, []) if p else []) or own)
        self.operand_scopes[i.name] = read_to
        root = inner.root
        made = [] if root is None else \
            list(root.operands) if root.opcode == "tuple" else [root.name]
        leaves = _leaves(i.shape)
        if len(made) != len(leaves):
            made = [None] * len(leaves)
        for (_, full, chip), name in zip(leaves, made):
            writes.append((self.written_bytes(inner, name, full)
                           if name else full, chip))
            scope = self.producer(inner, name) if name else None
            write_to.append([scope] if scope else own)
        return reads, read_to, writes, write_to

    def plain_traffic(self, i, c, own):
        """The same of an instruction that is no fusion."""
        op, result = i.opcode, i.shape
        if op.endswith("-start"):
            # an async pair once, here: the result is its `-done`'s
            done = [u for u, _ in c.users.get(i.name, ())
                    if u.opcode.endswith("-done")]
            result = done[0].shape if done else ""
            if op == "async-start":
                # the pair as XLA prints it without its short form (an
                # executable read back from the compile cache): what it
                # does is the root of the computation it wraps
                root = self.comp(_fused(i)).root
                op = root.opcode + "-start" if root is not None else op
        reads = []
        for k, o in enumerate(i.operands):
            shape = c.shape_of(o)
            full = _size(shape)
            if k == 0 and (op in _SLICING or op.startswith(
                    ("slice-", "dynamic-slice-"))):
                full = min(full, _size(result))
            elif k == 0 and op == "dynamic-update-slice" \
                    or o in i.operands[:k]:
                # updated in place; or an array handed over a second time
                # (a kernel that reads thirds of one array through three
                # operands reads the array once)
                full = 0
            reads.append((full, _on_chip(shape)))
        if op == "dynamic-update-slice" and len(i.operands) > 1:
            writes = [(_size(c.shape_of(i.operands[1])), _on_chip(result))]
        else:
            writes = [(b, chip) for _, b, chip in _leaves(result)]
        return reads, [own] * len(reads), writes, [own] * len(writes)

    def price(self, i, c):
        """The record of instruction *i* of computation *c*."""
        op = i.opcode
        own = [i.op_name] if i.op_name else []
        rec = {"op_name": i.op_name, "opcode": op}
        traffic, flops = ([], [], [], []), 0.0
        target = _TARGET.search(i.attrs) if op == "custom-call" else None
        if target:
            rec["target"] = target.group(1)
            rec["kernel"] = re.sub(r"[.\d]+$", "", i.name)
        if op in _FREE or op.endswith("-done") or \
                (target and target.group(1) in _FREE_TARGETS):
            pass
        elif op == "fusion":
            called = _fused(i)
            kind = _KIND.search(i.attrs)
            rec["kind"] = kind.group(1) if kind else None
            flops = self.flops(called)
            traffic = self.fusion_traffic(i, c, self.comp(called), own)
        else:
            traffic = self.plain_traffic(i, c, own)
            if op == "custom-call":
                said = _ESTIMATE.search(i.attrs)
                flops = float(said.group(1)) if said else None
            else:
                flops = _instruction_flops(i, c)
        reads, read_to, writes, write_to = traffic
        by_scope = {}
        for (b, chip), to in zip(reads + writes, read_to + write_to):
            if b and not chip:
                for name in to or [None]:
                    by_scope[name] = by_scope.get(name, 0.0) \
                        + b / max(len(to), 1)
        for key, moved in (("read", reads), ("written", writes)):
            rec["bytes_" + key] = sum(b for b, _ in moved)
            rec["hbm_bytes_" + key] = sum(b for b, chip in moved
                                          if not chip)
            rec["onchip_bytes_" + key] = sum(b for b, chip in moved
                                             if chip)
        rec["mxu_flops"] = flops
        rec["bytes_by_scope"] = by_scope
        return rec

    # -- a computation that runs as device events -----------------------------
    def outer_scopes(self, c, name, out, depth=0):
        """op_names that the consumers of *name* in *c* give its bytes
        to: a fusion's as it credits that operand, another's its own."""
        for u, k in c.users.get(name, ()):
            if u.name in self.operand_scopes:
                out.extend(self.operand_scopes[u.name][k])
            elif u.op_name:
                out.append(u.op_name)
            elif depth < 8:
                self.outer_scopes(c, u.name, out, depth + 1)
        return out

    def walk(self, name):
        """Price computation *name* and those its control flow names."""
        if name in self.walked:
            return
        self.walked.add(name)
        c = self.comp(name)
        for i in c.instrs:
            self.records[i.name] = dict(self.price(i, c), computation=name)
            if i.opcode in ("while", "conditional", "call"):
                called = _called(i)
                for key in ("body", "condition", "to_apply"):
                    if key in called:
                        self.walk(called[key])
                for branch in called.get("branches", ()):
                    self.walk(branch)
        # bytes with no op_name of their own go where their result's go
        for i in c.instrs:
            by_scope = self.records[i.name]["bytes_by_scope"]
            if None not in by_scope:
                continue
            to = _one_a_scope(self.outer_scopes(c, i.name, [])) or \
                [self.producer(c, i.name) or ""]
            share = by_scope.pop(None) / len(to)
            for scope in to:
                by_scope[scope] = by_scope.get(scope, 0.0) + share

    def totals(self, name, memo=None):
        """The sums of computation *name* as `HloCostAnalysis` adds a
        module up: a loop's body and condition once, of a conditional's
        branches the one with most bytes."""
        memo = {} if memo is None else memo
        if name in memo:
            return memo[name]
        out = dict.fromkeys(COST_SUMS, 0.0)

        def add(other):
            for k in out:
                out[k] += other[k] or 0.0
        for i in self.comp(name).instrs:
            add(self.records[i.name])
            if i.opcode == "conditional":
                branches = [self.totals(b, memo)
                            for b in _called(i).get("branches", ())]
                if branches:
                    add(max(branches, key=lambda t: t["bytes_read"]
                            + t["bytes_written"]))
            elif i.opcode in ("while", "call"):
                called = _called(i)
                for key in ("body", "condition", "to_apply"):
                    if key in called:
                        add(self.totals(called[key], memo))
        memo[name] = out
        return out


def price_optimized_hlo(parsed):
    """``(cost map, totals)`` of a parsed module (`parse_optimized_hlo`):
    one record for every instruction that can run as a device event (the
    ENTRY computation's, and those of the bodies, conditions and branches
    its `while`, `conditional` and `call` instructions name), and the
    module's sums (`COST_SUMS`) as XLA's `cost_analysis()` adds them up,
    with the ENTRY computation's name under ``entry``.  The comment above
    says what a record counts."""
    pricing = _Pricing(parsed)
    if pricing.entry is None:
        return {}, dict(dict.fromkeys(COST_SUMS, 0.0), entry=None)
    pricing.walk(pricing.entry)
    return pricing.records, dict(pricing.totals(pricing.entry),
                                 entry=pricing.entry)
