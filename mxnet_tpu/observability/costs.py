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

import re

__all__ = ["parse_hlo_ops", "cost_table", "format_table"]

# dtype byte widths for tensor<...x DTYPE> suffixes
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e5m2": 1, "f8e4m3fn": 1,
    "i64": 8, "ui64": 8, "i32": 4, "ui32": 4, "i16": 2, "ui16": 2,
    "i8": 1, "ui8": 1, "i1": 1, "pred": 1,
    "c64": 8, "c128": 16,
}

_TENSOR_RE = re.compile(r"tensor<([^>]*)>")
_OP_RE = re.compile(r'=\s+"?(?:stablehlo|mhlo|chlo)\.([a-zA-Z0-9_]+)"?')
_CONTRACT_RE = re.compile(r"contracting_dims\s*=\s*\[([0-9,\s]*)\]")
_BATCH_RE = re.compile(r"batching_dims\s*=\s*\[([0-9,\s]*)\]")
_FEATURE_GROUP_RE = re.compile(r"feature_group_count\s*=\s*(\d+)")
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
        return 2.0 * rcount * k
    if op == "convolution":
        # 2 * prod(out) * (kernel spatial) * in_channels / groups
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
        groups = 1
        g = _FEATURE_GROUP_RE.search(line)
        if g:
            groups = max(1, int(g.group(1)))
        return 2.0 * rcount * spatial * in_ch / groups
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
