"""The four readers that price a traced step (`benchmarks/program_costs.py`
over `profiler.cost_map`: `step_hbm_gb`, `step_floor_ms`,
`step_memory_bound_ms`, `step_optimizer_hbm_gb`) on a made-up outcome: a
hand-made event list and cost map whose every number is counted here by
hand, the "not priced" case, and a parent commit whose profiler has no
`cost_map` (every reader returns None and nothing is printed)."""

import json
import os

import pytest

import bench_suite_util as util
from benchmarks import harness, program_costs, program_spans, trace
from benchmarks.layer_metrics import (step_floor_ms, step_hbm_gb,
                                      step_memory_bound_ms,
                                      step_optimizer_hbm_gb)

CELL = "opt-1.3b_train_1chip"
READERS = {"step_hbm_gb": step_hbm_gb, "step_floor_ms": step_floor_ms,
           "step_memory_bound_ms": step_memory_bound_ms,
           "step_optimizer_hbm_gb": step_optimizer_hbm_gb}
FLOPS, BYTES = 197e12, 819e9        # peaks.json, "TPU v5 lite"
FWD = "jit(parallel_step)/mx.loss/jvp(FullyConnected:fc%d)/dot_general"
BWD = "jit(parallel_step)/mx.loss/transpose(jvp(FullyConnected:fc%d))/" \
    "dot_general"
OPT = "jit(parallel_step)/mx.optimizer/add"
FLASH = "jit(parallel_step)/mx.loss/jvp(_contrib_DotProductAttention:att0)" \
    "/mx.flash.fwd/mx_flash_fwd/pallas_call"


ENTRY = "main.1"


def record(op_name, opcode="fusion", read=0, written=0, onchip_read=0,
           flops=0.0, by_scope=None, computation=ENTRY, **more):
    hbm = read + written
    return dict(op_name=op_name, opcode=opcode, bytes_read=read + onchip_read,
                bytes_written=written, hbm_bytes_read=read,
                hbm_bytes_written=written, onchip_bytes_read=onchip_read,
                onchip_bytes_written=0, mxu_flops=flops,
                bytes_by_scope=by_scope if by_scope is not None
                else ({op_name: float(hbm)} if hbm else {}),
                computation=computation, **more)


#: instruction -> (record, device ns of one event, events a step)
STEP = {
    # two forward matmuls, MXU bound: 0.2 ms of MXU work each, 0.05 of HBM
    "fusion.1": (record(FWD % 0, read=30_000_000, written=10_950_000,
                        flops=0.2e-3 * FLOPS, kind="kOutput"), 251_000, 1),
    "fusion.2": (record(FWD % 1, read=30_000_000, written=10_950_000,
                        flops=0.2e-3 * FLOPS, kind="kOutput"), 250_000, 1),
    # a weight-gradient fusion that carries the update, bound by its
    # bytes: 0.1 ms of MXU work, 0.3 ms of HBM; 18 of its 24.57 MB are
    # the update's
    "fusion.3": (record(BWD % 0, read=163_800_000, written=81_900_000,
                        flops=0.1e-3 * FLOPS, kind="kOutput", by_scope={
                            BWD % 0: 65_700_000.0, OPT: 180_000_000.0}),
                 400_000, 1),
    # a prefetch of the master into the on-chip memory: the update's
    "copy-start.1": (record(None, "copy-start", read=40_000_000,
                            by_scope={OPT: 40_000_000.0}), 20_000, 1),
    "copy-done.1": (record(None, "copy-done"), 30_000, 1),
    # the update of a leaf that no fusion carries
    "fusion.4": (record(OPT, read=8_190_000, written=8_190_000,
                        kind="kLoop"), 30_000, 1),
    # a kernel that states no FLOPs: a bytes floor alone; a loop's body,
    # four times a step
    "mx_flash_fwd.1": (record(FLASH, "custom-call", read=4_095_000,
                              written=4_095_000, flops=None,
                              target="tpu_custom_call",
                              kernel="mx_flash_fwd",
                              computation="body.7"), 50_000, 4),
}
NOT_PRICED = ("convert_element_type.9", 7_000)      # the batch's cast
# a small program beside the step holds an instruction called as one of
# the step's is: its event must not be priced as the step's fusion
SAME_NAME = ("fusion.1", 1_000)


class Outcome:
    def __init__(self, cost_map, events, steps=1, **facts):
        self.cell = harness.Cell(CELL, 1, 1, 1, 0.0, util.REPO)
        self.facts = dict(facts, program_spans=[], program_scope_map={
                              k: r["op_name"] for k, r in
                              (cost_map or {}).items() if r["op_name"]},
                          program_cost_map=cost_map,
                          program_cost_totals={"entry": ENTRY},
                          device_kind="TPU v5 lite", traced_blocks=1,
                          steps_per_block=steps)
        self.end_to_end = {"setup_s": 30.0}
        self.trace = trace.Trace(events) if events else None
        self.spans = None


MARKER = "fusion.3"         # the entry computation's longest instruction


def _stream(steps, unpriced=True):
    """``[(instruction, ns)]`` as the device ran them inside a window of
    *steps* host steps: it runs behind the host, so the window opens in
    the middle of an earlier step (after its first two instructions) and
    closes in the middle of a later one (on its marker): *steps* whole
    cycles of the step from marker to marker, and two fragments."""
    one = [(name, dur) for name, (_, dur, times) in STEP.items()
           for _ in range(times)]
    if unpriced:
        one += [NOT_PRICED, SAME_NAME]
    upto = [n for n, _ in one].index(MARKER) + 1
    return one[2:] + one * (steps - 1) + one[:upto]


def _traced(steps=1, unpriced=True, costs=True):
    events = [{"plane": "/host:CPU", "line": "python",
               "name": "bench.fit_batch", "start_ns": 0,
               "dur_ns": 10_000_000 * steps}]
    t = 10
    for name, dur in _stream(steps, unpriced):
        events.append({"plane": "/device:TPU:0", "line": "XLA Ops",
                       "name": "%" + name + " = f32[] fusion()",
                       "start_ns": t, "dur_ns": dur})
        t += dur + 5
    return Outcome({k: v[0] for k, v in STEP.items()} if costs else None,
                   events, steps)


def _by_hand(steps=1):
    """What the four readers should say of `STEP`: counts and times over
    one whole cycle, whatever the host's count of steps."""
    hbm = floor = update = 0.0
    for rec, dur, times in STEP.values():
        moved = rec["hbm_bytes_read"] + rec["hbm_bytes_written"]
        hbm += times * moved
        mxu_s, hbm_s = (rec["mxu_flops"] or 0.0) / FLOPS, moved / BYTES
        floor += times * max(mxu_s, hbm_s)
        update += times * rec["bytes_by_scope"].get(OPT, 0.0)
    bound = sum(STEP[name][1]
                for name in ("fusion.3", "copy-start.1", "fusion.4"))
    return {"step_hbm_gb": hbm / 1e9, "step_floor_ms": 1e3 * floor,
            "step_memory_bound_ms": 1e-6 * bound,
            "step_optimizer_hbm_gb": update / 1e9}


@pytest.mark.parametrize("name", sorted(READERS))
@pytest.mark.parametrize("steps", [1, 3])
def test_a_reader_prices_the_traced_steps(name, steps, capsys):
    want = _by_hand(steps)
    # by hand again, as plain numbers: 0.2 + 0.2 + 0.3 ms of floors, the
    # prefetch, the small update and four kernel calls at their bytes
    assert want["step_floor_ms"] == pytest.approx(
        0.7 + 1e3 * (40e6 + 16.38e6 + 4 * 8.19e6) / BYTES)
    # the window holds one marker more than the host counted steps: the
    # times, as the counts, are a whole cycle's
    assert want["step_memory_bound_ms"] == pytest.approx(0.4 + 0.02 + 0.03)
    assert want["step_optimizer_hbm_gb"] == pytest.approx(0.23638)
    out = _traced(steps)
    assert READERS[name].read(out) == pytest.approx(want[name], rel=1e-9)
    said = capsys.readouterr().out
    assert said.startswith("bench: ")
    # a second reading prints nothing again (several readers share a line)
    READERS[name].read(out)
    assert capsys.readouterr().out == ""


def test_what_the_readers_print_beside_their_values(capsys):
    out = _traced()
    for reader in READERS.values():
        reader.read(out)
    said = capsys.readouterr().out
    # the events the map lacks: left out, their time printed (over one
    # whole cycle); so is the foreign `fusion.1`, the shorter of the two
    # events that bear that name in a cycle: counted, and not priced
    assert "cost map prices 7 instructions of the traced steps over 1 " \
        "whole cycles; not priced 0.008 ms a step, 0.67% of the events' " \
        "time [convert_element_type.9 0.007] [fusion.1 (beside the step) " \
        "0.001]; of it 1 events beside the step under a name of the " \
        "step's own" in said
    # the update's bytes, how many ride in another phase's instructions
    assert "the update moves 0.236 GB a step through HBM, 0.289 ms at " \
        "the peak; 0.220 GB (0.269 ms) of it inside instructions named " \
        "for the forward or the backward; step_optimizer_ms 0.030" in said
    # (times are a whole cycle's, as the counts are; the accepted
    # `step_device_ms` beside them is over the host's one step, and holds
    # the marker's second run, the 0.4 ms that closes the cycle)
    assert "bound by HBM bytes 0.450, by the MXU 0.501, in kernels that " \
        "state no FLOPs 0.200, in instructions that move nothing through " \
        "HBM (the waits that end an async pair, work fed from the on-chip " \
        "memory) 0.030" in said
    assert "against 1.189 ms of the same cycles' events (step_device_ms " \
        "1.589" in said
    # the table: scopes by ms over their floor, forward and backward
    # apart, every node of one operator together
    # whose bytes: the update's 0.236 GB, of which 0.016 in instructions
    # named for it; the backward's fusion bears 0.246 and owns 0.066
    table = said[said.index("bench: HBM GB a step by the scope"):]
    credit = [l.split() for l in table.splitlines()[1:4]]
    assert credit == [
        ["bench:", "mx.optimizer", "0.236", "0.016"],
        ["bench:", "FullyConnected", "forward", "0.082", "0.082"],
        ["bench:", "FullyConnected", "backward", "0.066", "0.246"]]
    said = said[said.index("bench: scopes by ms a step"):]
    rows = [(l[9:67].strip(), l[67:].split()) for l in said.splitlines()
            if l.startswith("bench:   ") and "hbm_GB" not in l]
    assert [key for key, _ in rows] == [
        "_contrib_DotProductAttention/mx.flash.fwd forward",
        "FullyConnected forward", "FullyConnected backward",
        "mx.optimizer", "(unscoped)"]
    forward = rows[1][1]
    assert [float(v) for v in forward[:5]] == pytest.approx(
        [0.501, 0.4, 0.4, 0.1, 0.0819], abs=1e-3) and forward[5] == "mxu"
    assert rows[0][1][5:] == ["bytes", "1"]


def test_the_totals_line_sets_the_map_s_sum_beside_xla_s(capsys):
    out = _traced()
    out.facts["program_cost_totals"] = {
        "bytes_read": 40e9, "bytes_written": 20e9, "hbm_bytes_read": 30e9,
        "hbm_bytes_written": 15e9, "onchip_bytes_read": 10e9,
        "onchip_bytes_written": 5e9, "mxu_flops": 17e12, "entry": ENTRY,
        "xla": {"bytes_accessed": 75e9, "flops": 17.5e12}}
    step_hbm_gb.read(out)
    said = capsys.readouterr().out
    assert "bench: cost map 60.000 GB (HBM 45.000 + on-chip 15.000; " in said
    assert "XLA 75.000 GB, ratio 0.800 (" in said
    assert "MXU 17.000 TFLOP, XLA's flops 17.500" in said


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_loop_s_body_that_outnumbers_the_entry_is_not_taken_for_it(
        name, monkeypatch):
    """Scanned layers: the body of a `while` runs more events a step than
    the ENTRY computation holds.  The cycles are still the ENTRY's
    (`cost_totals` names it), and the body counts as often as it ran."""
    rec, dur, _ = STEP["mx_flash_fwd.1"]
    monkeypatch.setitem(STEP, "mx_flash_fwd.1", (rec, dur, 24))
    out = _traced(steps=3)
    p = program_costs.priced(out)
    assert p.per_step == 3 and p.rows["mx_flash_fwd.1"][0] == 3 * 24
    assert READERS[name].read(out) == pytest.approx(_by_hand(3)[name])


def test_without_the_entry_s_name_the_host_s_steps_count():
    out = _traced(steps=3)
    out.facts["program_cost_totals"] = None
    p = program_costs.priced(out)
    assert p.per_step == 3 and p.beside == 0
    # ... over all of the window's events: two fragments more than three
    # whole cycles hold
    assert p.rows[MARKER][0] == 4
    # so too where the marker ran once (no whole cycle to count over):
    # the fragments' events are the step's, and none is taken for foreign
    short = _traced(steps=1)
    device, = short.trace.devices.values()
    seen = [e for e in device
            if program_spans.instruction(e[2]) == MARKER]
    device[:] = [e for e in device if e not in seen[1:]]
    p = program_costs.priced(short)
    assert p.per_step == 1 and p.beside == 0 and p.rows["fusion.1"][0] == 2


def test_an_entry_instruction_counted_twice_a_cycle_shows_as_not_priced():
    """A trace that holds an event twice over (or any other over-count of
    an ENTRY instruction) is not hidden: what is over goes to `unpriced`
    under the instruction's name and is counted in `beside`."""
    out = _traced(steps=2, unpriced=False)
    assert program_costs.priced(out).beside == 0
    again = _traced(steps=2, unpriced=False)
    device = again.trace.devices[0]
    twice = [e for e in device
             if program_spans.instruction(e[2]) == "fusion.4"]
    device.extend(twice)
    p = program_costs.priced(again)
    assert p.beside == len(twice) == 2 and p.rows["fusion.4"][0] == 2
    assert p.unpriced == {"fusion.4 (beside the step)":
                          pytest.approx(2 * 30e-6)}


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_parent_without_a_cost_map_reads_nothing(name, capsys,
                                                   monkeypatch):
    """The parent's profiler has no `cost_map`: the reader finds nothing,
    returns None and raises nothing; so does a run without a trace."""
    from mxnet_tpu import profiler
    monkeypatch.delattr(profiler, "cost_map")
    monkeypatch.delattr(profiler, "cost_totals")
    out = _traced()
    del out.facts["program_cost_map"], out.facts["program_cost_totals"]
    assert READERS[name].read(out) is None
    untraced = Outcome({k: v[0] for k, v in STEP.items()}, None)
    assert READERS[name].read(untraced) is None
    assert capsys.readouterr().out == ""


def test_a_program_before_its_first_call_reads_nothing():
    out = _traced(costs=False)
    assert program_costs.priced(out) is None
    assert all(r.read(out) is None for r in READERS.values())


@pytest.mark.parametrize("op_name, row", [
    (FWD % 3, "FullyConnected forward"),
    (BWD % 3, "FullyConnected backward"),
    (FLASH, "_contrib_DotProductAttention/mx.flash.fwd forward"),
    ("jit(parallel_step)/mx.loss/transpose(jvp(_contrib_RoutedExperts:"
     "contrib_routedexperts2))/cond/branch_1_fun/mx.moe.experts/gmm/"
     "pallas_call", "_contrib_RoutedExperts/mx.moe.experts backward"),
    ("jit(parallel_step)/mx.loss/reduce_sum", "mx.loss forward"),
    ("jit(parallel_step)/mx.grad_clip/mul", "mx.grad_clip"),
    (OPT, "mx.optimizer"),
    ("args[0]['fc0_weight']", "(unscoped)"), (None, "(unscoped)")])
def test_a_scope_s_row_in_the_table(op_name, row):
    assert program_costs.scope_key(op_name) == row


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(util.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_four_entries_name_all_five_cells(spec):
    cells = [w["name"] for w in spec["workloads"]]
    # found by name, not by place: a later PR appends its own after them
    for m in (util.named(spec["per_layer"], name) for name in READERS):
        assert set(cells[:5]) <= set(m["workloads"])
        assert (m["layer"], m["moves"], m["better"], m["source"]) == (
            "step program", "train_samples_per_s", "lower", "device_trace")
        assert not any(word in m["name"] + m["unit"]
                       for word in ("%", "roofline", "mfu", "util"))


KEYE_CELL = "keye-vl-2.0-30b-a3b_train_ep8share"
KEYE_CONFIG = "keye-vl-2.0-30b-a3b-ep8share"
KEYE_READERS = ("dsa_ms_per_step", "dsa_index_ms_per_step",
                "dsa_align_ms_per_step", "dsa_flash_roofline_pct",
                "dsa_index_roofline_pct")


def test_keye_s_declaration_with_every_entry_found_by_name(spec):
    """What `test_bench_keye_vl2.py::test_the_cell_is_declared_and_its_
    readers_list_it` holds the declaration to, with the entries found by
    `name`.  Until PR 42 that test looked for Keye's five entries by their
    place (`per_layer[-5:]`), and the four entries this file's readers are
    declared by, appended after them as ISSUE 37 asks, failed it (PERF.md
    section 7 row 35); it finds them by name now, and this copy stays."""
    with open(os.path.join(util.REPO, "benchmarks", "configs",
                           KEYE_CONFIG + ".json")) as f:
        cfg = json.load(f)
    entry = util.named(spec["configs"], KEYE_CONFIG)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["file"] == "benchmarks/configs/%s.json" % KEYE_CONFIG
    cell = util.named(spec["workloads"], KEYE_CELL)
    assert cell == {"name": KEYE_CELL, "config": KEYE_CONFIG,
                    "traffic": "fit_prefetch", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    for said in ("1x16384", "2048 of up to 16384 keys", "alignment term",
                 "top-8 of 128", "16 held"):
        assert said in cell["why"], said
    steps = cfg["train"]["steps_per_block"]
    assert ("every step" if steps == 1 else "every %d" % steps) \
        in cell["why"]
    # its own five readers are declared for it alone, one after the other
    names = [m["name"] for m in spec["per_layer"]]
    first = names.index(KEYE_READERS[0])
    assert tuple(names[first:first + 5]) == KEYE_READERS
    for name in KEYE_READERS:
        assert util.named(spec["per_layer"], name)["workloads"] \
            == [KEYE_CELL], name
    # this file's four name it, as do (since PR 42: PERF.md section 7 row
    # 31) the twenty accepted entries whose readers find something in it
    listed = {m["name"] for m in spec["per_layer"]
              if KEYE_CELL in m.get("workloads", ())}
    assert set(READERS) | set(KEYE_READERS) \
        | set(util.EXPERT_CELL_LISTS) <= listed
    # ... and reports the ones without a list
    unlisted = [m["name"] for m in spec["per_layer"] if "workloads" not in m]
    assert len(unlisted) == 9 and "model_flops_util_pct" in unlisted
