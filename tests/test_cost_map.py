"""The compiled step prices itself (`observability/costs.py`
`parse_optimized_hlo` / `price_optimized_hlo`, `profiler.cost_map`,
`profiler.cost_totals`): the parser on small recorded optimized-HLO texts
(`tests/data/hlo/`: three compiled for a described v5e and cut of their
`backend_config`, one written by hand), the scope map as the `op_name`
column of the same parse, and a step compiled here.  Counts, all on the
CPU; docs/observability.md "What the compiled step costs"."""

import gc
import json
import os
import re
import weakref

import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu import profiler
from mxnet_tpu.observability import costs

HLO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "hlo")
TEXTS = ("update_in_weight_gradient.tpu", "grouped_convolution.tpu",
         "kernel_cond_while.tpu", "handwritten")
# what `profiler.set_scope_map` parsed the text with before the cost map
OLD_SCOPE_MAP = re.compile(
    r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*?\bop_name="([^"]*)"', re.M)
MIB = 1 << 20
N = 2048 * 4096             # parameters of the recorded update


def _text(name):
    with open(os.path.join(HLO, name + ".txt")) as f:
        return f.read()


@pytest.fixture(scope="module")
def priced():
    out = {}
    for name in TEXTS:
        parsed = costs.parse_optimized_hlo(_text(name))
        out[name] = costs.price_optimized_hlo(parsed) + (parsed,)
    return out


@pytest.mark.parametrize("name", TEXTS)
def test_the_scope_map_is_the_op_name_column_of_the_same_parse(name):
    text = _text(name)
    old = {m.group(1): m.group(2) for m in OLD_SCOPE_MAP.finditer(text)}
    new = costs.hlo_op_names(costs.parse_optimized_hlo(text))
    assert new == old and list(new) == list(old) and old
    profiler.set_scope_map("a-recorded-text", text)
    try:
        assert profiler.scope_map("a-recorded-text") == old
    finally:
        profiler._compiled.pop("a-recorded-text")


UPDATE, CONV, CONTROL, HAND = TEXTS
LOSS, BWD, OPT = ("jit(update)/jvp(mx.loss)/",
                  "jit(update)/transpose(jvp(mx.loss))/",
                  "jit(update)/mx.optimizer/")
CONV_FLOPS = 2.0 * 128 * 8 * 83 * 83    # 83 taps an axis land on the input


@pytest.mark.parametrize("text, instruction, want", [
    # a kOutput fusion that carries the update: its dot's FLOPs, the
    # master read from the on-chip memory, its traffic divided between
    # the backward and the update
    (UPDATE, "fusion.3", dict(
        opcode="fusion", kind="kOutput", op_name=BWD + "dot_general",
        bytes_read=176 * MIB, hbm_bytes_read=64 * MIB,
        onchip_bytes_read=112 * MIB, bytes_written=80 * MIB,
        hbm_bytes_written=48 * MIB, onchip_bytes_written=32 * MIB,
        mxu_flops=2.0 * 2048 * 4096 * 4096,
        bytes_by_scope={BWD + "convert_element_type": 32.0 * MIB,
                        OPT + "mul": 32.0 * MIB, OPT + "sub": 32.0 * MIB,
                        OPT + "convert_element_type": 16.0 * MIB})),
    # a tuple result, flattened; a `bf_io->bf` convolution is a dot
    (UPDATE, "multiply_reduce_fusion", dict(
        kind="kOutput", bytes_written=32 * MIB + 4,
        hbm_bytes_read=80 * MIB, onchip_bytes_read=16 * MIB,
        mxu_flops=2.0 * 4096 * 4096 * 2048)),
    # an async pair once, at its start; a prefetch with no op_name goes
    # where its result's bytes go
    (UPDATE, "copy-start.2", dict(
        opcode="copy-start", op_name=None, hbm_bytes_read=32 * MIB,
        onchip_bytes_written=32 * MIB, hbm_bytes_written=0,
        bytes_by_scope={OPT + "mul": 32.0 * MIB})),
    (UPDATE, "copy-done.2", dict(opcode="copy-done", bytes_read=0,
                                 bytes_written=0, bytes_by_scope={})),
    (UPDATE, "copy-start.3", dict(
        onchip_bytes_read=32 * MIB, hbm_bytes_written=32 * MIB,
        bytes_by_scope={OPT + "convert_element_type": 32.0 * MIB})),
    # consumers under two scopes split a parameter evenly
    (UPDATE, "copy-start", dict(bytes_by_scope={
        LOSS + "dot_general": 8.0 * MIB, BWD + "dot_general": 8.0 * MIB})),
    (UPDATE, "get-tuple-element.4", dict(bytes_read=0, bytes_written=0)),
    (UPDATE, "tuple.4", dict(opcode="tuple", bytes_read=0, mxu_flops=0.0)),
    # window, stride, padding, feature_group_count: forward, and the two
    # gradients (a dilated input, batch_group_count) do the same work
    (CONV, "multiply_convert_fusion", dict(kind="kOutput",
                                           mxu_flops=CONV_FLOPS)),
    (CONV, "fusion.6", dict(mxu_flops=CONV_FLOPS, hbm_bytes_read=0,
                            hbm_bytes_written=0, bytes_by_scope={})),
    (CONV, "fusion", dict(mxu_flops=CONV_FLOPS, bytes_written=2304)),
    # a Mosaic kernel: target, name, no FLOPs
    (CONTROL, "mx_flash_fwd.1", dict(
        opcode="custom-call", target="tpu_custom_call",
        kernel="mx_flash_fwd", mxu_flops=None, bytes_read=6 * MIB,
        bytes_written=2 * MIB)),
    # wrappers cost nothing; their branches, body and condition are priced
    (CONTROL, "cond.3.clone", dict(opcode="conditional", bytes_read=0)),
    (CONTROL, "tanh_add_fusion", dict(kind="kLoop", bytes_read=4 * MIB,
                                      bytes_written=2 * MIB)),
    (CONTROL, "while", dict(opcode="while", bytes_read=0, bytes_written=0)),
    (CONTROL, "multiply_add_fusion.2", dict(bytes_read=4 * MIB)),
    (CONTROL, "lt.0", dict(opcode="compare", bytes_read=8)),
    # a parameter the fusion only slices counts at the slice's size, a
    # buffer updated in place at the update's
    (HAND, "upd", dict(bytes_read=128 * 256 * 4 + 4,
                       bytes_written=128 * 256 * 4)),
    # a kernel that states a cost_estimate: its FLOPs
    (HAND, "gmm.7", dict(kernel="gmm", mxu_flops=1e6,
                         bytes_read=512 * 128 * 2 + 4 * 128 * 256 * 2)),
    (HAND, "slice-start.1", dict(
        hbm_bytes_read=256 * 256 * 4, onchip_bytes_written=256 * 256 * 4,
        bytes_by_scope={"jit(f)/transpose(jvp(mx.loss))/neg": 262144.0})),
    # the same pair without its short form: an `async-start` that wraps
    # a slice reads the slice, and the wrapped computation is no event
    (HAND, "slice-start.2", dict(
        opcode="async-start", hbm_bytes_read=64 * 256 * 4,
        onchip_bytes_written=64 * 256 * 4, hbm_bytes_written=0)),
    (HAND, "slice-done.2", dict(opcode="async-done", bytes_read=0,
                                bytes_written=0)),
    # operands printed with their shapes, as older XLA prints them; an
    # array handed over twice is read once (a kernel that reads thirds of
    # one array through three operands)
    (HAND, "old", dict(bytes_read=262144, bytes_written=262144)),
    (HAND, "thirds.4", dict(kernel="thirds", mxu_flops=None,
                            bytes_read=512 * 128 * 2)),
    (HAND, "called", dict(opcode="call", bytes_read=0)),
    (HAND, "exp.1", dict(opcode="exponential", bytes_read=262144)),
    (HAND, "neg.f", dict(bytes_read=262144)),
    (HAND, "add.t", dict(bytes_read=262144)),
], ids=lambda v: v if isinstance(v, str) else None)
def test_an_instruction_s_record(priced, text, instruction, want):
    rec = priced[text][0][instruction]
    assert {k: rec[k] for k in want} == want
    # exact in bytes
    assert sum(rec["bytes_by_scope"].values()) == \
        rec["hbm_bytes_read"] + rec["hbm_bytes_written"]
    for side in ("read", "written"):
        assert rec["bytes_" + side] == rec["hbm_bytes_" + side] \
            + rec["onchip_bytes_" + side]


@pytest.mark.parametrize("name", TEXTS)
def test_every_instruction_that_can_run_is_priced_in_plain_values(
        priced, name):
    records, totals, (entry, comps) = priced[name]
    assert {i.name for i in comps[entry]} <= set(records)
    # the fused computations' own instructions are no device events
    assert not {"row", "dus", "convolution.5", "slice.9"} & set(records)
    for rec in records.values():
        assert all(type(v) in (str, int, float, dict, type(None))
                   for v in rec.values())
    assert set(totals) == set(costs.COST_SUMS) | {"entry"}
    assert totals["entry"] == entry


def test_the_update_in_a_weight_gradient_fusion_is_18_bytes_a_parameter(
        priced):
    """Master and momentum read and written, the bf16 weight written: the
    gradient never leaves the fusion.  10 B a parameter ride in the
    fusion named for the backward's dot, 8 in the master's two
    prefetches."""
    records = priced[UPDATE][0]
    update = sum(b for rec in records.values()
                 for op, b in rec["bytes_by_scope"].items()
                 if "/mx.optimizer/" in op)
    assert update == 18 * N
    assert sum(b for op, b in records["fusion.3"]["bytes_by_scope"].items()
               if "/mx.optimizer/" in op) == 10 * N


def _as_xla_counts(records, parsed, name=None):
    """The records' bytes added up in `HloCostAnalysis`'s own conventions,
    which explain where the map's plain sum and `cost_analysis()`'s
    `bytes accessed` differ: an async pair at both its ends, a kernel
    that states a `cost_estimate` at the bytes it states, a conditional's
    own operands and result beside its dearest branch."""
    entry, comps = parsed
    instrs = {i.name: i for i in comps[name or entry]}
    total = 0.0
    for i in comps[name or entry]:
        rec = records[i.name]
        moved = rec["bytes_read"] + rec["bytes_written"]
        said = costs._ESTIMATE.search(i.attrs) \
            if i.opcode == "custom-call" else None
        total += float(said.group(2)) if said else \
            moved * (2 if i.opcode.endswith("-start") else 1)
        called = costs._called(i)
        if i.opcode == "conditional":
            total += costs._size(i.shape) + sum(
                costs._size(instrs[o].shape) for o in i.operands)
            total += max(_as_xla_counts(records, parsed, b)
                         for b in called["branches"])
        elif i.opcode in ("while", "call"):
            total += sum(_as_xla_counts(records, parsed, called[key])
                         for key in ("body", "condition", "to_apply")
                         if key in called)
    return total


def test_the_sums_agree_with_xla_s_own_cost_analysis(priced):
    """Counted as XLA counts, the recorded texts' sums are
    `cost_analysis()`'s as it was recorded with them; the plain sum, an
    async pair once, is what `cost_totals` gives."""
    with open(os.path.join(HLO, "xla_cost_analysis.json")) as f:
        xla = json.load(f)
    for name, said in xla.items():
        records, totals, parsed = priced[name + ".tpu"]
        close = 0.001 if name != "kernel_cond_while" else 0.12
        assert _as_xla_counts(records, parsed) == pytest.approx(
            said["bytes_accessed"], rel=close), name
        assert totals["bytes_read"] + totals["bytes_written"] \
            <= _as_xla_counts(records, parsed)
        # XLA's flops hold the elementwise work too
        assert 0.9 * said["flops"] < totals["mxu_flops"] <= said["flops"] \
            or name == "kernel_cond_while"
    # a loop's body once; of the hand-written branches the dearer one
    hand = priced[HAND][1]
    assert hand["mxu_flops"] == 1e6
    assert hand["bytes_read"] + hand["bytes_written"] == sum(
        r["bytes_read"] + r["bytes_written"]
        for k, r in priced[HAND][0].items() if k != "add.t")


# -- a step compiled here -------------------------------------------------------
def _update(w, master, mom, x, y):
    def loss(wb):
        with jax.named_scope("mx.loss"):
            return jnp.mean((jnp.dot(x, wb).astype(jnp.float32) - y) ** 2)
    value, g = jax.value_and_grad(loss)(w)
    with jax.named_scope("mx.optimizer"):
        mom = 0.9 * mom - 0.1 * (g.astype(jnp.float32) + 1e-4 * master)
        master = master + mom
        return master.astype(jnp.bfloat16), master, mom, value


@pytest.fixture()
def compiled_here():
    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)
    compiled = jax.jit(_update, donate_argnums=(0, 1, 2)).lower(
        aval((64, 128), jnp.bfloat16), aval((64, 128), jnp.float32),
        aval((64, 128), jnp.float32), aval((32, 64), jnp.bfloat16),
        aval((32, 128), jnp.float32)).compile()
    profiler.set_scope_map("compiled-here", compiled.as_text(),
                           compiled.cost_analysis())
    yield compiled
    profiler._compiled.pop("compiled-here", None)


def test_nothing_is_parsed_until_a_map_is_asked_for(compiled_here):
    kept = profiler._compiled["compiled-here"]
    assert kept.packed is not None and kept.costs is None \
        and kept.scopes is None and kept.parsed is None
    assert len(kept.packed) < len(compiled_here.as_text()) / 3
    assert kept.xla["bytes_accessed"] > 0
    # the scope map asks for the parse alone: nothing is priced for it
    scopes = profiler.scope_map("compiled-here")
    assert kept.packed is None and scopes is kept.scopes \
        and kept.parsed is not None and kept.costs is None
    costs_of = profiler.cost_map("compiled-here")
    assert costs_of is kept.costs and costs_of and kept.parsed is None
    assert profiler.cost_totals("compiled-here")["entry"] in \
        {r["computation"] for r in costs_of.values()}
    assert profiler.scope_map("compiled-here") is scopes
    assert profiler.cost_map("no-such-program") is None
    assert profiler.cost_totals("no-such-program") is None


def test_the_update_s_bytes_on_a_step_compiled_here(compiled_here):
    """The CPU backend fuses no dot, so the gradient reaches memory and
    the update is three fusions: 18 B a parameter, and 12 more for what
    the three hand each other (the master read a second time, the new
    momentum and the new master read back).  The gradient's own read is
    the backward's: a transpose inside the fusion consumes it."""
    records = profiler.cost_map("compiled-here")
    n = 64 * 128
    by = {}
    for rec in records.values():
        for op, b in rec["bytes_by_scope"].items():
            phase = "update" if "/mx.optimizer/" in op else \
                "backward" if "transpose(" in op else "other"
            by[phase] = by.get(phase, 0.0) + b
    assert by["update"] == (18 + 12) * n
    grad = records["multiply_subtract_fusion"]["bytes_by_scope"]
    assert grad["jit(_update)/transpose(jvp(mx.loss))/transpose"] == 4 * n
    assert records["dot_general.3"]["mxu_flops"] == 2.0 * 128 * 64 * 32


def test_the_totals_are_within_a_tenth_of_xla_s_on_a_step_compiled_here(
        compiled_here):
    totals = profiler.cost_totals("compiled-here")
    xla = compiled_here.cost_analysis()
    assert totals["xla"] == {"bytes_accessed": xla["bytes accessed"],
                             "flops": xla["flops"]}
    assert totals["bytes_read"] + totals["bytes_written"] == pytest.approx(
        xla["bytes accessed"], rel=0.1)
    assert totals["onchip_bytes_read"] == 0     # no memory-space mark here
    assert 0.5 * xla["flops"] < totals["mxu_flops"] <= xla["flops"]


def test_a_text_that_cannot_be_priced_leaves_the_scope_map_whole(
        compiled_here, monkeypatch, caplog):
    """The accepted readers hang on the scope map: a text that the
    pricing stumbles over (another XLA's print) costs them nothing, and
    the cost readers find None, as at a parent without a cost map."""
    def stumble(parsed):
        raise ValueError("substring not found")
    monkeypatch.setattr(costs, "price_optimized_hlo", stumble)
    assert profiler.cost_map("compiled-here") is None
    assert "could not be priced" in caplog.text
    assert profiler.cost_totals("compiled-here") is None
    old = {m.group(1): m.group(2)
           for m in OLD_SCOPE_MAP.finditer(compiled_here.as_text())}
    assert profiler.scope_map("compiled-here") == old
    assert profiler._compiled["compiled-here"].parsed is None


def test_the_parse_is_made_outside_the_profiler_s_lock(compiled_here,
                                                       monkeypatch):
    """A span on another thread does not wait for the parse or the
    pricing: while each runs, the lock is free to another thread."""
    import threading
    free = []

    def watching(work):
        def watched(arg):
            def probe():
                free.append(profiler._lock.acquire(blocking=False))
                if free[-1]:
                    profiler._lock.release()
            other = threading.Thread(target=probe)
            other.start()
            other.join()
            return work(arg)
        return watched
    monkeypatch.setattr(costs, "parse_optimized_hlo",
                        watching(costs.parse_optimized_hlo))
    monkeypatch.setattr(costs, "price_optimized_hlo",
                        watching(costs.price_optimized_hlo))
    assert profiler.cost_map("compiled-here")
    assert free == [True, True]


def test_the_cost_map_holds_the_program_by_no_reference():
    def f(x):
        return jnp.tanh(x) @ x
    x = jnp.ones((64, 64))
    compiled = jax.jit(f).lower(x).compile()
    profiler.set_scope_map("held-by-nothing", compiled.as_text(),
                           compiled.cost_analysis())
    try:
        ref = weakref.ref(compiled)
        del compiled
        gc.collect()
        assert ref() is None
        records = profiler.cost_map("held-by-nothing")
        assert records and all(type(k) is str for k in records)
    finally:
        profiler._compiled.pop("held-by-nothing", None)
