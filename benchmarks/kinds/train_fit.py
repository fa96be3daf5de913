"""The training loop users run (`examples/train_imagenet.py`
``fit_parallel``): batches from an iterator through the program's
`DevicePrefetcher`, `trainer.fit_batch` for each, and the loss read back to
the host every `steps_per_block` steps.  A block is those steps ending in
the readback; the run's `train_samples_per_s` is all the window's samples
over all its time, and the blocks' own readings beside it say whether that
time was even (`benchmarks/blocks.py`).

Set-up builds ONE trainer, drives it from the seed through its first three
steps (per-step readback, three distinct batches, the window's own feed and
call), reads what `correct` compares from its state, and hands the same
object to the window.  The plain reference follows those three steps after
the window has closed and the trainer is freed.
"""

from __future__ import annotations

import gc
import math
import os
import shutil
import statistics
import time

import numpy as np

from .. import blocks, compare, harness, trace as trace_mod
from ..models import common as models_common
from ..reference import common as ref_common

CHECK_STEPS = 3
SPAN_NAMES = ("bench.batch_fetch", "bench.fit_batch",
              "bench.loss_readback")


def _leaf_state(trainer, slot):
    """``program name -> array`` of one optimizer slot of every trained
    leaf: 0 is the momentum, -1 the float32 master (the stored weight
    itself without multi-precision)."""
    out = {}
    for n in trainer.param_names:
        if n in trainer._frozen:
            continue
        state = trainer._opt_state[n]
        if slot == -1 and not trainer.multi_precision:
            out[n] = trainer._params[n]
        else:
            out[n] = state[slot]
    return out


def _program_readings(cell, trainer, feed, table, names, spans):
    """The first steps of the timed object (`CHECK_STEPS`, or the
    configuration's `check.steps`), through the window's own feed and
    call."""
    got = {"losses": []}
    to_ref = {prog: ref for ref, prog in names.items()}
    for step in range(cell.config["check"].get("steps", CHECK_STEPS)):
        loss = _one_step(trainer, feed, spans)
        got["losses"].append(float(np.asarray(loss)))
        if step == 0:
            # |mom_1| = lr * |g + wd*w|: the first gradient as the
            # optimizer got it
            mom = _leaf_state(trainer, 0)
            got["first_update_norms"] = ref_common.leaf_norms(mom)
            # ... and the update itself, on the host, under the
            # reference's names: its direction is compared too
            got["first_update"] = {to_ref[n]: np.asarray(a)
                                   for n, a in mom.items()}
    masters = {to_ref[n]: a for n, a in _leaf_state(trainer, -1).items()}
    dist = ref_common.distance_from_init(table, cell.seed, masters)
    got["total_update_norms"] = {names[r]: v for r, v in dist.items()}
    return got


def _one_step(trainer, feed, spans):
    with spans("bench.batch_fetch"):
        batch = next(feed)
    with spans("bench.fit_batch"):
        return trainer.fit_batch(batch.data[0], batch.label[0])


def _block(trainer, feed, spans, steps):
    """*steps* steps and the readback that ends them; the loss."""
    for _ in range(steps):
        loss = _one_step(trainer, feed, spans)
    with spans("bench.loss_readback"):
        return float(np.asarray(loss))


def _reference_readings(cell, family, table, batches, fp8=False,
                        first_update=None):
    """The plain reference over the same batches from the same seed (with
    *fp8*, as the control: `reference/common.py`).  *first_update* is the
    other side's first update, to measure against the reference's;
    without one the reference's own is handed back."""
    import jax

    cfg, ref = cell.config, family.reference
    train, check = cfg["train"], cfg.get("check", {})

    def loss_sum(p, x, y):
        return ref.loss_sum(p, cfg, x, y, fp8)

    with jax.default_matmul_precision("highest"):
        params = ref_common.init_params(table, cell.seed)
        return ref_common.follow_steps(
            loss_sum, params, batches[:check.get("steps", CHECK_STEPS)],
            {"lr": train["lr"], "momentum": train["momentum"],
             "wd": train["wd"]},
            lambda p: ref_common.distance_from_init(table, cell.seed, p),
            rows_per_block=check.get("reference_rows_per_block")
            if ref.ROWS_INDEPENDENT else None,
            first_update=first_update,
            keep_first_update=first_update is None)


def _print_blocks(block_spans, reading, spans):
    """Every block's seconds, and where the slowest one's time went beside
    a usual block's: what a stall in the window looks like from the host."""
    def inside(lo, hi):
        return [spans.seconds(n, lo, hi) for n in SPAN_NAMES]

    seconds = [hi - lo for lo, hi in block_spans]
    print("bench: block seconds " + " ".join("%.4f" % s for s in seconds),
          flush=True)
    i = reading["slowest"]
    usual = [statistics.median(v) for v in
             zip(*(inside(lo, hi) for lo, hi in block_spans))]
    print("bench: slowest block %d of %d took %.4f s against a median of "
          "%.4f s; host seconds in it (in a usual block) %s"
          % (i + 1, len(seconds), seconds[i], statistics.median(seconds),
             ", ".join("%s %.4f (%.4f)" % (n[len("bench."):], a, b)
                       for n, a, b in zip(SPAN_NAMES,
                                          inside(*block_spans[i]), usual))),
          flush=True)


def run(cell, devices):
    import jax
    from mxnet_tpu import profiler
    from mxnet_tpu.io.device_prefetch import DevicePrefetcher

    cfg, mix = cell.config, cell.traffic
    train = cfg["train"]
    family = cell.family()
    outcome = harness.Outcome(cell)
    spans = outcome.spans = harness.Spans()
    compiles = harness.CompileCounter()

    rows = train["per_chip_batch"] * len(devices)
    steps_per_block = int(train["steps_per_block"])
    table = family.reference.param_table(cfg)
    batches = family.batches(cfg, cell.seed, mix["ring_batches"], rows)
    if len(batches) < CHECK_STEPS:
        raise ValueError("the ring needs %d distinct batches" % CHECK_STEPS)

    # -- set-up: one trainer, its first steps, one block to settle ---------
    values = ref_common.init_params(table, cell.seed)
    net, loss = family.build(cfg)
    names = models_common.seeded_net(net, table, values)
    del values
    trainer = models_common.make_trainer(net, loss, train, devices)
    feed = DevicePrefetcher(models_common.RingIter(batches),
                            depth=mix["prefetch_depth"], mesh=trainer.mesh)
    try:
        got = _program_readings(cell, trainer, feed, table, names, spans)
        losses = list(got["losses"])
        losses.append(_block(trainer, feed, spans, steps_per_block))
        setup_misses = compiles.misses
        dispatched0 = profiler.counter_value("parallel_step_dispatches")
        updates0 = trainer._num_update
        compiles0 = compiles.requests
        del spans.records[:]

        # -- the window: whole blocks until --seconds have passed ----------
        window_start = time.perf_counter()
        outcome.end_to_end["setup_s"] = window_start - cell.started
        traced_blocks = mix["traced_blocks"] if cell.trace else 0
        if traced_blocks:
            shutil.rmtree(cell.trace_dir, ignore_errors=True)
            spans.annotate = True
            jax.profiler.start_trace(cell.trace_dir)
        block_spans, traced_until = [], None
        last = window_start
        while last - window_start < cell.seconds:
            losses.append(_block(trainer, feed, spans, steps_per_block))
            now = time.perf_counter()
            block_spans.append((last, now))
            last = now
            if traced_blocks and len(block_spans) == traced_blocks:
                jax.profiler.stop_trace()
                spans.annotate = False
                # the profiler's own stop is not the loop's time
                traced_until = last = time.perf_counter()
        window_end = last
        steps = steps_per_block * len(block_spans)
        dispatched = profiler.counter_value("parallel_step_dispatches") \
            - dispatched0
        completed = trainer._num_update - updates0
        window_compiles = compiles.requests - compiles0
        outcome.memory_peak_bytes = harness.memory_peak(devices)
        print("bench: memory_stats %r" % (devices[0].memory_stats(),),
              flush=True)
    finally:
        feed.close()
    del trainer, net, feed
    gc.collect()

    # -- what the window says ----------------------------------------------
    untraced = block_spans[traced_blocks:]
    reading = blocks.read_window(
        [hi - lo for lo, hi in untraced], rows * steps_per_block,
        min_blocks=mix["traced_min_blocks"] if cell.trace
        else blocks.MIN_BLOCKS)
    on_tpu = devices[0].platform == "tpu"
    if on_tpu:
        print("bench: blocks=%d steps_per_block=%d whole window %.6g "
              "samples/s, block median %.6g samples/s, deficit %.4f%%"
              % (reading["blocks"], steps_per_block, reading["window_rate"],
                 reading["median_rate"], reading["deficit_pct"]), flush=True)
        _print_blocks(untraced, reading, spans)
    outcome.end_to_end["train_samples_per_s"] = reading["window_rate"]
    outcome.attempted = steps
    outcome.failed = steps - completed
    outcome.facts.update(
        reading=reading, steps=steps, rows=rows, devices=len(devices),
        steps_per_block=steps_per_block, traced_blocks=traced_blocks,
        untraced_span=(traced_until or window_start, window_end),
        setup_cache_misses=setup_misses, device_kind=devices[0].device_kind,
        flops_per_sample=family.flops_per_sample(cfg))
    if traced_blocks and on_tpu:
        outcome.trace = trace_mod.Trace(
            trace_mod.load_events(trace_mod.find_xplane(cell.trace_dir)))
        shutil.rmtree(cell.trace_dir, ignore_errors=True)

    # -- correct -----------------------------------------------------------
    finite = all(math.isfinite(v) for v in losses)
    print("correct: window losses finite=%s compiles=%d dispatched=%d "
          "completed=%d of %d" % (finite, window_compiles, dispatched,
                                  completed, steps), flush=True)
    sound = finite and window_compiles == 0 and dispatched == steps \
        and completed == steps
    t0 = time.perf_counter()
    ref = _reference_readings(cell, family, table, batches,
                              first_update=got.pop("first_update"))
    numbers = compare.training_numbers(got, ref, names)
    compare.keep_readings(
        os.path.join(cell.root, ".bench_out", "readings-%s-%d.json"
                     % (cell.name, cell.seed)), got, ref, names)
    outcome.correct = compare.judge(numbers, cfg["check"]["limits"]) \
        and sound
    if on_tpu:
        print("bench: reference took %.1f s" % (time.perf_counter() - t0),
              flush=True)
    return outcome
