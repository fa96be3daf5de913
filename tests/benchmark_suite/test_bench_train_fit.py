"""The `train_fit` loop end to end on the tiny CPU fixtures, past the
harness's look for a chip: the last line's keys, counts only without a
TPU, `correct` false when the timed path is broken underneath, and the
lower-precision control coming out as not correct."""

import json
import os
import subprocess
import sys

import pytest

import bench_suite_util as util

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture()
def root(tmp_path):
    return util.fixture_root(tmp_path)


def _no_measurement_printed(text):
    """Without a TPU no time, rate or share may be printed."""
    for word in ("samples/s", " ms", "setup_s", "deficit", "busy_s"):
        assert word not in text, word


# the windows are wall-clock seconds and a reading needs 12 whole blocks:
# the ResNet's block is a CPU step of 0.9 s alone (17 blocks in 15 s) and 10
# fitted into 15 s in a six-worker run of the whole of tier-1
@pytest.mark.parametrize("workload, seconds", [
    ("tiny_lm_train", 1.0), ("tiny_resnet_train", 30.0)])
def test_a_cell_runs_and_is_correct(root, capsys, workload, seconds):
    outcome, line = util.run_cell(root, workload, seed=2 ** 31 + 3,
                                  seconds=seconds)
    assert set(line) == LINE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 12 and line["attempted"] == \
        outcome.facts["steps"]
    assert line["device"]["platform"] == "cpu"
    assert line["metrics"] == {}            # end-to-end: times and rates
    reading = outcome.facts["reading"]
    assert reading["blocks"] >= 12
    # the run's rate is all the samples over all the window's time
    assert outcome.end_to_end["train_samples_per_s"] == \
        reading["window_rate"]
    printed = capsys.readouterr().out
    for number in ("first_update_difference", "loss_gap",
                   "first_gradient_norm_gap", "update_norm_gap"):
        assert "correct: %s" % number in printed and "limit" in printed
    _no_measurement_printed(printed)


def test_a_traced_run_reports_counts_only_off_the_tpu(root, capsys):
    # a window of its own: its seconds run on through the two traced blocks
    # and the profiler's stop (0.14 to 0.42 s here, on 8 cores), and two
    # untraced blocks have to follow inside it (`TooFewBlocks` otherwise);
    # one second was too near that with five other workers on the host
    outcome, line = util.run_cell(root, "tiny_lm_train", seconds=4.0,
                                  trace=1)
    assert set(line) == LINE_KEYS           # no breakdown without a TPU
    assert set(line["metrics"]) == {"warm_cache_misses"}
    assert line["metrics"]["warm_cache_misses"]["unit"] == "count"
    assert "busy_s" not in line["device"]
    _no_measurement_printed(capsys.readouterr().out)


def test_a_stall_in_the_window_shows_in_the_run_s_rate(root, monkeypatch):
    """One block of the window held up for a while: the rate the run
    reports falls by it, the blocks' median does not, and the readers say
    which was which."""
    import time
    from benchmarks.kinds import train_fit
    from benchmarks.layer_metrics import (block_median_samples_per_s,
                                          window_mean_deficit_pct)
    real = train_fit._block
    seen = []

    def block(trainer, feed, spans, steps):
        seen.append(1)
        if len(seen) == 6:      # 1 settles in set-up: the window's fifth
            time.sleep(0.5)
        return real(trainer, feed, spans, steps)

    monkeypatch.setattr(train_fit, "_block", block)
    outcome, _ = util.run_cell(root, "tiny_lm_train")
    reading = outcome.facts["reading"]
    assert reading["slowest"] == 4
    assert outcome.end_to_end["train_samples_per_s"] < \
        0.8 * reading["median_rate"]
    assert block_median_samples_per_s.read(outcome) == \
        reading["median_rate"]
    assert window_mean_deficit_pct.read(outcome) > 20.0


def test_where_the_slowest_block_s_time_went_is_printed(capsys):
    from benchmarks import blocks, harness
    from benchmarks.kinds import train_fit
    spans = harness.Spans()
    block_spans = []
    for i in range(12):
        lo = 10.0 * i
        fit = 7.0 if i == 5 else 1.0    # block 6 waits in fit_batch
        spans.records += [("bench.batch_fetch", lo, lo + 0.5),
                          ("bench.fit_batch", lo + 0.5, lo + 0.5 + fit),
                          ("bench.loss_readback", lo + 0.5 + fit,
                           lo + 1.0 + fit)]
        block_spans.append((lo, lo + 1.0 + fit))
    reading = blocks.read_window([hi - lo for lo, hi in block_spans], 1)
    train_fit._print_blocks(block_spans, reading, spans)
    out = capsys.readouterr().out
    assert "slowest block 6 of 12 took 8.0000 s against a median of " \
        "2.0000 s" in out
    assert "fit_batch 7.0000 (1.0000)" in out
    assert "batch_fetch 0.5000 (0.5000)" in out


def test_a_window_too_short_fails_instead_of_reporting(root):
    from benchmarks import blocks
    with pytest.raises(blocks.TooFewBlocks):
        util.run_cell(root, "tiny_lm_train", seconds=0.0)


def test_a_compile_inside_the_window_makes_correct_false(root, monkeypatch):
    import jax
    import jax.numpy as jnp
    from benchmarks.kinds import train_fit
    real = train_fit._block
    seen = []

    def block(trainer, feed, spans, steps):
        if len(seen) == 3:      # a new shape, so a new program, mid-window
            jax.jit(lambda x: x * 3 + len(seen))(jnp.ones((7, len(seen))))
        seen.append(1)
        return real(trainer, feed, spans, steps)

    monkeypatch.setattr(train_fit, "_block", block)
    _, line = util.run_cell(root, "tiny_lm_train")
    assert line["correct"] is False


@pytest.mark.parametrize("fault", ["state_unchanged", "part_of_the_batch"])
def test_a_broken_timed_path_is_not_correct(root, monkeypatch, capsys, fault):
    """The rest of a run, with the step broken underneath it."""
    from mxnet_tpu.parallel.data_parallel import ParallelTrainer
    real = ParallelTrainer.fit_batch

    def fit_batch(self, x, y):
        if fault == "part_of_the_batch":
            # half of every batch is the other half again
            import jax.numpy as jnp
            half = x.shape[0] // 2
            x = jnp.concatenate([x._data[:half]] * 2)
            y = jnp.concatenate([y._data[:half]] * 2)
            return real(self, x, y)
        if self._step_fn is None or self._num_update < 1:
            return real(self, x, y)
        # a step that returns its state unchanged: run it on copies
        import jax
        keep = jax.tree_util.tree_map(
            lambda a: a.copy(), (self._params, self._opt_state, self._aux))
        loss = real(self, x, y)
        self._params, self._opt_state, self._aux = keep
        return loss

    monkeypatch.setattr(ParallelTrainer, "fit_batch", fit_batch)
    _, line = util.run_cell(root, "tiny_lm_train")
    assert line["correct"] is False
    assert "OUTSIDE" in capsys.readouterr().out


@pytest.mark.parametrize("seed", [11, 12, 2 ** 31 + 13])
def test_the_fp8_control_is_not_correct(root, capsys, seed):
    """The reference in the program's place, one precision below bf16."""
    import jax
    from benchmarks import compare, control, harness
    cell = harness.Cell("tiny_lm_train", seed, 0, 0, 0.0, root)
    numbers = control.control_numbers(cell, jax.devices()[:1])
    limits = cell.config["check"]["limits"]
    assert not compare.judge(numbers, limits)
    assert numbers["first_update_difference"][0] > \
        1.3 * limits["first_update_difference"]
    assert "OUTSIDE" in capsys.readouterr().out


def test_run_py_without_a_tpu_prints_no_result_and_exits_1():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(util.REPO, "benchmarks", "run.py"),
         "--workload", "resnet50_train", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=util.REPO, timeout=300)
    assert proc.returncode == 1
    assert "nothing was run" in proc.stderr
    last = [l for l in proc.stdout.splitlines() if l.strip()][-1]
    assert not last.startswith("{")
    with pytest.raises(ValueError):
        json.loads(last)
