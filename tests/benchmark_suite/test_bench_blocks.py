"""How a training window is read (benchmarks/blocks.py)."""

import pytest

import bench_suite_util  # noqa: F401  (puts the repo on sys.path)
from benchmarks import blocks


def test_an_even_window_reads_the_same_both_ways():
    r = blocks.read_window([1.0] * 12, samples_per_block=2560)
    assert r["blocks"] == 12
    assert r["median_rate"] == pytest.approx(2560.0)
    assert r["window_rate"] == pytest.approx(2560.0)
    assert r["deficit_pct"] == pytest.approx(0.0, abs=1e-9)


def test_one_stalled_block_moves_the_run_s_rate_and_not_the_median():
    quiet = blocks.read_window([1.0] * 24, 2560)
    stalled = blocks.read_window([1.0] * 23 + [1.7], 2560)
    assert stalled["median_rate"] == quiet["median_rate"]
    # 0.7 s lost of 24.7 s: the run's rate carries it, the deficit names it
    assert stalled["window_rate"] == pytest.approx(2560 * 24 / 24.7)
    assert stalled["deficit_pct"] == pytest.approx(100 * 0.7 / 24.7)
    assert stalled["slowest"] == 23


def test_the_run_s_rate_is_all_samples_over_all_the_time():
    seconds = [0.9, 1.1, 1.0, 1.3] * 3
    r = blocks.read_window(seconds, 100)
    assert r["window_rate"] == pytest.approx(100 * 12 / sum(seconds))
    assert r["median_rate"] == pytest.approx((100 / 1.1 + 100 / 1.0) / 2)
    assert r["slowest"] == 3


def test_a_stall_in_every_block_moves_both():
    slow = blocks.read_window([1.05] * 12, 2560)
    assert slow["median_rate"] == pytest.approx(2560 / 1.05)
    assert slow["window_rate"] == pytest.approx(2560 / 1.05)
    assert slow["deficit_pct"] == pytest.approx(0.0, abs=1e-9)


@pytest.mark.parametrize("held", [0, 1, 11])
def test_a_window_too_short_for_a_dozen_blocks_is_an_error(held):
    with pytest.raises(blocks.TooFewBlocks, match="needs 12"):
        blocks.read_window([1.0] * held, 2560)


def test_a_block_of_no_time_is_an_error():
    with pytest.raises(ValueError):
        blocks.read_window([1.0] * 11 + [0.0], 2560)
