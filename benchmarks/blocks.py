"""How a training window is read: the run's rate is all its samples over
all its time, and one reading per block beside it says whether that time
was even (their median, and how far the whole window falls short of it)."""

from __future__ import annotations

import statistics

MIN_BLOCKS = 12


class TooFewBlocks(RuntimeError):
    """The window was too short for a dozen readings: no number."""


def read_window(block_seconds, samples_per_block, min_blocks=MIN_BLOCKS):
    """*block_seconds* are the wall times of the window's whole blocks,
    one after the other with nothing between them, each of
    *samples_per_block* samples ending in a loss readback.

    Returns ``window_rate`` (the run's samples/s: all samples over all the
    time, stalls included), ``median_rate`` (the median of the per-block
    rates, which one stalled block moves by nothing), ``deficit_pct`` (how
    far the first falls short of the second: the share of the window that
    stalls took) and ``slowest`` (the index of the longest block)."""
    if len(block_seconds) < min_blocks:
        raise TooFewBlocks(
            "the window held %d whole blocks, a reading needs %d: give it "
            "more seconds" % (len(block_seconds), min_blocks))
    if min(block_seconds) <= 0:
        raise ValueError("a block took no time")
    median_rate = statistics.median(
        samples_per_block / s for s in block_seconds)
    window_rate = samples_per_block * len(block_seconds) / sum(block_seconds)
    return {"blocks": len(block_seconds),
            "window_rate": window_rate,
            "median_rate": median_rate,
            "deficit_pct": 100.0 * (1.0 - window_rate / median_rate),
            "slowest": max(range(len(block_seconds)),
                           key=block_seconds.__getitem__)}
