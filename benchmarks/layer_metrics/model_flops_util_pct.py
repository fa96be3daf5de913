"""Model FLOP/s utilization: the shape function's FLOPs per sample
(`benchmarks/flops.py`: forward + backward, recomputation not counted)
times the traced run's samples/s (all the samples of its untraced blocks
over all their time), over chips times the published bf16
peak of the device kind (`benchmarks/peaks.json`)."""

LAYER = "step program"
UNIT = "%"
MOVES = "train_samples_per_s"
BETTER = "higher"
SOURCE = "host_clock"


def read(outcome):
    f = outcome.facts
    if outcome.trace is None or "reading" not in f:
        return None
    peak = outcome.cell.peak(f["device_kind"], "bf16_flops_per_s")
    return 100.0 * f["flops_per_sample"] * f["reading"]["window_rate"] / (
        f["devices"] * peak)
