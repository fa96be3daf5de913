"""`memory_stats()["peak_bytes_in_use"]` of the fullest chip when the
window closes (before the reference runs), in GB."""

LAYER = "device"
UNIT = "GB"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "program_counter"


def read(outcome):
    if not outcome.memory_peak_bytes:
        return None
    return outcome.memory_peak_bytes / 1e9
