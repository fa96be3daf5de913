"""Recurrent state the forward pass keeps for the backward, over the mamba
layers of one step, in GB: from the `mx.ssm.plan` spans (`state_kept_bytes` a
traced call: one float32 ``P x N`` a head at each chunk boundary) times the
configuration's mamba layers.  A chunkwise scan keeps ``S / Q`` states a
layer (0.30 GB over nine layers at 4096 tokens, 64 heads of 64 x 128 and
chunks of 256); one that kept the state of every token would read
`per_token_state_bytes`, 256 times that.  A count: static per shape, so a CPU
test reads the cell's own.  Nothing to read from a program that records no
such plan."""

from .. import program_spans
from . import ssm_ms_per_step

LAYER = "kernels"
UNIT = "GB"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "program_counter"


def read(outcome):
    plans = ssm_ms_per_step.plans(outcome)
    calls = sum(n for _, n in plans)
    layers = outcome.cell.config.get("layer_types", []).count("mamba")
    if not calls or not layers:
        return None
    # the traced calls' mean, a layer each (one shape in a cell: its own)
    kept = sum(p["state_kept_bytes"] * n for p, n in plans) / calls
    every = sum(p["per_token_state_bytes"] * n for p, n in plans) / calls
    program_spans.say_once(
        outcome, "ssm-state",
        "bench: state kept for the backward in %d mamba layers: %.4g GB at "
        "the chunk boundaries; a state a token would be %.4g GB"
        % (layers, layers * kept / 1e9, layers * every / 1e9))
    return layers * kept / 1e9
