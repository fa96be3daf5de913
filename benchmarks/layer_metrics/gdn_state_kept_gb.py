"""Recurrent state the forward pass keeps for the backward, over the
linear-attention layers of one step, in GB: from the `mx.gdn.plan` spans
(`state_kept_bytes` a traced call: one float32 ``dk x dv`` a head at each
chunk boundary) times the configuration's linear layers.  A chunkwise rule
keeps ``S / C`` states a layer (0.42 GB over three layers at 4096 tokens, 30
heads of 96 x 192 and chunks of 64); one that kept the state of every token
would read `per_token_state_bytes`, 64 times that.  A count: static per
shape, so a CPU test reads the cell's own.  Nothing to read from a program
that records no such plan."""

from .. import program_spans
from . import gdn_ms_per_step

LAYER = "kernels"
UNIT = "GB"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "program_counter"


def read(outcome):
    plans = gdn_ms_per_step.plans(outcome)
    calls = sum(n for _, n in plans)
    layers = outcome.cell.config.get("layer_types", []).count(
        "linear_attention")
    if not calls or not layers:
        return None
    # the traced calls' mean, a layer each (one shape in a cell: its own)
    kept = sum(p["state_kept_bytes"] * n for p, n in plans) / calls
    every = sum(p["per_token_state_bytes"] * n for p, n in plans) / calls
    program_spans.say_once(
        outcome, "gdn-state",
        "bench: state kept for the backward in %d linear layers: %.4g GB at "
        "the chunk boundaries; a state a token would be %.4g GB"
        % (layers, layers * kept / 1e9, layers * every / 1e9))
    return layers * kept / 1e9
