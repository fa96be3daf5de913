"""Set-up seconds materialising the model's state: every parameter's
`mx.initialize` (zeros, the initializer, the copy to its context) and the
trainer's `mx.trainer.gather_state` (masters, optimizer state and their
placement over the mesh)."""

from .. import program_spans

LAYER = "trainers"
UNIT = "s"
MOVES = "setup_s"
BETTER = "lower"
SOURCE = "program_span"


def read(outcome):
    return program_spans.setup_seconds(
        outcome, ("mx.initialize", "mx.trainer.gather_state"))
