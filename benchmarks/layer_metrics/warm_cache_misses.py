"""Persistent compile-cache misses during set-up (`jax.monitoring`, as
`chip_smoke.py` counts them): 0 once a checkout's first run has compiled."""

LAYER = "process and platform set-up"
UNIT = "count"
MOVES = "setup_s"
BETTER = "lower"
SOURCE = "program_counter"


def read(outcome):
    return outcome.facts.get("setup_cache_misses")
