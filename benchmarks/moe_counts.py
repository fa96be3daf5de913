"""Operations and bytes of a routed expert layer's grouped products, from
the token-expert assignments that were counted and the layer's shapes:
the only place these counts live.  Useful work only: the rows of the
worst-case buffer beyond the pairs that exist, and the hidden states a
backward pass computes again, are not counted.
"""

from __future__ import annotations

#: a routed expert is three products: W1 and W3 (hidden -> expert width)
#: and W2 (expert width -> hidden)
PRODUCTS = 3


def expert_matmul_flops(assignments, hidden, expert_hidden, training=True):
    """FLOPs of the three products for *assignments* token-expert pairs
    (summed over the routed layers): 2 a multiply-add, and with
    *training* the backward pass at twice the forward."""
    forward = PRODUCTS * 2 * assignments * hidden * expert_hidden
    return 3 * forward if training else forward


def expert_matmul_bytes(assignments, hidden, expert_hidden, experts_held,
                        routed_layers, itemsize=2, training=True):
    """Bytes the three products have to move at the least: each reads its
    rows and its experts' weights once and writes its rows (one pair's
    row is *hidden* wide on one side and *expert_hidden* on the other).
    With *training* three times that: each backward product (against the
    transposed weights, and the rows' outer product for the weights'
    gradient) moves what the forward one does."""
    rows = assignments * (hidden + expert_hidden)
    weights = routed_layers * experts_held * hidden * expert_hidden
    forward = PRODUCTS * (rows + weights) * itemsize
    return 3 * forward if training else forward


def roofline_seconds(flops, bytes_moved, flops_per_s, bytes_per_s):
    """The least time the chip could take, and which peak bounds it."""
    compute, memory = flops / flops_per_s, bytes_moved / bytes_per_s
    return (compute, "compute") if compute >= memory else (memory, "memory")


def program_counters(outcome, names):
    """The program's counters *names* as they stand when first asked for
    (kept with the outcome); None from a program without them."""
    from . import program_spans
    return program_spans._from_program(
        outcome, "program_counters:" + ",".join(names),
        lambda p: {n: p.counter_value(n) for n in names})
