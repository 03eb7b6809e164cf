"""Operations and bytes the held experts' grouped matmuls need, from what
the routing really was: the (token, expert) pairs computed and the experts
that had at least one pair, as the program counted them
(`pipeline_config.routing`, `swarm_expert_pairs_total`,
`swarm_expert_active_total`). Never from the row buffer's shape: it is
sized for the worst case (every token on eight held experts), and a least
time counted from it would be tens of times too high.
"""

from __future__ import annotations


def needed(pairs: int, active: int, hidden: int, width: int,
           itemsize: int = 2) -> tuple[float, float]:
    """(flops, bytes) of gate, up and down for `pairs` rows over `active`
    experts' matrices: 2 flops a multiply-add; an expert that had a pair
    has its three matrices read once (a lower bound: a group of more than
    one row tile reads them once a tile), a pair's row read at `hidden`,
    its inner activation written and read at `width`, its result written
    at `hidden`."""
    flops = 2.0 * 3 * pairs * hidden * width
    nbytes = float(itemsize) * (3 * active * hidden * width
                                + pairs * (2 * hidden + 2 * width))
    return flops, nbytes
