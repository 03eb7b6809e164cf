"""Operations and bytes one prefill call of `sparse_latent_attention`
needs, from the keys its queries SELECTED.

`queries` queries that are the last positions of `keys` keys; query `i` at
position `t = keys - queries + i` attends to `min(t + 1, topk)` keys, the
selection's. What the algorithm needs is the softmax over those: QK^T and
PV at 2 operations a multiply-add over the selected pairs of every head
(keys and values expanded to the heads' width, as the call has them); the
queries read and the output written once, every head's keys and values
read once (any key may be some query's choice), and the selection as one
column number a selected pair. The same work whatever implements it: a
kernel that computes every VISIBLE pair under a mask (the program's, PR 49)
does `visible / selected` times this, and that is its cost, not the
algorithm's; one that gathers a query's keys would move them once a query.
"""

from __future__ import annotations

# a traced call says what it was as the indexer's does: (queries, the row's
# whole bucket of keys, heads), and `span_of` there tells its span
from .lightning_indexer import call_of  # noqa: F401


def selected_pairs(queries: int, keys: int, topk: int) -> int:
    """(query, key) pairs the selection lets through: query `i` sees `t +
    1` keys, `t = keys - queries + i`, and picks `topk` of them at most."""
    offset = keys - queries
    # the first `short` queries see no more keys than the selection takes
    short = max(min(topk - offset, queries), 0)
    return (short * offset + short * (short + 1) // 2
            + (queries - short) * topk)


def needed(batch: int, heads: int, queries: int, keys: int, topk: int,
           head_dim: int, itemsize: int = 2) -> tuple[float, float]:
    """(flops, bytes)."""
    pairs = batch * selected_pairs(queries, keys, topk)
    flops = 4.0 * heads * pairs * head_dim
    nbytes = float(itemsize * batch * heads * head_dim
                   * (2 * queries + 2 * keys) + 4 * pairs)
    return flops, nbytes
