"""Operations and bytes one `lightning_indexer` call needs, from its
visible pairs.

`queries` queries that are the last positions of `keys` keys (a span of a
row and what the row has cached up to the span's end), query `i` at
position `t = keys - queries + i` seeing key `u` iff `u <= t`
(`costs/banded_attention.py` `visible_pairs`, no window). A visible pair
costs every index head one dot product of `dim` (2 operations a
multiply-add), its ReLU, its weight and its add into the pair's score. The
queries, their head weights and the ONE key a position all heads share are
read once; a score is written once a visible pair. Counted from the pairs,
never from a kernel's blocks or from what it writes where no query sees a
key: that is its cost, not the algorithm's.
"""

from __future__ import annotations

from .banded_attention import visible_pairs


def needed(batch: int, queries: int, keys: int, heads: int, dim: int,
           itemsize: int = 2) -> tuple[float, float]:
    """(flops, bytes)."""
    pairs = batch * visible_pairs(queries, keys, 0)
    flops = float(pairs) * heads * (2 * dim + 3)
    nbytes = float(batch * (queries * heads * (dim * itemsize + 4)
                            + keys * dim * itemsize) + 4 * pairs)
    return flops, nbytes


def span_of(number: int, layers: int, queries: int, bucket: int) -> int:
    """Which span of its row the `number`-th traced call of a pass was: a
    row's `bucket // queries` spans go through in turn, every one of the
    `layers` layers' calls of a span before the next span's. (The kernel
    is handed the span's first position as it runs; a call's instruction
    holds the bucket's width alone.)"""
    return (number // layers) % max(bucket // queries, 1)


def call_of(shapes: list) -> tuple[int, int, int] | None:
    """(queries, the row's whole bucket of keys, heads) of a traced call:
    the kernel's own metadata, `queries[n] keys[n] heads[n]` in the
    instruction's text, which the trace's reduction keeps as three
    one-number shapes behind the operands'. None for a call that carries
    none."""
    for at in range(len(shapes) - 3, 0, -1):
        if all(len(shape) == 1 for shape in shapes[at:at + 3]):
            (queries,), (keys,), (heads,) = shapes[at:at + 3]
            return queries, keys, heads
    return None
