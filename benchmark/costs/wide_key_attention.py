"""Operations and bytes one `wide_key_attention` call needs, from its mask.

`queries` queries against keys wider than their values: query `i` stands at
key index `t = offset + i` and sees key `u` iff `u <= t` and, under a
window, `t - u < window` (`costs/banded_attention.py` `visible_pairs`, the
same mask). A visible pair costs a query head one dot product of `key_dim`
and one weighted sum of `value_dim`, 2 operations a multiply-add; the
queries are read and the output written once, each key head's band of keys
and values read once (a key head is never repeated for the query heads
that share it), and a sink is 4 bytes a head. Never from a kernel's blocks
or from the lanes it pads a head to: a kernel that contracts 256 where the
head has 192 does more than this, and that is its cost, not the
algorithm's.

What a traced call was is its own metadata (`call_of`); which span of its
row a full layer's call walked is data the instruction does not hold, and
follows from the call's place among the calls of its kind
(`costs/lightning_indexer.py` `span_of`).
"""

from __future__ import annotations

from .banded_attention import band_keys, visible_pairs
# which span of its row a traced call walked, from its place among the calls
# of its kind: the rule the selection's kernels are read by
from .lightning_indexer import span_of  # noqa: F401

# what the kernel writes into its instruction, in this order
FIELDS = ("queries", "keys", "window", "sink", "heads", "key_heads",
          "key_dim", "value_dim")


def needed(batch: int, heads: int, kv_heads: int, queries: int, keys: int,
           window: int, key_dim: int, value_dim: int, sink: bool = False,
           itemsize: int = 2) -> tuple[float, float]:
    """(flops, bytes) of a call whose queries are the last `queries`
    positions of `keys` keys (a full layer's span: the keys up to the
    span's end)."""
    width = key_dim + value_dim
    flops = 2.0 * batch * heads * visible_pairs(queries, keys, window) * width
    nbytes = float(itemsize * batch * (
        heads * queries * width
        + kv_heads * band_keys(queries, keys, window) * width)
        + (4 * heads if sink else 0))
    return flops, nbytes


def call_of(shapes: list) -> dict | None:
    """A traced call's own metadata as a dict of `FIELDS`: `queries[n]
    keys[n] window[n] sink[n] heads[n] keyheads[n] keydim[n] valuedim[n]`
    in the instruction's text, which the trace's reduction keeps as eight
    one-number shapes behind the operands'. None for a call that carries
    none."""
    tail = shapes[-len(FIELDS):]
    if len(tail) < len(FIELDS) or any(len(shape) != 1 for shape in tail):
        return None
    return dict(zip(FIELDS, (shape[0] for shape in tail)))
