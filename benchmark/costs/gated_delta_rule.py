"""Operations and bytes one `gated_delta_step` call needs, from the call's
own operand shapes: the state `[rows, heads, keys, values]` in float32 is
read once and written once, a head's `q` and `k` (`keys` each), `v`
(`values`), `g` and `beta` (one each) are read and `o` (`values`) is
written, all float32. It counts the work, never the implementation: the
same number whatever tiles the kernel uses and however it lays a head's
scalars out (a kernel that hands itself `g` and `beta` along 128 lanes
moves more than this, and that is its cost, not the rule's).
"""

from __future__ import annotations


def needed(rows: int, heads: int, keys: int, values: int,
           itemsize: int = 4) -> tuple[float, float]:
    """(flops, bytes): a position's decay of the state (1 a value), `m =
    S^T k` (2), the rank-one update (2), `o = S^T q` (2); the state in and
    out, the vectors and scalars once."""
    state = rows * heads * keys * values
    flops = 7.0 * state
    nbytes = float(itemsize) * (
        2 * state + rows * heads * (2 * keys + 2 * values + 2))
    return flops, nbytes


def call_of(shapes: list) -> tuple[int, int, int, int] | None:
    """(rows, heads, keys, values) of a traced call: the state's shape, the
    one four-dimensional array among the instruction's results and
    operands. None for a call that has none."""
    for shape in shapes:
        if len(shape) == 4:
            return tuple(shape)
    return None
