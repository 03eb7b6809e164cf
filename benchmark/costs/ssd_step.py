"""Operations and bytes one `ssd_step` call needs, from the call's own
operand shapes: the state `[rows, heads, state, head dim]` in float32 is
read once and written once; a head's `x` (`head dim`), its step `dt` and
its decay's rate and skip (one each) are read and `y` (`head dim`) is
written, a group's `B` and `C` (`state` each) are read once a group and
not once a head, all float32. It counts the work, never the
implementation: the same number whatever tiles the kernel uses and however
it lays a head's scalars out (a kernel that hands itself the decay along
128 lanes moves more than this, and that is its cost, not the rule's).
"""

from __future__ import annotations


def needed(rows: int, heads: int, size: int, dim: int, groups: int,
           itemsize: int = 4) -> tuple[float, float]:
    """(flops, bytes): a position's decay of the state (1 a value), the
    rank-one write `B (x) dt x` (2), the read `C . S` (2); the state in and
    out, the vectors and scalars once."""
    state = rows * heads * size * dim
    flops = 5.0 * state
    nbytes = float(itemsize) * (
        2 * state + rows * heads * (2 * dim + 3) + 2 * rows * groups * size)
    return flops, nbytes


def call_of(shapes: list) -> tuple[int, int, int, int, int] | None:
    """(rows, heads, state, head dim, groups) of a traced call: the state
    is the four-dimensional array whose third axis is not 1 and whose last
    is not 2, the groups the second axis of the `[rows, groups, state, 2]`
    array that holds `B` and `C`. None for a call that does not show
    both."""
    state = groups = None
    for shape in shapes:
        if len(shape) != 4:
            continue
        if shape[-1] == 2 and groups is None:
            groups = shape[1]
        elif shape[-1] != 2 and state is None:
            state = tuple(shape)
    if state is None or groups is None:
        return None
    return (*state, groups)
