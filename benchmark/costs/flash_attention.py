"""Operations and bytes one flash-attention call needs, from its shapes.

The kernel's operands in the trace are padded to its block sizes
(`[B, H, S_pad, D]`; 77 text keys ride as 128, 2304 queries as 2560). What
the algorithm needs is counted at the true lengths, which the
configuration lists (`attention_shapes`: `[Sq, Skv, heads, D]`, a batch row
each) — `true_lengths` maps a padded call back to its listed shape.
"""

from __future__ import annotations

# the widest padding the kernel adds to either axis: one block less one row
MAX_PAD = 511


def needed(batch: int, heads: int, sq: int, skv: int, head_dim: int,
           itemsize: int = 2) -> tuple[float, float]:
    """(flops, bytes): QK^T and PV at 2 flops a multiply-add; q, k, v read
    once and the output written once (the score matrix never leaves the
    chip — that is the kernel's point)."""
    flops = 4.0 * batch * heads * sq * skv * head_dim
    nbytes = float(batch * heads * head_dim * (2 * sq + 2 * skv) * itemsize)
    return flops, nbytes


def true_lengths(heads: int, sq_pad: int, skv_pad: int, head_dim: int,
                 listed: list) -> tuple[int, int, bool]:
    """(Sq, Skv, matched): the listed shape this padded call stands for —
    the same heads and head width, each length at most MAX_PAD under its
    padded one. Unmatched calls keep their padded lengths (their share of
    the roofline is then overstated, and the reader says how many)."""
    best = None
    for sq, skv, h, d in listed:
        if (h, d) == (heads, head_dim) and 0 <= sq_pad - sq <= MAX_PAD \
                and 0 <= skv_pad - skv <= MAX_PAD:
            waste = (sq_pad - sq) + (skv_pad - skv)
            if best is None or waste < best[0]:
                best = (waste, sq, skv)
    if best is None:
        return sq_pad, skv_pad, False
    return best[1], best[2], True
