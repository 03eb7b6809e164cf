"""Operations and bytes one GroupNorm(+SiLU) call over `[B, N, C]` needs."""

from __future__ import annotations


def needed(batch: int, rows: int, channels: int, itemsize: int = 2,
           silu: bool = True) -> tuple[float, float]:
    """(flops, bytes): per element a sum and a sum of squares (3), the
    normalise-scale-shift (2) and, with SiLU, a sigmoid and a multiply
    (counted 2); the activations read once and written once."""
    elements = float(batch * rows * channels)
    return elements * (7.0 if silu else 5.0), 2.0 * elements * itemsize
