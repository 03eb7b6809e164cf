"""Peak rates of one chip, keyed by `device_kind` as jax reports it.

One table, with its source. A kind that is not here is an error, never a
default: a roofline share against a guessed peak is not a measurement.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak rates known for device kind {device_kind!r}; add its "
            "row, with the source, to benchmark/costs/peaks.py") from None


def least_seconds(flops: float, nbytes: float, device_kind: str) -> tuple[float, str]:
    """The least time the chip could take for `flops` and `nbytes`, and
    which of the two bounds it (`compute` or `memory`)."""
    peak = peaks_of(device_kind)
    compute = flops / peak["flops_per_s"]
    memory = nbytes / peak["bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
