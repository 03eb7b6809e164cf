"""Serving-path cost plane: per-pass FLOPs, achieved TFLOP/s, MFU.

ISSUE 17. The serving path billed tenants in chip-seconds with no idea how
many FLOPs (the analytic count of models/flops.py) it served or what MFU a
pass achieved. This module is its cost vocabulary:

- ``PEAK_TFLOPS`` / ``peak_tflops(device)``: the per-chip peak dense
  bf16 table, keyed by ``device_kind``. A TPU kind with no row is an
  error; a non-TPU platform (CPU tests, dry runs) yields None — MFU then
  reports ``null`` while FLOPs are still counted, so the cost plane
  degrades to pure work accounting instead of lying.
- ``pass_cost`` / ``job_cost``: the ``pipeline_config.cost`` stamp the
  pipeline attaches to every envelope (solo, batched, sharded, chunked
  — all four run through the two stamping sites in
  pipelines/stable_diffusion.py). ``flops`` is the JOB's own integer
  FLOP count (so the hive ledger's per-tenant sums equal the sum of
  envelope stamps exactly); the pass-level figures (achieved TFLOP/s
  over the denoise span, MFU) are shared by every envelope of a
  coalesced pass, like ``embed_cache``.
- ``note_divergence``: the analytic-vs-XLA cross-check fed by the
  compiled-program ledger (programs.py) — every first call of a denoise
  program compares models/flops.py against XLA's own cost_analysis()
  and publishes the ratio, closing the "denominator is uncorroborated"
  gap without waiting for a TPU window.

Import-time jax-free (telemetry only): the hive-side tools read these
stamps without an accelerator runtime.
"""

from __future__ import annotations

from . import telemetry

# peak dense bf16 TFLOP/s per chip, by device kind prefix (the MFU
# denominator's denominator; v5e: Google Cloud documentation, "TPU v5e").
# Extend it when a new TPU generation lands — an unknown TPU kind is an
# error, never a made-up ratio.
PEAK_TFLOPS = {
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,
    "TPU v5p": 459.0,
    "TPU v5": 459.0,
    "TPU v6 lite": 918.0,
}

_PASS_FLOPS = telemetry.counter(
    "swarm_pass_flops_total",
    "Analytic UNet FLOPs served by completed denoise passes, per model "
    "(models/flops.py; the serving-path twin of the bench's MFU "
    "denominator)",
    ("model",),
)
_PASS_MFU = telemetry.gauge(
    "swarm_pass_mfu",
    "Model FLOPs utilisation of the most recent denoise pass, per model "
    "and mesh geometry (analytic UNet FLOPs over the denoise+decode "
    "span against the slice's aggregate peak; absent on platforms with "
    "no peak-TFLOPs entry)",
    ("model", "geometry"),
)
_DIVERGENCE = telemetry.gauge(
    "swarm_flops_divergence_ratio",
    "XLA cost_analysis FLOPs over the analytic models/flops.py count "
    "for the most recently compiled denoise program, per model (~1.0 = "
    "the MFU denominator is corroborated; XLA counts the whole program "
    "— scheduler + decode included — so a small overshoot is expected)",
    ("model",),
)


def peak_tflops(device) -> float | None:
    """Per-chip peak dense bf16 TFLOP/s for `device` (anything with a
    ``device_kind``). A TPU whose kind has no table row is an error — add
    the row with its source, never a default; any other platform (CPU
    tests and dry runs) has no peak and yields None, so MFU reads null."""
    kind = getattr(device, "device_kind", "") or ""
    for prefix, tf in PEAK_TFLOPS.items():
        if kind.startswith(prefix):
            return tf
    if getattr(device, "platform", "") == "tpu" or kind.startswith("TPU"):
        raise ValueError(
            f"no peak TFLOP/s known for TPU device kind {kind!r}; "
            "add it to costs.PEAK_TFLOPS")
    return None


def pass_cost(*, model: str, pass_flops: float, denoise_s: float | None,
              chips: int, device=None, geometry: str = "replicated") -> dict:
    """One denoise pass's cost figures, counted into the pass metrics.
    Called once per PASS (a coalesced pass calls it once for the whole
    group); per-envelope stamps derive from it via ``job_cost``.

    ``denoise_s`` is the envelope's ``denoise_decode_s`` span; a span
    too short to measure (rounds to 0 on toy configs) reports achieved
    TFLOP/s and MFU as None rather than dividing by zero."""
    flops = int(round(max(float(pass_flops), 0.0)))
    chips = max(int(chips or 1), 1)
    peak = peak_tflops(device) if device is not None else None
    achieved = None
    if denoise_s and denoise_s > 0:
        achieved = flops / float(denoise_s) / 1e12
    mfu = None
    if achieved is not None and peak:
        mfu = round(achieved / (peak * chips), 4)
    if flops > 0:
        _PASS_FLOPS.inc(flops, model=model)
    if mfu is not None:
        _PASS_MFU.set(mfu, model=model, geometry=geometry)
    return {
        "pass_flops": flops,
        "denoise_s": denoise_s,
        "tflops_per_s": None if achieved is None else round(achieved, 4),
        "chips": chips,
        "peak_tflops_per_chip": peak,
        "mfu": mfu,
    }


def job_cost(pass_figures: dict, job_flops: float) -> dict:
    """The per-envelope ``pipeline_config.cost`` stamp: the job's OWN
    integer FLOPs first (what the tenant ledger sums — envelope sums and
    hive totals must agree exactly), then the shared pass figures."""
    return {"flops": int(round(max(float(job_flops), 0.0))), **pass_figures}


def note_divergence(model: str, analytic_flops: float,
                    xla_flops: float) -> float | None:
    """Publish the XLA/analytic FLOP ratio for one compiled program.
    Returns the ratio (None when either side is unusable — a missing
    cost model must read as "uncorroborated", not as divergence 0)."""
    try:
        analytic = float(analytic_flops)
        xla = float(xla_flops)
    except (TypeError, ValueError):
        return None
    if analytic <= 0 or xla <= 0:
        return None
    ratio = xla / analytic
    _DIVERGENCE.set(round(ratio, 4), model=model)
    return ratio
