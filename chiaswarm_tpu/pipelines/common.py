"""Shared per-job helpers for the resident pipelines.

The img2img start logic (strength clamp, scan start index, init-image
VAE encode through a cached jitted program) is identical across the
Kandinsky families — one implementation here so fixes land once.

Also home to the cross-job micro-batching helpers (batching.py design):
row-padding buckets so coalesce factors 3 and 4 share one compiled
program, per-request splitting of a coalesced image batch, and capacity
chunking that keeps every request's rows inside one denoise pass.
"""

from __future__ import annotations

import numpy as np

from .. import telemetry

# same metric the SD family's _trim_program_caches feeds — telemetry
# dedups by name, so whichever pipeline module imports first registers it
PROGRAM_EVICTED = telemetry.counter(
    "swarm_program_cache_evicted_total",
    "Compiled denoise programs / assembled runners evicted LRU at the "
    "program_cache_max bound, by kind",
    ("kind",),
)

RESIDENT_PARAM_BYTES = telemetry.gauge(
    "swarm_resident_param_bytes",
    "Bytes of a pipeline's placed parameter tree on the chip that holds "
    "most of it, set at placement: a partition rule that falls through to "
    "replicated reads the whole tree here, before it is an OOM",
    ("model",),
)


def program_cache_cap() -> int:
    """Settings.program_cache_max at call time (env-overridable,
    CHIASWARM_PROGRAM_CACHE_MAX); 0 = unbounded. The dormant pipelines'
    `_programs` caches bound themselves with this (SW007 shrink,
    ISSUE 18) — the SD family keeps its own richer trim that also frees
    evicted executables (_trim_program_caches)."""
    try:
        from ..settings import load_settings

        return max(int(getattr(
            load_settings(), "program_cache_max", 64) or 0), 0)
    except Exception:  # settings must never gate a compile
        return 64


def pad_bucket(rows: int) -> int:
    """Next power-of-two row count >= rows.

    The batched denoise program is compiled per total row count; padding
    a coalesced batch up to the bucket boundary means factors 3 and 4
    (say) share one executable instead of compiling each distinct
    coalesce count the queue happens to produce.
    """
    p = 1
    while p < rows:
        p *= 2
    return p


def split_by_counts(items, counts: list[int]) -> list[list]:
    """Slice a flat per-row list back into per-request groups.

    The inverse of the row concatenation a coalesced batch performs;
    trailing padding rows (len(items) > sum(counts)) are dropped.
    """
    out, offset = [], 0
    for n in counts:
        out.append(list(items[offset:offset + n]))
        offset += n
    return out


def chunk_by_rows(counts: list[int], max_rows: int) -> list[tuple[int, int]]:
    """Greedy [start, end) request ranges whose row sums fit max_rows.

    Requests are atomic — one request's images never straddle two denoise
    passes. A single request bigger than max_rows still gets its own
    chunk (the pipeline's per-request capacity cap handles it), so every
    request is always served.
    """
    chunks: list[tuple[int, int]] = []
    start, rows = 0, 0
    for i, n in enumerate(counts):
        if i > start and rows + n > max_rows:
            chunks.append((start, i))
            start, rows = i, 0
        rows += n
    chunks.append((start, len(counts)))
    return chunks


def clamp_strength(value) -> float:
    """Strength outside [0,1] would index the schedule negatively."""
    return min(max(float(value), 0.0), 1.0)


def img2img_t_start(steps: int, strength: float) -> int:
    """Scan start index for an img2img job at this strength."""
    return min(max(int(steps * (1.0 - strength)), 0), steps - 1)


def encode_init_image(pipe, vae_params, image, width: int, height: int,
                      n_images: int, lh: int, lw: int, channels: int):
    """PIL init image -> [n_images, lh, lw, channels] float32 latents.

    Encodes through ONE cached jitted program per pipeline instance —
    an op-by-op `vae.apply` on the job hot path costs a host->device
    round trip per op (round-1 measurement: >50% of job time host-side,
    stable_diffusion.py's `_vae_encode_program` rationale).
    """
    import jax
    import jax.numpy as jnp
    from PIL import Image

    program = getattr(pipe, "_vae_encode_program", None)
    if program is None:
        program = jax.jit(
            lambda p, px: pipe.vae.apply(
                {"params": p}, px, method=pipe.vae.encode
            ).astype(jnp.float32)
        )
        pipe._vae_encode_program = program

    arr = (
        np.asarray(
            image.convert("RGB").resize((width, height), Image.LANCZOS),
            np.float32,
        )
        / 127.5
        - 1.0
    )
    latents = program(vae_params, jnp.asarray(arr)[None].astype(pipe.dtype))
    return jnp.broadcast_to(latents, (n_images, lh, lw, channels))
