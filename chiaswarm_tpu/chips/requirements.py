"""Per-model capacity requirements: the TPU-native replacement for the
reference's memory-pressure knobs.

Reference behavior replaced: swarm/diffusion/diffusion_func.py:134-146
(VAE slicing/tiling, attention slicing, model/sequential CPU offload) —
CUDA-side degradation hacks that trade 2-10x latency for VRAM. On TPU the
policy is explicit capacity accounting instead (SURVEY §2.6 row
'memory-pressure fallbacks'):

- every model family carries a parameter-footprint estimate and a
  per-image activation estimate;
- a job that cannot fit at the requested batch is capped to the batch
  that fits (recorded in pipeline_config, never silent);
- a model whose parameters alone exceed the slice's HBM is a fatal job
  error naming the chip count it needs — the operator scales the slice
  (tensor parallelism) instead of thrashing host offload.

Numbers are engineering estimates in bf16 serving dtype, anchored on
measured fits (SDXL batch 4 @ 1024^2 runs on one 16 GB v5e chip with
~2 GB/image of transient headroom — bench_r02).
"""

from __future__ import annotations

from ..text_families import TEXT_FAMILIES, text_family_of
from ..models.configs import model_family

# static parameter + resident-state footprint, GiB (bf16, incl. text/vae)
FAMILY_PARAMS_GB: dict[str, float] = {
    "sd15": 1.8,
    "sd21": 2.1,
    "sdxl": 8.0,
    "sdxl_refiner": 7.2,
    # measured from the real flux-dev geometry via eval_shape in
    # tests/test_flux_tp.py (12B MMDiT + 4.7B T5-XXL, bf16)
    "flux": 31.4,
    "kandinsky": 6.0,  # prior + decoder + CLIP-bigG text tower
    "kandinsky3": 16.0,  # 3B UNet + FLAN-T5-XXL encoder
    "cascade": 11.0,  # stage C 3.6B + stage B 1.5B + text tower
    "deepfloyd_if": 18.0,  # IF-I XL + T5-XXL encoder
}

# transient activations per image in the fused denoise+decode program,
# GiB at a 1024^2 canvas; scales with canvas area
FAMILY_ACT_GB_PER_IMAGE: dict[str, float] = {
    "sd15": 1.0,
    "sd21": 1.1,
    "sdxl": 2.0,
    "sdxl_refiner": 1.8,
    # what a row costs where the VAE decodes the whole batch at once (any
    # slice without tensor sharding): ~3.3 GB of decode temporaries a row
    # by the compile for described v5e chips (PR 27). On [data=1, tensor=4]
    # the rows are decoded one at a time and two rows peaked 10.08 GB
    # beside 9.01 GB of weights, 0.54 GB an image (chip run, PR 27): the
    # table has one figure a family and keeps the one that binds
    "flux": 2.5,
    "kandinsky": 1.2,
    "kandinsky3": 2.2,
    "cascade": 1.5,
    "deepfloyd_if": 1.5,
}

# Families whose rows are sequences, not canvases (a text job's rows:
# pipelines/text_generation.py) are text_families.py `TEXT_FAMILIES`': a
# row of it has the weights the chip holds, the working set and what a row's
# cache costs (`params_gb`, `working_gb`, `cache_layers`, `row_bytes`, said
# there with where each number came from); `size` is then the positions a
# row keeps (prompt slots + new tokens).

# the cached positions one pass holds at most, whatever the memory left:
# 256 rows of 512 positions. A pass is budgeted in positions and not in
# rows, at the job's own `prompt slots + new tokens`: a pass of long rows
# is few rows (16512 positions: 4), so that it settles in seconds and not
# in the half minute the memory alone would allow.
SEQUENCE_PASS_POSITIONS = 256 * 512
# the positions the one-number appetite a worker advertises a family
# (`family_gang_rows`) is reckoned at; beside it the worker advertises the
# pass's positions (`family_gang_positions`), and the hive reckons a gang
# at the job's own (hive_server/dispatch.py `rows_per_pass`)
SEQUENCE_REFERENCE_POSITIONS = 512


def sequence_row_bytes(family: str, positions: int) -> float:
    """What one row of `positions` cached positions holds, every layer by
    its kind: the positions it keeps, and what it holds a row whatever
    they are."""
    positions = max(int(positions), 1)
    costs = TEXT_FAMILIES[family]
    return costs.get("row_bytes", 0.0) + sum(
        per_position * (min(positions, window) if window else positions)
        for per_position, window in costs["cache_layers"])


def pass_positions_limit(chipset, family: str) -> int:
    """The cached positions one pass of a sequence family may hold on this
    slice: `SEQUENCE_PASS_POSITIONS`, or what the memory left beside the
    weights holds of them (a position reckoned as a reference row's)."""
    if chipset is None or chipset.platform != "tpu":
        return SEQUENCE_PASS_POSITIONS
    costs = TEXT_FAMILIES[family]
    free = (chipset.hbm_bytes() / max(chipset.chip_count(), 1)
            - (costs["params_gb"] + costs["working_gb"]) * (1 << 30))
    a_position = (sequence_row_bytes(family, SEQUENCE_REFERENCE_POSITIONS)
                  / SEQUENCE_REFERENCE_POSITIONS)
    return int(min(SEQUENCE_PASS_POSITIONS, max(free / a_position, 0)))


# native serving canvas per family (everything else serves 1024)
_FAMILY_CANVAS: dict[str, int] = {
    "sd15": 512,
    "sd21": 768,
    "kandinsky": 512,  # K2.x decoder default (pipelines/kandinsky.py)
}

_DEFAULT_PARAMS_GB = 2.0
_DEFAULT_ACT_GB = 1.0


def _family_key(model_name: str) -> str:
    """Capacity bucket — model_family()'s catch-all is 'sd15', so the
    non-SD families that every capacity table keys on resolve by name
    FIRST (a Kandinsky charged as a 1.8 GB SD model would defeat the
    gate)."""
    name = model_name.lower()
    if "flux" in name:
        return "flux"
    text = text_family_of(name)
    if text is not None:
        return text
    if "kandinsky-3" in name or "kandinsky3" in name:
        return "kandinsky3"
    if "kandinsky" in name:
        return "kandinsky"
    if "cascade" in name:
        return "cascade"
    if name.startswith("deepfloyd/"):
        return "deepfloyd_if"
    return model_family(model_name)


# families whose pipelines give a `test/` name the PUBLISHED geometry with
# seeded weights and keep the tiny preset for names with `tiny`
# (stable_diffusion.py `_family_configs`, flux.py `_flux_configs`); every
# other pipeline gives any `test/` name its tiny preset
_PUBLISHED_TEST_FAMILIES = frozenset(
    {"sd15", "sd21", "sdxl", "sdxl_refiner", "flux", *TEXT_FAMILIES})


def _is_stand_in(model_name: str) -> bool:
    """A tiny stand-in, a few MB whatever family it mimics: the footprint
    table is wrong for it by three orders of magnitude. The rule is the
    pipelines' own: `tiny` in the name, or a `test/` name of a family that
    has no full-size seeded form. `test/FLUX.1-dev` and
    `test/stable-diffusion-xl-base-1.0` are the real footprint and are
    accounted like it."""
    from ..weights import is_test_model

    if "tiny" in model_name.lower():
        return True
    return (is_test_model(model_name)
            and _family_key(model_name) not in _PUBLISHED_TEST_FAMILIES)


def _area_scale(height: int, width: int | None = None) -> float:
    width = height if width is None else width
    return max((height * width) / (1024.0 * 1024.0), 0.05)


def _sequence_costs(fam: str, positions: int) -> tuple[float, float]:
    """(GiB that do not grow with the rows, GiB a row) of a sequence
    family: weights + working set, and a row's cache at `positions`."""
    costs = TEXT_FAMILIES[fam]
    return (costs["params_gb"] + costs["working_gb"],
            sequence_row_bytes(fam, positions) / (1 << 30))


def required_hbm_gb(model_name: str, batch: int, size: int,
                    width: int | None = None) -> float:
    """Estimated HBM for `batch` images at size x (width or size); for a
    sequence family, `batch` rows of `size` positions."""
    fam = _family_key(model_name)
    if fam in TEXT_FAMILIES:
        fixed, per_row = _sequence_costs(fam, size)
        return fixed + batch * per_row
    params = FAMILY_PARAMS_GB.get(fam, _DEFAULT_PARAMS_GB)
    act = FAMILY_ACT_GB_PER_IMAGE.get(fam, _DEFAULT_ACT_GB)
    return params + batch * act * _area_scale(size, width)


def default_canvas(model_name: str) -> int:
    """The family's native serving canvas (the gate's estimate when a job
    names no dims — it must match what the pipeline will actually serve,
    in both directions: 1024 for a 512-native family over-caps batches,
    512 for a 1024-native family admits OOMs)."""
    return _FAMILY_CANVAS.get(_family_key(model_name), 1024)


def min_chips(model_name: str, hbm_gb_per_chip: float, size: int = 1024,
              width: int | None = None) -> int:
    """TP shards needed so the per-chip parameter cut + one image at this
    canvas fits."""
    fam = _family_key(model_name)
    params = FAMILY_PARAMS_GB.get(fam, _DEFAULT_PARAMS_GB)
    act = FAMILY_ACT_GB_PER_IMAGE.get(fam, _DEFAULT_ACT_GB)
    one_image = act * _area_scale(size, width)
    n = 1
    while params / n + one_image > hbm_gb_per_chip and n < 64:
        n *= 2
    return n


# Flux weight streaming (the TPU analog of the reference's sequential CPU
# offload, swarm/job_arguments.py:209-218): the 12B MMDiT pages through the
# chip block-by-block from host RAM, so only the resident tail (T5-XXL
# 9.4 GB + CLIP/VAE/head/final ~0.8) plus two ~0.8 GB double-buffered
# block transfers must fit alongside activations.
FLUX_STREAM_RESIDENT_GB = 12.0


def flux_stream_fit(chipset, batch: int, size: int,
                    width: int | None = None) -> int:
    """Largest batch a single-chip slice serves with flux weight
    streaming; 0 when even the resident tail + one image doesn't fit.
    Streaming v1 targets exactly the small-worker gap: one-chip slices,
    tensor=1 (multi-chip slices shard the resident model instead)."""
    if chipset is None or chipset.platform != "tpu":
        return batch
    if chipset.chip_count() != 1 or max(getattr(chipset, "tensor", 1), 1) > 1:
        return 0
    per_chip_hbm = chipset.hbm_bytes() / (1 << 30)
    act = FAMILY_ACT_GB_PER_IMAGE["flux"]
    free = per_chip_hbm - FLUX_STREAM_RESIDENT_GB
    per_image = act * _area_scale(size, width)
    if free < per_image:
        return 0
    return min(batch, int(free / per_image))


def streaming_enabled() -> bool:
    # load_settings already degrades to defaults on a missing/corrupt
    # file; anything it does raise (e.g. a malformed env override) must
    # propagate — silently forcing streaming ON would override an
    # operator's explicit flux_streaming: false
    from ..settings import load_settings

    return bool(load_settings().flux_streaming)


def flux_admissible(chipset, batch: int, size: int,
                    width: int | None = None,
                    model_name: str = "black-forest-labs/FLUX.1-dev",
                    ) -> tuple[int, str]:
    """The ONE flux admission rule (resident fit, else streaming fit) —
    shared by check_capacity, the worker's flux_runnable advertisement,
    and FluxPipeline's auto-streaming detection, so the hive's placement
    decision, the job gate, and the pipeline's actual mode cannot drift.

    Returns (admissible batch, mode) where mode is "resident",
    "streaming", or "refuse" (batch 0)."""
    resident = fit_batch(chipset, model_name, batch, size, width)
    if resident:
        return resident, "resident"
    if streaming_enabled():
        streamed = flux_stream_fit(chipset, batch, size, width)
        if streamed:
            return streamed, "streaming"
    return 0, "refuse"


def fit_batch(chipset, model_name: str, batch: int, size: int,
              width: int | None = None) -> int:
    """Largest batch (<= requested) this slice fits; 0 = model doesn't fit.

    Accounting is PER CHIP: with tensor=1 the parameter tree replicates
    onto every chip, so a model bigger than one chip's HBM fails no matter
    how many data-parallel chips the slice has. Non-accelerator slices
    (CPU tests) always fit — the host heap is not HBM.
    """
    if chipset is None or chipset.platform != "tpu":
        return batch
    if _is_stand_in(model_name):
        return batch
    per_chip_hbm = chipset.hbm_bytes() / (1 << 30) / max(chipset.chip_count(), 1)
    # Closed form (the batch arrives unvalidated from the wire — a loop
    # decrementing from 1e9 would stall the worker): the busiest data
    # shard holds ceil(batch/data) images, so the largest admissible
    # batch is floor(free / per_image) * data.
    fam = _family_key(model_name)
    if fam in TEXT_FAMILIES:
        # one chip's share of a deployment is one chip's: nothing of it
        # is divided over a slice's chips, and every chip sees every row
        fixed, per_row = _sequence_costs(fam, size)
        free = per_chip_hbm - fixed
        return min(batch, int(free / per_row)) if free >= per_row else 0
    params = FAMILY_PARAMS_GB.get(fam, _DEFAULT_PARAMS_GB)
    act = FAMILY_ACT_GB_PER_IMAGE.get(fam, _DEFAULT_ACT_GB)
    tensor = max(getattr(chipset, "tensor", 1), 1)
    seq = max(getattr(chipset, "seq", 1), 1)
    data = max(chipset.chip_count() // (tensor * seq), 1)
    free = per_chip_hbm - params / tensor
    per_image = act * _area_scale(size, width)
    if free < per_image:
        return 0
    return min(batch, int(free / per_image) * data)


def _pow2_floor(n: int) -> int:
    """Largest power of two <= n (n >= 1)."""
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def coalesce_rows_limit(chipset, model_name: str, size: int,
                        width: int | None = None,
                        ceiling: int = 256) -> int:
    """Most images (rows of `size` cached positions, for a family whose
    rows are sequences) one coalesced cross-job batch may hold on this
    slice.

    The batching scheduler (batching.py) sizes its groups with this BEFORE
    dispatch so a coalesced batch arrives already admissible — the batched
    path caps groups, it never rejects one (each member job passed the
    single-job gate on its own). Non-accelerator slices return the
    ceiling: the host heap is not HBM.

    The budget is a power-of-two BUCKET boundary, not the raw fit:
    run_batched pads the admitted row count up to pad_bucket(rows) AFTER
    admission, so a raw budget of (say) 5 would admit a 5-row group that
    executes an 8-row padded pass and OOMs before the per-job fallback
    (the ROADMAP pad-vs-admission item). Capping at pow2_floor(fit) makes
    every admissible group's PADDED pass fit too.
    """
    allowed = fit_batch(chipset, model_name, ceiling, size, width)
    if _family_key(model_name) in TEXT_FAMILIES and allowed >= 1:
        # a pass of sequences is budgeted in cached positions too, at the
        # job's own (`size`), on any platform: what a pass is does not
        # depend on the memory beside it
        allowed = max(min(allowed, SEQUENCE_PASS_POSITIONS
                          // max(int(size), 1)), 1)
    # a 0 here means the MODEL doesn't fit — that's the single-job gate's
    # fatal error to raise with its remediation text, not a grouping
    # concern; never let the probe block grouping below one job
    return _pow2_floor(allowed) if allowed >= 1 else 1


def coalesced_fit(chipset, model_name: str, total_rows: int, size: int,
                  width: int | None = None) -> int:
    """Admit a coalesced batch of total_rows images: returns the capped
    row budget for ONE denoise pass (the executor splits the request list
    into passes of at most this many rows). Raises only when even one
    image cannot fit — the same fatal contract as check_capacity, which
    each member job already cleared individually.

    Like coalesce_rows_limit, the budget accounts for padding: a pass of
    r rows executes as pad_bucket(r) rows, so the per-pass budget is the
    largest power of two within the raw fit — any chunk at or under it
    pads to at most the budget itself."""
    total_rows = max(int(total_rows), 1)
    # probe the slice's RAW capacity (independent of the request size so
    # the pow2 budget is a property of the slice, not of this group)
    fit = check_capacity(
        chipset, model_name, max(total_rows, 256), size, width)
    return min(total_rows, _pow2_floor(fit))


def check_capacity(chipset, model_name: str, batch: int, size: int,
                   width: int | None = None) -> int:
    """-> allowed batch, or raise a fatal job error naming the fix."""
    if _family_key(model_name) == "flux":
        allowed, _ = flux_admissible(chipset, batch, size, width, model_name)
    else:
        allowed = fit_batch(chipset, model_name, batch, size, width)
    if allowed == 0:
        hbm_gb = chipset.hbm_bytes() / (1 << 30)
        per_chip = hbm_gb / max(chipset.chip_count(), 1)
        fam = _family_key(model_name)
        if fam in TEXT_FAMILIES:
            raise ValueError(
                f"{model_name} does not fit on this {chipset.chip_count()}"
                f"-chip slice ({per_chip:.0f} GB HBM a chip): its weights, "
                f"working set and one row of {size} cached positions need "
                f"about {required_hbm_gb(model_name, 1, size):.1f} GB on "
                "every chip. Serve it from higher-HBM chips.")
        act = FAMILY_ACT_GB_PER_IMAGE.get(fam, _DEFAULT_ACT_GB)
        one_image = act * _area_scale(size, width)
        base = (
            f"{model_name} does not fit on this {chipset.chip_count()}-chip "
            f"slice ({hbm_gb:.0f} GB HBM, tensor="
            f"{max(getattr(chipset, 'tensor', 1), 1)}): it needs about "
            f"{required_hbm_gb(model_name, 1, size, width):.0f} GB at this "
            f"canvas. "
        )
        if one_image >= per_chip:
            # activations don't shard over tensor: no degree can save this
            raise ValueError(
                base + "One image's activations alone exceed a chip's HBM "
                "at this canvas — reduce the canvas or serve from "
                "higher-HBM chips."
            )
        need = min_chips(model_name, per_chip, size, width)
        raise ValueError(
            base + f"Serve it from a slice with tensor parallelism >= "
            f"{need} (chips shard the parameters; data-parallel chips "
            f"each hold a full copy)."
        )
    return allowed
