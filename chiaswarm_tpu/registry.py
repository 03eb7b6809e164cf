"""Pipeline registry with weight residency — the heart of the TPU redesign.

The reference resolves diffusers class names from job JSON by reflection
(swarm/type_helpers.py:9-22) and calls `from_pretrained` on EVERY job
(swarm/diffusion/diffusion_func.py:103) — disk -> VRAM per job is its #1
perf loss (SURVEY §2.2). Here:

- job `pipeline_type` strings map to registered `PipelineFactory` entries
  (a fixed table, no reflection / no arbitrary imports);
- built pipelines are cached by (model_name, pipeline_type, variant): Flax
  params are loaded once, transferred to the job's mesh, and stay resident;
  jitted programs are cached by XLA per (shape bucket, step count) on top;
- an LRU bound keeps HBM use sane when a worker serves many models.
"""

from __future__ import annotations

import logging
import threading
from collections import OrderedDict
from typing import Callable

from .text_families import TEXT_FAMILIES, text_family_of
from .telemetry import Span

logger = logging.getLogger(__name__)

# wire-name -> family; the table covers every pipeline_type string the
# reference hive can send (SURVEY §2.7) so legacy jobs resolve.
PIPELINE_FAMILIES: dict[str, str] = {
    "DiffusionPipeline": "sd",
    "StableDiffusionPipeline": "sd",
    "StableDiffusionImg2ImgPipeline": "sd",
    "StableDiffusionInpaintPipeline": "sd",
    "StableDiffusionControlNetPipeline": "sd",
    "StableDiffusionControlNetImg2ImgPipeline": "sd",
    "StableDiffusionControlNetInpaintPipeline": "sd",
    "StableDiffusionXLPipeline": "sdxl",
    "StableDiffusionXLImg2ImgPipeline": "sdxl",
    "StableDiffusionXLInpaintPipeline": "sdxl",
    "StableDiffusionXLControlNetPipeline": "sdxl",
    "StableDiffusionXLControlNetImg2ImgPipeline": "sdxl",
    "StableDiffusionXLControlNetInpaintPipeline": "sdxl",
    "StableDiffusionInstructPix2PixPipeline": "sd",
    "StableDiffusionXLInstructPix2PixPipeline": "sdxl",
    "StableDiffusionLatentUpscalePipeline": "sd_upscale",
    "KandinskyPipeline": "kandinsky",
    "KandinskyImg2ImgPipeline": "kandinsky",
    "KandinskyV22Pipeline": "kandinsky",
    "KandinskyV22Img2ImgPipeline": "kandinsky",
    "KandinskyV22ControlnetPipeline": "kandinsky",
    "KandinskyV22ControlnetImg2ImgPipeline": "kandinsky",
    "KandinskyV22PriorPipeline": "kandinsky_prior",
    "KandinskyV22PriorEmb2EmbPipeline": "kandinsky_prior",
    "Kandinsky3Pipeline": "kandinsky3",
    "Kandinsky3Img2ImgPipeline": "kandinsky3",
    "AutoPipelineForText2Image": "sd",
    "StableCascadeDecoderPipeline": "cascade",
    "StableCascadePriorPipeline": "cascade_prior",
    "StableCascadeCombinedPipeline": "cascade",
    "FluxPipeline": "flux",
    "IFPipeline": "deepfloyd_if",
    "IFSuperResolutionPipeline": "deepfloyd_if",
    "AudioLDMPipeline": "audioldm",
    "AudioLDM2Pipeline": "audioldm2",
    "BarkPipeline": "bark",
    "AnimateDiffPipeline": "animatediff",
    "TextToVideoSDPipeline": "animatediff",
    "VideoToVideoSDPipeline": "animatediff",
    "I2VGenXLPipeline": "i2vgenxl",
    "StableVideoDiffusionPipeline": "svd",
    "BlipForConditionalGeneration": "blip",
    "BlipForQuestionAnswering": "blip",
    # the text families' wire names (text_families.py `TEXT_FAMILIES`)
    **{what["wire"]: family for family, what in TEXT_FAMILIES.items()},
}

# family -> factory(model_name, chipset, **variant) -> pipeline bundle.
# A bundle holds ONE resident param set per (model, family) and serves every
# pipeline_type of that family: run() dispatches txt2img/img2img/inpaint from
# the kwargs it receives (image/mask_image presence), so the txt2img and
# inpaint wire names share weights instead of loading twice.
_FACTORIES: dict[str, Callable] = {}

_CACHE_LOCK = threading.Lock()
_CACHE: OrderedDict[tuple, object] = OrderedDict()
_BUILD_LOCKS: dict[tuple, threading.Lock] = {}
MAX_RESIDENT_PIPELINES = 4


def register_family(family: str):
    def deco(factory: Callable):
        _FACTORIES[family] = factory
        return factory

    return deco


def family_of(pipeline_type: str) -> str:
    try:
        return PIPELINE_FAMILIES[pipeline_type]
    except KeyError:
        raise ValueError(f"Unknown pipeline type: {pipeline_type}") from None


def _auto_family(model_name: str) -> str:
    """Generic wire names (AutoPipelineFor*, DiffusionPipeline) resolve by
    MODEL name, the way diffusers' AutoPipeline does — the reference hive
    sends e.g. Kandinsky jobs as AutoPipelineForText2Image
    (swarm/test.py:96,144)."""
    name = model_name.lower()
    if "kandinsky-3" in name or "kandinsky3" in name:
        return "kandinsky3"
    if "kandinsky" in name:
        return "kandinsky_prior" if "prior" in name else "kandinsky"
    if "cascade" in name:
        return "cascade_prior" if "prior" in name else "cascade"
    if "flux" in name:
        return "flux"
    text = text_family_of(name)
    if text is not None:
        return text
    if name.startswith("deepfloyd/") or "tiny-if" in name:
        return "deepfloyd_if"
    if "latent-upscaler" in name or "tiny-upscaler" in name:
        return "sd_upscale"
    from .models.configs import model_family

    return "sdxl" if "xl" in model_family(model_name) else "sd"


def get_pipeline(model_name: str, pipeline_type: str, chipset=None, **variant):
    """Resolve (and cache) a resident pipeline for this model on this mesh."""
    _ensure_builtin_families()
    if pipeline_type.startswith(("AutoPipeline", "AutoModel")) \
            or pipeline_type == "DiffusionPipeline":
        family = _auto_family(model_name)
    else:
        family = family_of(pipeline_type)
    factory = _FACTORIES.get(family)
    if factory is None:
        raise ValueError(
            f"Pipeline family '{family}' ({pipeline_type}) is not available on "
            "this worker."
        )

    slice_id = getattr(chipset, "slice_id", 0)
    key = (model_name, family, slice_id, tuple(sorted(variant.items())))
    with _CACHE_LOCK:
        if key in _CACHE:
            _CACHE.move_to_end(key)
            pipeline = _CACHE[key]
            hit = True
        else:
            build_lock = _BUILD_LOCKS.setdefault(key, threading.Lock())
            hit = False
    if hit:
        # a cache hit is a residency signal too: this slice serves the
        # model right now, so the dispatch board should keep routing
        # same-model groups here (recency refresh)
        if chipset is not None:
            _note_resident(model_name, slice_id)
        return pipeline

    # build outside the cache lock (weight load/convert can take seconds) but
    # serialized per key so concurrent slices don't double-load weights
    with build_lock:
        with _CACHE_LOCK:
            if key in _CACHE:
                _CACHE.move_to_end(key)
                pipeline = _CACHE[key]
                hit = True
        if hit:
            if chipset is not None:
                _note_resident(model_name, slice_id)
            return pipeline
        logger.info("building pipeline %s/%s", model_name, family)
        # span "registry_build": shapes, weights and placement of one
        # pipeline, on a miss only; fires at start-up too, outside any job
        with Span("registry_build"):
            pipeline = factory(model_name, chipset, **variant)
        if chipset is not None:
            # the load event feeding the placement layer: this model is
            # now warm on this slice, so the dispatch board routes the
            # next same-model group here (chips/allocator residency map)
            _note_resident(model_name, slice_id)

        with _CACHE_LOCK:
            _CACHE[key] = pipeline
            while len(_CACHE) > MAX_RESIDENT_PIPELINES:
                evicted_key, evicted = _CACHE.popitem(last=False)
                logger.info("evicting resident pipeline %s", evicted_key)
                _clear_resident(evicted_key[0], evicted_key[2])
                release = getattr(evicted, "release", None)
                if release:
                    release()
    return pipeline


def _note_resident(model_name: str, slice_id: int) -> None:
    try:
        from .chips.allocator import note_resident

        note_resident(model_name, slice_id)
    except Exception:  # placement is advisory; never fail a build over it
        logger.debug("residency note failed", exc_info=True)


def _clear_resident(model_name: str, slice_id: int) -> None:
    try:
        from .chips.allocator import clear_resident

        clear_resident(model_name, slice_id)
    except Exception:
        logger.debug("residency clear failed", exc_info=True)


def clear_cache() -> None:
    with _CACHE_LOCK:
        _CACHE.clear()


def resident_models(slice_id: int | None = None) -> list[str]:
    """Model names currently resident in HBM (telemetry /healthz).

    With `slice_id`, only models resident on THAT slice — pipelines and
    their jitted programs are per-slice, so a process-wide answer would
    deny a stolen group its first-compile watchdog allowance on the
    slice that actually has to compile."""
    with _CACHE_LOCK:
        return sorted({
            key[0] for key in _CACHE
            if slice_id is None or key[2] == slice_id
        })


_BUILTINS_LOADED = False


def _ensure_builtin_families() -> None:
    """Import pipeline modules lazily so the registry is importable without jax."""
    global _BUILTINS_LOADED
    if _BUILTINS_LOADED:
        return
    _BUILTINS_LOADED = True
    for module in ("stable_diffusion", "video", "svd", "i2vgen", "audio",
                   "audioldm2",
                   "captioning", "text_generation", "flux", "kandinsky", "kandinsky3", "cascade",
                   "upscale", "deepfloyd", "bark"):
        try:
            __import__(f"{__package__}.pipelines.{module}")
        except Exception as e:
            logger.warning("pipeline family module %s unavailable: %s", module, e)
