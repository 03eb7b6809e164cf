"""Coalesce compatibility: the shared, jax-free vocabulary of batching.

`coalesce_key(job)` decides whether two raw hive jobs may share ONE
padded jitted denoise+decode invocation. Until ISSUE 9 that decision
lived inside the worker's batching layer, so the only place compatible
jobs could meet was a 50 ms linger window on one worker — batchmates
that landed in different polls (or on different workers) ran solo by
bad luck. The hive now gang-schedules: `hive_server/queue.py` keeps a
secondary index from this exact key to queued jobs, and
`hive_server/dispatch.py` hands same-key jobs out as ONE pre-batched
/work reply. For that to be sound, both sides MUST agree on the key —
hence this module: imported by the worker's BatchScheduler, the hive's
queue/dispatcher, and the test fake alike, with no jax dependency so a
chip-less coordinator can import it.

Everything here operates on plain wire-format job dicts:

- `coalesce_key(job)` -> tuple | None: the compatibility bucket; None
  means "not batchable, single-job path".
- `job_rows(job)`: rows the job contributes to a coalesced batch: its
  images, or for a text job its sequences.
- `is_interactive(job)`: the latency-sensitive marker both the hive's
  priority classes and the worker's linger fast-path read.
- `placement_model(job)`: the model name residency maps know the job
  by (the tiny stand-in when `test_tiny_model` is set).
"""

from __future__ import annotations

import os

# the text families' table and the family a model's name tells: theirs, handed
# on because this module is the one every side imports
from .text_families import TEXT_FAMILIES, text_family_of  # noqa: F401

# wire pipeline_type strings whose txt2img semantics the batched program
# reproduces exactly (plain prompt-conditioned CFG denoise + decode)
_BATCHABLE_PIPELINE_TYPES = {
    None,
    "DiffusionPipeline",
    "StableDiffusionPipeline",
    "StableDiffusionXLPipeline",
    "AutoPipelineForText2Image",
}

# img2img wire names the stacked-init-latent program variant serves
_BATCHABLE_I2I_PIPELINE_TYPES = {
    None,
    "DiffusionPipeline",
    "StableDiffusionImg2ImgPipeline",
    "StableDiffusionXLImg2ImgPipeline",
    "AutoPipelineForImage2Image",
}

# families with a run_batched entry (pipelines/stable_diffusion.py for
# the UNet families; pipelines/flux.py since ISSUE 20)
_BATCHABLE_FAMILIES = {"sd", "sdxl", "flux"}

# txt2img wire names the coalesced flux pass reproduces exactly (plain
# prompt-conditioned rectified-flow denoise + decode; no CFG doubling)
_BATCHABLE_FLUX_PIPELINE_TYPES = {
    None,
    "DiffusionPipeline",
    "FluxPipeline",
    "AutoPipelineForText2Image",
}

# job-level keys that mean per-job structure the padded batch can't carry
# (start_image_uri and strength are handled per-workflow: txt2img refuses
# them, img2img REQUIRES the start image and keys on the strength; `lora`
# left this list in ISSUE 13 — adapters now ride PER ROW as runtime
# low-rank deltas, so adapter identity no longer splits the bucket)
_UNBATCHABLE_JOB_KEYS = (
    "mask_image_uri",
    "refiner",
    "upscale",
    "textual_inversion",
    "vae",
)

# the only `parameters` keys a batchable job may carry; anything else
# (scheduler_args, aesthetic_score, ...) is per-job behavior we refuse
# to guess at — the job falls through to the single path. `controlnet`
# is handled explicitly (the shared-ControlNet component below), and
# cross_attention_kwargs / lora_rank ride with the per-row adapter.
_SAFE_PARAMETER_KEYS = frozenset({
    "test_tiny_model",
    "pipeline_type",
    "scheduler_type",
    "num_inference_steps",
    "guidance_scale",
    "num_images_per_prompt",
    # flux only: the T5 token budget is a static shape of the batched
    # program, so it is a key dimension there (and keys to None elsewhere)
    "max_sequence_length",
    "large_model",
    "use_karras_sigmas",
    "default_height",
    "default_width",
    "controlnet",
    "cross_attention_kwargs",
    "lora_rank",
})

# txt2img-ControlNet wire names whose batched semantics the shared-
# ControlNet group reproduces (one control image conditions every row)
_BATCHABLE_CN_PIPELINE_TYPES = {
    None,
    "StableDiffusionControlNetPipeline",
    "StableDiffusionXLControlNetPipeline",
}

# the only `parameters` keys a batchable text job may carry, and those a
# job of a family that decodes by blocks may carry besides
_SAFE_TEXT_PARAMETER_KEYS = frozenset(
    {"pipeline_type", "max_new_tokens", "temperature"})
_BLOCK_TEXT_PARAMETER_KEYS = frozenset(
    {"denoising_steps", "confidence_threshold"})
DEFAULT_NEW_TOKENS = 256
DEFAULT_TEMPERATURE = 1.0
# the smallest bucket a prompt is padded to
TEXT_MIN_PROMPT_SLOTS = 16

DEFAULT_STEPS = 30
DEFAULT_GUIDANCE = 7.5
DEFAULT_SCHEDULER = "DPMSolverMultistepScheduler"
DEFAULT_STRENGTH = 0.75
# T5 tokens a flux job gets when it names no `max_sequence_length`
# (pipelines/flux.py `run` / `run_batched`)
DEFAULT_FLUX_TEXT_LEN = 512

# --- stage-graph vocabulary (ISSUE 20) -------------------------------
# Stage-typed placement needs one spelling of stage names on BOTH sides
# of the wire: the hive's dispatcher gates hand-outs on the stages a
# worker advertises, and the worker derives its advertisement (and its
# local routing — chip slice vs. the jax-free stage executor) from the
# same sets. Chip stages run accelerator programs; CPU stages are
# jax-free host work (prompt/conditioning prep, NSFW check + packaging)
# that can land on a chip-less host.

CHIP_STAGES = frozenset({
    "denoise", "upscale", "svd", "i2vgen", "txt2vid", "vid2vid", "audio",
})
CPU_STAGES = frozenset({
    "encode", "decode", "postprocess", "stitch", "caption",
})


def stage_of(job: dict) -> str | None:
    """The stage name a stage-job carries, or None for a monolithic job.
    The `stage` context dict is stamped by the hive's workflow expander
    (hive_server/dag.py); its absence IS the monolithic path."""
    stage = job.get("stage")
    if isinstance(stage, dict):
        name = stage.get("name")
        if isinstance(name, str) and name:
            return name
    return None


def is_interactive(job: dict) -> bool:
    """Latency-sensitive marker (ROADMAP "priority-aware batching", minimal
    slice): a job carrying `priority: "interactive"` (or the legacy
    `sdaas_priority` spelling) must not sit in a linger window."""
    return "interactive" in (
        str(job.get("priority", "")).lower(),
        str(job.get("sdaas_priority", "")).lower(),
    )


def prompt_slots(longest: int) -> int:
    """The bucket a pass pads its prompts to: the next power of two at or
    over the longest row, `TEXT_MIN_PROMPT_SLOTS` at least. The program
    is compiled a bucket, so it is a key dimension of a text job."""
    return max(_pow2_bucket(max(int(longest), 1)), TEXT_MIN_PROMPT_SLOTS)


def text_shape(job: dict) -> tuple[int, int] | None:
    """(prompt slots, new tokens) of a `txt2txt` job, None where its
    `prompt_ids` are not rows of ids (the formatter's error to raise)."""
    rows = job.get("prompt_ids")
    if not isinstance(rows, list) or not rows or not all(
            isinstance(row, list) and row for row in rows):
        return None
    params = job.get("parameters")
    params = params if isinstance(params, dict) else {}
    new = int(params.get("max_new_tokens",
                         job.get("max_new_tokens", DEFAULT_NEW_TOKENS)))
    return prompt_slots(max(len(row) for row in rows)), new


def checked_denoising_steps(steps, length: int) -> int:
    """The denoise forwards a block of `length` positions gets at most:
    `steps`, a divisor of `length` (none given: `length`), else a
    ValueError."""
    steps = length if steps is None else steps
    if isinstance(steps, bool) or not isinstance(steps, int) \
            or not 1 <= steps <= length or length % steps:
        raise ValueError(
            f"denoising_steps must be a divisor of the block length "
            f"{length}, not {steps!r}")
    return steps


def block_denoising(job: dict, family: str) -> tuple:
    """What a job says of a block decode, `(denoising_steps,
    confidence_threshold or None)`; `()` for a family that decodes a token
    a step and a job that says nothing of it. Raises ValueError where the
    job carries either for such a family, or steps that are not a divisor
    of the family's block length."""
    params = job.get("parameters")
    params = params if isinstance(params, dict) else {}
    given = {key: params.get(key, job.get(key))
             for key in _BLOCK_TEXT_PARAMETER_KEYS}
    length = TEXT_FAMILIES[family].get("block_length")
    if not length:
        if any(value is not None for value in given.values()):
            raise ValueError(
                f"{family} decodes a token a step: a job of it takes no "
                "denoising_steps or confidence_threshold")
        return ()
    threshold = given["confidence_threshold"]
    return (checked_denoising_steps(given["denoising_steps"], length),
            None if threshold is None else round(float(threshold), 6))


def _text_key(job: dict) -> tuple | None:
    """The bucket of a `txt2txt` job: everything the two jitted programs
    close over (model, prompt bucket, new tokens, sampling and, for a
    family that decodes by blocks, the denoise forwards a block gets and
    the threshold, behind the elements the hive reads by place); the ids,
    the seed and how many sequences a job has ride per row."""
    model = job.get("model_name")
    if not isinstance(model, str) or not model:
        return None
    family = text_family_of(model)
    if family is None:
        return None
    params = job.get("parameters") or {}
    allowed = _SAFE_TEXT_PARAMETER_KEYS | (
        _BLOCK_TEXT_PARAMETER_KEYS
        if TEXT_FAMILIES[family].get("block_length") else frozenset())
    if not isinstance(params, dict) or not set(params) <= allowed:
        return None
    shape = text_shape(job)
    if shape is None or shape[1] < 1:
        return None
    try:
        blocks = block_denoising(job, family)
    except (TypeError, ValueError):
        return None  # the formatter's error to raise
    temperature = round(float(params.get(
        "temperature", job.get("temperature", DEFAULT_TEMPERATURE))), 4)
    return (model, family, "txt2txt", *shape, temperature, *blocks)


def job_rows(job: dict) -> int:
    """Rows this job contributes to a coalesced batch: its images, or the
    sequences of a text job."""
    if job.get("workflow") == "txt2txt":
        rows = job.get("prompt_ids")
        return max(len(rows), 1) if isinstance(rows, list) else 1
    params = job.get("parameters") or {}
    try:
        n = int(params.get("num_images_per_prompt",
                           job.get("num_images_per_prompt", 1)) or 1)
    except (TypeError, ValueError):
        return 1
    return max(n, 1)


def placement_model(job: dict) -> str | None:
    """The model name the residency map will know this job by — the tiny
    stand-in when `test_tiny_model` is set (that is the name the registry
    loads and therefore the name load events record)."""
    model = job.get("model_name")
    if not isinstance(model, str) or not model:
        return None
    params = job.get("parameters")
    tiny = bool(job.get("test_tiny_model"))
    if isinstance(params, dict):
        tiny = tiny or bool(params.get("test_tiny_model"))
    if tiny:
        try:
            from .workflows.diffusion import _tiny_stand_in

            return _tiny_stand_in(model)
        except Exception:  # placement is advisory; never fail a job over it
            return model
    return model


def adapter_ref(job: dict) -> str | None:
    """The adapter IDENTITY one job carries, or None — per-row data for
    the batched program, but the hive's gang dispatcher and the worker's
    scheduler both cap DISTINCT adapters per gang at `lora_slots_max`
    (the stacked-factor slot dimension), so both need one canonical
    spelling. Handles the raw wire string and the resolved
    {lora, weight_name, subfolder} dict alike."""
    lora = job.get("lora")
    if lora is None or lora == "":
        return None
    if isinstance(lora, dict):
        return "|".join(
            str(lora.get(k) or "")
            for k in ("lora", "weight_name", "subfolder"))
    return str(lora)


def wire_adapter_ref(ref, weight_name=None, subfolder=None) -> str:
    """Resolved adapter parts -> the WIRE spelling the submitting
    client used (loras.resolve_lora inverted). The worker's operand
    cache is keyed by the RESOLVED dict — its `lora` field holds the
    worker-local root dir for bare-name references — while the hive
    reads the raw job string, so cross-process identity (the /work
    resident-adapter advertisement, ISSUE 16) must reconstruct the
    form both started from:

      local root dir + weight file      -> the bare file name
      hub repo [+ subfolder] [+ file]   -> "pub/repo[/sub...][/file]"

    A worker-local root dir is configuration, not adapter identity —
    two workers with different `lora_root_dir` serving the same
    adapter must advertise the same ref."""
    ref = str(ref or "")
    name = str(weight_name or "")
    sub = str(subfolder or "")
    if name and os.path.isabs(os.path.expanduser(ref)):
        return "/".join(p for p in (sub, name) if p)
    return "/".join(p for p in (ref, sub, name) if p)


def canonical_adapter_ref(job: dict) -> str | None:
    """adapter_ref normalized for CROSS-PROCESS identity (the /work
    resident-adapter advertisement, ISSUE 16): the resolved dict
    spelling and the raw wire string collapse to one form via
    wire_adapter_ref, so a worker whose operand cache was fed by
    resolved-dict jobs still matches a string-form job's adapters."""
    lora = job.get("lora")
    if lora is None or lora == "":
        return None
    if isinstance(lora, dict):
        return wire_adapter_ref(
            lora.get("lora"), lora.get("weight_name"),
            lora.get("subfolder"))
    # legacy pipe-joined string spellings ("style-a||") still collapse
    return str(lora).rstrip("|")


# smallest padded factor rank the batched program compiles
# (lora_runtime.MIN_RANK imports this): declared ranks below it all run
# as the same rank-4-padded program, so they must share one bucket here
LORA_MIN_RANK = 4


def _pow2_bucket(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


# _runtime_delta_on memo: (env spelling, settings-file mtime_ns) -> flag.
# coalesce_key runs per job on the hive submit and worker enqueue hot
# paths; a full settings read+parse per adapter job would add disk I/O
# there, but the flag only changes when the env var or the file does —
# one getenv + one stat re-validates it.
_DELTA_FLAG: tuple[tuple, bool] | None = None


def _runtime_delta_on() -> bool:
    """Settings.lora_runtime_delta at call time — jax-free. The kill
    switch restores the pre-ISSUE-13 serving shape end to end: with
    deltas off, run_batched refuses adapter groups, so admitting them
    here would only buy a doomed coalesced attempt + a noisy solo
    fallback per group."""
    global _DELTA_FLAG
    try:
        import os

        from .settings import get_settings_dir, load_settings

        # get_settings_full_path() mkdirs the settings dir as a side
        # effect — derive the path without it, one stat only
        root = os.getenv("SDAAS_ROOT")
        try:
            mtime = os.stat(
                get_settings_dir() / "settings.json").st_mtime_ns
        except OSError:
            mtime = None
        fingerprint = (os.getenv("CHIASWARM_LORA_RUNTIME_DELTA"), root,
                       mtime)
        if _DELTA_FLAG is not None and _DELTA_FLAG[0] == fingerprint:
            return _DELTA_FLAG[1]
        flag = bool(getattr(load_settings(), "lora_runtime_delta", True))
        _DELTA_FLAG = (fingerprint, flag)
        return flag
    except Exception:  # settings trouble must never unbatch plain jobs
        return True


def _adapter_component(job: dict, params: dict) -> tuple | None:
    """The coalesce key's adapter-slot dimension (ISSUE 13): jobs
    carrying an adapter coalesce with each other AND with adapter-free
    jobs on the same base model (adapter-free rows ride slot 0 of the
    stacked factors with an exact zero delta), so adapter PRESENCE never
    splits the bucket — identity rides per row. Only a submitter-
    declared `lora_rank` splits, by power-of-two RANK BUCKET: a gang's
    stacked factors share one padded rank, and an explicit hint keeps a
    rank-4 fleet from padding to a declared rank-128 outlier. Undeclared
    ranks coalesce with everything; zero-padding keeps any mix exact
    either way."""
    if adapter_ref(job) is None:
        return None
    try:
        rank = int(params.get("lora_rank", job.get("lora_rank", 0)) or 0)
    except (TypeError, ValueError):
        rank = 0
    if rank <= 0:
        return None  # same bucket as adapter-free jobs
    return ("lora", _pow2_bucket(max(rank, LORA_MIN_RANK)))


def _controlnet_component(job: dict, params: dict,
                          workflow: str) -> tuple | None | bool:
    """The shared-ControlNet dimension (ISSUE 13 second rung): jobs
    conditioned by ONE identical ControlNet branch + control image
    coalesce, with the control residuals computed once per group. False
    = the job carries ControlNet structure the batched program cannot
    share (per-job start-image conditioning, QR prepipelines) -> single
    path; None = no ControlNet."""
    cn = params.get("controlnet")
    if cn is None:
        return None
    if not isinstance(cn, dict) or workflow != "txt2img":
        return False
    cn_params = cn.get("parameters") or {}
    if not isinstance(cn_params, dict):
        return False
    if cn_params.get("controlnet_prepipeline_type"):
        return False  # QR two-stage chains per job
    if cn.get("qr_code_contents"):
        return False  # generated control images are per-job content
    uri = cn.get("control_image_uri")
    if not uri:
        return False
    if params.get("pipeline_type") not in _BATCHABLE_CN_PIPELINE_TYPES:
        return False
    return (
        str(cn.get("controlnet_model_name",
                   "lllyasviel/control_v11p_sd15_canny")),
        str(uri),
        str(cn.get("preprocessor") or ""),
        round(float(cn.get("controlnet_conditioning_scale", 1.0)), 4),
        round(float(cn.get("control_guidance_start", 0.0)), 4),
        round(float(cn.get("control_guidance_end", 1.0)), 4),
    )


def coalesce_key(job: dict) -> tuple | None:
    """Compatibility bucket for one raw hive job; None = not batchable.

    Two jobs with equal keys produce identical results whether they run
    alone or coalesced: everything the jitted program closes over or
    shares across the batch (model, canvas, step count, scheduler,
    guidance scale, workflow, img2img strength, the SHARED ControlNet
    branch + control image) is in the key; everything per-row (prompt,
    negative, seed, start image, image count, ADAPTER identity + scale)
    rides outside it. The adapter-slot element splits only by rank
    bucket — same base model + compatible rank coalesce, thousands of
    adapters over one resident tree (ISSUE 13).
    """
    try:
        workflow = job.get("workflow")
        if workflow == "txt2txt":
            return _text_key(job)
        if workflow not in ("txt2img", "img2img"):
            return None
        # stage-jobs (ISSUE 20): only the denoise stage is the padded
        # jitted program; it coalesces with OTHER denoise stages but
        # never with monolithic jobs (the envelopes differ — a denoise
        # stage hands off raw rows instead of packaged outputs), so the
        # stage name is a key dimension. Every other stage is host work
        # on the single path.
        stage = stage_of(job)
        if stage is not None and stage != "denoise":
            return None
        model = job.get("model_name")
        if not isinstance(model, str) or not model:
            return None
        if any(k in job for k in _UNBATCHABLE_JOB_KEYS):
            return None
        params = job.get("parameters") or {}
        if not isinstance(params, dict):
            return None
        if not set(params) <= _SAFE_PARAMETER_KEYS:
            return None

        adapter = _adapter_component(job, params)
        if adapter_ref(job) is not None and not _runtime_delta_on():
            # lora_runtime_delta=0: adapters serve via merged trees on
            # the single path — adapter jobs are uncoalesceable again
            return None
        cn = _controlnet_component(job, params, workflow)
        if cn is False:
            return None
        if cn is not None and adapter_ref(job) is not None:
            # each is batchable alone; the combination stays on the
            # single path (the delta interceptor is scoped to the UNet,
            # but the grouping matrix stays small and tested)
            return None

        from .registry import _auto_family

        family = _auto_family(model)
        if family not in _BATCHABLE_FAMILIES:
            return None
        text_len = None
        if "max_sequence_length" in params:
            if family != "flux":
                return None
            text_len = int(params["max_sequence_length"])
            if text_len == DEFAULT_FLUX_TEXT_LEN:
                # naming the default shares the bucket of leaving it out
                text_len = None
        if family == "flux":
            # flow-matching txt2img only: no CFG pair, no adapter delta
            # path, no ControlNet branch in the MMDiT program. Steps and
            # guidance must be EXPLICIT — the solo path's defaults are
            # model-variant-dependent (schnell distills to 4 steps,
            # guidance 3.5 vs the UNet families' 7.5), which this
            # jax-free key cannot reproduce without guessing.
            if workflow != "txt2img" or cn is not None \
                    or adapter_ref(job) is not None:
                return None
            if params.get("pipeline_type") \
                    not in _BATCHABLE_FLUX_PIPELINE_TYPES:
                return None
            if params.get("num_inference_steps",
                          job.get("num_inference_steps")) is None:
                return None
            if params.get("guidance_scale",
                          job.get("guidance_scale")) is None:
                return None

        # canvas: explicit dims, else the model-pinned default the
        # formatter would apply; jobs relying on the family default share
        # the None bucket (they all resolve to the same canvas)
        height = job.get("height", params.get("default_height"))
        width = job.get("width", params.get("default_width"))
        if (height is None) != (width is None):
            return None
        if height is not None:
            height, width = int(height), int(width)

        strength = None
        if workflow == "txt2img":
            # a txt2img job carrying img2img-shaped fields is something
            # the formatter may interpret per-job — single path
            if "start_image_uri" in job or "strength" in job:
                return None
            # the shared-ControlNet component validated its own pipeline
            # types, and the flux branch above validated flux wire
            # names; a plain txt2img job keeps the original gate
            if cn is None and family != "flux" and (
                    params.get("pipeline_type")
                    not in _BATCHABLE_PIPELINE_TYPES):
                return None
        else:  # img2img: per-request start images -> stacked init latents
            if not job.get("start_image_uri"):
                return None
            # without an explicit canvas the solo path sizes the pass to
            # each start image — a group can't share a program over
            # unknown per-image canvases, so explicit dims are required
            if height is None:
                return None
            if params.get("pipeline_type") not in _BATCHABLE_I2I_PIPELINE_TYPES:
                return None
            name = model.lower()
            # edit/inpaint architectures condition on the channel dim —
            # different program semantics, out of the batched variant
            if any(s in name for s in ("pix2pix", "ip2p", "inpaint")):
                return None
            strength = round(float(job.get("strength", DEFAULT_STRENGTH)), 4)

        steps = int(params.get("num_inference_steps",
                               job.get("num_inference_steps", DEFAULT_STEPS)))
        guidance = round(float(params.get(
            "guidance_scale", job.get("guidance_scale", DEFAULT_GUIDANCE))), 4)
        scheduler = str(params.get("scheduler_type", DEFAULT_SCHEDULER))
        karras = bool(params.get("use_karras_sigmas", False))
        # the tiny flag rides at either level on the wire (formatters copy
        # the whole job); both must split the bucket or a real job could
        # coalesce behind a tiny-flagged one and run on the stand-in model
        tiny = bool(params.get("test_tiny_model", False)) \
            or bool(job.get("test_tiny_model", False))
        # large_model flips the SD-vs-SDXL default pipeline class
        large = bool(params.get("large_model", False))
        return (model, family, height, width, steps, scheduler, guidance,
                karras, tiny, large, workflow, strength, adapter, cn,
                stage, text_len)
    except (TypeError, ValueError):
        # hive-controlled values that don't parse: let the single-job
        # path produce its usual fatal envelope for them
        return None
