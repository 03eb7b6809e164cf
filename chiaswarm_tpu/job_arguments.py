"""Job JSON -> (workload callback, normalized kwargs).

Dispatch parity with reference swarm/job_arguments.py:24-397: same workflow
keys (txt2img / img2img / inpaint / txt2vid / img2vid / vid2vid / txt2audio /
img2txt / stitch), same defaults (30 SD steps, 25 video, 20 audio; 1024 size
cap; SD-vs-SDXL pipeline selection via `large_model`; instruct-pix2pix
strength -> image_guidance_scale x5), same ControlNet argument extraction.

Differences by design:
- `parameters.pipeline_type` / `scheduler_type` stay *strings* resolved
  against our pipeline registry (`registry.py`) at execution time — no
  `__import__` reflection over class names (reference swarm/type_helpers.py:
  9-22), which was both a supply-chain hazard and a torch-ism.
- The reference's inpaint bug (swarm/job_arguments.py:234 passes
  device_identifier as `size`) is fixed: size flows through properly.
- Workload callbacks are imported lazily so formatting is testable without
  pulling in model code.
"""

from __future__ import annotations

import asyncio

from .external_resources import (
    download_images,
    get_image,
    get_qrcode_image,
    is_not_blank,
    max_size,
)
from .loras import Loras
from .pre_processors.image_utils import center_crop_resize, resize_square

# Default wire names (reference defaults at swarm/job_arguments.py:83-118,206-210)
DEFAULT_SCHEDULER = "DPMSolverMultistepScheduler"

# models whose strength parameter maps to image_guidance_scale (x5)
_PIX2PIX_MODELS = {"timbrooks/instruct-pix2pix", "diffusers/sdxl-instructpix2pix-768"}
_SIZE_LOCKED_MODELS = {
    "diffusers/sdxl-instructpix2pix-768",
    "kandinsky-community/kandinsky-2-2-controlnet-depth",
}


async def format_args(job: dict, settings, device_identifier: str):
    args = prepare_args(job, settings)
    stage = args.pop("stage", None)
    workflow = args.pop("workflow", None)

    if isinstance(stage, dict) and stage.get("name"):
        # stage-graph jobs (ISSUE 20): host stages (encode/decode) route
        # to their own callbacks; chip stages fall through to the classic
        # dispatch below with the graph metadata (emit_raw handoff,
        # injected start image) already applied to `args`
        from .workflows.stages import format_stage_args

        routed = await format_stage_args(
            stage, workflow, args, settings, device_identifier)
        if routed is not None:
            return routed

    if workflow == "echo":
        from .workflows.echo import echo_callback

        return echo_callback, args

    if workflow == "txt2audio":
        if args["model_name"] == "suno/bark":
            from .workflows.audio import bark_callback

            return bark_callback, args
        return format_txt2audio_args(args)

    if workflow == "stitch":
        return await format_stitch_args(args)

    if workflow == "img2txt":
        return await format_img2txt_args(args)

    if workflow == "txt2txt":
        return format_txt2txt_args(args)

    if workflow == "vid2vid":
        from .workflows.video import vid2vid_callback

        return vid2vid_callback, args

    if workflow == "txt2vid":
        return format_txt2vid_args(args)

    if workflow == "img2vid":
        return await format_img2vid_args(args)

    if args["model_name"].startswith("DeepFloyd/"):
        from .workflows.diffusion import deepfloyd_if_callback

        return deepfloyd_if_callback, args

    return await format_stable_diffusion_args(args, workflow, device_identifier)


def prepare_args(job: dict, settings) -> dict:
    args = dict(job)
    if "lora" in args:
        args["lora"] = Loras(settings.lora_root_dir).resolve_lora(args["lora"])
    return args


# --- non-diffusion workflows ---


async def format_stitch_args(args: dict):
    from .workflows.stitch import stitch_callback

    image_urls = [j["resultUri"] for j in args["jobs"]]
    args["images"] = await download_images(image_urls)
    return stitch_callback, args


async def format_img2txt_args(args: dict):
    from .workflows.captioning import caption_callback

    if "start_image_uri" in args:
        args["image"] = await get_image(args.pop("start_image_uri"), None)
    return caption_callback, args


def format_txt2txt_args(args: dict):
    """Text completion from token ids: `model_name`, `prompt_ids` (a list
    of rows, each a list of ids of the model's vocabulary),
    `max_new_tokens`, `temperature`, `seed` and, for a model that decodes
    by blocks, `denoising_steps` and `confidence_threshold`. The result is
    JSON. An id is drawn from the softmax of its logits over `temperature`
    (0: the largest logit's id) with one uniform number a position, from
    `seed` folded by the row's number in the job and the step
    (ops/sampling.py): the same job gives the same ids, whatever rides
    beside it."""
    from .coalesce import (
        DEFAULT_NEW_TOKENS,
        DEFAULT_TEMPERATURE,
        block_denoising,
        text_family_of,
    )
    from .workflows.text import txt2txt_callback

    parameters = args.pop("parameters", None) or {}
    rows = args.get("prompt_ids")
    if not isinstance(rows, list) or not rows or not all(
            isinstance(row, list) and row
            and all(isinstance(i, int) and not isinstance(i, bool)
                    for i in row) for row in rows):
        raise ValueError(
            "txt2txt needs prompt_ids: a list of rows, each a list of "
            "token ids with at least one id")
    args["pipeline_type"] = parameters.pop(
        "pipeline_type", "AutoModelForCausalLM")
    args["max_new_tokens"] = int(parameters.pop(
        "max_new_tokens", args.get("max_new_tokens", DEFAULT_NEW_TOKENS)))
    args["temperature"] = float(parameters.pop(
        "temperature", args.get("temperature", DEFAULT_TEMPERATURE)))
    family = text_family_of(str(args.get("model_name", "")))
    if family is not None:
        blocks = block_denoising({**args, "parameters": parameters}, family)
        for key in ("denoising_steps", "confidence_threshold"):
            parameters.pop(key, None)
            args.pop(key, None)
        if blocks:
            args["denoising_steps"], args["confidence_threshold"] = blocks
    args["content_type"] = "application/json"
    return txt2txt_callback, args


def format_txt2audio_args(args: dict):
    from .workflows.audio import txt2audio_callback

    parameters = args.pop("parameters", {})
    args.setdefault("prompt", "")
    args.setdefault("num_inference_steps", 20)
    args["pipeline_type"] = parameters.pop("pipeline_type", "AudioLDMPipeline")
    args["scheduler_type"] = parameters.pop("scheduler_type", DEFAULT_SCHEDULER)
    _drop_unsupported(args, parameters)
    # remaining special parameters (test_tiny_model, audio_length_in_s, ...)
    # pass through WITHOUT overwriting already-formatted top-level args —
    # a hive-controlled parameters dict must not rewrite model_name/prompt
    _merge_passthrough(args, parameters)
    return txt2audio_callback, args


def format_txt2vid_args(args: dict):
    from .workflows.video import txt2vid_callback

    parameters = args.pop("parameters", {})
    args.setdefault("prompt", "")
    args.setdefault("num_inference_steps", 25)
    args.pop("num_images_per_prompt", None)

    args["pipeline_type"] = parameters.pop("pipeline_type", "DiffusionPipeline")

    # model-pinned scheduler args trump user settings (reference :109-119)
    if "scheduler_args" in parameters:
        scheduler_args = parameters["scheduler_args"]
        args["scheduler_type"] = scheduler_args.pop("scheduler_type", "LCMScheduler")
        args["scheduler_args"] = scheduler_args
    else:
        args["scheduler_type"] = parameters.pop("scheduler_type", DEFAULT_SCHEDULER)

    if "motion_adapter" in parameters:
        args["motion_adapter"] = parameters["motion_adapter"]
    if "lora" in parameters:
        args["lora"] = parameters["lora"]

    _drop_unsupported(args, parameters)
    _merge_passthrough(args, parameters)
    return txt2vid_callback, args


async def format_img2vid_args(args: dict):
    from .workflows.video import img2vid_callback

    parameters = args.pop("parameters", {})
    args.setdefault("prompt", "")
    args.setdefault("num_inference_steps", 25)
    args.pop("num_images_per_prompt", None)

    args["pipeline_type"] = parameters.pop("pipeline_type", "I2VGenXLPipeline")
    args["scheduler_type"] = parameters.pop("scheduler_type", DEFAULT_SCHEDULER)

    if "start_image_uri" in args:
        args["image"] = await get_image(args.pop("start_image_uri"), None)

    _drop_unsupported(args, parameters)
    _merge_passthrough(args, parameters)
    return img2vid_callback, args


# --- stable-diffusion family ---


async def format_stable_diffusion_args(args: dict, workflow, device_identifier: str):
    from .workflows.diffusion import diffusion_callback

    size = None
    if "height" in args and "width" in args:
        if args["height"] > max_size or args["width"] > max_size:
            raise Exception(
                f"The max image size is ({max_size}, {max_size}); "
                f"got ({args['height']}, {args['width']})."
            )
        # PIL (width, height) convention throughout the input path
        size = (args["width"], args["height"])

    args.setdefault("prompt", "")
    parameters = args.pop("parameters", {})

    if workflow == "img2img":
        await format_img2img_args(args, parameters, size, device_identifier)
    elif workflow == "inpaint" or "mask_image_uri" in args:
        await format_inpaint_args(args, parameters, size, device_identifier)
    elif workflow == "txt2img":
        await format_txt2img_args(args, parameters, size, device_identifier)

    args.setdefault("num_inference_steps", 30)

    if "pipeline_prior_type" in parameters:
        args["pipeline_prior_type"] = parameters.pop("pipeline_prior_type")
    if "prior_timesteps" in parameters:
        args["prior_timesteps"] = parameters.pop("prior_timesteps")

    args["pipeline_type"] = parameters.pop("pipeline_type", "DiffusionPipeline")
    args["scheduler_type"] = parameters.pop("scheduler_type", DEFAULT_SCHEDULER)

    # model-specified default canvas (reference :213-219)
    default_height = parameters.pop("default_height", None)
    default_width = parameters.pop("default_width", None)
    if default_height is not None and "height" not in args:
        args["height"] = default_height
    if default_width is not None and "width" not in args:
        args["width"] = default_width

    _drop_unsupported(args, parameters)
    # remaining special parameters pass straight through to the pipeline
    # (protected identity keys excepted — same rule as the other formatters)
    _merge_passthrough(args, parameters)

    return diffusion_callback, args


async def format_txt2img_args(args, parameters, size, device_identifier):
    if "controlnet" in parameters:
        parameters.setdefault(
            "pipeline_type",
            "StableDiffusionXLControlNetPipeline"
            if parameters.get("large_model", False)
            else "StableDiffusionControlNetPipeline",
        )
        await format_controlnet_args(args, parameters, None, size, device_identifier)


async def format_inpaint_args(args, parameters, size, device_identifier):
    # pick the inpaint pipeline class BEFORE delegating to img2img setup so
    # img2img's own default doesn't claim the slot (the reference effectively
    # dispatched bare inpaint jobs to the img2img class, :234+290)
    large = parameters.get("large_model", False)
    if "controlnet" in parameters:
        parameters.setdefault(
            "pipeline_type",
            "StableDiffusionXLControlNetInpaintPipeline"
            if large
            else "StableDiffusionControlNetInpaintPipeline",
        )
    else:
        parameters.setdefault(
            "pipeline_type",
            "StableDiffusionXLInpaintPipeline"
            if large
            else "StableDiffusionInpaintPipeline",
        )

    # inpaint inherits img2img setup since it has a start image
    # (size is threaded through properly — reference :234 dropped it)
    await format_img2img_args(args, parameters, size, device_identifier)
    args["mask_image"] = await get_image(args.pop("mask_image_uri"), size)
    args.pop("height", None)
    args.pop("width", None)

    if "controlnet" in parameters:
        await format_controlnet_args(args, parameters, None, size, device_identifier)


async def format_img2img_args(args, parameters, size, device_identifier):
    start_image = await get_image(args.pop("start_image_uri", None), size)

    if size is None and start_image is not None:
        size = start_image.size

    if "controlnet" in parameters:
        await format_controlnet_args(
            args, parameters, start_image, size, device_identifier
        )
        parameters.setdefault(
            "pipeline_type",
            "StableDiffusionXLControlNetImg2ImgPipeline"
            if parameters.get("large_model", False)
            else "StableDiffusionControlNetImg2ImgPipeline",
        )
    elif "pipeline_type" not in parameters:
        parameters["pipeline_type"] = (
            "StableDiffusionXLImg2ImgPipeline"
            if parameters.get("large_model", False)
            else "StableDiffusionImg2ImgPipeline"
        )
        args.pop("height", None)
        args.pop("width", None)

    if args["model_name"] in _PIX2PIX_MODELS:
        # pix2pix uses image_guidance_scale (range 1-5) instead of strength (0-1)
        args["image_guidance_scale"] = args.pop("strength", 0.6) * 5

    if start_image is None and args.get("control_image") is not None:
        start_image = args["control_image"]
    if start_image is None:
        raise ValueError("Workflow requires an input image. None provided")

    if args["model_name"] in _SIZE_LOCKED_MODELS and not parameters.get(
        "test_tiny_model"
    ):
        # these checkpoints error off their native 768 canvas (reference
        # :314-321); tiny-model test jobs keep their small canvas
        start_image = resize_square(start_image).resize((768, 768))
        args["height"] = start_image.height
        args["width"] = start_image.width

    if "control_image" in args:
        start_image = center_crop_resize(start_image, args["control_image"].size)

    args["image"] = start_image


def _flag_degraded(args: dict, preprocessor: str) -> None:
    """Surface classical-CV annotator stand-ins in the result envelope
    (VERDICT r03 weak #5): the hive/user must be able to see that the
    conditioning image came from an approximation, not the learned
    detector the reference runs."""
    from .pre_processors.controlnet import is_degraded_preprocessor

    if is_degraded_preprocessor(preprocessor):
        args.setdefault("degraded_preprocessors", []).append(preprocessor)


async def _preprocess_off_loop(image, preprocessor: str, device_identifier: str):
    """Model-backed preprocessors (depth etc.) load weights and jit-compile;
    run them in the default executor so the poll/upload loops keep breathing
    (the same boundary do_work uses for pipeline execution)."""
    from .pre_processors.controlnet import preprocess_image

    loop = asyncio.get_running_loop()
    return await loop.run_in_executor(
        None, preprocess_image, image, preprocessor, device_identifier
    )


async def format_controlnet_args(args, parameters, start_image, size, device_identifier):
    controlnet = parameters.pop("controlnet")
    control_image = await get_image(controlnet.get("control_image_uri"), size)
    args["save_preprocessed_input"] = True

    if is_not_blank(controlnet.get("qr_code_contents")):
        # a QR code overrides any provided control image
        control_image = await get_qrcode_image(controlnet["qr_code_contents"], size)
        if start_image is None:
            start_image = control_image
    elif start_image is not None and is_not_blank(controlnet.get("preprocessor")):
        control_image = await _preprocess_off_loop(
            start_image, controlnet["preprocessor"], device_identifier
        )
        _flag_degraded(args, controlnet["preprocessor"])
    elif control_image is not None and is_not_blank(controlnet.get("preprocessor")):
        control_image = await _preprocess_off_loop(
            control_image, controlnet["preprocessor"], device_identifier
        )
        _flag_degraded(args, controlnet["preprocessor"])
    elif control_image is None:
        control_image = start_image

    if control_image is None:
        raise ValueError("Controlnet specified but no control image provided")

    controlnet_parameters = controlnet.get("parameters", {})
    args["controlnet_model_type"] = controlnet_parameters.get(
        "controlnet_model_type", "ControlNetModel"
    )
    if "controlnet_prepipeline_type" in controlnet_parameters:
        args["controlnet_prepipeline_type"] = controlnet_parameters[
            "controlnet_prepipeline_type"
        ]
    args["controlnet_model_name"] = controlnet.get(
        "controlnet_model_name", "lllyasviel/control_v11p_sd15_canny"
    )
    args["controlnet_conditioning_scale"] = float(
        controlnet.get("controlnet_conditioning_scale", 1.0)
    )
    args["control_guidance_start"] = float(controlnet.get("control_guidance_start", 0.0))
    args["control_guidance_end"] = float(controlnet.get("control_guidance_end", 1.0))

    if args["model_name"] == "kandinsky-community/kandinsky-2-2-controlnet-depth":
        # kandinsky controlnet takes a depth "hint" instead of "image"
        from .pre_processors.depth_estimator import make_hint

        loop = asyncio.get_running_loop()
        args["hint"] = await loop.run_in_executor(None, make_hint, control_image)
    elif parameters.get("pipeline_type") in (
        "StableDiffusionControlNetPipeline",
        "StableDiffusionXLControlNetPipeline",
    ):
        args["image"] = control_image
    else:
        args["control_image"] = control_image


def _drop_unsupported(args: dict, parameters: dict) -> None:
    for arg in parameters.pop("unsupported_pipeline_arguments", []):
        args.pop(arg, None)


# identity / payload keys a hive-controlled parameters dict may FILL but
# never rewrite (pipeline_type/scheduler_type are popped explicitly by each
# formatter before the merge, so they never reach it)
_PROTECTED_ARGS = frozenset({
    "model_name", "prompt", "negative_prompt", "image", "mask_image",
    "control_image", "workflow", "id", "rng", "chipset",
})


def _merge_passthrough(args: dict, parameters: dict) -> None:
    """Passthrough with reference precedence — parameters win (model-pinned
    steps/scheduler knobs must override formatter defaults) — EXCEPT the
    protected identity keys, which parameters may fill but never rewrite.
    A formatter's neutral default (None/"", e.g. setdefault('prompt',''))
    counts as fillable, not as a value to protect."""
    for k, v in parameters.items():
        if k in _PROTECTED_ARGS and args.get(k) not in (None, ""):
            continue
        args[k] = v
