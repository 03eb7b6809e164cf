"""Stable-diffusion-family workload callback.

The TPU rebuild of reference swarm/diffusion/diffusion_func.py:15-167. Where
the reference re-runs `from_pretrained` on every job, this callback resolves
(model, pipeline_type, shape bucket) against the residency registry
(`..registry`) and invokes an already-compiled jitted program; weights stay
on-chip between jobs.
"""

from __future__ import annotations

from ..post_processors.output_processor import OutputProcessor
from ..registry import get_pipeline
from ..telemetry import Span


def _tiny_stand_in(model_name: str) -> str:
    """hermetic test hook (SURVEY §4): the tiny random-weight stand-in of
    the requested architecture family (`test_tiny_model` job parameter)."""
    from ..models.configs import model_family

    name = model_name.lower()
    if "pix2pix" in name or "ip2p" in name:
        return "test/tiny-pix2pix"  # keep the 8-channel edit arch
    if "flux" in name:
        return "test/tiny-flux-schnell" if "schnell" in name else "test/tiny-flux"
    if "kandinsky-3" in name or "kandinsky3" in name:
        return "test/tiny-kandinsky3"
    if "kandinsky" in name:
        if "controlnet" in name:
            return "test/tiny-kandinsky-controlnet"
        if "prior" in name:
            return "test/tiny-kandinsky-prior"
        return "test/tiny-kandinsky"
    if "cascade" in name:
        return (
            "test/tiny-cascade-prior" if "prior" in name else "test/tiny-cascade"
        )
    if "xl" in model_family(model_name):
        return "test/tiny-xl"
    return "test/tiny-sd"


def diffusion_callback(device_identifier: str, model_name: str, **kwargs):
    content_type = kwargs.pop("content_type", "image/jpeg")
    outputs = kwargs.pop("outputs", ["primary"])
    # stage-graph handoff (ISSUE 20): a denoise stage-job skips the
    # host-side decode tail and emits raw rows for its successor stage
    emit_raw = bool(kwargs.pop("emit_raw", False))
    # classical-stand-in annotators used for conditioning (job_arguments
    # _flag_degraded) surface in the result envelope, not just the logs
    degraded_preprocessors = kwargs.pop("degraded_preprocessors", None)

    if kwargs.pop("test_tiny_model", False):
        model_name = _tiny_stand_in(model_name)

    pipeline_type = kwargs.pop("pipeline_type", "DiffusionPipeline")

    # capacity gate BEFORE residency: a model that cannot fit this slice is
    # a fatal job error naming the chip count it needs; a batch that does
    # not fit is capped (the TPU-native analog of the reference's
    # offload/slicing knobs — chips/requirements.py)
    from ..chips.requirements import check_capacity

    chipset = kwargs.get("chipset")
    requested_batch = int(kwargs.get("num_images_per_prompt", 1) or 1)
    # canvas: explicit dims, else the start image's (img2img/inpaint jobs
    # drop height/width during formatting), else the 1024 family default
    from ..chips.requirements import default_canvas

    height = kwargs.get("height")
    width = kwargs.get("width")
    image = kwargs.get("image")
    if (height is None or width is None) and image is not None:
        probe = image[0] if isinstance(image, list) else image
        if hasattr(probe, "size"):
            width, height = probe.size
    height = int(height or default_canvas(model_name))
    width = int(width or height)
    batch_capped = None
    if chipset is not None:
        allowed = check_capacity(
            chipset, model_name, requested_batch, height, width
        )
        if allowed < requested_batch:
            kwargs["num_images_per_prompt"] = allowed
            batch_capped = {"requested": requested_batch, "served": allowed}

    # class-aware slice geometry (ISSUE 12): the worker attaches these
    # for interactive solos on multi-chip slices; forwarded only to
    # pipelines that understand per-pass mesh views (SD family) so a
    # kandinsky/cascade job routed through this callback is unaffected
    geometry = kwargs.pop("geometry", None)
    reshard_probe = kwargs.pop("reshard_probe", None)

    # mid-pass durability seam (ISSUE 18): the worker attaches these for
    # checkpoint-armed solos; forwarded only to pipelines whose chunked
    # runner exposes the boundary (`supports_checkpoint`), so other
    # families routed through this callback run untouched
    ckpt_kwargs = {
        key: kwargs.pop(key)
        for key in ("checkpoint_every_chunks", "preview_every_chunks",
                    "checkpoint_cb", "preview_cb", "resume")
        if key in kwargs
    }

    # set-up seconds as their own figure: ~0 when the model is resident,
    # the weight load (or seeded init) + placement when this job built it
    with Span("load") as load:
        pipeline = get_pipeline(
            model_name, pipeline_type=pipeline_type, chipset=chipset
        )
    if geometry is not None and hasattr(pipeline, "resolve_geometry"):
        kwargs["geometry"] = geometry
        if reshard_probe is not None:
            kwargs["reshard_probe"] = reshard_probe
    if ckpt_kwargs and getattr(pipeline, "supports_checkpoint", False):
        kwargs.update(ckpt_kwargs)
    images, pipeline_config = pipeline.run(pipeline_type=pipeline_type, **kwargs)
    pipeline_config.setdefault("timings", {})["load_s"] = round(
        load.elapsed, 3)
    if batch_capped:
        pipeline_config["batch_capped"] = batch_capped
    if degraded_preprocessors:
        pipeline_config["degraded_preprocessors"] = degraded_preprocessors

    if emit_raw:
        from .stages import pack_raw

        with Span("handoff", pipeline_config.setdefault("timings", {})):
            handoff = {"raw": pack_raw(images)}
        return handoff, pipeline_config

    # stage "decode": host-side postprocess after the on-device decode
    # that ends the denoise program, parent of "safety" (real NSFW
    # detection on the decoded pixels — reference envelope parity:
    # swarm/worker.py:166; auxiliary, never fails the job). The pass
    # ends here: the grid composite + encode ("artifact_encode") needs
    # no chip, so the images leave unpackaged
    with Span("decode", pipeline_config.setdefault("timings", {})):
        nsfw, checked = _flag(images)
        pipeline_config["nsfw"] = nsfw
        pipeline_config["nsfw_checked"] = checked
    return Unpackaged(images, outputs, content_type), pipeline_config


def _flag(images):
    """Span "safety": the NSFW pass over the decoded pixels."""
    from ..pipelines.safety import flag_images

    with Span("safety"):
        return flag_images(images)


def _package(images, outputs, content_type):
    """Grid composite, PNG/JPEG encode, base64 and hash of one job's
    images: the hive `artifacts` dict."""
    processor = OutputProcessor(outputs, content_type)
    processor.add_outputs(images)
    return processor.get_results()


class Unpackaged:
    """What the two callbacks return in the place of a job's `artifacts`:
    its images (host memory only — the read-back ended inside the pass)
    and what `_package` needs to turn them into one. Packaging needs no
    chip, so it is the caller's to run once the slice is free; the worker
    does, on a host thread, while the slice's next pass runs."""

    __slots__ = ("images", "outputs", "content_type")

    def __init__(self, images, outputs, content_type):
        self.images = images
        self.outputs = outputs
        self.content_type = content_type

    def package(self, spans: list | None = None) -> dict:
        """Span "artifact_encode" on thread "host": the pass's trace has
        closed, so the span joins `spans` (the job's own
        `pipeline_config.spans`) directly."""
        with Span("artifact_encode", thread="host", spans=spans):
            return _package(self.images, self.outputs, self.content_type)


def packaged(artifacts):
    """A callback's artifacts as the hive's dict, for whoever drives one
    without the worker (smoke, goldens, tests): what was `Unpackaged` is
    packaged here and now."""
    if isinstance(artifacts, Unpackaged):
        return artifacts.package()
    return artifacts


def diffusion_batched_callback(device_identifier: str, requests: list[dict]):
    """Cross-job coalesced txt2img/img2img (batching.py design): every
    request in `requests` shares one coalesce key — same model, canvas,
    steps, scheduler, guidance, workflow (and strength for img2img) — and
    differs only per-row (prompt, negative, seed, start image, image
    count). Executes the group in as few padded jitted denoise+decode
    passes as capacity allows (usually one) and returns per-request
    (artifacts, pipeline_config) envelopes in order, the artifacts
    `Unpackaged` as `diffusion_callback`'s are.

    Raising here (capacity, weights) is fine: the worker falls back to
    the single-job path, which reproduces the error per job with the
    existing fatal/transient attribution.
    """
    from ..chips.requirements import coalesced_fit, default_canvas
    from ..pipelines.common import chunk_by_rows

    shared = requests[0]
    model_name = shared["model_name"]
    if shared.get("test_tiny_model", False):
        model_name = _tiny_stand_in(model_name)
    pipeline_type = shared.get("pipeline_type", "DiffusionPipeline")
    chipset = shared.get("chipset")
    # shared ControlNet (ISSUE 13 second rung): coalesce_key guarantees
    # every member carries the IDENTICAL branch + control image, so the
    # group conditions on ONE image — for txt2img-ControlNet wire names
    # the formatter delivered it as `image`, which therefore must not be
    # mistaken for an img2img start image
    cn_name = shared.get("controlnet_model_name")
    control_image = None
    if cn_name:
        control_image = (shared.get("control_image")
                         if shared.get("control_image") is not None
                         else shared.get("image"))
    # None flows through to run_batched, which defaults to the pipeline's
    # own default_size (or, for img2img, the shared start-image canvas) —
    # the same resolution the single path's run() does; the canvas below
    # is only the capacity gate's estimate
    height = shared.get("height")
    width = shared.get("width")
    i2i = shared.get("image") is not None and not cn_name
    if (height is None or width is None) and cn_name \
            and control_image is not None:
        est_w, est_h = control_image.size
    elif (height is None or width is None) and i2i:
        # img2img formatting pops height/width after resizing every start
        # image to the shared explicit canvas — read it back off the image
        est_w, est_h = shared["image"].size
    else:
        est_h = int(height or default_canvas(model_name))
        est_w = int(width or est_h)
    if cn_name and (height is None or width is None):
        # solo ControlNet passes size the canvas to the control image;
        # the shared group must reproduce that, not the family default
        height, width = est_h, est_w
    steps = int(shared.get("num_inference_steps", 30))
    guidance = float(shared.get("guidance_scale", 7.5))
    scheduler_type = shared.get("scheduler_type", "DPMSolverMultistepScheduler")
    karras = bool(shared.get("use_karras_sigmas", False))
    # NB `or`-defaulting would silently rewrite an explicit strength of
    # 0.0 and make the coalesced output diverge from the solo path's
    raw_strength = shared.get("strength")
    strength = 0.75 if raw_strength is None else float(raw_strength)

    # per-request envelope parameters + the run_batched row spec
    envelopes = []
    row_specs = []
    counts = []
    for r in requests:
        envelopes.append({
            "content_type": r.get("content_type", "image/jpeg"),
            "outputs": r.get("outputs", ["primary"]),
            # stage-graph denoise members (ISSUE 20) hand off raw rows;
            # the coalesce key's stage element keeps them from mixing
            # with monolithic jobs, so a group is all-raw or all-packaged
            "emit_raw": bool(r.get("emit_raw")),
        })
        n = max(int(r.get("num_images_per_prompt", 1) or 1), 1)
        counts.append(n)
        xattn = r.get("cross_attention_kwargs") or {}
        row_specs.append({
            "prompt": r.get("prompt", ""),
            "negative_prompt": r.get("negative_prompt", ""),
            "rng": r.get("rng"),
            "num_images_per_prompt": n,
            "image": None if cn_name else r.get("image"),
            # per-row adapter (ISSUE 13): the resolved reference becomes
            # a slot in the batched program's stacked low-rank factors;
            # scale rides per row like the reference's
            # cross_attention_kwargs.scale
            "lora": r.get("lora"),
            "lora_scale": float(r.get("lora_scale",
                                      xattn.get("scale", 1.0)) or 0.0),
            # per-row cancel token key (ISSUE 10): run_batched probes the
            # cancel registry for this id at denoise chunk boundaries
            "job_id": r.get("id"),
        })

    # capacity admits the COALESCED batch, capping rather than rejecting:
    # a group bigger than one pass splits into passes that fit (the
    # batching scheduler already sized groups with coalesce_rows_limit,
    # so more than one chunk means the estimate moved under us)
    max_rows = sum(counts)
    if chipset is not None:
        max_rows = coalesced_fit(chipset, model_name, max_rows, est_h, est_w)
    # per-request cap, mirroring the single path's check_capacity clamp:
    # a request bigger than one pass serves the batch that fits, recorded
    # in its envelope, never silently
    capped: dict[int, dict] = {}
    for i, n in enumerate(counts):
        if n > max_rows:
            capped[i] = {"requested": n, "served": max_rows}
            counts[i] = max_rows
            row_specs[i]["num_images_per_prompt"] = max_rows

    with Span("load"):  # the group's one model look-up
        pipeline = get_pipeline(
            model_name, pipeline_type=pipeline_type, chipset=chipset
        )
    cn_kwargs = {}
    if cn_name:
        cn_kwargs = {
            "controlnet_model_name": cn_name,
            "control_image": control_image,
            "controlnet_conditioning_scale": float(
                shared.get("controlnet_conditioning_scale", 1.0)),
            "control_guidance_start": float(
                shared.get("control_guidance_start", 0.0)),
            "control_guidance_end": float(
                shared.get("control_guidance_end", 1.0)),
        }
    text_kwargs = {}
    if shared.get("max_sequence_length") is not None:
        # a flux group's shared T5 token budget (a coalesce-key dimension)
        text_kwargs["max_sequence_length"] = int(
            shared["max_sequence_length"])
    results = []
    chunks = list(chunk_by_rows(counts, max_rows))
    if len(chunks) > 1:
        # a group split across passes surfaces every adapter refusal
        # up front: a LATER chunk's refusal would discard earlier
        # chunks' finished denoise work and re-count their row metrics
        # on the worker's re-batch
        prescan = getattr(pipeline, "prescan_adapter_chunks", None)
        if prescan is not None:
            prescan([row_specs[s:e] for s, e in chunks])
    for start, end in chunks:
        results.extend(pipeline.run_batched(
            row_specs[start:end],
            height=height,
            width=width,
            num_inference_steps=steps,
            guidance_scale=guidance,
            scheduler_type=scheduler_type,
            use_karras_sigmas=karras,
            pipeline_type=pipeline_type,
            strength=strength,
            **cn_kwargs,
            **text_kwargs,
        ))

    out = []
    for i, ((images, pipeline_config), env) in enumerate(zip(results, envelopes)):
        # classical-CV annotator stand-ins surface per envelope exactly
        # like the solo path (the conditioning image is an approximation)
        if requests[i].get("degraded_preprocessors"):
            pipeline_config["degraded_preprocessors"] = \
                requests[i]["degraded_preprocessors"]
        if pipeline_config.get("cancelled"):
            # hive-revoked mid-denoise: no safety pass, no packaging —
            # the worker drops this slot (no envelope is ever delivered)
            out.append((None, pipeline_config))
            continue
        if env["emit_raw"]:
            from .stages import pack_raw

            with Span("handoff", pipeline_config.setdefault("timings", {})):
                handoff = {"raw": pack_raw(images)}
            pipeline_config["batched_with"] = len(requests)
            if i in capped:
                pipeline_config["batch_capped"] = capped[i]
            out.append((handoff, pipeline_config))
            continue
        with Span("decode", pipeline_config.setdefault("timings", {})):
            nsfw, checked = _flag(images)
            pipeline_config["nsfw"] = nsfw
            pipeline_config["nsfw_checked"] = checked
            pipeline_config["batched_with"] = len(requests)
            if i in capped:
                pipeline_config["batch_capped"] = capped[i]
        out.append((Unpackaged(images, env["outputs"], env["content_type"]),
                    pipeline_config))
    return out


# the worker runs a group of jobs that all formatted to one callback as one
# pass through that callback's `batched` (worker.py synchronous_do_batch)
diffusion_callback.batched = diffusion_batched_callback


def deepfloyd_if_callback(device_identifier: str, model_name: str, **kwargs):
    """DeepFloyd IF jobs dispatch early (job_arguments.py:78-81, mirroring
    reference :49-50), so the raw job `parameters` still ride in kwargs.
    The reference's own IF path (diffusion_func_if.py:13-69) shipped broken
    — random prompt embeds, NameError at :62; this cascade works."""
    parameters = kwargs.pop("parameters", {}) or {}
    content_type = kwargs.pop("content_type", "image/jpeg")
    outputs = kwargs.pop("outputs", ["primary"])
    if parameters.pop("test_tiny_model", False) or kwargs.pop(
        "test_tiny_model", False
    ):
        model_name = "test/tiny-if"
    pipeline_type = parameters.pop("pipeline_type", "IFPipeline")
    kwargs.update(parameters)
    kwargs.pop("start_image_uri", None)  # base stage is txt2img-only

    pipeline = get_pipeline(
        model_name, pipeline_type=pipeline_type, chipset=kwargs.get("chipset")
    )
    images, pipeline_config = pipeline.run(pipeline_type=pipeline_type, **kwargs)

    from ..pipelines.safety import flag_images

    nsfw, checked = flag_images(images)
    pipeline_config["nsfw"] = nsfw
    pipeline_config["nsfw_checked"] = checked

    processor = OutputProcessor(outputs, content_type)
    processor.add_outputs(images)
    return processor.get_results(), pipeline_config
