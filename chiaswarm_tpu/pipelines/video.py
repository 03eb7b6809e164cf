"""Video diffusion pipelines: txt2vid, img2vid, vid2vid.

Reference swarm/video/* rebuilt TPU-first:
- txt2vid (tx2vid.py:15-81): motion-module UNet, whole clip denoised in ONE
  jitted scan (frames ride the batch dim), VAE-decoded per frame, exported
  mp4/webm/gif.
- img2vid (img2vid.py:14-38): owned by pipelines/svd.py (SVD) and
  pipelines/i2vgen.py (I2VGenXL, the workflow default).
- vid2vid (pix2pix.py:14-191): the reference edits frames one at a time in
  a Python loop (up to 100 sequential pipeline calls, :47-68); here frames
  batch through the image pipeline's jitted program in fixed-size chunks.
"""

from __future__ import annotations

import logging
import os
import time
import zlib
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
from PIL import Image

from ..models import configs as cfgs
from ..models.clip import CLIPTextEncoder
from ..models.tokenizer import load_tokenizer
from ..models.unet2d import UNet2DConfig
from ..models.vae import AutoencoderKL
from ..models.video_unet import VideoUNet, VideoUNetConfig
from ..post_processors.output_processor import make_result
from ..registry import register_family
from ..schedulers import get_scheduler
from ..toolbox.video_helpers import (
    download_video,
    export_frames,
    first_frame_thumbnail,
    split_video_frames,
)

logger = logging.getLogger(__name__)

DEFAULT_FPS = 8
VID2VID_CHUNK = 8  # frames per batched img2img program call

from ..weights import DEFAULT_MOTION_ADAPTER  # noqa: F401  (job default)


def _model_dir(model_name: str):
    from ..weights import model_dir_for

    return model_dir_for(model_name)


def _load_converted_video(model_name: str, motion_adapter: str | None,
                          model_dir=None):
    """-> {"unet","text","vae","model_dir"} or None. AnimateDiff's
    composition: an SD1.5-family spatial UNet checkpoint overlaid with a
    MotionAdapter's temporal modules, plus the checkpoint's CLIP/VAE —
    all-or-nothing (spatial weights with random temporal modules are a
    no-op video model; the reverse hallucinates)."""
    name = model_name.lower()
    if "tiny" in name or name.startswith("test/"):
        return None
    d = model_dir if model_dir is not None else _model_dir(model_name)
    adapter_dir = _model_dir(motion_adapter or DEFAULT_MOTION_ADAPTER)
    if d is None:
        return None
    from ..models.conversion import (
        convert_clip,
        convert_vae,
        convert_video_unet,
        load_torch_state_dict,
    )
    from ..weights import MissingWeightsError

    try:
        unet_state = load_torch_state_dict(d, "unet")
        if any("temp_convs" in k for k in unet_state):
            # zeroscope / modelscope text-to-video: a native
            # UNet3DConditionModel checkpoint (temporal convs +
            # frame-attention), geometry inferred from the state dict
            import json

            from ..models.conversion import (
                convert_unet3d,
                infer_unet3d_config,
            )

            from ..models.clip import CLIPTextConfig
            from ..models.conversion import infer_vae_config

            def read_json(sub):
                p = d / sub / "config.json"
                return json.loads(p.read_text()) if p.is_file() else {}

            unet3d_cfg = infer_unet3d_config(unet_state, read_json("unet"))
            # zeroscope's text tower is CLIP ViT-H (1024), not the SD1.5
            # default — geometry from the checkpoint's own config.json
            tj = read_json("text_encoder")
            base = CLIPTextConfig()
            clip_cfg = CLIPTextConfig(
                vocab_size=int(tj.get("vocab_size", base.vocab_size)),
                hidden_size=int(tj.get("hidden_size", base.hidden_size)),
                num_layers=int(
                    tj.get("num_hidden_layers", base.num_layers)
                ),
                num_heads=int(
                    tj.get("num_attention_heads", base.num_heads)
                ),
                max_positions=int(
                    tj.get("max_position_embeddings", base.max_positions)
                ),
                hidden_act=str(tj.get("hidden_act", base.hidden_act)),
            )
            vae_state = load_torch_state_dict(d, "vae")
            return {
                "unet3d": convert_unet3d(unet_state),
                "unet3d_cfg": unet3d_cfg,
                "clip_cfg": clip_cfg,
                "vae_cfg": infer_vae_config(vae_state, read_json("vae")),
                "text": convert_clip(load_torch_state_dict(d, "text_encoder")),
                "vae": convert_vae(vae_state),
                "model_dir": d,
            }
        if adapter_dir is None:
            raise FileNotFoundError(
                f"motion adapter {motion_adapter or DEFAULT_MOTION_ADAPTER} "
                "not downloaded"
            )
        unet = convert_video_unet(
            unet_state,
            load_torch_state_dict(adapter_dir),
        )
        text = convert_clip(load_torch_state_dict(d, "text_encoder"))
        vae = convert_vae(load_torch_state_dict(d, "vae"))
    except (FileNotFoundError, OSError):
        return None
    except Exception as e:
        raise MissingWeightsError(
            f"checkpoint under {d} could not be converted for "
            f"'{model_name}': {e}"
        ) from e
    return {"unet": unet, "text": text, "vae": vae, "model_dir": d}


def _replace(cfg: UNet2DConfig, **kw) -> UNet2DConfig:
    import dataclasses

    return dataclasses.replace(cfg, **kw)


def _video_configs(model_name: str):
    name = model_name.lower()
    if "tiny" in name or name.startswith("test/"):
        return (
            VideoUNetConfig(base=cfgs.TINY_UNET, num_frames=8),
            cfgs.TINY_CLIP,
            cfgs.TINY_VAE,
            64,
        )
    # AnimateDiff / zeroscope / damo / SVD ride SD1.5-geometry UNets
    return (
        VideoUNetConfig(base=cfgs.SD15_UNET, num_frames=16),
        cfgs.SD15_CLIP,
        cfgs.SD_VAE,
        512,
    )


class VideoPipeline:
    """Resident motion-module pipeline; serves txt2vid and img2vid."""

    def __init__(self, model_name: str, chipset=None,
                 allow_random_init: bool = False, motion_adapter=None):
        from ..weights import require_weights_present

        self.model_name = model_name
        self.chipset = chipset
        # txt2vid serves real AnimateDiff weights (spatial SD1.5 checkpoint
        # + motion adapter) or a native UNet3D checkpoint; img2vid is owned
        # by pipelines/svd.py and pipelines/i2vgen.py
        self._loaded_adapter = motion_adapter or DEFAULT_MOTION_ADAPTER
        self._converted = _load_converted_video(model_name, motion_adapter)
        if self._converted is None:
            require_weights_present(
                model_name, None, allow_random_init,
                component="video model",
                hint="Video weights were not found under the model root; "
                     "AnimateDiff serving needs the base SD checkpoint AND "
                     "the motion adapter downloaded (initialize --download).",
            )
        video_cfg, clip_cfg, vae_cfg, self.default_size = _video_configs(model_name)
        if self._converted and "clip_cfg" in self._converted:
            # native UNet3D checkpoints carry their own tower geometry
            clip_cfg = self._converted["clip_cfg"]
            vae_cfg = self._converted["vae_cfg"]
        self.config = video_cfg
        self.latent_factor = 2 ** (len(vae_cfg.block_out_channels) - 1)

        on_tpu = jax.default_backend() == "tpu"
        self.dtype = jnp.bfloat16 if on_tpu else jnp.float32
        self.unet3d = bool(self._converted) and "unet3d" in self._converted
        if self.unet3d:
            # native zeroscope/modelscope UNet3D checkpoint: motion-adapter
            # and motion-LoRA overlays do not apply to this graph
            from ..models.unet3d import UNet3DConditionModel

            self.unet = UNet3DConditionModel(
                self._converted["unet3d_cfg"], dtype=self.dtype
            )
        else:
            self.unet = VideoUNet(video_cfg, dtype=self.dtype)
        self.text_encoder = CLIPTextEncoder(clip_cfg, dtype=self.dtype)
        self.vae = AutoencoderKL(vae_cfg, dtype=self.dtype)
        self.tokenizer = load_tokenizer(
            self._converted["model_dir"] if self._converted else None,
            vocab_size=clip_cfg.vocab_size,
        )

        t0 = time.perf_counter()
        self.params = self._init_params()
        logger.info(
            "%s video pipeline resident in %.1fs", model_name,
            time.perf_counter() - t0,
        )
        # insertion-ordered so the program_cache_max bound below can evict
        # least-recently-used first (SW007; same knob as the SD family)
        self._programs: OrderedDict = OrderedDict()
        # param trees with motion-LoRAs merged, keyed by (ref, scale);
        # bounded — each entry pins a full UNet copy
        self._lora_cache: OrderedDict[tuple, dict] = OrderedDict()

    def _adapter_params(self, params: dict, motion_adapter) -> dict:
        """Params with the REQUESTED adapter's temporal modules overlaid
        (jobs may pin e.g. AnimateLCM instead of the resident default)."""
        name = (
            motion_adapter.get("model_name")
            if isinstance(motion_adapter, dict)
            else str(motion_adapter)
        )
        if not name or name == self._loaded_adapter:
            return params
        key = ("adapter", name)
        if key in self._lora_cache:
            self._lora_cache.move_to_end(key)
            return self._lora_cache[key]
        from ..models.conversion import (
            convert_motion_adapter,
            load_torch_state_dict,
        )
        from ..weights import MissingWeightsError

        d = _model_dir(name)
        if d is None:
            raise MissingWeightsError(
                f"motion adapter '{name}' is not downloaded; run "
                f"initialize --download"
            )
        motion = convert_motion_adapter(load_torch_state_dict(d))
        cast = lambda x: jnp.asarray(x, self.dtype)
        unet = dict(params["unet"])
        for k, sub in motion.items():
            unet[k] = jax.tree_util.tree_map(cast, sub)
        out = dict(params)
        out["unet"] = unet
        self._lora_cache[key] = out
        while len(self._lora_cache) > 2:
            self._lora_cache.popitem(last=False)
        return out

    def _lora_params(self, base_params: dict, lora: dict, scale: float) -> dict:
        """Base params with a motion-LoRA merged into the video UNet
        (reference tx2vid.py:26-48 loads AnimateDiff motion adapters /
        LoRA adapter weights per job; here the merge happens once and the
        merged tree stays resident)."""
        key = (lora.get("lora"), lora.get("weight_name"),
               lora.get("subfolder"), round(scale, 4))
        if key in self._lora_cache:
            self._lora_cache.move_to_end(key)
            return self._lora_cache[key]
        from ..models.lora import resolve_and_merge

        merged_unet = resolve_and_merge(
            base_params["unet"], lora, scale, self.model_name
        )
        cast = lambda x: jnp.asarray(x, self.dtype)
        out = dict(base_params)
        out["unet"] = jax.tree_util.tree_map(cast, merged_unet)
        self._lora_cache[key] = out
        while len(self._lora_cache) > 2:
            self._lora_cache.popitem(last=False)
        return out

    def _init_params(self):
        rng = jax.random.key(zlib.crc32(self.model_name.encode()))
        k1, k2, k3 = jax.random.split(rng, 3)
        frames = self.config.num_frames
        hw = 2 ** max(len(self.config.base.block_out_channels), 3)
        unet_args = (
            jnp.zeros((frames, hw, hw, self.config.base.in_channels)),
            jnp.zeros((frames,)),
            jnp.zeros((frames, 77, self.config.base.cross_attention_dim)),
        )
        with jax.default_device(jax.local_devices(backend="cpu")[0]):
            if self._converted is not None:
                from ..models.conversion import checked_converted as _checked_converted

                if self.unet3d:
                    import functools

                    from ..models.conversion import (
                        assert_tree_shapes_match,
                    )
                    from ..weights import MissingWeightsError

                    cfg3d = self._converted["unet3d_cfg"]
                    # num_frames is a STATIC python int (reshape factor):
                    # partial it so eval_shape never traces it
                    expected = jax.eval_shape(
                        functools.partial(self.unet.init, num_frames=frames),
                        k1,
                        jnp.zeros((frames, hw, hw, cfg3d.in_channels)),
                        jnp.zeros((frames,)),
                        jnp.zeros((frames, 77, cfg3d.cross_attention_dim)),
                    )["params"]
                    try:
                        assert_tree_shapes_match(
                            self._converted["unet3d"], expected, prefix="unet"
                        )
                    except ValueError as e:
                        raise MissingWeightsError(str(e)) from None
                    unet_params = self._converted["unet3d"]
                else:
                    unet_params = _checked_converted(
                        self.unet, unet_args, self._converted["unet"],
                        "unet", k1,
                    )
                text_params = _checked_converted(
                    self.text_encoder, (jnp.zeros((1, 77), jnp.int32),),
                    self._converted["text"], "text", k2,
                )
                vae_params = _checked_converted(
                    self.vae,
                    (jnp.zeros((1, hw * self.latent_factor,
                                hw * self.latent_factor, 3)),),
                    self._converted["vae"], "vae", k3,
                )
                logger.info(
                    "loaded converted AnimateDiff weights for %s",
                    self.model_name,
                )
            else:
                unet_params = self.unet.init(k1, *unet_args)["params"]
                text_params = self.text_encoder.init(
                    k2, jnp.zeros((1, 77), jnp.int32)
                )["params"]
                vae_params = self.vae.init(
                    k3,
                    jnp.zeros(
                        (1, hw * self.latent_factor, hw * self.latent_factor, 3)
                    ),
                )["params"]
        cast = lambda x: jnp.asarray(x, self.dtype)
        return jax.tree_util.tree_map(
            cast, {"unet": unet_params, "text": text_params, "vae": vae_params}
        )

    def release(self):
        self.params = None
        self._programs.clear()
        self._lora_cache.clear()

    def _program(self, key):
        if key in self._programs:
            self._programs.move_to_end(key)
            return self._programs[key]
        lh, lw, frames, steps, sched_name = key
        scheduler = get_scheduler(sched_name)
        schedule = scheduler.schedule(steps)

        def run(params, latents, context, guidance_scale, rng):
            """latents [F, lh, lw, 4]; context [2, 77, D] = (uncond, cond)."""
            latents = latents * jnp.asarray(schedule.init_noise_sigma, latents.dtype)
            state = scheduler.init_state(latents.shape, latents.dtype)
            f = latents.shape[0]
            ctx2 = jnp.concatenate(
                [
                    jnp.broadcast_to(context[:1], (f,) + context.shape[1:]),
                    jnp.broadcast_to(context[1:2], (f,) + context.shape[1:]),
                ],
                axis=0,
            ).astype(self.dtype)

            def body(carry, i):
                latents, state = carry
                inp = scheduler.scale_model_input(schedule, latents, i)
                model_in = jnp.concatenate([inp, inp], axis=0).astype(self.dtype)
                t = jnp.broadcast_to(
                    jnp.asarray(schedule.timesteps)[i], (model_in.shape[0],)
                )
                out = self.unet.apply(
                    {"params": params["unet"]}, model_in, t, ctx2,
                    num_frames=f,
                ).astype(jnp.float32)
                out_u, out_c = jnp.split(out, 2, axis=0)
                out = out_u + guidance_scale * (out_c - out_u)
                noise = jax.random.normal(
                    jax.random.fold_in(rng, i), latents.shape, jnp.float32
                )
                state, latents = scheduler.step(schedule, state, i, latents, out, noise)
                return (latents, state), ()

            (latents, _), _ = jax.lax.scan(
                body, (latents.astype(jnp.float32), state), jnp.arange(steps)
            )
            return self.vae.apply(
                {"params": params["vae"]}, latents.astype(self.dtype),
                method=self.vae.decode,
            ).astype(jnp.float32)

        program = jax.jit(run)
        self._programs[key] = program
        from .common import PROGRAM_EVICTED, program_cache_cap

        cap = program_cache_cap()
        while cap and len(self._programs) > cap:
            self._programs.popitem(last=False)
            PROGRAM_EVICTED.inc(kind="program")
        return program

    def run(self, prompt="", negative_prompt="", image=None, **kwargs):
        # snapshot once: a concurrent registry eviction nulls self.params
        params = self.params
        if params is None:
            raise Exception(f"pipeline {self.model_name} was evicted; resubmit")
        timings = {}
        # requested AnimateDiff/LCM motion adapter (reference tx2vid.py:26-36
        # loads it onto the torch UNet per job). With converted weights the
        # requested adapter's temporal modules overlay the resident tree;
        # tiny/random pipelines record the request for observability.
        motion_adapter = kwargs.pop("motion_adapter", None)
        ignored_adapters = []
        if motion_adapter is not None and self._converted is not None:
            if self.unet3d:
                # a native UNet3D graph has no motion modules to overlay —
                # surface the ignored request instead of silently echoing
                # it as applied
                ignored_adapters.append(
                    f"motion_adapter:{motion_adapter}"
                )
                motion_adapter = None
            else:
                params = self._adapter_params(params, motion_adapter)
        lora = kwargs.pop("lora", None)
        xattn_kwargs = kwargs.pop("cross_attention_kwargs", {}) or {}
        lora_scale = float(
            kwargs.pop("lora_scale", xattn_kwargs.get("scale", 1.0))
        )
        if lora is not None:
            if self.unet3d:
                ignored_adapters.append(f"motion_lora:{lora}")
            else:
                params = self._lora_params(params, lora, lora_scale)
        steps = int(kwargs.pop("num_inference_steps", 25))
        guidance_scale = float(kwargs.pop("guidance_scale", 7.5))
        # AnimateDiff's positional table caps the clip length; the native
        # UNet3D graph has no positional embedding — its bound is memory,
        # budgeted generously here
        max_frames = 48 if self.unet3d else self.config.num_frames
        requested_frames = int(
            kwargs.pop("num_frames", 24 if self.unet3d
                       else self.config.num_frames)
        )
        frames = min(requested_frames, max_frames)
        frames_truncated = frames < requested_frames
        fps = int(kwargs.pop("fps", DEFAULT_FPS))
        scheduler_type = kwargs.pop(
            "scheduler_type", "EulerAncestralDiscreteScheduler"
        )
        rng = kwargs.pop("rng", None)
        if rng is None:
            rng = jax.random.key(0)
        height = int(kwargs.pop("height", None) or self.default_size)
        width = int(kwargs.pop("width", None) or self.default_size)
        height, width = (max(64, (d // 64) * 64) for d in (height, width))
        lh, lw = height // self.latent_factor, width // self.latent_factor

        ids = jnp.asarray(self.tokenizer([negative_prompt, prompt]))
        context = self.text_encoder.apply(
            {"params": params["text"]}, ids
        )["hidden_states"]

        rng, init_rng, step_rng = jax.random.split(rng, 3)
        noise = jax.random.normal(init_rng, (frames, lh, lw, 4), jnp.float32)

        key = (lh, lw, frames, steps, scheduler_type)
        t0 = time.perf_counter()
        program = self._program(key)
        from ..ops.platform import mesh_scope

        mesh = self.chipset.mesh() if self.chipset is not None else None
        with mesh_scope(mesh):
            pixels = jax.block_until_ready(
                program(params, noise, context, jnp.float32(guidance_scale),
                        step_rng)
            )
        timings["denoise_decode_s"] = round(time.perf_counter() - t0, 3)

        arr = np.clip(np.asarray(pixels, np.float32) * 0.5 + 0.5, 0, 1)
        pil_frames = [
            Image.fromarray((f * 255).round().astype(np.uint8)) for f in arr
        ]
        config = {
            "model": self.model_name,
            "frames": frames,
            "fps": fps,
            "steps": steps,
            "size": [width, height],
            "scheduler": scheduler_type,
            **(
                {"motion_adapter": str(motion_adapter)}
                if motion_adapter is not None
                else {}
            ),
            **({"ignored_adapters": ignored_adapters}
               if ignored_adapters else {}),
            **({"frames_truncated": True} if frames_truncated else {}),
            "timings": timings,
        }
        return pil_frames, config


@register_family("animatediff")
def _build_animatediff(model_name, chipset, **variant):
    return VideoPipeline(model_name, chipset, **variant)


# "svd" is owned by pipelines/svd.py and "i2vgenxl" by pipelines/i2vgen.py
# (true architectures with conversion).


def _frames_artifact(frames, fps, content_type):
    buffer, actual_type = export_frames(frames, content_type, fps)
    return make_result(buffer, first_frame_thumbnail(frames), actual_type)


def run_txt2vid(device_identifier: str, model_name: str, **kwargs):
    """txt2vid job -> video artifact (reference swarm/video/tx2vid.py:15-81)."""
    from ..registry import get_pipeline

    content_type = kwargs.pop("content_type", "video/mp4")
    kwargs.pop("outputs", None)
    if kwargs.pop("test_tiny_model", False):
        model_name = "test/tiny-video"
    # hive txt2vid jobs often say "DiffusionPipeline" (reference resolved it
    # reflectively); the workflow itself pins the video family
    from ..registry import PIPELINE_FAMILIES

    ptype = kwargs.pop("pipeline_type", "AnimateDiffPipeline")
    if PIPELINE_FAMILIES.get(ptype) != "animatediff":
        ptype = "AnimateDiffPipeline"
    chipset = kwargs.pop("chipset", None)
    pipeline = get_pipeline(model_name, pipeline_type=ptype, chipset=chipset)

    # motion-LoRA refs may ride parameters as bare strings — resolve them
    # through the same path resolver job-level loras use
    lora = kwargs.pop("lora", None)
    if isinstance(lora, str):
        from ..loras import Loras
        from ..settings import load_settings

        lora = Loras(load_settings().lora_root_dir).resolve_lora(lora)
    if lora is not None:
        kwargs["lora"] = lora

    # zeroscope-style upscale pass (reference tx2vid.py:66-76 chains
    # zeroscope_v2_XL over the produced clip): the learned 2x upscaler runs
    # over the frames; resolved BEFORE the denoise so missing weights fail
    # fast
    upscaler = None
    if kwargs.pop("upscale", False):
        from .upscale import upscaler_name_for

        upscaler = get_pipeline(
            upscaler_name_for(model_name),
            pipeline_type="StableDiffusionLatentUpscalePipeline",
            chipset=chipset,
        )

    prompt = kwargs.get("prompt", "")
    frames, config = pipeline.run(**kwargs)
    if upscaler is not None:
        t0 = time.perf_counter()
        frames = upscaler.upscale(frames, prompt=prompt)
        config.setdefault("timings", {})["upscale_s"] = round(
            time.perf_counter() - t0, 3
        )
        config["upscaled"] = True
        config["output_size"] = [frames[0].width, frames[0].height]
    return {"primary": _frames_artifact(frames, config["fps"], content_type)}, config


def run_img2vid(device_identifier: str, model_name: str, **kwargs):
    """img2vid job (reference swarm/video/img2vid.py:14-38)."""
    from ..registry import get_pipeline

    content_type = kwargs.pop("content_type", "video/mp4")
    kwargs.pop("outputs", None)
    if kwargs.pop("test_tiny_model", False):
        model_name = "test/tiny-video-svd"
    pipeline = get_pipeline(
        model_name,
        pipeline_type=kwargs.pop("pipeline_type", "I2VGenXLPipeline"),
        chipset=kwargs.pop("chipset", None),
    )
    # decode_chunk_size is a CUDA-memory knob with no TPU analog (the whole
    # decode is one program); SVD's micro-conditioning keys pass through
    kwargs.pop("decode_chunk_size", None)
    if not getattr(pipeline, "accepts_micro_conditioning", False):
        for drop in ("motion_bucket_id", "noise_aug_strength"):
            kwargs.pop(drop, None)
    frames, config = pipeline.run(**kwargs)
    return {"primary": _frames_artifact(frames, config["fps"], content_type)}, config


def run_vid2vid(device_identifier: str, model_name: str, **kwargs):
    """vid2vid: chunked-batch frame editing (reference swarm/video/pix2pix.py).

    The reference's hot loop — one full pipeline invocation per frame — runs
    as batched img2img: VID2VID_CHUNK frames per jitted call, one compile.
    """
    from ..registry import get_pipeline

    content_type = kwargs.pop("content_type", "video/mp4")
    kwargs.pop("outputs", None)
    video_uri = kwargs.pop("video_uri", None)
    if video_uri is None:
        raise ValueError("vid2vid requires a video_uri. None provided")
    if kwargs.pop("test_tiny_model", False):
        model_name = "test/tiny-sd"

    path = download_video(video_uri)
    try:
        frames, fps = split_video_frames(path)
    finally:
        os.unlink(path)

    pipeline = get_pipeline(
        model_name,
        pipeline_type=kwargs.pop(
            "pipeline_type", "StableDiffusionInstructPix2PixPipeline"
        ),
        chipset=kwargs.pop("chipset", None),
    )
    rng = kwargs.pop("rng", None)
    if rng is None:
        rng = jax.random.key(0)
    prompt = kwargs.pop("prompt", "")
    steps = int(kwargs.pop("num_inference_steps", 25))
    strength = float(kwargs.pop("strength", 0.6))
    # edit-tuned checkpoints consume dual-guidance strength; non-pix2pix
    # models ignore it and fall back to strength-based img2img (recorded as
    # approximated_as in the per-chunk config)
    image_guidance = kwargs.pop("image_guidance_scale", None)

    # size-normalize all frames so every chunk hits the same program bucket
    w, h = frames[0].size
    frames = [f if f.size == (w, h) else f.resize((w, h)) for f in frames]

    out_frames = []
    edit_mode = None
    t0 = time.perf_counter()
    for start in range(0, len(frames), VID2VID_CHUNK):
        chunk = frames[start : start + VID2VID_CHUNK]
        pad = VID2VID_CHUNK - len(chunk)
        run_kw = dict(
            prompt=prompt,
            image=chunk + [chunk[-1]] * pad,  # pad partial chunk, slice below
            strength=strength,
            num_inference_steps=steps,
            rng=jax.random.fold_in(rng, start),
        )
        if image_guidance is not None:
            run_kw["image_guidance_scale"] = image_guidance
        images, chunk_cfg = pipeline.run(**run_kw)
        edit_mode = chunk_cfg.get("approximated_as", chunk_cfg.get("mode"))
        out_frames.extend(images[: len(chunk)])
    config = {
        "model": model_name,
        "frames": len(frames),
        "fps": fps,
        "mode": edit_mode,
        # reference cost metric (swarm/video/pix2pix.py:79)
        "compute_cost": 512 * 512 * steps * len(frames),
        "timings": {"edit_s": round(time.perf_counter() - t0, 3)},
    }
    return {"primary": _frames_artifact(out_frames, int(fps), content_type)}, config
