"""Resident Flux pipeline: rectified-flow txt2img on the MMDiT transformer.

Reference behavior replaced: FluxPipeline jobs at bf16 with *sequential CPU
offload* to fit CUDA VRAM (swarm/test.py:244-290, job_arguments large-model
branches) — a per-job `from_pretrained` plus layer-by-layer host<->device
shuffling. TPU design: weights are resident, the whole sampling loop is one
jitted `lax.scan` (flow-matching Euler over resolution-shifted sigmas), and
memory scaling comes from mesh sharding, not offload.

Flux-dev carries distilled guidance as an *embedding input* — there is no
CFG batch doubling, so batch = N images (half the UNet-family cost per
image at the same step count). Schnell ignores guidance entirely.
"""

from __future__ import annotations

import logging
import threading
import time
import zlib
from collections import OrderedDict
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from ..models import configs as cfgs
from ..models.clip import CLIPTextEncoder
from ..models.flux import (
    FINAL_KEYS,
    HEAD_KEYS,
    TINY_FLUX,
    DoubleStreamBlock,
    FluxConfig,
    FluxFinal,
    FluxHead,
    FluxTransformer,
    SingleStreamBlock,
    grouped_layout,
    head_groups_for,
    patchify,
    rope_frequencies,
    unpatchify,
)
from ..models.t5 import TINY_T5, T5Config, T5Encoder
from ..models.tokenizer import load_tokenizer
from ..models.vae import AutoencoderKL
from ..ops.platform import mesh_scope
from ..parallel.mesh import batch_sharding, make_mesh, replicated
from ..registry import register_family
from ..schedulers import FlowMatchEulerScheduler
from ..schedulers.common import SchedulerConfig
from ..settings import load_settings
from ..telemetry import Span
from ..weights import require_weights_present

logger = logging.getLogger(__name__)


def _flux_configs(model_name: str):
    """(flux_cfg, t5_cfg, clip_cfg, vae_cfg, default_size, default_steps,
    dynamic_shift). schnell is distilled on UNSHIFTED sigmas (shift=1);
    dev uses resolution-dependent dynamic shifting (see _sigma_shift).

    Names with `tiny` are the tiny stand-ins; `test/FLUX.1-dev` and
    `test/FLUX.1-schnell` are the published geometry with random weights
    allowed (as `test/stable-diffusion-xl-base-1.0` is SDXL's)."""
    import dataclasses

    name = model_name.lower()
    schnell = "schnell" in name
    if "tiny" in name:
        flux = TINY_FLUX
        if schnell:
            flux = dataclasses.replace(flux, guidance_embed=False)
        return flux, TINY_T5, cfgs.TINY_CLIP, cfgs.TINY_VAE, 64, 4, not schnell
    if schnell:
        return (
            dataclasses.replace(FluxConfig(), guidance_embed=False),
            T5Config(), cfgs.SD15_CLIP, cfgs.FLUX_VAE, 1024, 4, False,
        )
    return FluxConfig(), T5Config(), cfgs.SD15_CLIP, cfgs.FLUX_VAE, 1024, 28, True


def _sigma_shift(image_seq_len: int, dynamic: bool) -> float:
    """Flow-matching sigma shift for the sampling schedule.

    Dev-family checkpoints use dynamic shifting: mu interpolates linearly
    with the image token count between (256, 0.5) and (4096, 1.15), and the
    trained time warp is t' = exp(mu)*t / (1 + (exp(mu)-1)*t) — exactly our
    scheduler's `shift` parameter with shift = exp(mu). Schnell is distilled
    on the unshifted schedule (shift = 1).
    """
    if not dynamic:
        return 1.0
    import math

    m = (1.15 - 0.5) / (4096 - 256)
    mu = 0.5 + m * (image_seq_len - 256)
    return math.exp(mu)


class FluxPipeline:
    """One resident Flux bundle per (model, slice)."""

    def __init__(self, model_name: str, chipset=None, dtype=None,
                 allow_random_init: bool = False,
                 streaming: bool | None = None, weights=None):
        """`weights`, where given, takes the place of the checkpoint and
        of the seeded host init: `weights(shapes, shardings)` returns the
        parameter tree with every leaf already on this pipeline's mesh
        (`param_shapes` / `param_shardings`), so a caller can make or load
        the leaves shard by shard and the whole tree never sits on the
        host or on one chip."""
        self.model_name = model_name
        self.chipset = chipset
        (self.config, t5_cfg, clip_cfg, vae_cfg, self.default_size,
         self.default_steps, self.dynamic_shift) = _flux_configs(model_name)
        if dtype is None:
            dtype = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
        self.dtype = dtype

        self.mesh = (
            chipset.mesh() if chipset is not None else make_mesh(jax.devices()[:1])
        )
        self.data_parts = self.mesh.shape.get("data", 1)
        self.tensor_parts = self.mesh.shape.get("tensor", 1)
        # the placed layout of the blocks' fused kernels: one group of
        # heads (and of MLP units) a tensor shard (models/flux.py)
        self.head_groups = head_groups_for(self.config, self.tensor_parts)
        self.transformer = FluxTransformer(
            self.config, dtype=dtype, head_groups=self.head_groups)
        self.t5 = T5Encoder(t5_cfg, dtype=dtype)
        self.clip = CLIPTextEncoder(clip_cfg, dtype=dtype)
        self.vae = AutoencoderKL(vae_cfg, dtype=dtype)
        self.latent_factor = 2 ** (len(vae_cfg.block_out_channels) - 1)
        self.latent_channels = vae_cfg.latent_channels

        if streaming is None:
            # auto: page transformer blocks from host RAM when the model
            # cannot sit resident on this slice (the TPU analog of the
            # reference's enable_sequential_cpu_offload — VERDICT r04 #2).
            # Same flux_admissible rule as the job gate and the worker's
            # flux_runnable advertisement — stream exactly when admission
            # came from the streaming arm.
            from ..chips.requirements import flux_admissible

            if chipset is None:
                streaming = False
            else:
                _, mode = flux_admissible(
                    chipset, 1, self.default_size, model_name=model_name)
                streaming = mode == "streaming"
        self.streaming = bool(streaming)
        self._host_double: list = []
        self._host_single: list = []
        self._stream_int8 = False  # set for real in _place_streaming

        t0 = time.perf_counter()
        if weights is not None:
            if self.streaming:
                raise ValueError(
                    "a weight-streaming flux pages its blocks from the "
                    "host: it takes no pre-placed tree")
            shapes = self.param_shapes()
            self.params = self._adopt(
                weights(shapes, self.param_shardings(shapes)), shapes)
        else:
            self.params = self._load_params(allow_random_init)
        model_dir = self._model_dir()
        self.clip_tokenizer = load_tokenizer(model_dir, clip_cfg.vocab_size)
        self.t5_tokenizer = _load_t5_tokenizer(model_dir, t5_cfg.vocab_size)
        logger.info("%s resident in %.1fs (dtype=%s)", model_name,
                    time.perf_counter() - t0, dtype)

        self._jit_lock = threading.Lock()
        # insertion-ordered so the program_cache_max bound below can evict
        # least-recently-used first (SW007; same knob as the SD family)
        self._programs: OrderedDict = OrderedDict()
        self._encode_program = jax.jit(self._encode_impl)

    def _model_dir(self) -> Path | None:
        root = Path(load_settings().model_root_dir).expanduser()
        d = root / self.model_name
        return d if d.is_dir() else None

    # --- the parameter tree: shapes, shardings, placement ---

    def _init(self):
        """The four modules' seeded init, as `_load_params` runs it on the
        host and `param_shapes` traces it."""
        cfg = self.config
        seed = zlib.crc32(self.model_name.encode())
        k1, k2, k3, k4 = jax.random.split(jax.random.key(seed), 4)
        s_img, s_txt = 4, 8
        # an init's values do not depend on `head_groups`: the tree reads
        # as the checkpoint-order one, and `_place` regroups it
        flux_params = self.transformer.init(
            k1,
            jnp.zeros((1, s_img, cfg.in_channels)),
            jnp.zeros((1, s_img, 3), jnp.int32),
            jnp.zeros((1, s_txt, cfg.context_dim)),
            jnp.zeros((1, s_txt, 3), jnp.int32),
            jnp.zeros((1,)),
            jnp.zeros((1, cfg.pooled_dim)),
            guidance=jnp.ones((1,)),
        )["params"]
        t5_params = self.t5.init(k2, jnp.zeros((1, 8), jnp.int32))["params"]
        clip_params = self.clip.init(k3, jnp.zeros((1, 77), jnp.int32))["params"]
        hw = 2 * self.latent_factor
        vae_params = self.vae.init(k4, jnp.zeros((1, hw, hw, 3)))["params"]
        return {"flux": flux_params, "t5": t5_params, "clip": clip_params,
                "vae": vae_params}

    def param_shapes(self):
        """The resident parameter tree as `jax.ShapeDtypeStruct`s in the
        serving dtype (`jax.eval_shape` of the modules' own init: nothing
        is allocated). Leaves are in the placed layout: on a tensor mesh
        the blocks' fused kernels are in group order
        (`models.flux.grouped_layout(tree, config, head_groups)` takes a
        checkpoint-order tree there, leaf by leaf)."""
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, self.dtype),
            jax.eval_shape(self._init))

    def param_shardings(self, shapes=None):
        """The tree of `NamedSharding`s on this pipeline's mesh, leaf for
        leaf of `param_shapes()`: the MMDiT and T5 by their partition
        rules (parallel/tensor.py), CLIP-L and the VAE whole on every
        chip."""
        from ..parallel.tensor import (
            flux_partition_rules,
            sharding_tree,
            t5_partition_rules,
        )

        shapes = self.param_shapes() if shapes is None else shapes
        whole = replicated(self.mesh)
        if self.tensor_parts <= 1:
            return jax.tree_util.tree_map(lambda _: whole, shapes)
        rules = {"flux": flux_partition_rules(self.head_groups > 1),
                 "t5": t5_partition_rules()}
        return {
            key: (sharding_tree(self.mesh, tree, rules[key])
                  if key in rules
                  else jax.tree_util.tree_map(lambda _: whole, tree))
            for key, tree in shapes.items()
        }

    def _adopt(self, params, shapes):
        """A tree placed elsewhere (`weights=`): held to `param_shapes`,
        then counted like one placed here."""
        got = jax.tree_util.tree_map(lambda x: tuple(x.shape), params)
        want = jax.tree_util.tree_map(lambda x: tuple(x.shape), shapes)
        if got != want:
            raise ValueError(
                f"{self.model_name}: the weights handed in are not the "
                "tree param_shapes() describes")
        return self._count_resident(params)

    def _count_resident(self, params):
        from ..parallel.tensor import largest_device_bytes
        from .common import RESIDENT_PARAM_BYTES

        RESIDENT_PARAM_BYTES.set(
            largest_device_bytes(params), model=self.model_name)
        return params

    def _place(self, params):
        """A host tree in the checkpoint's order -> the serving dtype, the
        placed layout, this mesh."""
        if self.streaming:
            return self._count_resident(self._place_streaming(params))
        params = dict(params, flux=grouped_layout(
            params["flux"], self.config, self.head_groups))
        cast = lambda x: jnp.asarray(x, self.dtype)
        params = jax.tree_util.tree_map(cast, params)
        return self._count_resident(
            jax.device_put(params, self.param_shardings(params)))

    def _place_streaming(self, params):
        """Resident tail (T5/CLIP/VAE + flux head/final) on the chip;
        transformer blocks stay in HOST RAM (serving-dtype jax CPU arrays,
        halving the per-step PCIe traffic vs f32 — or int8 with
        per-channel scales when flux_stream_int8 is on, halving it again)
        and page through the chip double-buffered during sampling."""
        cfg = self.config
        cpu = jax.local_devices(backend="cpu")[0]
        flux = params["flux"]
        self._stream_int8 = bool(load_settings().flux_stream_int8)
        if self._stream_int8:
            from ..ops.quant import quantize_tree

            pack = lambda tree: quantize_tree(tree, self.dtype)
        else:
            pack = lambda tree: jax.tree_util.tree_map(
                lambda x: jnp.asarray(x, self.dtype), tree)
        with jax.default_device(cpu):
            self._host_double = [
                pack(flux[f"double_blocks_{i}"])
                for i in range(cfg.depth_double)
            ]
            self._host_single = [
                pack(flux[f"single_blocks_{i}"])
                for i in range(cfg.depth_single)
            ]
        cast = lambda x: jnp.asarray(x, self.dtype)
        resident = {
            "flux": {k: flux[k] for k in (*HEAD_KEYS, *FINAL_KEYS)
                     if k in flux},
            "t5": params["t5"], "clip": params["clip"], "vae": params["vae"],
        }
        resident = jax.tree_util.tree_map(cast, resident)
        return jax.device_put(resident, replicated(self.mesh))

    def _load_params(self, allow_random_init: bool) -> dict:
        model_dir = self._model_dir()
        if model_dir is not None:
            try:
                return self._convert_params(model_dir)
            except FileNotFoundError:
                require_weights_present(
                    self.model_name, model_dir, allow_random_init
                )
                logger.warning("no safetensors under %s; random init", model_dir)
        else:
            require_weights_present(self.model_name, None, allow_random_init)

        with jax.default_device(jax.local_devices(backend="cpu")[0]):
            # one program, not an eager op at a time: seconds for a tiny
            # stand-in where the eager init took a quarter of a minute
            params = jax.jit(self._init)()
        return self._place(params)

    def _convert_params(self, model_dir: Path) -> dict:
        from ..models.conversion import (
            convert_clip,
            convert_flux,
            convert_t5,
            convert_vae,
            load_torch_state_dict,
        )

        params = {
            "flux": convert_flux(load_torch_state_dict(model_dir, "transformer")),
            "t5": convert_t5(load_torch_state_dict(model_dir, "text_encoder_2")),
            "clip": convert_clip(load_torch_state_dict(model_dir, "text_encoder")),
            "vae": convert_vae(load_torch_state_dict(model_dir, "vae")),
        }
        return self._place(params)

    def release(self):
        self.params = None
        self._programs.clear()
        self._host_double = []
        self._host_single = []
        if hasattr(self, "_sfns"):
            del self._sfns

    # --- conditioning ---

    def _encode_impl(self, params, clip_ids, t5_ids):
        pooled = self.clip.apply({"params": params["clip"]}, clip_ids)["pooled"]
        context = self.t5.apply({"params": params["t5"]}, t5_ids)
        return context, pooled

    def _encode(self, params, clip_ids, t5_ids):
        with mesh_scope(self.mesh):
            return self._encode_program(params, clip_ids, t5_ids)

    def _decode_rows(self, vae_params, latents):
        """Latents -> uint8 pixels. On a tensor slice every chip decodes
        every row, and the decoder's 1024^2 stages in float32 with its
        mid-block's 16384^2 scores are ~3.3 GB of temporaries a row: a
        2-row gang decoded at once was 6.65 GB beside 9 GB of weights on a
        16 GB chip (the compile for four described v5e chips, PR 27). So
        there, and only there, the rows are decoded one at a time inside
        the program; the decode is a percent of a pass. Elsewhere the rows
        stay one batch, sharded over `data` as before."""
        vae = self.vae

        def pixels_of(latents):
            pixels = vae.apply(
                {"params": vae_params}, latents, method=vae.decode)
            return (
                (pixels.astype(jnp.float32) + 1.0) * 127.5
            ).clip(0.0, 255.0).round().astype(jnp.uint8)

        if self.tensor_parts <= 1:
            return pixels_of(latents)
        return jax.lax.map(lambda row: pixels_of(row[None])[0], latents)

    # --- sampling program ---

    def _program(self, key: tuple):
        with self._jit_lock:
            if key in self._programs:
                self._programs.move_to_end(key)
                return self._programs[key]
        lh, lw, batch, steps, txt_len = key
        shift = _sigma_shift((lh // 2) * (lw // 2), self.dynamic_shift)
        scheduler = FlowMatchEulerScheduler(
            SchedulerConfig(prediction_type="flow", shift=shift)
        )
        schedule = scheduler.schedule(steps)
        sigmas = jnp.asarray(schedule.sigmas)
        transformer = self.transformer
        latent_c = self.latent_channels

        def flux_denoise_decode(params, init_rng, context, pooled, guidance):
            latents = jax.random.normal(
                init_rng, (batch, lh, lw, latent_c), jnp.float32
            )
            img, img_ids = patchify(latents.astype(self.dtype))
            txt_ids = jnp.zeros((batch, txt_len, 3), jnp.int32)

            def body(img, i):
                t = jnp.broadcast_to(sigmas[i], (batch,))
                velocity = transformer.apply(
                    {"params": params["flux"]},
                    img.astype(self.dtype),
                    img_ids,
                    context,
                    txt_ids,
                    t,
                    pooled,
                    guidance=guidance,
                ).astype(jnp.float32)
                img = img.astype(jnp.float32) + (
                    sigmas[i + 1] - sigmas[i]
                ) * velocity
                return img, ()

            img, _ = jax.lax.scan(body, img.astype(jnp.float32),
                                  jnp.arange(steps))
            latents = unpatchify(img, lh, lw).astype(self.dtype)
            return self._decode_rows(params["vae"], latents)

        program = jax.jit(flux_denoise_decode)
        with self._jit_lock:
            self._programs[key] = program
            from .common import PROGRAM_EVICTED, program_cache_cap

            cap = program_cache_cap()
            while cap and len(self._programs) > cap:
                self._programs.popitem(last=False)
                PROGRAM_EVICTED.inc(kind="program")
        return program

    def _canvas(self, height: int, width: int):
        """(height, width, latent rows, latent columns): the latent grid
        must patchify 2x2, so the canvas snaps to /16 of pixel space."""
        snap = self.latent_factor * 2
        height, width = (max(snap, (d // snap) * snap) for d in (height, width))
        return (height, width, height // self.latent_factor,
                width // self.latent_factor)

    def denoise_program(self, height: int, width: int, rows: int,
                        steps: int, txt_len: int = 512,
                        batched: bool = True):
        """The jitted denoise + decode program `run_batched` (`batched`:
        operands `params, latents [rows, h/8, w/8, C] f32, context, pooled,
        guidance`) or `run` (`params, rng key, context, pooled, guidance`)
        calls for this canvas, row count and step count. Trace, lower or
        call it under `mesh_scope(self.mesh)`."""
        _, _, lh, lw = self._canvas(height, width)
        if batched:
            return self._batched_program(
                ("batched", lh, lw, rows, steps, txt_len))
        return self._program((lh, lw, rows, steps, txt_len))

    # --- weight-streaming sampler (host-RAM paged transformer blocks) ---

    def _stream_fns(self) -> dict:
        """Jitted per-block programs: ONE executable per block type is
        reused by all 19/38 block instances (identical shapes/structure),
        so compile cost is constant, not per-block."""
        with self._jit_lock:
            if hasattr(self, "_sfns"):
                return self._sfns
        cfg, dtype = self.config, self.dtype
        head = FluxHead(cfg, dtype=dtype)
        final = FluxFinal(cfg, dtype=dtype)
        dbl = DoubleStreamBlock(cfg, dtype=dtype)
        sgl = SingleStreamBlock(cfg, dtype=dtype)
        vae = self.vae
        if self._stream_int8:
            # transfers stay int8 over PCIe; the dequant runs on-chip as
            # part of the same jitted block program
            from ..ops.quant import dequantize_tree

            dq = lambda p: dequantize_tree(p, dtype)
        else:
            dq = lambda p: p
        fns = {
            "head": jax.jit(lambda p, img, txt, t, pooled, g: head.apply(
                {"params": p}, img, txt, t, pooled, guidance=g)),
            "double": jax.jit(lambda p, img, txt, vec, cos, sin: dbl.apply(
                {"params": dq(p)}, img, txt, vec, cos, sin)),
            "single": jax.jit(lambda p, x, vec, cos, sin: sgl.apply(
                {"params": dq(p)}, x, vec, cos, sin)),
            "final": jax.jit(lambda p, x, vec: final.apply(
                {"params": p}, x, vec)),
            "euler": jax.jit(lambda img, v, ds: (
                img.astype(jnp.float32) + ds * v.astype(jnp.float32))),
            "decode": jax.jit(lambda p, lat: (
                (vae.apply({"params": p}, lat, method=vae.decode)
                 .astype(jnp.float32) + 1.0) * 127.5
            ).clip(0.0, 255.0).round().astype(jnp.uint8)),
        }
        with self._jit_lock:
            self._sfns = fns
        return fns

    def _run_streaming(self, lh, lw, batch, steps, txt_len, init_rng,
                       context, pooled, guidance):
        """Python-loop sampler: per step, page every transformer block
        through the chip. `jax.device_put` is async, so issuing block
        i+1's transfer BEFORE dispatching block i's compute overlaps PCIe
        with the MXU — the same pipelining trick as the reference's
        sequential offload, minus the per-job from_pretrained."""
        cfg = self.config
        fns = self._stream_fns()
        shift = _sigma_shift((lh // 2) * (lw // 2), self.dynamic_shift)
        scheduler = FlowMatchEulerScheduler(
            SchedulerConfig(prediction_type="flow", shift=shift)
        )
        sigmas = np.asarray(scheduler.schedule(steps).sigmas, np.float32)

        params = self.params
        head_p = {k: params["flux"][k] for k in HEAD_KEYS
                  if k in params["flux"]}
        final_p = {k: params["flux"][k] for k in FINAL_KEYS
                   if k in params["flux"]}

        latents = jax.random.normal(
            init_rng, (batch, lh, lw, self.latent_channels), jnp.float32
        )
        carry, img_ids = patchify(latents)
        txt_ids = jnp.zeros((batch, txt_len, 3), jnp.int32)
        ids = jnp.concatenate([txt_ids, img_ids], axis=1)
        cos, sin = rope_frequencies(ids, cfg.axes_dims_rope, cfg.theta)
        cos, sin = cos.astype(self.dtype), sin.astype(self.dtype)

        # page blocks onto THIS pipeline's slice, not the process default
        # device — a 1-chip slice k>0 on a multi-chip host would otherwise
        # compute against device 0 (or pay a silent extra hop per block)
        target = replicated(self.mesh)
        page = lambda tree: jax.device_put(tree, target)

        for i in range(steps):
            t = jnp.broadcast_to(jnp.float32(sigmas[i]), (batch,))
            img, txt, vec = fns["head"](
                head_p, carry.astype(self.dtype), context, t, pooled,
                guidance,
            )
            # seed the prefetch from the first NON-EMPTY block list: a
            # config with depth_double == 0 must hand the first
            # SingleStreamBlock a real param tree, not None (ADVICE r05)
            if cfg.depth_double:
                nxt = page(self._host_double[0])
            elif cfg.depth_single:
                nxt = page(self._host_single[0])
            else:
                nxt = None
            for b in range(cfg.depth_double):
                cur = nxt
                if b + 1 < cfg.depth_double:
                    nxt = page(self._host_double[b + 1])
                elif cfg.depth_single:
                    nxt = page(self._host_single[0])
                img, txt = fns["double"](cur, img, txt, vec, cos, sin)
            x = jnp.concatenate([txt, img], axis=1)
            for b in range(cfg.depth_single):
                cur = nxt
                if b + 1 < cfg.depth_single:
                    nxt = page(self._host_single[b + 1])
                x = fns["single"](cur, x, vec, cos, sin)
            x = x[:, txt_len:]
            velocity = fns["final"](final_p, x, vec)
            carry = fns["euler"](
                carry, velocity, jnp.float32(sigmas[i + 1] - sigmas[i])
            )

        latents = unpatchify(carry, lh, lw).astype(self.dtype)
        return fns["decode"](params["vae"], latents)

    # --- public job API ---

    def run(self, prompt="", negative_prompt="", pipeline_type="FluxPipeline",
            **kwargs):
        params = self.params
        if params is None:
            raise Exception(
                f"pipeline {self.model_name} was evicted; resubmit the job"
            )
        timings: dict[str, float] = {}
        steps = int(kwargs.pop("num_inference_steps", self.default_steps))
        guidance_scale = float(kwargs.pop("guidance_scale", 3.5))
        n_images = int(kwargs.pop("num_images_per_prompt", 1))
        max_seq = int(kwargs.pop("max_sequence_length", 512))
        rng = kwargs.pop("rng", None)
        if rng is None:
            rng = jax.random.key(0)
        kwargs.pop("chipset", None)
        kwargs.pop("scheduler_type", None)  # flow matching is the family's solver

        height, width, lh, lw = self._canvas(
            int(kwargs.pop("height", None) or self.default_size),
            int(kwargs.pop("width", None) or self.default_size))

        with Span("text_encode", timings):
            clip_ids = jnp.asarray(self.clip_tokenizer([prompt] * n_images))
            t5_ids = jnp.asarray(
                self.t5_tokenizer([prompt] * n_images, max_seq), jnp.int32
            )
            context, pooled = self._encode(params, clip_ids, t5_ids)

        def place_b(x):
            if self.data_parts > 1 and x.shape[0] % self.data_parts == 0:
                return jax.device_put(x, batch_sharding(self.mesh, x.ndim))
            return jax.device_put(x, replicated(self.mesh))

        context, pooled = place_b(context), place_b(pooled)
        guidance = jnp.full((n_images,), guidance_scale, jnp.float32)

        rng, init_rng = jax.random.split(rng)
        if self.streaming:
            with Span("denoise", timings, key="denoise_decode_s"), \
                    mesh_scope(self.mesh):
                pixels = jax.block_until_ready(
                    self._run_streaming(
                        lh, lw, n_images, steps, int(t5_ids.shape[1]),
                        init_rng, context, pooled, guidance,
                    )
                )
        else:
            key = (lh, lw, n_images, steps, int(t5_ids.shape[1]))
            with Span("compile", timings, key="trace_s"):
                program = self._program(key)
            # jit traces on the first call: the kernel routing (a
            # trace-time branch on the mesh) lands in the compiled program
            with Span("denoise", timings, key="denoise_decode_s"), \
                    mesh_scope(self.mesh):
                pixels = jax.block_until_ready(
                    program(params, init_rng, context, pooled, guidance)
                )

        from PIL import Image

        with Span("readback", timings):
            images = [Image.fromarray(img) for img in np.asarray(pixels)]
        pipeline_config = {
            "model": self.model_name,
            "pipeline": pipeline_type,
            "scheduler": "FlowMatchEulerScheduler",
            "mode": "txt2img",
            "steps": steps,
            "size": [width, height],
            "guidance_scale": guidance_scale,
            "timings": timings,
        }
        if self.streaming:
            # visible in the envelope like the reference's offload mode:
            # slower, but serving on hardware the resident model outgrows
            pipeline_config["weight_streaming"] = True
            if self._stream_int8:
                pipeline_config["stream_int8"] = True
        return images, pipeline_config

    # --- coalesced txt2img (ISSUE 20: flux joins run_batched) ---

    def _batched_program(self, key: tuple):
        """Like _program, but the init latents arrive PRE-DRAWN: each
        request's rows are sampled eagerly from its own rng with the
        exact split + draw shape run() uses, so a coalesced row matches
        its solo twin to within one uint8 quantization step — the
        MMDiT/VAE programs are row-independent and nothing inside the
        jit depends on who a row was batched with; only XLA's
        batch-width vectorization can move the last float bit. Shares
        the LRU-bounded program cache with the solo entries (the
        leading "batched" tag keeps the two key shapes from
        colliding)."""
        with self._jit_lock:
            if key in self._programs:
                self._programs.move_to_end(key)
                return self._programs[key]
        _tag, lh, lw, batch, steps, txt_len = key
        shift = _sigma_shift((lh // 2) * (lw // 2), self.dynamic_shift)
        scheduler = FlowMatchEulerScheduler(
            SchedulerConfig(prediction_type="flow", shift=shift)
        )
        sigmas = jnp.asarray(scheduler.schedule(steps).sigmas)
        transformer = self.transformer

        def flux_batched_denoise_decode(params, latents, context, pooled,
                                        guidance):
            img, img_ids = patchify(latents.astype(self.dtype))
            txt_ids = jnp.zeros((batch, txt_len, 3), jnp.int32)

            def body(img, i):
                t = jnp.broadcast_to(sigmas[i], (batch,))
                velocity = transformer.apply(
                    {"params": params["flux"]},
                    img.astype(self.dtype),
                    img_ids,
                    context,
                    txt_ids,
                    t,
                    pooled,
                    guidance=guidance,
                ).astype(jnp.float32)
                img = img.astype(jnp.float32) + (
                    sigmas[i + 1] - sigmas[i]
                ) * velocity
                return img, ()

            img, _ = jax.lax.scan(body, img.astype(jnp.float32),
                                  jnp.arange(steps))
            latents = unpatchify(img, lh, lw).astype(self.dtype)
            return self._decode_rows(params["vae"], latents)

        program = jax.jit(flux_batched_denoise_decode)
        with self._jit_lock:
            self._programs[key] = program
            from .common import PROGRAM_EVICTED, program_cache_cap

            cap = program_cache_cap()
            while cap and len(self._programs) > cap:
                self._programs.popitem(last=False)
                PROGRAM_EVICTED.inc(kind="program")
        return program

    def run_batched(self, requests: list[dict], *, height=None, width=None,
                    num_inference_steps=None, guidance_scale=3.5,
                    max_sequence_length: int = 512,
                    pipeline_type: str = "FluxPipeline", **_shared):
        """Coalesced flux txt2img: N independent requests, ONE padded
        jitted flow-matching pass (batching.py design; coalesce_key
        admits only the shapes this reproduces — txt2img, no adapters,
        no ControlNet, explicit steps + guidance). Per-row payload is
        prompt / rng / num_images_per_prompt; everything shared rides as
        keyword arguments. There is no CFG row doubling, so the pass
        batches exactly sum(rows) images padded to a power-of-two
        bucket.

        Returns [(images_j, pipeline_config_j)] aligned with requests.
        Raising here is fine: the worker's solo fallback serves the
        members individually (the contract SD's run_batched set)."""
        from .common import pad_bucket, split_by_counts

        params = self.params
        if params is None:
            raise Exception(
                f"pipeline {self.model_name} was evicted; resubmit the job"
            )
        if self.streaming:
            # the paged sampler is host-RAM-bound, not row-bound: wider
            # rows don't amortize the PCIe traffic, and the python-loop
            # sampler has no batched-latents seam — solo fallback
            raise ValueError(
                "weight-streaming flux serves members individually")
        if any(r.get("lora") for r in requests):
            raise ValueError("flux adapters serve on the single path")
        if any(r.get("image") is not None for r in requests):
            raise ValueError("flux has no coalesced img2img variant")

        timings: dict[str, float] = {}
        steps = int(num_inference_steps or self.default_steps)
        guidance_scale = float(guidance_scale)
        max_seq = int(max_sequence_length)
        height = int(height or self.default_size)
        height, width, lh, lw = self._canvas(height, int(width or height))

        counts = [
            max(int(r.get("num_images_per_prompt", 1) or 1), 1)
            for r in requests
        ]
        total = sum(counts)
        padded = pad_bucket(total)
        pad_rows = padded - total

        # --- conditioning: every row carries its own prompt; padding
        # rows are empty prompts whose outputs are discarded ---
        with Span("text_encode", timings):
            prompts: list[str] = []
            for r, n in zip(requests, counts):
                prompts.extend([str(r.get("prompt") or "")] * n)
            prompts.extend([""] * pad_rows)
            clip_ids = jnp.asarray(self.clip_tokenizer(prompts))
            t5_ids = jnp.asarray(
                self.t5_tokenizer(prompts, max_seq), jnp.int32)
            context, pooled = self._encode(params, clip_ids, t5_ids)

        def place_b(x):
            if self.data_parts > 1 and x.shape[0] % self.data_parts == 0:
                return jax.device_put(x, batch_sharding(self.mesh, x.ndim))
            return jax.device_put(x, replicated(self.mesh))

        context, pooled = place_b(context), place_b(pooled)
        guidance = jnp.full((padded,), guidance_scale, jnp.float32)

        # --- per-request init latents, drawn EXACTLY as run() draws
        # them (split the request's rng, sample the request-shaped
        # block) so each row matches its solo twin; padding rows are
        # zeros a row-independent program never mixes in ---
        blocks = []
        for r, n in zip(requests, counts):
            base = r.get("rng")
            if base is None:
                base = jax.random.key(0)
            init_rng = jax.random.split(base)[1]
            blocks.append(jax.random.normal(
                init_rng, (n, lh, lw, self.latent_channels), jnp.float32))
        if pad_rows:
            blocks.append(jnp.zeros(
                (pad_rows, lh, lw, self.latent_channels), jnp.float32))
        latents = place_b(jnp.concatenate(blocks, axis=0))

        key = ("batched", lh, lw, padded, steps, int(t5_ids.shape[1]))
        with Span("compile", timings, key="trace_s"):
            program = self._batched_program(key)
        with Span("denoise", timings, key="denoise_decode_s"), \
                mesh_scope(self.mesh):
            pixels = jax.block_until_ready(
                program(params, latents, context, pooled, guidance)
            )

        from PIL import Image

        with Span("readback", timings):
            groups = split_by_counts(
                [Image.fromarray(a) for a in np.asarray(pixels[:total])],
                counts)
        results = []
        offset = 0
        for n, images in zip(counts, groups):
            results.append((images, {
                "model": self.model_name,
                "pipeline": pipeline_type,
                "scheduler": "FlowMatchEulerScheduler",
                "mode": "txt2img",
                "steps": steps,
                "size": [width, height],
                "guidance_scale": guidance_scale,
                "batched_with": len(requests),
                "batch_rows": [offset, n],
                "padded_rows": padded,
                # shared pass timings, copied per envelope: the envelope
                # must stand alone once the hive splits the batch apart
                "timings": dict(timings),
            }))
            offset += n
        return results


class _HashT5Tokenizer:
    """Deterministic stand-in (tiny models / missing spiece.model)."""

    def __init__(self, vocab_size: int):
        self.vocab_size = vocab_size

    def __call__(self, texts: list[str], max_length: int):
        out = np.zeros((len(texts), max_length), np.int64)
        for r, text in enumerate(texts):
            ids = [zlib.crc32(w.encode()) % (self.vocab_size - 2) + 2
                   for w in text.lower().split()][: max_length - 1]
            ids.append(1)  # T5 EOS
            out[r, : len(ids)] = ids
        return out


class _SentencePieceT5Tokenizer:
    def __init__(self, model_path: Path):
        import sentencepiece

        self.sp = sentencepiece.SentencePieceProcessor(model_file=str(model_path))

    def __call__(self, texts: list[str], max_length: int):
        out = np.zeros((len(texts), max_length), np.int64)
        for r, text in enumerate(texts):
            ids = self.sp.encode(text)[: max_length - 1] + [1]  # EOS=1, PAD=0
            out[r, : len(ids)] = ids
        return out


def _load_t5_tokenizer(model_dir: Path | None, vocab_size: int):
    if model_dir is not None:
        for rel in ("tokenizer_2/spiece.model", "tokenizer/spiece.model",
                    "spiece.model"):
            path = model_dir / rel
            if path.is_file():
                try:
                    return _SentencePieceT5Tokenizer(path)
                except ImportError:
                    logger.warning(
                        "sentencepiece not installed; hash T5 tokenizer"
                    )
                    break
    return _HashT5Tokenizer(vocab_size)


@register_family("flux")
def _build_flux(model_name, chipset, **variant):
    return FluxPipeline(model_name, chipset, **variant)
