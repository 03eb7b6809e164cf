"""Resident, jitted Stable-Diffusion pipelines (SD1.x / SD2.x / SDXL).

Replaces reference swarm/diffusion/diffusion_func.py:15-167. Key design
inversions for TPU:

- Weights are loaded ONCE per (model, mesh) and stay in HBM; the reference
  runs `from_pretrained` per job (diffusion_func.py:103).
- The whole denoise loop is ONE jitted program: `lax.scan` over steps,
  classifier-free guidance as a batch-of-2N (uncond rows stacked before
  cond rows), scheduler state carried functionally. No Python per step.
- The image batch (CFG-doubled) shards over the ChipSet mesh's `data` axis
  when it divides evenly; otherwise it stays replicated — same program
  either way, XLA inserts the collectives.
- txt2img / img2img / inpaint are modes of one bundle (shared weights),
  where the reference loaded a separate diffusers pipeline class per wire
  name (swarm/job_arguments.py:260-327).

Jitted programs are cached per shape bucket (H, W, steps, batch, scheduler,
mode); `initialize --download`'s analog warms these up ahead of jobs.
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
from PIL import Image

from .. import costs, embed_cache, programs
from ..models import configs as cfgs
from ..models.clip import CLIPTextEncoder
from ..models.tokenizer import load_tokenizer
from ..models.unet2d import UNet2DConditionModel
from ..models.vae import AutoencoderKL
from ..parallel.mesh import (
    batch_sharding,
    make_mesh,
    replicated,
)
from ..registry import register_family
from ..schedulers import get_scheduler
from ..schedulers.common import SchedulerConfig
from ..settings import load_settings
from ..telemetry import Span, counter as telemetry_counter

logger = logging.getLogger(__name__)

# jitted-program cache effectiveness: a "miss" pays a full XLA trace +
# compile; the shape-bucket design lives or dies by this ratio
_COMPILE_CACHE = telemetry_counter(
    "swarm_compile_cache_total",
    "Denoise-program cache lookups by outcome (miss = trace + XLA compile)",
    ("event",),
)

# ISSUE 15 (SW007 headline): the program/runner variant caches gained an
# unbounded growth axis with runtime-delta LoRA — one compiled variant
# per (slot-bucket, rank-bucket, targeted-module-path-set), and the
# path-set fan-out is census-dependent. `program_cache_max` bounds both
# caches per pipeline; evictions (with the compiled executable freed via
# clear_cache) are counted here so a thrashing fleet is visible
_PROGRAM_EVICTED = telemetry_counter(
    "swarm_program_cache_evicted_total",
    "Compiled denoise programs / assembled runners evicted LRU at the "
    "program_cache_max bound, by kind",
    ("kind",),
)

# padded-vs-real rows through run_batched: how much of each coalesced
# pass was real work vs power-of-two padding (batching ROI, per PR 1)
_BATCH_ROWS = telemetry_counter(
    "swarm_batch_pass_rows_total",
    "Image rows through coalesced passes, real vs padding",
    ("kind",),
)

# per-pass slice-geometry accounting (ISSUE 12): one count per denoise
# pass, labelled by the mesh view it ran under — "replicated" (data-only
# mesh, today's coalescing view), "tensorN"/"seqN"/"tensorN_seqM" for
# sharded passes. The class-aware scheduler's whole point is that this
# distribution shifts with the traffic mix.
_SHARDED_PASSES = telemetry_counter(
    "swarm_sharded_passes_total",
    "Denoise passes by slice geometry (replicated | tensorN | seqN ...)",
    ("geometry",),
)

# merged-tree LoRA fallback LRU (each entry pins a FULL UNet copy in
# HBM). Small by design since ISSUE 13: the serving path applies
# adapters as runtime per-row deltas against the ONE resident base tree
# (pipelines/lora_runtime.py + the byte-capped factor cache in
# lora_cache.py); merged trees remain only for adapters the delta
# cannot express
MAX_RESIDENT_LORAS = 2
MAX_RESIDENT_TI = 4
MAX_RESIDENT_VAES = 2
# placed param copies per pipeline beyond the default view: each sharded
# geometry pins ~1/tensor of the model per chip next to the replicated
# copy, so the LRU stays tiny
MAX_RESIDENT_GEOMETRIES = 2


def geometry_label(tensor: int, seq: int) -> str:
    """Canonical metric label for a mesh view (swarm_sharded_passes_total).
    Any data-only view is "replicated" regardless of its data degree —
    the batch shards, the model does not."""
    if tensor <= 1 and seq <= 1:
        return "replicated"
    parts = []
    if tensor > 1:
        parts.append(f"tensor{tensor}")
    if seq > 1:
        parts.append(f"seq{seq}")
    return "_".join(parts)


def load_learned_embeddings(ref) -> list[dict]:
    """Textual-inversion file -> [{"tokens": [alias, ...], "vectors":
    [[k, D] float32, ...]}] groups (aliases share one id run; multiple
    vectors cover SDXL's per-encoder embeds).

    Accepts a direct path, a model-root entry, or a lora-root entry;
    handled formats: diffusers (one key per placeholder token), kohya
    `emb_params`, and the SDXL dual-encoder `clip_l`/`clip_g` layout. The
    file-named formats register both the bare stem and `<stem>` as
    triggers (prompts conventionally use either). Reference behavior
    replaced: diffusers load_textual_inversion per job
    (swarm/diffusion/diffusion_func.py:105-111).
    """
    from safetensors import safe_open

    settings = load_settings()
    candidates: list[Path] = []
    for base in (
        Path(str(ref)).expanduser(),
        Path(settings.model_root_dir).expanduser() / str(ref),
        Path(settings.lora_root_dir).expanduser() / str(ref),
    ):
        if base.is_file():
            candidates.append(base)
        elif base.is_dir():
            candidates.extend(sorted(base.glob("*.safetensors")))
    for f in candidates:
        try:
            with safe_open(str(f), framework="np") as sf:
                state = {k: sf.get_tensor(k) for k in sf.keys()}
        except Exception:  # noqa: BLE001 — try the next candidate
            continue
        if not state:
            continue
        as2d = lambda v: np.atleast_2d(np.asarray(v, np.float32))
        keys = set(state)
        stem_aliases = [f.stem, f"<{f.stem}>"]
        if keys == {"emb_params"}:
            return [{"tokens": stem_aliases,
                     "vectors": [as2d(state["emb_params"])]}]
        if keys <= {"clip_l", "clip_g"} and keys:
            return [{
                "tokens": stem_aliases,
                "vectors": [as2d(v) for v in state.values()],
            }]
        return [
            {"tokens": [token], "vectors": [as2d(v)]}
            for token, v in state.items()
        ]
    raise ValueError(
        f"Could not load textual inversion {ref}: no embedding safetensors "
        f"found (looked at {[str(c) for c in candidates] or 'no candidates'})"
    )



def _config_prediction_type(model_name: str) -> str | None:
    """`prediction_type` from the downloaded scheduler config JSON —
    authoritative over any name heuristic (a v-prediction fine-tune named
    without '768' would otherwise silently get epsilon and produce garbage
    with real weights). None when the checkpoint isn't local."""
    import json
    from pathlib import Path

    from ..settings import load_settings

    root = Path(load_settings().model_root_dir).expanduser() / model_name
    p = root / "scheduler" / "scheduler_config.json"
    if p.is_file():
        try:
            pred = json.loads(p.read_text()).get("prediction_type")
            if pred:
                return str(pred)
        except (OSError, ValueError):
            pass
    return None


def _family_configs(model_name: str):
    """(unet_cfg, [clip_cfgs], vae_cfg, default_size, prediction_type)."""
    import dataclasses

    name = model_name.lower()
    if "tiny" in name:
        if "xl" in name:
            out = (
                cfgs.TINY_XL_UNET,
                [cfgs.TINY_CLIP, cfgs.TINY_CLIP_2],
                cfgs.TINY_VAE,
                64,
                "epsilon",
            )
        else:
            out = (cfgs.TINY_UNET, [cfgs.TINY_CLIP], cfgs.TINY_VAE, 64, "epsilon")
    else:
        family = cfgs.model_family(model_name)
        if family == "sdxl":
            out = (cfgs.SDXL_UNET, [cfgs.SDXL_CLIP_1, cfgs.SDXL_CLIP_2],
                   cfgs.SDXL_VAE, 1024, "epsilon")
        elif family == "sdxl_refiner":
            out = (cfgs.SDXL_REFINER_UNET, [cfgs.SDXL_CLIP_2], cfgs.SDXL_VAE,
                   1024, "epsilon")
        elif family == "sd21":
            # SD2.1-768 is v-prediction; the 512 base is epsilon. The hive
            # sends full model names, so key off the canonical 768 name.
            pred = (
                "v_prediction" if "768" in name or name.endswith("2-1") else "epsilon"
            )
            out = (cfgs.SD21_UNET, [cfgs.SD21_CLIP], cfgs.SD_VAE, 768, pred)
        else:
            out = (cfgs.SD15_UNET, [cfgs.SD15_CLIP], cfgs.SD_VAE, 512, "epsilon")
    unet_cfg, clip_cfgs, vae_cfg, size, pred = out
    cfg_pred = _config_prediction_type(model_name)
    if cfg_pred is not None:
        pred = cfg_pred
    if "pix2pix" in name or "ip2p" in name:
        # edit-tuned checkpoints (timbrooks/instruct-pix2pix and the SDXL
        # variant, reference swarm/job_arguments.py:299-305) take the start-
        # image latents on the channel dim: 8-channel UNet input
        unet_cfg = dataclasses.replace(
            unet_cfg, in_channels=2 * vae_cfg.latent_channels
        )
    elif "inpaint" in name:
        # dedicated inpaint checkpoints (runwayml/stable-diffusion-inpainting
        # family): 9-channel input = latents + mask + masked-image latents
        unet_cfg = dataclasses.replace(
            unet_cfg, in_channels=2 * vae_cfg.latent_channels + 1
        )
    return unet_cfg, clip_cfgs, vae_cfg, size, pred


def _pil_to_array(image: Image.Image, width: int, height: int) -> np.ndarray:
    """PIL -> float32 [H, W, 3] in [-1, 1], resized to the job canvas."""
    image = image.convert("RGB")
    if image.size != (width, height):
        image = image.resize((width, height), Image.LANCZOS)
    arr = np.asarray(image, np.float32) / 127.5 - 1.0
    return arr


def _mask_to_latent_array(mask: Image.Image, width: int, height: int,
                          factor: int) -> np.ndarray:
    """Mask PIL -> float32 [H/f, W/f, 1]; 1 = repaint, 0 = keep."""
    mask = mask.convert("L").resize((width // factor, height // factor), Image.NEAREST)
    return (np.asarray(mask, np.float32)[..., None] / 255.0 > 0.5).astype(np.float32)


def dummy_added_cond(unet_cfg, b: int):
    """Zero SDXL micro-conditioning inputs for init/eval_shape; None for SD."""
    if unet_cfg.addition_embed_dim <= 0:
        return None
    pooled_dim = unet_cfg.addition_embed_dim - 6 * unet_cfg.addition_time_embed_dim
    return {
        "text_embeds": jnp.zeros((b, pooled_dim)),
        "time_ids": jnp.zeros((b, 6)),
    }


def _to_pil(batch: np.ndarray) -> list[Image.Image]:
    """[B, H, W, 3] uint8 (or legacy [-1, 1] float) -> PIL images."""
    arr = np.asarray(batch)
    if arr.dtype == np.uint8:  # quantized on device: 4x smaller transfer
        return [Image.fromarray(img) for img in arr]
    arr = np.clip(arr.astype(np.float32) * 0.5 + 0.5, 0.0, 1.0)
    return [Image.fromarray((img * 255).round().astype(np.uint8)) for img in arr]


class SDPipeline:
    """One model family resident on one ChipSet; serves all SD wire names."""

    # the chunked runner's boundary doubles as a checkpoint/resume seam
    # (ISSUE 18); workflows gate the checkpoint kwargs on this attribute
    # the same way geometry kwargs gate on resolve_geometry
    supports_checkpoint = True

    def __init__(self, model_name: str, chipset=None, dtype=None,
                 allow_random_init: bool = False):
        self.model_name = model_name
        self.chipset = chipset
        self.allow_random_init = allow_random_init
        unet_cfg, clip_cfgs, vae_cfg, self.default_size, pred = _family_configs(
            model_name
        )
        self.prediction_type = pred
        if dtype is None:
            dtype = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
        self.dtype = dtype
        self.is_xl = unet_cfg.addition_embed_dim > 0

        self.unet = UNet2DConditionModel(unet_cfg, dtype=dtype)
        self.text_encoders = [CLIPTextEncoder(c, dtype=dtype) for c in clip_cfgs]
        self.vae = AutoencoderKL(vae_cfg, dtype=dtype)

        # VAE spatial reduction: one 2x downsample per block transition
        self.latent_factor = 2 ** (len(vae_cfg.block_out_channels) - 1)
        self.latent_channels = vae_cfg.latent_channels
        # edit-tuned (instruct-pix2pix) checkpoints concat start-image latents
        # on the channel dim; dedicated inpaint checkpoints add a mask plane;
        # detect both by architecture, not by name
        self.is_pix2pix = unet_cfg.in_channels == 2 * vae_cfg.latent_channels
        self.is_inpaint_unet = (
            unet_cfg.in_channels == 2 * vae_cfg.latent_channels + 1
        )
        self.mesh = (
            chipset.mesh() if chipset is not None else make_mesh(jax.devices()[:1])
        )
        self.data_parts = self.mesh.shape.get("data", 1)
        self.tensor_parts = self.mesh.shape.get("tensor", 1)
        # the slice's construction-time view; per-pass `geometry` requests
        # resolve against it (default_geometry passes run exactly the
        # pre-ISSUE-12 programs, byte for byte)
        self.default_geometry = (self.tensor_parts, self.mesh.shape.get("seq", 1))
        # lazily-built alternate views over the SAME chips: geometry ->
        # (mesh, placed base params). LRU-bounded — each sharded entry
        # pins ~1/tensor of the model per chip next to the default copy.
        self._geometries: OrderedDict[tuple, tuple] = OrderedDict()

        t0 = time.perf_counter()
        self.params = self._load_params()
        self.tokenizers = [
            load_tokenizer(self._model_dir(), vocab_size=c.vocab_size)
            for c in clip_cfgs
        ]
        self.load_s = round(time.perf_counter() - t0, 3)
        logger.info("%s resident in %.1fs (dtype=%s)", model_name, self.load_s, dtype)

        self._jit_lock = threading.Lock()
        # LRU-bounded (program_cache_max; _trim_program_caches): the
        # runtime-delta adapter path compiles one variant per signature
        # and the signature space is census-dependent, so the cache must
        # evict — executables included — instead of growing forever
        self._programs: OrderedDict[tuple, callable] = OrderedDict()
        # assembled denoise runners (fused wrapper or chunked set) keyed
        # (bucket key, chunk size): a warm pass is one dict lookup, not a
        # scheduler rebuild + per-sub-program cache probe
        self._runner_cache: OrderedDict[tuple, callable] = OrderedDict()
        # jitted aux programs — ONE device dispatch for text encode and VAE
        # encode instead of op-by-op applies (each unjitted op is a separate
        # host->device round trip; round 1 measured >50% of job time on the
        # host side, VERDICT weak #2). jit retraces per shape bucket.
        self._encode_program = programs.instrument(
            jax.jit(self._encode_impl), model=model_name, kind="encode")
        # text-encoder-LoRA twin (ISSUE 16): the TE delta operands ride
        # as traced ARGUMENTS, so swapping adapters never retraces —
        # jit retraces per operand structure (sig), like _encode_program
        # retraces per shape bucket
        self._encode_delta_program = programs.instrument(
            jax.jit(self._encode_delta_impl), model=model_name,
            kind="encode_delta")
        # per-pass operand-residency stats for the envelope (ISSUE 16):
        # set by _lora_operands, reset at pass start by run/run_batched
        self.last_operand_stats = None
        self._vae_encode_program = programs.instrument(
            jax.jit(
                lambda vae_params, px: self.vae.apply(
                    {"params": vae_params}, px, method=self.vae.encode
                ).astype(jnp.float32)
            ),
            model=model_name, kind="vae_encode")
        # weights-free 2x: encode -> bilinear latent resize -> decode.
        # Kept as the explicit `upscale` fallback when the learned sd-x2
        # upscaler has no converted weights (otherwise every production
        # upscale job would die on MissingWeightsError)
        self._latent2x_program = programs.instrument(
            jax.jit(self._latent2x_impl), model=model_name, kind="latent2x")
        # resident ControlNet branches keyed by controlnet model name
        self._controlnets: dict[str, tuple] = {}
        # param trees with LoRAs merged, keyed by (lora ref, scale); LRU-
        # bounded — each entry pins a full UNet copy in HBM
        self._lora_cache: OrderedDict[tuple, dict] = OrderedDict()
        # textual inversions: (extended text params, wrapped tokenizers)
        self._ti_cache: OrderedDict[str, tuple] = OrderedDict()
        # per-job custom VAEs (reference diffusion_func.py:46-49)
        self._vae_cache: OrderedDict[str, dict] = OrderedDict()

    # --- weights ---

    def _model_dir(self) -> Path | None:
        root = Path(load_settings().model_root_dir).expanduser()
        d = root / self.model_name
        return d if d.is_dir() else None

    def _load_params(self) -> dict:
        """Converted weights when the model ships locally; otherwise fail
        loudly — random init is reserved for test/tiny models and explicit
        `allow_random_init` opt-in (benchmarks). See weights.py policy."""
        from ..weights import require_weights_present

        model_dir = self._model_dir()
        if model_dir is not None:
            try:
                return self._convert_params(model_dir)
            except FileNotFoundError:
                require_weights_present(
                    self.model_name, model_dir, self.allow_random_init
                )
                logger.warning(
                    "no safetensors under %s; falling back to random init", model_dir
                )
        else:
            require_weights_present(self.model_name, None, self.allow_random_init)
        # NOT hash(): str hash is salted per process; weights must agree
        # across workers for the same model name
        seed = zlib.crc32(self.model_name.encode())
        rng = jax.random.key(seed)
        with jax.default_device(jax.local_devices(backend="cpu")[0]):
            k1, k2, k3 = jax.random.split(rng, 3)
            # param shapes don't depend on the canvas — init at the smallest
            # spatial size the block stack can downsample (a full-res init
            # forward on host CPU would take minutes for SDXL)
            n_down = len(self.unet.config.block_out_channels) - 1
            sample_hw = 2 ** max(n_down, 2)
            unet_vars = self.unet.init(
                k1,
                jnp.zeros((1, sample_hw, sample_hw, self.unet.config.in_channels)),
                jnp.zeros((1,)),
                jnp.zeros((1, 77, self.unet.config.cross_attention_dim)),
                added_cond=self._dummy_added_cond(1),
            )
            text_vars = [
                enc.init(k2, jnp.zeros((1, 77), jnp.int32)) for enc in self.text_encoders
            ]
            vae_vars = self.vae.init(
                k3,
                jnp.zeros(
                    (1, sample_hw * self.latent_factor,
                     sample_hw * self.latent_factor, 3)
                ),
            )
        params = {
            "unet": unet_vars["params"],
            "text": [tv["params"] for tv in text_vars],
            "vae": vae_vars["params"],
        }
        return self._place(params)

    def _convert_params(self, model_dir: Path) -> dict:
        from ..models.conversion import (
            convert_clip,
            convert_unet,
            convert_vae,
            load_torch_state_dict,
        )

        params = {
            "unet": convert_unet(load_torch_state_dict(model_dir, "unet")),
            "vae": convert_vae(load_torch_state_dict(model_dir, "vae")),
            "text": [],
        }
        for sub in ("text_encoder", "text_encoder_2")[: len(self.text_encoders)]:
            params["text"].append(
                convert_clip(load_torch_state_dict(model_dir, sub))
            )
        return self._place(params)

    def _place(self, params, mesh=None, tensor_parts=None):
        """Cast to the serving dtype and place on the mesh.

        Data-only mesh: everything replicated (the batch shards instead).
        Tensor-parallel mesh: UNet / text-encoder / ControlNet kernels shard
        Megatron-style per parallel/tensor.py partition rules — XLA inserts
        the psums where row-parallel matmuls contract. The VAE stays
        replicated; its decode shards over `data` via the batch sharding.

        `mesh`/`tensor_parts` default to the pipeline's construction-time
        view; the elastic-geometry path (params_for) passes an alternate
        mesh over the same chips.
        """
        mesh = self.mesh if mesh is None else mesh
        if tensor_parts is None:
            tensor_parts = mesh.shape.get("tensor", 1)
        cast = lambda x: jnp.asarray(x, self.dtype)
        params = jax.tree_util.tree_map(cast, params)
        if tensor_parts <= 1:
            return jax.device_put(params, replicated(mesh))
        from ..parallel.tensor import shard_params

        def place_component(name, tree):
            if name == "vae":
                return jax.device_put(tree, replicated(mesh))
            if isinstance(tree, list):
                return [shard_params(mesh, t) for t in tree]
            return shard_params(mesh, tree)

        return {k: place_component(k, v) for k, v in params.items()}

    # --- elastic slice geometry (ISSUE 12) ---

    def resolve_geometry(self, geometry) -> tuple[int, int]:
        """A per-pass geometry request -> validated (tensor, seq) over
        this pipeline's chipset; anything that cannot mesh (no chipset,
        bad divisor, single chip) falls back to the default view so a
        malformed request degrades to the classic pass, never fails it.
        Accepts a dict ({"tensor": t, "seq": s}), a (tensor, seq) tuple,
        or None/"default"."""
        if geometry is None or geometry == "default" or self.chipset is None:
            return self.default_geometry
        try:
            if isinstance(geometry, dict):
                tensor = geometry.get("tensor")
                seq = geometry.get("seq")
            else:
                tensor, seq = geometry
            resolved = self.chipset.resolve_geometry(tensor, seq)
        except (TypeError, ValueError):
            resolved = None
        if resolved is None:
            logger.warning(
                "geometry request %r does not fit slice %s; serving the "
                "default view", geometry,
                getattr(self.chipset, "identifier", lambda: "?")())
            return self.default_geometry
        return resolved

    def _geometry_view(self, geo: tuple[int, int]):
        """(mesh, placed base params) for one validated geometry over the
        slice's chips. The default view is the construction-time mesh +
        self.params (no copy); alternates are placed lazily from the
        resident tree — a reshard over ICI, not a reload — and kept in a
        tiny LRU. Thread-safe under the jit lock: geometry swaps happen on
        executor threads."""
        if geo == self.default_geometry:
            return self.mesh, self.params
        with self._jit_lock:
            if geo in self._geometries:
                self._geometries.move_to_end(geo)
                return self._geometries[geo]
        tensor, seq = geo
        mesh = self.chipset.mesh(tensor=tensor, seq=seq)
        base = self.params
        if base is None:
            raise Exception(
                f"pipeline {self.model_name} was evicted; resubmit the job")
        placed = self._place(base, mesh=mesh, tensor_parts=tensor)
        with self._jit_lock:
            self._geometries[geo] = (mesh, placed)
            self._geometries.move_to_end(geo)
            while len(self._geometries) > MAX_RESIDENT_GEOMETRIES:
                self._geometries.popitem(last=False)
        if self.chipset is not None:
            from ..chips.allocator import note_resident

            note_resident(self.model_name, self.chipset.slice_id)
        return mesh, placed

    def _dummy_added_cond(self, b):
        return dummy_added_cond(self.unet.config, b) if self.is_xl else None

    def _xl_time_ids(self, pooled_dim: int, height: int, width: int,
                     aesthetic_score: float = 6.0) -> list:
        """SDXL micro-conditioning id vector for this canvas. ONE
        implementation for the solo and batched paths — the 5-id refiner
        layout carries the aesthetic score (SDXL paper appendix)."""
        cfg = self.unet.config
        n_ids = (cfg.addition_embed_dim - pooled_dim) // (
            cfg.addition_time_embed_dim
        )
        if n_ids == 5:
            return [height, width, 0, 0, float(aesthetic_score)]
        return [height, width, 0, 0, height, width][:n_ids]

    def _place_batch(self, x, mesh=None):
        """Shard a leading-batch array over the mesh's data axis when the
        batch divides it evenly; replicate otherwise (rank-preserving
        placeholders, odd batches). Shared by solo and batched paths;
        `mesh` defaults to the construction-time view."""
        mesh = self.mesh if mesh is None else mesh
        data_parts = mesh.shape.get("data", 1)
        if data_parts > 1 and x.shape[0] % data_parts == 0:
            return jax.device_put(x, batch_sharding(mesh, x.ndim))
        return jax.device_put(x, replicated(mesh))

    def release(self):
        """Drop device references so HBM frees on registry eviction."""
        from .. import lora_operands

        # device-resident operand stacks for this model free WITH it
        # (their buffers were placed for this pipeline's mesh)
        lora_operands.invalidate_model(self.model_name)
        self.params = None
        self._programs.clear()
        self._runner_cache.clear()
        self._geometries.clear()
        self._controlnets.clear()
        self._lora_cache.clear()
        self._ti_cache.clear()
        self._vae_cache.clear()

    def _note_base_residency(self) -> None:
        """Residency event for an ADAPTER pass, keyed on the BASE model
        (ISSUE 13 satellite): a LoRA-heavy tenant's traffic must warm the
        same slice affinity as plain traffic — the registry's load/hit
        events fire at get_pipeline, but the adapter resolution inside a
        pass is a residency signal of its own (the factors, programs,
        and base tree all live here now)."""
        if self.chipset is None:
            return
        try:
            from ..chips.allocator import note_resident

            note_resident(self.model_name, self.chipset.slice_id)
        except Exception:  # placement is advisory; never fail a job over it
            logger.debug("adapter residency note failed", exc_info=True)

    def _adapter_delta_factors(self, lora: dict) -> dict | None:
        """Matched, delta-eligible factors for one adapter reference —
        the runtime per-row path (ISSUE 13) — or None when the adapter
        must fall back to the merged-tree path: runtime deltas disabled
        (Settings.lora_runtime_delta), modules the per-row Dense delta
        cannot express (conv/LoCon, shape-mismatched), or a rank past
        Settings.lora_rank_max (the padded stack would dwarf the batch).
        Load failures raise ValueError (fatal job error, reference
        contract). Resolution goes through the process-wide byte-capped
        factor cache (lora_cache.py) either way."""
        settings = load_settings()
        if not bool(getattr(settings, "lora_runtime_delta", True)):
            return None
        from .. import lora_cache
        from .lora_runtime import adapter_rank

        factors, derived = lora_cache.resolve_entry(lora, self.model_name)
        self._note_base_residency()
        # the Dense match walks the whole UNet param tree — fully
        # determined by (adapter, model), so it memoizes in the cache
        # entry's derived slot (same lifetime as the factors; the
        # rank-cap gate below stays per-call so a settings flip applies
        # to resident adapters too)
        memo_key = ("dense_match", self.model_name)
        verdict = derived.get(memo_key) if derived is not None else None
        if verdict is None:
            from ..models.lora import (match_dense_factors,
                                       match_te_dense_factors)

            matched, unmatched = match_dense_factors(
                factors, self.params["unet"])
            # text-encoder factors (te{i}:-namespaced, ISSUE 16) match
            # against the encoder trees and ride the SAME operand dict —
            # the ':' in their keys keeps the UNet interceptor away
            te_matched, te_unmatched = match_te_dense_factors(
                factors, self.params.get("text") or [])
            matched = {**matched, **te_matched}
            unmatched += te_unmatched
            if not matched:
                raise ValueError(
                    f"Could not load lora {lora}: no modules matched "
                    f"{self.model_name}'s parameter tree"
                )
            if unmatched:
                # the adapter carries content the per-row Dense delta
                # can't express (conv/LoCon) — route it to the merged
                # tree, the one conservative path for such adapters.
                # KNOWN GAP (ROADMAP): _merge_deltas currently also
                # skips shape-mismatched modules with a warning, so
                # today both paths drop the conv content; the fallback
                # keeps these adapters on the path where a real LoCon
                # conv merge lands when implemented, rather than baking
                # partial-delta semantics into the gang vocabulary
                logger.info(
                    "adapter %s has %d non-Dense module(s); merged-tree "
                    "fallback", lora.get("lora"), unmatched)
            verdict = (None if unmatched else matched,
                       adapter_rank(matched))
            if derived is not None:
                derived[memo_key] = verdict
        matched, rank = verdict
        if matched is None:
            return None
        rank_cap = int(getattr(settings, "lora_rank_max", 128) or 0)
        if rank_cap and rank > rank_cap:
            logger.info(
                "adapter %s rank %d exceeds lora_rank_max=%d; merged-tree "
                "fallback", lora.get("lora"), rank, rank_cap)
            return None
        return matched

    @staticmethod
    def _require_runtime_delta() -> None:
        """The kill switch: delta serving disabled means adapter groups
        refuse (the solo fallback serves each member via the merged
        tree). Shared by run_batched and the multi-chunk prescan so the
        refusal (and the message callers match on) cannot drift."""
        if not bool(getattr(load_settings(), "lora_runtime_delta", True)):
            raise ValueError(
                "runtime LoRA deltas are disabled "
                "(lora_runtime_delta=0); serving members individually")

    @staticmethod
    def _adapter_slots_cap(lora_slots_max: int | None) -> int:
        return int(lora_slots_max
                   or getattr(load_settings(), "lora_slots_max", 8)
                   or 8)

    def _scan_adapter_specs(self, specs) -> tuple[dict, set, list]:
        """One pass's adapter eligibility scan: resolve every DISTINCT
        adapter once (factor-cache backed; the match verdict memoizes
        in the entry's derived slot) -> (factors_of by adapter key,
        distinct eligible keys, ineligible member job_ids). Load
        FAILURES raise plain ValueError: the classic whole-group
        fallback reproduces the fatal error with per-job attribution.
        Shared by run_batched and prescan_adapter_chunks."""
        from .. import lora_cache

        factors_of: dict[tuple, dict | None] = {}
        distinct: set = set()
        ineligible: list = []
        for spec in specs:
            lora = spec.get("lora")
            if not lora:
                continue
            akey = lora_cache.adapter_key(lora)
            if akey not in factors_of:
                factors_of[akey] = self._adapter_delta_factors(lora)
            if factors_of[akey] is None:
                ineligible.append(spec.get("job_id"))
            else:
                distinct.add(akey)
        return factors_of, distinct, ineligible

    def prescan_adapter_chunks(self, chunks: list[list[dict]],
                               lora_slots_max: int | None = None) -> None:
        """Raise every adapter refusal run_batched would hit in ANY pass
        of a multi-pass group — the kill switch, delta-ineligible
        adapters (DeltaIneligibleError naming every affected member),
        the per-pass distinct-adapter slots cap — BEFORE the first pass
        runs. A group split across passes otherwise wastes work: a
        LATER chunk's refusal discards earlier chunks' finished denoise
        output and re-counts their row metrics on the worker's
        re-batch. Built from the same scan run_batched uses per call,
        so the two cannot desynchronize."""
        if not any(s.get("lora") for chunk in chunks for s in chunk):
            return
        from .lora_runtime import DeltaIneligibleError

        self._require_runtime_delta()
        slots_cap = self._adapter_slots_cap(lora_slots_max)
        ineligible: list = []
        overflow = False
        for chunk in chunks:
            _factors, distinct, inel = self._scan_adapter_specs(chunk)
            ineligible.extend(inel)
            overflow = overflow or len(distinct) > slots_cap
        # ineligibility outranks the cap, as in run_batched (its slot
        # assignment never starts when the eligibility scan refuses)
        if ineligible:
            raise DeltaIneligibleError(ineligible)
        if overflow:
            raise ValueError(
                f"group carries more than {slots_cap} distinct adapters "
                "in one pass; serving members individually")

    def _lora_operands(self, adapters: list[dict], row_slots: list[int],
                       row_gains: list[float],
                       adapter_keys: tuple | None = None):
        """Stack matched factors into the jitted program's lora operand,
        replicated over the pass mesh (the stacks are weights-like: a
        few MiB against the batch, and the slot dim must never be
        mistaken for a batch dim by the data-axis sharder).

        Operand residency (ISSUE 16): with `adapter_keys` (the factor-
        cache keys in SLOT ORDER — the stack recipe), the device-resident
        operand cache (lora_operands.py) is consulted FIRST; a hit skips
        assembly and upload entirely — steady state is a dict lookup
        handing jit the resident stacks plus this pass's tiny slot/gain
        vectors. Sets `self.last_operand_stats` for the envelope."""
        from .. import lora_operands
        from .lora_runtime import build_stacks, row_operands, stacks_sig

        sig = stacks_sig(adapters)
        cache = lora_operands.get_cache()
        key = None
        if cache is not None and adapter_keys is not None:
            key = (self.model_name, tuple(adapter_keys), sig,
                   np.dtype(self.dtype).name, self.default_geometry)
        a_map = b_map = None
        hits, bytes_saved = 0, 0
        if key is not None:
            entry = cache.lookup(key)
            if entry is not None:
                (a_map, b_map), nbytes = entry
                hits, bytes_saved = 1, int(nbytes)
        if a_map is None:
            a_map, b_map, nbytes = build_stacks(adapters, self.dtype, sig)
            if self.mesh.devices.size > 1:
                a_map = jax.device_put(a_map, replicated(self.mesh))
                b_map = jax.device_put(b_map, replicated(self.mesh))
            if key is not None:
                cache.put(key, (a_map, b_map), nbytes)
        operands = row_operands(a_map, b_map, row_slots, row_gains)
        if self.mesh.devices.size > 1:
            operands["slot"] = jax.device_put(
                operands["slot"], replicated(self.mesh))
            operands["gain"] = jax.device_put(
                operands["gain"], replicated(self.mesh))
        self.last_operand_stats = {"hits": hits, "misses": 1 - hits,
                                   "bytes_saved": bytes_saved}
        return operands, sig

    def _lora_params(self, base_params: dict, lora: dict, scale: float) -> dict:
        """Base params with a LoRA merged into the UNet — the FALLBACK
        path (ISSUE 13): adapters the runtime per-row delta cannot
        express still work, at the old cost of a full UNet copy. Merges
        from the byte-capped factor cache (lora_cache.py), so the
        safetensors parse is shared with the delta path; the merged
        trees themselves keep only a tiny LRU (each entry pins a full
        UNet copy in HBM — the very cost the delta path removes).
        Load failures raise ValueError -> fatal job error, matching the
        reference's "incompatible lora" contract.
        """
        key = (lora.get("lora"), lora.get("weight_name"), lora.get("subfolder"),
               round(scale, 4))
        if key in self._lora_cache:
            self._lora_cache.move_to_end(key)
            return self._lora_cache[key]
        from .. import lora_cache
        from ..models.lora import merge_factors, merge_te_factors

        factors = lora_cache.resolve(lora, self.model_name)
        self._note_base_residency()
        ref = str(lora.get("lora"))
        merged_unet, matched = merge_factors(
            base_params["unet"], factors, scale, ref)
        # text-encoder factors merge into encoder-tree copies (ISSUE
        # 16); swapping params["text"] off the resident list makes the
        # prompt-embedding cache's identity check bypass automatically
        merged_text, te_matched = merge_te_factors(
            base_params.get("text") or [], factors, scale, ref)
        if matched + te_matched == 0:
            raise ValueError(
                f"Could not load lora {lora}: no modules matched "
                f"{self.model_name}'s parameter tree"
            )
        logger.info(
            "merged LoRA %s into %s (%d unet + %d text modules, "
            "scale %.2f)",
            lora.get("lora"), self.model_name, matched, te_matched, scale,
        )
        params = dict(base_params)
        if matched:
            params["unet"] = self._place({"unet": merged_unet})["unet"]
        if te_matched:
            params["text"] = self._place({"text": merged_text})["text"]
        self._lora_cache[key] = params
        while len(self._lora_cache) > MAX_RESIDENT_LORAS:
            self._lora_cache.popitem(last=False)
        return params

    def _ti_apply(self, ti_ref) -> tuple[list, list]:
        """-> (per-encoder extra-embedding tables, tokenizers with the
        placeholder tokens). Cached per ref; vectors route to whichever
        encoder's hidden width they match (SDXL ships per-encoder embeds).
        The placeholder vectors ride as *inputs* to the encoders (ids past
        vocab_size index into them), leaving the resident params untouched.
        """
        key = str(ti_ref)
        if key in self._ti_cache:
            self._ti_cache.move_to_end(key)
            return self._ti_cache[key]
        from ..models.tokenizer import PlaceholderTokenizer

        groups = load_learned_embeddings(ti_ref)
        extras = []
        tokenizers = []
        applied = False
        for enc, tok in zip(self.text_encoders, self.tokenizers):
            dim = enc.config.hidden_size
            vocab = enc.config.vocab_size
            placeholders = {}
            rows = []
            next_id = vocab
            for group in groups:
                vec = next(
                    (v for v in group["vectors"] if v.shape[-1] == dim), None
                )
                if vec is None:
                    continue
                ids = list(range(next_id, next_id + vec.shape[0]))
                for alias in group["tokens"]:
                    placeholders[alias] = ids
                rows.append(vec)
                next_id += vec.shape[0]
            if not rows:
                extras.append(None)
                tokenizers.append(tok)
                continue
            extras.append(
                jax.device_put(
                    jnp.asarray(np.concatenate(rows, axis=0), self.dtype),
                    replicated(self.mesh),
                )
            )
            tokenizers.append(PlaceholderTokenizer(tok, placeholders))
            applied = True
            logger.info(
                "textual inversion %s: %d group(s) for %s's encoder %d",
                ti_ref, len(rows), self.model_name, len(extras) - 1,
            )
        if not applied:
            dims = sorted({
                v.shape[-1] for g in groups for v in g["vectors"]
            })
            raise ValueError(
                f"Textual inversion {ti_ref} is incompatible with "
                f"{self.model_name}: embedding widths {dims} match no "
                f"text encoder"
            )
        self._ti_cache[key] = (extras, tokenizers)
        while len(self._ti_cache) > MAX_RESIDENT_TI:
            self._ti_cache.popitem(last=False)
        return extras, tokenizers

    def _custom_vae(self, name: str) -> dict:
        """Converted per-job VAE (reference diffusion_func.py:46-49),
        resident + LRU-bounded; missing weights are a fatal job error."""
        if name in self._vae_cache:
            self._vae_cache.move_to_end(name)
            return self._vae_cache[name]
        from ..models.conversion import convert_vae, load_torch_state_dict

        root = Path(load_settings().model_root_dir).expanduser() / name
        state = None
        for sub in ("", "vae"):
            try:
                state = load_torch_state_dict(root, sub)
                break
            except FileNotFoundError:
                continue
        if state is None:
            raise ValueError(
                f"Could not load custom VAE {name}: no safetensors under "
                f"{root}. Prefetch it with `chiaswarm-tpu-init --download "
                f"--models {name}`."
            )
        params = self._place({"vae": convert_vae(state)})["vae"]
        self._vae_cache[name] = params
        while len(self._vae_cache) > MAX_RESIDENT_VAES:
            self._vae_cache.popitem(last=False)
        return params

    def _get_controlnet(self, name: str):
        """Resident ControlNet branch sharing this model's UNet config.

        Converted weights when `<model_root>/<name>` ships safetensors.
        Missing weights are a fatal job error — a zero-init branch is a
        mathematical no-op that would silently ignore the user's control
        image (VERDICT weak #6); zero-init remains only for test/tiny
        control names and explicit random-init opt-in.
        """
        if name in self._controlnets:
            return self._controlnets[name]
        from ..models.controlnet import ControlNetModel
        from ..weights import require_weights_present

        cn = ControlNetModel(
            self.unet.config, cond_downscale=self.latent_factor, dtype=self.dtype
        )
        root = Path(load_settings().model_root_dir).expanduser() / name
        params = None
        if root.is_dir():
            try:
                from ..models.conversion import (
                    convert_unet,
                    load_torch_state_dict,
                )

                params = self._place(
                    {"cn": convert_unet(load_torch_state_dict(root))}
                )["cn"]
            except FileNotFoundError:
                pass
        if params is None:
            require_weights_present(
                name, root, self.allow_random_init, component="ControlNet"
            )
            logger.warning("no safetensors under %s; zero-init control", root)
            sample_hw = 2 * self.latent_factor  # any valid spatial size
            with jax.default_device(jax.local_devices(backend="cpu")[0]):
                params = cn.init(
                    jax.random.key(zlib.crc32(name.encode())),
                    jnp.zeros((1, sample_hw, sample_hw, self.unet.config.in_channels)),
                    jnp.zeros((1,)),
                    jnp.zeros((1, 77, self.unet.config.cross_attention_dim)),
                    jnp.zeros(
                        (1, sample_hw * self.latent_factor,
                         sample_hw * self.latent_factor, 3)
                    ),
                    added_cond=self._dummy_added_cond(1),
                )["params"]
            params = self._place({"cn": params})["cn"]
        self._controlnets[name] = (cn, params)
        return cn, params

    def _run_qr_two_stage(self, prompt, negative_prompt, pipeline_type,
                          **kwargs):
        """QR-monster chain (reference diffusion_func.py:78-101): a plain
        txt2img prepipeline composes the scene at half resolution, the
        result upscales, and the ControlNet img2img pass imposes the QR
        structure at full size. The reference chained through a raw latent
        2x interpolation; here the handoff is pixel-space (upscale + VAE
        re-encode), preserving the two-stage semantics with one code path.
        """
        kwargs.pop("controlnet_prepipeline_type", None)
        height = int(kwargs.pop("height", None) or self.default_size)
        width = int(kwargs.pop("width", None) or self.default_size)
        strength = float(kwargs.pop("strength", 0.9))
        rng = kwargs.pop("rng", None)
        if rng is None:
            rng = jax.random.key(0)
        rng, stage1_rng, stage2_rng = jax.random.split(rng, 3)

        cn_kwargs = {
            k: kwargs.pop(k)
            for k in (
                "controlnet_model_name", "control_image",
                "controlnet_conditioning_scale", "control_guidance_start",
                "control_guidance_end",
            )
            if k in kwargs
        }
        # the txt2img-ControlNet wire delivers the QR as `image`
        # (job_arguments format_controlnet_args sets args["image"])
        start_image = kwargs.pop("image", None)
        if cn_kwargs.get("control_image") is None and start_image is not None:
            cn_kwargs["control_image"] = start_image
        if cn_kwargs.get("control_image") is None:
            raise ValueError("Controlnet specified but no control image provided")

        stage1_kwargs = dict(kwargs)
        # one composition image is all stage 2 consumes
        stage1_kwargs["num_images_per_prompt"] = 1
        t0 = time.perf_counter()
        stage1, _ = self.run(
            prompt=prompt,
            negative_prompt=negative_prompt,
            pipeline_type=pipeline_type,
            height=max(height // 2, 64),
            width=max(width // 2, 64),
            rng=stage1_rng,
            **stage1_kwargs,
        )
        prepipeline_s = round(time.perf_counter() - t0, 3)

        base = stage1[0].resize((width, height), Image.LANCZOS)
        images, config = self.run(
            prompt=prompt,
            negative_prompt=negative_prompt,
            pipeline_type=pipeline_type,
            image=base,
            strength=strength,
            height=height,
            width=width,
            rng=stage2_rng,
            **cn_kwargs,
            **kwargs,
        )
        config["prepipeline"] = "qr_two_stage"
        config["timings"]["prepipeline_s"] = prepipeline_s
        return images, config

    # --- text conditioning (host + tiny device work, once per job) ---

    def _latent2x_impl(self, vae_params, px):
        """Encode -> bilinear 2x latent resize -> decode, one program.

        The round-1 `upscale: true` behavior, retained as the explicit
        fallback when stabilityai/sd-x2-latent-upscaler has no converted
        weights on this worker (reference chains the learned upscaler at
        swarm/diffusion/diffusion_func.py:163)."""
        z = self.vae.apply(
            {"params": vae_params}, px.astype(self.dtype),
            method=self.vae.encode,
        )
        b, h, w, c = z.shape
        z2 = jax.image.resize(
            z.astype(jnp.float32), (b, 2 * h, 2 * w, c), "bilinear"
        ).astype(self.dtype)
        out = self.vae.apply(
            {"params": vae_params}, z2, method=self.vae.decode
        )
        return (
            (out.astype(jnp.float32) + 1.0) * 127.5
        ).clip(0.0, 255.0).round().astype(jnp.uint8)

    def _encode_impl(self, text_params, ids_list, extras_list):
        """All text encoders fused into one jitted program."""
        hiddens, pooled = [], None
        for enc, p, ids, extra in zip(
            self.text_encoders, text_params, ids_list, extras_list
        ):
            out = enc.apply({"params": p}, ids, extra_embeddings=extra)
            hiddens.append(out["hidden_states"])
            pooled = out["pooled"]  # last encoder's pooled (SDXL: encoder 2)
        context = jnp.concatenate(hiddens, axis=-1) if len(hiddens) > 1 else hiddens[0]
        return context, pooled

    def _encode_delta_impl(self, text_params, ids_list, extras_list,
                           te_operands):
        """_encode_impl with the per-row TE-LoRA delta interceptor
        (ISSUE 16) wrapped around each encoder apply: the resident text
        params and the compiled structure stay untouched — adapter
        identity is data, exactly like the UNet delta path. Each encoder
        only matches stacks under ITS te{i}: namespace."""
        import flax.linen as nn

        from .lora_runtime import make_te_interceptor

        hiddens, pooled = [], None
        for i, (enc, p, ids, extra) in enumerate(zip(
            self.text_encoders, text_params, ids_list, extras_list
        )):
            with nn.intercept_methods(make_te_interceptor(te_operands, i)):
                out = enc.apply({"params": p}, ids, extra_embeddings=extra)
            hiddens.append(out["hidden_states"])
            pooled = out["pooled"]
        context = jnp.concatenate(hiddens, axis=-1) if len(hiddens) > 1 else hiddens[0]
        return context, pooled

    def encode_prompts(self, prompts: list[str], params: dict,
                       tokenizers=None, extra_embeddings=None,
                       te_operands=None):
        """-> (context [B,77,D], pooled [B,P] or None).

        One batched pass over all encoders in a single jitted dispatch —
        callers stack [negatives + prompts] so uncond/cond conditioning is
        one program call, not per-encoder op-by-op applies. `tokenizers` /
        `extra_embeddings` override the residents for textual-inversion
        placeholder tokens.

        Rows are served from the process-wide embedding cache
        (embed_cache.py, keyed (model, text)) whenever nothing job-
        specific perturbs the encoder: no tokenizer/embedding overrides
        and the pipeline's own resident text params. Only the texts the
        cache misses run the encoder — padded to a power-of-two bucket
        so distinct miss counts share one compiled program — so gang
        members and repeat prompts (the shared "" negative above all)
        skip text_encode entirely.
        """
        toks = tokenizers or self.tokenizers
        extras = extra_embeddings or [None] * len(toks)
        # per-pass cache stats for the envelope (the hive's tenant
        # ledger attributes embed-cache hits per job from it); reset
        # here so a bypassed encode reports nothing rather than the
        # previous pass's numbers. Instance state is safe: the slice
        # busy lock serializes passes through one pipeline.
        self.last_encode_stats = None
        cache = embed_cache.get_cache()
        # the resident text params, identity-compared below: a job that
        # swapped them (merged LoRA touching the encoders, custom
        # params) must bypass the cache or a stale row would leak in
        resident_text = (self.params.get("text")
                         if isinstance(self.params, dict) else None)
        if (cache is None or tokenizers is not None
                or extra_embeddings is not None
                or te_operands is not None
                or resident_text is None
                or params.get("text") is not resident_text):
            ids_list = [jnp.asarray(tok(prompts)) for tok in toks]
            if te_operands is not None:
                # TE-LoRA delta rows are adapter-specific: they bypass
                # the (model, text)-keyed embedding cache and run the
                # interceptor-wrapped twin program (ISSUE 16)
                context, pooled = self._encode_delta_program(
                    params["text"], ids_list, extras, te_operands)
            else:
                context, pooled = self._encode_program(
                    params["text"], ids_list, extras)
            return context, (pooled if self.is_xl else None)

        found: dict[str, tuple | None] = {}
        hits = misses = 0
        for text in prompts:
            if text in found:
                # duplicate row in this batch: whether its first
                # occurrence hit or missed, THIS row skips its encoder
                # forward (the batch encodes unique texts once), which
                # is exactly what the hit counter measures
                hits += 1
            else:
                found[text] = cache.lookup((self.model_name, text))
                if found[text] is None:
                    misses += 1
                else:
                    hits += 1
        cache.note_rows(hits, misses)
        self.last_encode_stats = (hits, misses)
        missing = [t for t, v in found.items() if v is None]
        if missing:
            from .common import pad_bucket

            # repeat the last miss into the padding rows: jit retraces
            # per batch shape, and pow2 bucketing keeps distinct miss
            # counts on a handful of compiled programs
            padded = missing + [missing[-1]] * (
                pad_bucket(len(missing)) - len(missing))
            ids_list = [jnp.asarray(tok(padded)) for tok in toks]
            context_m, pooled_m = self._encode_program(
                params["text"], ids_list, extras)
            ctx_np = np.asarray(context_m)
            pooled_np = (np.asarray(pooled_m)
                         if self.is_xl and pooled_m is not None else None)
            for i, text in enumerate(missing):
                # copy the row OUT of the padded batch: a bare ctx_np[i]
                # is a view whose .base pins the whole encode batch, so
                # the cache's byte accounting (row nbytes) would wildly
                # undercount what it actually keeps resident
                value = (np.ascontiguousarray(ctx_np[i]),
                         (np.ascontiguousarray(pooled_np[i])
                          if pooled_np is not None else None))
                found[text] = value
                cache.put((self.model_name, text), value)
        context = jnp.asarray(np.stack([found[t][0] for t in prompts]))
        pooled = None
        if self.is_xl:
            pooled = jnp.asarray(np.stack([found[t][1] for t in prompts]))
        return context, pooled

    # --- the jitted core ---

    def _denoise_parts(self, key, controlnet_module=None, mesh=None):
        """The denoise program's composable pieces for one bucket:
        ``prep`` (initial latents + scheduler state), ``make_steps(n)``
        (n compiled iterations of the shared step body, starting at a
        traced ``offset``), and ``decode`` (VAE decode + on-device uint8
        quantize), plus the loop bounds. ``_denoise_program`` fuses them
        into the classic single jitted program (the zero-cost
        ``denoise_chunk_steps=0`` path); the chunked path jits them
        separately so the executor thread can probe cancel tokens
        (cancel.py) between compiled chunks. Both paths run the exact
        same ops on the same values in the same order, so their outputs
        are bitwise identical (pinned by tests/test_cancel.py).

        key = (mode, lh, lw, batch, steps, scheduler_key, t_start,
               cn_key) where cn_key = (controlnet_name, cg_lo, cg_hi) or None
        """
        mode, lh, lw, batch, steps, sched_key, t_start, cn_key = key
        scheduler = get_scheduler(
            sched_key[0],
            **dict(sched_key[1]),
        )
        # On a multi-chip mesh every jax.random draw inside the program is
        # pinned replicated: GSPMD otherwise propagates the consumers'
        # sharding back into the threefry computation, and this jax's
        # non-partitionable RNG lowering then generates DIFFERENT values
        # per shard layout (the sharded-vs-replicated numerics drift that
        # broke test_parallel/test_seq_parallel_serving). The draw is a
        # few KB of latents against a multi-second denoise, so replicating
        # it costs nothing; single-chip programs keep their exact HLO.
        mesh = self.mesh if mesh is None else mesh
        multichip = mesh.devices.size > 1
        rep_sharding = replicated(mesh) if multichip else None

        def pin(z):
            if multichip:
                return jax.lax.with_sharding_constraint(z, rep_sharding)
            return z

        def draw_normal(rng_key, shape):
            return pin(jax.random.normal(rng_key, shape, jnp.float32))
        schedule = scheduler.schedule(steps)
        # most solvers: one model call per user step; Heun interleaves two
        # and maps the bounds onto its doubled index space
        loop_start, loop_end = scheduler.loop_bounds(schedule, steps, t_start)

        unet_apply = self.unet.apply
        vae = self.vae
        latent_c = self.latent_channels
        # pix2pix runs a 3-way CFG: rows [uncond | image-only | image+text]
        cfg_rows = 3 if mode == "pix2pix" else 2
        # chunked single-chip decode bounds peak decoder activations on big
        # canvases (batch 4 x 1024^2 OOM'd a v5e chip in round 1); on a
        # multi-chip mesh the batch is sharded so the full decode stays
        decode_area = lh * lw
        big_decode = decode_area >= 9216 and batch >= 2 and self.data_parts == 1

        def prep(params, init_rng, image_latents):
            """Initial latents (f32) + scheduler state, pre-step-loop."""
            if mode in ("batched", "batched_i2i"):
                # cross-job coalesced pass: init_rng is a [batch] key
                # array, one per row, each derived only from its own job's
                # seed — a job's images must not depend on its batchmates
                latents = pin(jax.vmap(
                    lambda k: jax.random.normal(k, (lh, lw, latent_c), jnp.float32)
                )(init_rng))
            else:
                latents = draw_normal(init_rng, (batch, lh, lw, latent_c))
            if mode in ("img2img", "batched_i2i", "inpaint"):
                # batched_i2i: image_latents is the [batch] stack of each
                # row's own start-image latents (padding rows zeros);
                # inpaint denoises from the clean image's noised latents
                latents = scheduler.add_noise(
                    schedule, image_latents, latents, loop_start
                )
            else:
                # txt2img and pix2pix both denoise from pure noise; pix2pix's
                # image conditioning rides the UNet's channel dim instead
                latents = latents * jnp.asarray(
                    schedule.init_noise_sigma, latents.dtype
                )
            state = scheduler.init_state(latents.shape, latents.dtype)
            return latents.astype(jnp.float32), state

        def make_steps(length: int):
            """`length` step-body iterations from a traced `offset` (the
            fused program passes loop_start once; the chunked path walks
            the same index sequence in denoise_chunk_steps strides)."""

            def run_steps(params, latents, state, context, added,
                          guidance_scale, image_guidance, image_latents,
                          mask, rng, cn_params, control_cond, cn_scale,
                          lora, offset):
                """context [cfg_rows*B,77,D] (uncond first). `lora` is the
                stacked per-row adapter operand (lora_runtime.py) — an
                EMPTY dict for adapter-free passes, which traces to the
                identical program (zero pytree leaves, no extra HLO)."""
                if lora:
                    from .lora_runtime import make_interceptor

                    lora_interceptor = make_interceptor(lora, cfg_rows)
                if mode == "pix2pix":
                    # per-row channel conditioning: zeros for the uncond
                    # row so image guidance has a true no-image baseline
                    cond_rows = jnp.concatenate(
                        [jnp.zeros_like(image_latents), image_latents,
                         image_latents], axis=0,
                    ).astype(self.dtype)
                if mode == "inpaint":
                    clean = image_latents
                if mode == "inpaint9":
                    # dedicated inpaint UNet: mask plane + masked-image
                    # latents ride the channel dim on both CFG rows
                    cond9 = jnp.concatenate([mask, image_latents], axis=-1)
                    cond9 = jnp.concatenate(
                        [cond9, cond9], axis=0).astype(self.dtype)
                if cn_key is not None:
                    control2 = jnp.concatenate(
                        [control_cond, control_cond], axis=0
                    ).astype(self.dtype)
                    _, cg_lo, cg_hi = cn_key

                def body(carry, i):
                    latents, state = carry
                    inp = scheduler.scale_model_input(schedule, latents, i)
                    model_in = jnp.concatenate(
                        [inp] * cfg_rows, axis=0).astype(self.dtype)
                    if mode == "pix2pix":
                        # image latents join unscaled: the edit checkpoint was
                        # trained on raw latent-dist modes
                        model_in = jnp.concatenate([model_in, cond_rows], axis=-1)
                    elif mode == "inpaint9":
                        model_in = jnp.concatenate([model_in, cond9], axis=-1)
                    t = jnp.asarray(schedule.timesteps)[i]
                    t_vec = jnp.broadcast_to(t, (model_in.shape[0],))
                    residual_kw = {}
                    if cn_key is not None:
                        # guidance window: the control branch is active only for
                        # steps in [cg_lo, cg_hi) (control_guidance_start/end)
                        eff = cn_scale * ((i >= cg_lo) & (i < cg_hi)).astype(
                            jnp.float32
                        )
                        down_res, mid_res = controlnet_module.apply(
                            {"params": cn_params},
                            model_in,
                            t_vec,
                            context,
                            control2,
                            conditioning_scale=eff,
                            added_cond=added,
                        )
                        residual_kw = {
                            "down_residuals": down_res,
                            "mid_residual": mid_res,
                        }
                    unet_in = (
                        {"params": params["unet"]}, model_in, t_vec, context)
                    if lora:
                        # scoped to the UNet apply alone: the ControlNet
                        # branch above shares module names (down_blocks_*/
                        # attn*), so a body-wide interceptor would apply
                        # the UNet's deltas to the control branch too
                        import flax.linen as fnn

                        with fnn.intercept_methods(lora_interceptor):
                            out = unet_apply(
                                *unet_in, added_cond=added, **residual_kw
                            ).astype(jnp.float32)
                    else:
                        out = unet_apply(
                            *unet_in, added_cond=added, **residual_kw
                        ).astype(jnp.float32)
                    # named scopes: flax scopes every module's ops by its
                    # path; the parts of the program that are no module
                    # get a name here, so a device trace can say what a
                    # `fusion` belongs to
                    with jax.named_scope("cfg_combine"):
                        if mode == "pix2pix":
                            # dual guidance (InstructPix2Pix eq. 3): text
                            # guidance pulls away from image-only, image
                            # guidance away from the fully-unconditional row
                            out_u, out_i, out_c = jnp.split(out, 3, axis=0)
                            out = (
                                out_u
                                + guidance_scale * (out_c - out_i)
                                + image_guidance * (out_i - out_u)
                            )
                        else:
                            out_u, out_c = jnp.split(out, 2, axis=0)
                            out = out_u + guidance_scale * (out_c - out_u)

                    if mode in ("batched", "batched_i2i"):
                        # per-row ancestral noise from per-job keys (same
                        # independence argument as the init draw)
                        noise = pin(jax.vmap(lambda k: jax.random.normal(
                            jax.random.fold_in(k, i), (lh, lw, latent_c),
                            jnp.float32))(rng))
                    else:
                        noise = draw_normal(
                            jax.random.fold_in(rng, i), latents.shape
                        )
                    with jax.named_scope("scheduler_step"):
                        state, latents = scheduler.step(
                            schedule, state, i, latents, out, noise
                        )
                    if mode == "inpaint":
                        # keep the unmasked region on the original image's
                        # noise trajectory (4-channel inpainting)
                        keep = scheduler.add_noise(
                            schedule,
                            clean,
                            draw_normal(
                                jax.random.fold_in(rng, 7919 + i), clean.shape
                            ),
                            jnp.minimum(i + 1, loop_end - 1),
                        )
                        keep = jnp.where(i == loop_end - 1, clean, keep)
                        latents = mask * latents + (1.0 - mask) * keep
                    return (latents, state), ()

                (latents, state), _ = jax.lax.scan(
                    body, (latents, state), jnp.arange(length) + offset
                )
                return latents, state

            return run_steps

        @jax.named_scope("vae_decode")
        def decode(params, latents):
            latents = latents.astype(self.dtype)
            if big_decode:
                pixels = jax.lax.map(
                    lambda z: vae.apply(
                        {"params": params["vae"]}, z[None], method=vae.decode
                    )[0],
                    latents,
                )
            else:
                pixels = vae.apply(
                    {"params": params["vae"]}, latents, method=vae.decode
                )
            # quantize on device: uint8 transfer is 4x smaller than fp32 and
            # leaves the host with nothing to do but wrap PIL around it
            return (
                (pixels.astype(jnp.float32) + 1.0) * 127.5
            ).clip(0.0, 255.0).round().astype(jnp.uint8)

        return prep, make_steps, decode, (loop_start, loop_end)

    @staticmethod
    def _program_cache_max() -> int:
        """Settings.program_cache_max at call time (env-overridable,
        CHIASWARM_PROGRAM_CACHE_MAX); 0 = unbounded. A malformed value
        fails loudly, like every other setting."""
        return max(int(load_settings().program_cache_max or 0), 0)

    def _trim_program_caches(self) -> None:
        """LRU-bound both variant caches to program_cache_max (caller
        holds _jit_lock). Evicted programs get their compiled executable
        dropped too (PjitFunction.clear_cache) — evicting only the dict
        reference would leak the XLA executable until pipeline release,
        which is exactly the unbounded axis this bound exists to close.
        A runner closure may still reference a cleared program; its next
        call retraces (counted as a compile-cache miss), never breaks."""
        cap = self._program_cache_max()
        if cap <= 0:
            return
        while len(self._programs) > cap:
            _, evicted = self._programs.popitem(last=False)
            clear = getattr(evicted, "clear_cache", None)
            if callable(clear):
                try:
                    clear()
                except Exception:  # freeing best-effort, never fatal
                    logger.debug("clear_cache failed on evicted program",
                                 exc_info=True)
            _PROGRAM_EVICTED.inc(kind="program")
        while len(self._runner_cache) > cap:
            self._runner_cache.popitem(last=False)
            _PROGRAM_EVICTED.inc(kind="runner")

    def _program(self, cache_key, build, kind="program",
                 analytic_flops=None):
        """One jitted program per cache key, sharing the compile-cache
        metrics and the placement-layer residency note across every
        denoise program kind (fused, prep, chunk, decode). Every compile
        registers with the program ledger (programs.py, ISSUE 17);
        `analytic_flops` — supplied by sites that know their program's
        models/flops.py count — arms the analytic-vs-XLA divergence
        cross-check on first call."""
        with self._jit_lock:
            cached = self._programs.get(cache_key)
            if cached is not None:
                self._programs.move_to_end(cache_key)
                _COMPILE_CACHE.inc(event="hit")
                return cached
        _COMPILE_CACHE.inc(event="miss")
        if self.chipset is not None:
            # compile event -> placement layer: refresh this model's
            # residency so the dispatch board keeps routing same-model
            # groups at the slice that owns the jitted programs
            from ..chips.allocator import note_resident

            note_resident(self.model_name, self.chipset.slice_id)
        program = programs.instrument(
            jax.jit(build()), model=self.model_name, kind=kind,
            key=cache_key, analytic_flops=analytic_flops)
        with self._jit_lock:
            self._programs[cache_key] = program
            self._programs.move_to_end(cache_key)
            self._trim_program_caches()
        return program

    def _geo_key(self, key, geo):
        """Program-cache key for one bucket under one geometry. The
        default view keeps the BARE bucket key — byte-identical to the
        pre-geometry cache, so the zero-cost pinning (exactly one
        program per bucket at chunk=0) holds — and alternates suffix it."""
        if geo is None or geo == self.default_geometry:
            return key
        return (key, "geo", geo)

    @staticmethod
    def _sig_key(gkey, lora_sig):
        """Adapter-pass program-cache suffix (ISSUE 13): adapter-free
        passes keep the bare (geometry-suffixed) key so every pre-LoRA
        cache-shape pin holds; runtime-delta passes compile per
        (slot-bucket, rank-bucket, targeted-module-set) signature — adapter
        IDENTITY is data,
        so swapping adapters inside one signature never recompiles."""
        if lora_sig is None:
            return gkey
        return (gkey, "lora", lora_sig)

    def _denoise_program(self, key, controlnet_module=None, geo=None,
                         mesh=None, lora_sig=None, analytic_flops=None):
        """Build (or fetch) the classic fused jitted denoise+decode
        program for one bucket — prep, the full step loop, and decode in
        ONE dispatch. This is the denoise_chunk_steps=0 path, cached
        under the bare bucket key exactly as before the chunked seam
        (geometry-suffixed for non-default mesh views, signature-suffixed
        for runtime-delta adapter passes)."""

        def build():
            prep, make_steps, decode, (lo, hi) = self._denoise_parts(
                key, controlnet_module, mesh=mesh)
            run_steps = make_steps(hi - lo)

            def sd_denoise_decode(params, init_rng, context, added,
                                  guidance_scale, image_guidance,
                                  image_latents, mask, rng, cn_params,
                                  control_cond, cn_scale, lora):
                latents, state = prep(params, init_rng, image_latents)
                latents, _ = run_steps(
                    params, latents, state, context, added, guidance_scale,
                    image_guidance, image_latents, mask, rng, cn_params,
                    control_cond, cn_scale, lora, jnp.int32(lo))
                return decode(params, latents)

            return sd_denoise_decode

        return self._program(
            self._sig_key(self._geo_key(key, geo), lora_sig), build,
            kind="fused", analytic_flops=analytic_flops)

    def _denoise_chunk_steps(self) -> int:
        """Settings.denoise_chunk_steps at call time (env-overridable per
        process, CHIASWARM_DENOISE_CHUNK_STEPS); 0 = single fused pass."""
        return max(int(load_settings().denoise_chunk_steps or 0), 0)

    def _chunk_programs(self, key, controlnet_module, geo, mesh, chunk,
                        lora_sig=None, analytic_flops=None):
        """(prep, chunk_for, decode, lengths, lo) — the compiled program
        set for one bucket under one geometry, plus the chunk walk it
        serves. ``chunk_for(n)`` resolves the compiled length-n step
        chunk — the walk's lengths are resolved eagerly here (so the
        caller's compile span stays honest), while a length the original
        walk never needed (a mid-pass RESUME's remainder chunk, ISSUE 18)
        compiles on first request under the same cache key scheme.
        Shared by the chunked runner and the mid-pass re-shard path
        (which resolves the TARGET geometry's set lazily at the first
        seam that needs it; the walk is bucket-derived, so both
        geometries share it). Adapter passes (lora_sig) suffix only
        the STEP chunks: prep and decode never see the lora operand, so
        adapter and plain passes share those compiled programs."""
        prep_fn, make_steps, decode_fn, (lo, hi) = self._denoise_parts(
            key, controlnet_module, mesh=mesh)
        lengths: list[int] = []
        pos = lo
        while pos < hi:
            lengths.append(min(chunk, hi - pos))
            pos += lengths[-1]
        gkey = self._geo_key(key, geo)
        skey = self._sig_key(gkey, lora_sig)
        prep_prog = self._program((gkey, "prep"), lambda: prep_fn,
                                  kind="prep")
        # the analytic count covers the whole denoise span; a length-n
        # chunk owns its proportional share of the (hi - lo) steps
        per_step = (analytic_flops / (hi - lo)
                    if analytic_flops and hi > lo else None)

        def chunk_for(n: int):
            n = int(n)
            return self._program(
                (skey, "chunk", n), lambda: make_steps(n), kind="chunk",
                analytic_flops=(per_step * n if per_step else None))

        for n in set(lengths):
            chunk_for(n)
        decode_prog = self._program((gkey, "decode"), lambda: decode_fn,
                                    kind="decode")
        return prep_prog, chunk_for, decode_prog, lengths, lo

    def _migrate_operands(self, mesh, operands: tuple) -> tuple:
        """Re-place a chunked pass's live operands onto another mesh view
        of the same chips (the chunk-seam re-shard): leading-batch arrays
        keep their data-axis sharding when divisible, everything else
        replicates. Pure data movement — values are bit-identical, so a
        migrated pass equals an undisturbed one up to the float
        reassociation the geometries themselves differ by."""

        def place(x):
            if getattr(x, "ndim", 0) == 0:
                return jax.device_put(x, replicated(mesh))
            return self._place_batch(x, mesh=mesh)

        # tree_map traverses dicts (added, cn_params), skips Nones, and
        # applies directly to bare arrays (latents, context, rng keys)
        return tuple(jax.tree_util.tree_map(place, op) for op in operands)

    def _rehydrate(self, resume, latents, state, mesh, lo, hi):
        """Swap a freshly-prepped (latents, scheduler state) for a
        checkpoint's arrays (ISSUE 18 resume-on-redelivery): prep
        supplies the pytree STRUCTURE and the placement recipe, the
        checkpoint supplies values, so the resumed chunk programs see
        operands indistinguishable from an undisturbed pass at step K.
        Validates the step against this bucket's denoise span and every
        array against its prepped twin — any mismatch raises and the
        caller degrades to the full pass."""
        at = int(resume.get("step", lo))
        if not (lo < at < hi):
            raise ValueError(
                f"resume step {at} outside the denoise span [{lo}, {hi})")
        ck_latents = np.asarray(resume["latents"])
        if (tuple(ck_latents.shape) != tuple(latents.shape)
                or ck_latents.dtype != np.dtype(latents.dtype)):
            raise ValueError(
                f"checkpoint latents {ck_latents.dtype}{ck_latents.shape} "
                f"do not match this bucket's "
                f"{np.dtype(latents.dtype)}{tuple(latents.shape)}")
        leaves, treedef = jax.tree_util.tree_flatten(state)
        ck_leaves = list(resume.get("state_leaves") or [])
        if len(ck_leaves) != len(leaves):
            raise ValueError(
                f"checkpoint carries {len(ck_leaves)} scheduler leaves, "
                f"this program has {len(leaves)}")

        def place(x):
            if getattr(x, "ndim", 0) == 0:
                return jax.device_put(jnp.asarray(x), replicated(mesh))
            return self._place_batch(jnp.asarray(x), mesh=mesh)

        placed = []
        for fresh, ck in zip(leaves, ck_leaves):
            ck = np.asarray(ck)
            if (tuple(ck.shape) != tuple(getattr(fresh, "shape", ()))
                    or ck.dtype != np.dtype(fresh.dtype)):
                raise ValueError("checkpoint scheduler leaf mismatch")
            placed.append(place(ck))
        return (at, place(ck_latents),
                jax.tree_util.tree_unflatten(treedef, placed))

    def _denoise_runner(self, key, controlnet_module=None, geo=None,
                        lora_sig=None, analytic_flops=None):
        """Resolve the execution strategy for one bucket. Returns
        ``runner(*program_args, cancel_probe=None, reshard_probe=None)
        -> uint8 pixels``.

        denoise_chunk_steps=0: the fused single program — the probe (if
        any) runs once before launch, so a job cancelled while it waited
        for the slice still aborts for free, but a cancel landing
        mid-pass waits out the full pass (the pre-chunking behavior).

        denoise_chunk_steps=N: prep, length-N step chunks (plus one
        remainder chunk), and decode are separate compiled programs; the
        probe runs between every chunk, so a cancelled pass frees the
        slice within one chunk. All programs are resolved (and counted,
        and compiled) HERE, not lazily mid-loop, so the caller's compile
        span stays honest.

        `geo` selects the mesh view ((tensor, seq) over the slice's
        chips; None = the construction default). The chunk boundary is
        also the RE-SHARD seam (ISSUE 12): `reshard_probe`, consulted at
        every boundary next to the cancel probe, may return a different
        validated geometry — the runner then re-places the live latents
        / conditioning onto the new mesh view and continues with that
        geometry's compiled chunk set, so a pass can migrate
        sharded->replicated (or back) mid-denoise when the queue shifts."""
        chunk = self._denoise_chunk_steps()
        geo = self.default_geometry if geo is None else geo
        cache_key = (key, chunk, geo, lora_sig)
        with self._jit_lock:
            cached = self._runner_cache.get(cache_key)
            if cached is not None:
                self._runner_cache.move_to_end(cache_key)
        if cached is not None:
            return cached
        mesh, _ = self._geometry_view(geo)
        if chunk <= 0:
            program = self._denoise_program(
                key, controlnet_module, geo=geo, mesh=mesh,
                lora_sig=lora_sig, analytic_flops=analytic_flops)

            def runner(*args, cancel_probe=None, reshard_probe=None,
                       boundary_cb=None, resume=None):
                # no chunk seams: a fused pass cannot re-shard, cannot
                # checkpoint, and cannot resume mid-flight — boundary_cb
                # and resume are accepted (and ignored) so the caller
                # need not care which strategy resolved
                if cancel_probe is not None:
                    cancel_probe()
                return program(*args)
        else:
            prep_prog, chunk_progs, decode_prog, lengths, lo = \
                self._chunk_programs(key, controlnet_module, geo, mesh,
                                     chunk, lora_sig=lora_sig,
                                     analytic_flops=analytic_flops)

            def runner(params, init_rng, context, added, guidance_scale,
                       image_guidance, image_latents, mask, rng,
                       cn_params, control_cond, cn_scale, lora,
                       cancel_probe=None, reshard_probe=None,
                       boundary_cb=None, resume=None):
                # Each boundary BLOCKS on the previous chunk before
                # probing. This sync is load-bearing, not optional: jax
                # dispatches asynchronously, so without it the host
                # races through every chunk_prog call in milliseconds
                # and all probes fire before the first chunk's compute
                # finishes — a mid-pass cancel could never interject
                # (observed empirically in the e2e drive). Chunks are
                # data-dependent, so no device-side pipelining is lost;
                # the happy-path cost is one host round trip per chunk,
                # microseconds against a multi-second chunk. A pass
                # with no probe (direct pipeline calls) runs free.
                from ..ops.platform import mesh_scope

                cur_geo, cur_mesh = geo, mesh
                cur_chunks, cur_decode = chunk_progs, decode_prog
                resharded: list[tuple] = []
                if cancel_probe is not None:
                    cancel_probe()
                latents, state = prep_prog(params, init_rng, image_latents)
                at = lo
                hi = lo + sum(lengths)
                walk = lengths
                if resume is not None:
                    # rehydrate at the checkpointed step: prep already
                    # produced the right state STRUCTURE and sharding,
                    # so the checkpointed leaves just replace the fresh
                    # ones. Any mismatch (shape drift, torn blob)
                    # degrades to the full pass — resume is an
                    # optimization, never a gate
                    try:
                        at, latents, state = self._rehydrate(
                            resume, latents, state, cur_mesh, lo, hi)
                    except Exception:
                        logger.warning(
                            "checkpoint rehydration failed; running the "
                            "full pass", exc_info=True)
                        at = lo
                    if at != lo:
                        walk = []
                        pos = at
                        while pos < hi:
                            walk.append(min(chunk, hi - pos))
                            pos += walk[-1]
                self._last_resume_step = at if at != lo else None
                start_at = at
                for n in walk:
                    if at != start_at and (cancel_probe is not None
                                           or reshard_probe is not None
                                           or boundary_cb is not None):
                        jax.block_until_ready(latents)
                        if cancel_probe is not None:
                            cancel_probe()
                        if reshard_probe is not None:
                            target = reshard_probe()
                            if target is not None:
                                target = self.resolve_geometry(target)
                            if target is not None and target != cur_geo:
                                # a cold target program set compiles
                                # HERE, inside the caller's denoise
                                # span — timed so run() can re-attribute
                                # it to the compile stage (a multi-
                                # second XLA compile folded into the
                                # denoise EWMA would trip the PR 11
                                # straggler detector on exactly the
                                # shard-capable workers shard_hold
                                # prefers)
                                t0 = time.perf_counter()
                                cur_mesh, geo_params = self._geometry_view(
                                    target)
                                with mesh_scope(cur_mesh):
                                    _, cur_chunks, cur_decode, _, _ = \
                                        self._chunk_programs(
                                            key, controlnet_module, target,
                                            cur_mesh, chunk,
                                            lora_sig=lora_sig,
                                            analytic_flops=analytic_flops)
                                compile_s = time.perf_counter() - t0
                                (latents, state, context, added,
                                 image_latents, mask, rng, cn_params,
                                 control_cond) = self._migrate_operands(
                                    cur_mesh,
                                    (latents, state, context, added,
                                     image_latents, mask, rng, cn_params,
                                     control_cond))
                                params = geo_params
                                logger.info(
                                    "re-sharded mid-pass at step %d: "
                                    "%s -> %s", at, cur_geo, target)
                                resharded.append(
                                    (cur_geo, target, at, compile_s))
                                cur_geo = target
                        if boundary_cb is not None:
                            # durability/preview seam (ISSUE 18): hand
                            # the host the live latents + scheduler
                            # state, plus a lazy decode bound to the
                            # CURRENT geometry's program — the callback
                            # decides whether this boundary is due
                            def _decode(latents=latents, params=params,
                                        dec=cur_decode, m=cur_mesh):
                                with mesh_scope(m):
                                    return dec(params, latents)

                            boundary_cb(at, latents, state, _decode)
                    with mesh_scope(cur_mesh):
                        latents, state = cur_chunks(n)(
                            params, latents, state, context, added,
                            guidance_scale, image_guidance, image_latents,
                            mask, rng, cn_params, control_cond, cn_scale,
                            lora, jnp.int32(at))
                    at += n
                if cancel_probe is not None:
                    jax.block_until_ready(latents)
                    cancel_probe()
                self._last_reshards = resharded
                with mesh_scope(cur_mesh):
                    return cur_decode(params, latents)

        with self._jit_lock:
            self._runner_cache[cache_key] = runner
            self._runner_cache.move_to_end(cache_key)
            self._trim_program_caches()
        return runner

    @staticmethod
    def _split_compile_time(timings: dict, compiled_before: float,
                            extra: float = 0.0) -> None:
        """jit compiles lazily, on a program's first call — inside the
        denoise span. Move those seconds (programs.compile_seconds since
        `compiled_before`, plus `extra`) to the compile stage, so the
        cost stamp and the straggler EWMAs see execution time and a warm
        pass reports no compile."""
        compile_s = programs.compile_seconds() - compiled_before + extra
        if compile_s > 0.01:
            timings["denoise_decode_s"] = round(max(
                timings.get("denoise_decode_s", 0.0) - compile_s, 0.0), 3)
            timings["trace_s"] = round(
                timings.get("trace_s", 0.0) + compile_s, 3)

    @staticmethod
    def _solo_cancel_probe():
        """Abort probe for a single-job pass: raises JobCancelled when
        the job pinned on this executor thread (the telemetry trace
        context) has been revoked by the hive. None when no job id is
        pinned (direct pipeline calls, tests, tools)."""
        from ..cancel import JobCancelled, cancelled, current_job_ids

        ids = current_job_ids()
        if not ids:
            return None

        def probe():
            if any(cancelled(j) for j in ids):
                raise JobCancelled(ids)

        return probe

    # --- public job API ---

    def run(self, prompt="", negative_prompt="", pipeline_type="DiffusionPipeline",
            **kwargs):
        """Execute one job; returns (list[PIL.Image], pipeline_config).

        `geometry` ({"tensor": t, "seq": s} or (t, s); ISSUE 12) asks for
        a per-pass mesh view over the slice's chips: an interactive job
        fans ONE image's attention heads / sequence blocks across every
        chip for latency instead of the default data-parallel view.
        Requests that cannot mesh — or that arrive with per-job structure
        the sharded placement does not cover (LoRA-merged or custom
        params, ControlNet) — fall back to the default view and the pass
        runs exactly as before. `reshard_probe` (chunked passes only) is
        consulted at every denoise chunk boundary and may return a new
        geometry to migrate the live pass to (the chunk-seam re-shard)."""
        geometry = kwargs.pop("geometry", None)
        reshard_probe = kwargs.pop("reshard_probe", None)
        # preemption-tolerant denoise (ISSUE 18): the worker arms the
        # chunk boundary with these. All default to off/None, so direct
        # pipeline calls and the classic fused path stay byte-identical.
        ckpt_every = int(kwargs.pop("checkpoint_every_chunks", 0) or 0)
        preview_every = int(kwargs.pop("preview_every_chunks", 0) or 0)
        checkpoint_cb = kwargs.pop("checkpoint_cb", None)
        preview_cb = kwargs.pop("preview_cb", None)
        resume_offer = kwargs.pop("resume", None)
        if (
            kwargs.get("controlnet_prepipeline_type")
            and kwargs.get("controlnet_model_name")
            and kwargs.get("mask_image") is None
        ):
            # NB the hive's txt2img-ControlNet wire puts the QR image in
            # `image` (job_arguments.py format_controlnet_args), so the
            # guard must not require image=None; _run_qr_two_stage sorts
            # control vs start image out
            return self._run_qr_two_stage(
                prompt, negative_prompt, pipeline_type, **kwargs
            )
        # snapshot at entry: registry LRU eviction may release() this bundle
        # mid-job from another thread; the snapshot keeps this job's arrays
        # alive (and correct) until it finishes
        base_params = self.params
        if base_params is None:
            raise Exception(
                f"pipeline {self.model_name} was evicted; resubmit the job"
            )
        timings: dict[str, float] = {}
        steps = int(kwargs.pop("num_inference_steps", 30))
        guidance_scale = float(kwargs.pop("guidance_scale", 7.5))
        n_images = int(kwargs.pop("num_images_per_prompt", 1))
        scheduler_type = kwargs.pop("scheduler_type", "DPMSolverMultistepScheduler")
        rng = kwargs.pop("rng", None)
        if rng is None:
            rng = jax.random.key(0)
        kwargs.pop("chipset", None)

        image = kwargs.pop("image", None)
        mask_image = kwargs.pop("mask_image", None)
        strength = float(kwargs.pop("strength", 0.75))
        image_guidance = kwargs.pop("image_guidance_scale", None)

        # chained stages (reference pipeline_steps.py:40-105 semantics)
        refiner = kwargs.pop("refiner", None)
        upscale = bool(kwargs.pop("upscale", False))
        upscaler = None
        upscale_fallback = False
        if upscale:
            # resolve (and weight-check) the upscaler BEFORE spending the
            # denoise: a missing-weights failure must not cost a full job
            from ..registry import get_pipeline
            from ..weights import MissingWeightsError
            from .upscale import upscaler_name_for

            try:
                upscaler = get_pipeline(
                    upscaler_name_for(self.model_name),
                    pipeline_type="StableDiffusionLatentUpscalePipeline",
                    chipset=self.chipset,
                )
            except MissingWeightsError:
                # no converted sd-x2 weights on this worker: serve the job
                # anyway with the latent-resize path and record the
                # degradation in pipeline_config instead of failing
                logger.warning(
                    "sd-x2 upscaler weights missing; falling back to "
                    "latent-resize 2x for this job"
                )
                upscale_fallback = True

        lora = kwargs.pop("lora", None)
        # reference wire: scale rides in cross_attention_kwargs.scale
        # (swarm/job_arguments.py lora path) or a direct lora_scale
        xattn_kwargs = kwargs.pop("cross_attention_kwargs", {}) or {}
        lora_scale = float(kwargs.pop("lora_scale", xattn_kwargs.get("scale", 1.0)))
        kwargs.pop("lora_rank", None)  # advisory coalesce-key hint only
        # adapter routing (ISSUE 13): runtime per-row delta against the
        # ONE resident base tree whenever the adapter is delta-eligible;
        # merged-tree copy only as the fallback. lora_mode feeds the
        # swarm_lora_rows_total counter + the envelope.
        lora_operands, lora_sig, delta_factors = None, None, None
        lora_mode = "none"
        self.last_operand_stats = None  # adapter-free passes stamp nothing
        job_params = base_params
        if lora is not None:
            delta_factors = self._adapter_delta_factors(lora)
            if delta_factors is not None:
                # operands are stacked per ROW further down, once the
                # final row count is known (a list of start images
                # rewrites num_images_per_prompt)
                lora_mode = "delta"
            else:
                job_params = self._lora_params(base_params, lora, lora_scale)
                lora_mode = "merged"

        # per-job conditioning/decoding add-ons (reference
        # diffusion_func.py:46-49 custom VAE, :105-111 textual inversion)
        job_tokenizers = None
        job_extras = None
        ti_ref = kwargs.pop("textual_inversion", None)
        if ti_ref:
            job_extras, job_tokenizers = self._ti_apply(ti_ref)
        vae_ref = kwargs.pop("vae", None)
        if vae_ref:
            job_params = dict(job_params)
            job_params["vae"] = self._custom_vae(str(vae_ref))

        # --- ControlNet wire args (swarm/job_arguments.py:330-397 parity) ---
        controlnet_name = kwargs.pop("controlnet_model_name", None)
        cn_scale = float(kwargs.pop("controlnet_conditioning_scale", 1.0))
        cg_start = float(kwargs.pop("control_guidance_start", 0.0))
        cg_end = float(kwargs.pop("control_guidance_end", 1.0))
        for drop in ("controlnet_model_type", "save_preprocessed_input"):
            kwargs.pop(drop, None)
        kwargs.pop("controlnet_prepipeline_type", None)  # handled at entry
        control_image = kwargs.pop("control_image", None)
        if controlnet_name and control_image is None:
            # diffusers txt2img-ControlNet convention: `image` IS the control
            control_image, image = image, None

        if isinstance(image, (list, tuple)):
            n_images = len(image)  # batch of distinct start images
        height = kwargs.pop("height", None)
        width = kwargs.pop("width", None)
        if height is None and image is not None:
            width, height = (
                image[0].size if isinstance(image, (list, tuple)) else image.size
            )
        if height is None and control_image is not None:
            width, height = control_image.size
        height = int(height or self.default_size)
        width = int(width or self.default_size)
        # XLA static shapes: canvas snaps to the /64 grid the reference also
        # used for condition images (swarm/pre_processors/image_utils.py:43-51)
        height, width = (max(64, (d // 64) * 64) for d in (height, width))
        lh, lw = height // self.latent_factor, width // self.latent_factor

        if mask_image is not None:
            if image is None:
                # without an init image the placeholder zeros would decode as
                # garbage in the unmasked region — job-level error instead
                raise ValueError("inpaint requires an init image. None provided")
            # dedicated inpaint checkpoints take mask + masked-image latents
            # on the channel dim (full denoise); 4-channel models use latent
            # masking along the original's noise trajectory
            mode = "inpaint9" if self.is_inpaint_unet else "inpaint"
        elif image is not None and self.is_pix2pix:
            mode = "pix2pix"
            if controlnet_name:
                raise ValueError(
                    "ControlNet is not supported with instruct-pix2pix models"
                )
            if image_guidance is None:
                image_guidance = 1.5  # edit-checkpoint default
        elif image is not None:
            mode = "img2img"
        else:
            mode = "txt2img"

        t_start = 0
        if mode in ("img2img", "inpaint"):
            t_start = min(max(int(steps * (1.0 - strength)), 0), steps - 1)

        # --- per-row adapter operand (ISSUE 13/16), stacked at the FINAL
        # row count (the start-image list above rewrote it last) and
        # BEFORE text encode, so TE-LoRA factors ride the same resident
        # stacks into the encoder: every row of this job carries slot 1
        te_operands = None
        if delta_factors is not None:
            from .. import lora_cache
            from .lora_runtime import row_operands

            lora_operands, lora_sig = self._lora_operands(
                [delta_factors], [1] * n_images, [lora_scale] * n_images,
                adapter_keys=(lora_cache.adapter_key(lora),))
            if any(":" in p for p in lora_sig[2]):
                # the adapter carries text-encoder content: the encode
                # batch is [negatives*N | prompt*N], every row slot 1
                te_operands = row_operands(
                    lora_operands["a"], lora_operands["b"],
                    [1] * (2 * n_images), [lora_scale] * (2 * n_images))

        # --- conditioning: one batched pass, rows [uncond*N | cond*N];
        # pix2pix duplicates the uncond rows for its image-only CFG row ---
        with Span("text_encode", timings):
            cfg_rows = 3 if mode == "pix2pix" else 2
            texts = [negative_prompt] * n_images + [prompt] * n_images
            context, pooled = self.encode_prompts(
                texts, job_params, tokenizers=job_tokenizers,
                extra_embeddings=job_extras, te_operands=te_operands,
            )
            pooled_u = pooled[:n_images] if pooled is not None else None
            pooled_c = pooled[n_images:] if pooled is not None else None
            if cfg_rows == 3:
                context = jnp.concatenate(
                    [context[:n_images], context], axis=0)

            added = None
            if self.is_xl:
                ids = self._xl_time_ids(
                    pooled_c.shape[-1], height, width,
                    float(kwargs.pop("aesthetic_score", 6.0)),
                )
                time_ids = jnp.asarray(
                    [ids] * (cfg_rows * n_images), jnp.float32)
                pooled_rows = [pooled_u] * (cfg_rows - 1) + [pooled_c]
                added = {
                    "text_embeds": jnp.concatenate(pooled_rows, axis=0),
                    "time_ids": time_ids,
                }

        # --- latents (initial noise is drawn inside the jitted program) ---
        rng, init_rng, step_rng = jax.random.split(rng, 3)
        latent_c = self.latent_channels

        # rank-preserving (1,1,1,C) placeholders when a mode doesn't use an
        # input — no dead full-res buffers riding along (program cache is
        # keyed by mode, so shapes are consistent per bucket)
        image_latents = jnp.zeros((1, 1, 1, latent_c), jnp.float32)
        mask = jnp.zeros((1, 1, 1, 1), jnp.float32)
        if image is not None:
            # one start image broadcast over the batch, or a list of distinct
            # images (e.g. vid2vid frames batched through one program)
            if isinstance(image, (list, tuple)):
                pixels = jnp.stack(
                    [jnp.asarray(_pil_to_array(im, width, height)) for im in image]
                )
            else:
                pixels = jnp.broadcast_to(
                    jnp.asarray(_pil_to_array(image, width, height))[None],
                    (n_images, height, width, 3),
                )
            if mode == "inpaint9":
                # the 9-channel checkpoint conditions on the MASKED image:
                # repaint region blanked before encoding
                mask_px = np.asarray(
                    mask_image.convert("L").resize(
                        (width, height), Image.NEAREST
                    ),
                    np.float32,
                )[None, ..., None] / 255.0
                pixels = pixels * jnp.asarray(mask_px <= 0.5, jnp.float32)
            image_latents = self._vae_encode_program(
                job_params["vae"], pixels.astype(self.dtype)
            )
            if mode == "pix2pix":
                # the edit checkpoint conditions on raw latent-dist modes —
                # undo the sampling scale our encode applies
                image_latents = image_latents / self.vae.config.scaling_factor
        if mask_image is not None:
            m = jnp.asarray(
                _mask_to_latent_array(mask_image, width, height, self.latent_factor)
            )[None]
            mask = jnp.broadcast_to(m, (n_images, lh, lw, 1))

        controlnet_module, cn_params, cn_key = None, {}, None
        control_cond = jnp.zeros((1, 1, 1, 3), jnp.float32)
        if controlnet_name and control_image is None:
            # reference parity: job-level error, not a crash
            # (swarm/job_arguments.py:331 "Controlnet specified but no
            # control image provided")
            raise ValueError("Controlnet specified but no control image provided")
        if controlnet_name:
            controlnet_module, cn_params = self._get_controlnet(controlnet_name)
            # diffusers ControlNet conditioning is [0, 1], not [-1, 1]
            cond = (
                _pil_to_array(control_image, width, height) + 1.0
            ) / 2.0
            control_cond = jnp.broadcast_to(
                jnp.asarray(cond)[None], (n_images, height, width, 3)
            )
            cn_key = (
                controlnet_name,
                int(cg_start * steps),
                max(int(np.ceil(cg_end * steps)), int(cg_start * steps) + 1),
            )

        # --- pick the pass's mesh view (ISSUE 12): sharded geometry only
        # for passes on the resident base params — LoRA-merged / custom
        # trees and ControlNet branches live on the default mesh, and a
        # geometry request for them degrades to the classic pass ---
        geo = self.resolve_geometry(geometry)
        if geo != self.default_geometry and (
                job_params is not base_params or controlnet_module is not None
                or lora_operands is not None):
            logger.info(
                "geometry %s refused for a pass with job-specific params; "
                "serving the default view", geo)
            geo = self.default_geometry
        pass_mesh, geo_params = self._geometry_view(geo)
        if geo != self.default_geometry:
            job_params = geo_params

        # --- shard or replicate over the slice (per array: placeholders
        # with batch dim 1 stay replicated; the CFG-doubled 2N batch shards
        # evenly iff N does) ---
        context, image_latents, mask, control_cond = (
            self._place_batch(x, mesh=pass_mesh)
            for x in (context, image_latents, mask, control_cond)
        )
        if added is not None:
            added = {k: self._place_batch(v, mesh=pass_mesh)
                     for k, v in added.items()}

        # --- compile (cached) + execute ---
        sched_cfg = SchedulerConfig(
            prediction_type=self.prediction_type,
            use_karras_sigmas=bool(kwargs.pop("use_karras_sigmas", False)),
        )
        sched_key = (
            scheduler_type,
            tuple(sorted(dataclass_items(sched_cfg))),
        )
        key = (mode, lh, lw, n_images, steps, sched_key, t_start, cn_key)
        # analytic UNet FLOPs of this pass (models/flops.py) — the cost
        # stamp's numerator AND the program ledger's divergence hint
        from ..models.flops import denoise_flops

        pass_flops_raw = denoise_flops(
            self.unet.config, lh, lw, n_images, steps - t_start,
            cfg_rows=cfg_rows)
        # stage "compile" is program-cache resolution: ~0 on a hit, the
        # full trace+XLA compile on a miss (swarm_compile_cache_total
        # tells the two apart in aggregate). With denoise_chunk_steps>0
        # the runner resolves the whole chunked program set here.
        with Span("compile", timings, key="trace_s"):
            runner = self._denoise_runner(
                key, controlnet_module, geo=geo, lora_sig=lora_sig,
                analytic_flops=pass_flops_raw)

        # --- preemption-tolerant denoise (ISSUE 18): the program
        # signature pins which compiled-program family a checkpoint is
        # valid for — a resume offer cut under a different (model, bucket,
        # dtype, geometry) would feed latents to a program with a
        # different meaning of "step K", so it degrades to a full pass,
        # never an error. boundary_cb turns the chunk seam into the
        # durability/preview seam at the knobbed cadence. ---
        boundary_cb = None
        resume_state = None
        chunk_steps = self._denoise_chunk_steps()
        arm_ckpt = checkpoint_cb is not None and ckpt_every > 0
        arm_preview = preview_cb is not None and preview_every > 0
        if chunk_steps > 0 and (resume_offer is not None
                                or arm_ckpt or arm_preview):
            from .. import checkpoint as _ckpt

            pass_signature = _ckpt.program_signature(
                self.model_name, key, self.dtype, geo)
            if resume_offer is not None:
                if str(resume_offer.get("signature", "")) == pass_signature:
                    resume_state = resume_offer
                else:
                    logger.warning(
                        "resume offer signature %s does not match this "
                        "pass's %s; running the full pass",
                        resume_offer.get("signature"), pass_signature)
            if arm_ckpt or arm_preview:
                boundaries = {"n": 0}

                def boundary_cb(step, latents, state, decode,
                                _sig=pass_signature):
                    boundaries["n"] += 1
                    k = boundaries["n"]
                    if arm_ckpt and k % ckpt_every == 0:
                        leaves = jax.tree_util.tree_leaves(state)
                        checkpoint_cb(
                            int(step), np.asarray(latents),
                            [np.asarray(x) for x in leaves], _sig)
                    if arm_preview and k % preview_every == 0:
                        preview_cb(int(step), np.asarray(decode()))

        # long-sequence self-attention shards over the mesh seq axis (ring
        # attention) when this pass's mesh view carved one out; trace-time
        # routing, so it binds on the first (tracing) call of each bucket
        from ..ops.platform import mesh_scope

        # a re-shard mid-pass must only swap between BASE-params views —
        # the same gate as the initial geometry above, ControlNet
        # included (its branch params never get geometry placement, so a
        # probe migrating a ControlNet pass onto a sharded mesh would
        # run the exact combination the initial gate refuses)
        if controlnet_module is not None or lora_operands is not None or (
                job_params is not base_params
                and job_params is not geo_params):
            reshard_probe = None
        self._last_reshards = []
        self._last_resume_step = None
        compiled_before = programs.compile_seconds()
        with Span("denoise", timings, key="denoise_decode_s"):
            with mesh_scope(pass_mesh):
                pixels = runner(
                    job_params,
                    init_rng,
                    context,
                    added,
                    jnp.float32(guidance_scale),
                    jnp.float32(image_guidance or 0.0),
                    image_latents,
                    mask,
                    step_rng,
                    cn_params,
                    control_cond,
                    jnp.float32(cn_scale),
                    # stacked per-row adapter factors (ISSUE 13); the
                    # empty dict traces to the identical adapter-free HLO
                    lora_operands or {},
                    # a hive-revoked job aborts at the next chunk
                    # boundary (JobCancelled propagates to the worker,
                    # which frees the slice and produces no envelope)
                    cancel_probe=self._solo_cancel_probe(),
                    # the chunk boundary doubles as the re-shard seam
                    reshard_probe=reshard_probe,
                    # ... and the durability/preview seam (ISSUE 18)
                    boundary_cb=boundary_cb,
                    resume=resume_state,
                )
            pixels = jax.block_until_ready(pixels)
        # a mid-pass re-shard that had to resolve its target program set
        # did so inside the denoise span, like every program's own XLA
        # compile; move those seconds to the compile stage
        self._split_compile_time(
            timings, compiled_before, extra=sum(
                entry[3] for entry in self._last_reshards if len(entry) > 3))
        pass_geometry = {
            "data": pass_mesh.shape.get("data", 1),
            "tensor": pass_mesh.shape.get("tensor", 1),
            "seq": pass_mesh.shape.get("seq", 1),
        }
        _SHARDED_PASSES.inc(geometry=geometry_label(
            pass_geometry["tensor"], pass_geometry["seq"]))
        if self.chipset is not None:
            self.chipset.note_geometry(**pass_geometry)
        from .lora_runtime import LORA_ROWS

        LORA_ROWS.inc(n_images, mode=lora_mode)

        # span "readback": device -> host copy of the decoded pixels and
        # the PIL wrap; what leaves the pass is host memory only
        with Span("readback", timings):
            images = _to_pil(np.asarray(pixels))

        if refiner is not None:
            # SDXL refiner stage (reference pipeline_steps.py:40-68): the
            # base output re-enters a second resident pipeline as img2img
            from ..registry import get_pipeline

            refiner_pipe = get_pipeline(
                refiner["model_name"],
                pipeline_type="StableDiffusionXLImg2ImgPipeline",
                chipset=self.chipset,
            )
            t0 = time.perf_counter()
            refiner_kw = dict(
                prompt=prompt,
                negative_prompt=negative_prompt,
                strength=float(refiner.get("strength", 0.3)),
                num_inference_steps=steps,
                guidance_scale=guidance_scale,
                scheduler_type=scheduler_type,
            )
            # one batched refiner call: the whole base batch denoises as a
            # single jitted program with per-image noise (no per-image Python
            # loop, no shared rng trajectory across the batch)
            try:
                images, _ = refiner_pipe.run(
                    image=list(images), rng=rng, **refiner_kw
                )
            except Exception as e:
                if "RESOURCE_EXHAUSTED" not in str(e) and "emory" not in str(e):
                    raise
                # memory-tight slice: fall back to sequential batch-1 calls
                # with per-image keys
                logger.warning("batched refiner OOM; refining sequentially")
                refined = []
                for idx, img in enumerate(images):
                    out, _ = refiner_pipe.run(
                        image=img, rng=jax.random.fold_in(rng, idx), **refiner_kw
                    )
                    refined.extend(out)
                images = refined
            timings["refiner_s"] = round(time.perf_counter() - t0, 3)

        if upscaler is not None:
            # learned SD-x2 latent upscaler stage (reference upscale.py:5-36
            # chained at diffusion_func.py:163; 20 unguided steps)
            t0 = time.perf_counter()
            images = upscaler.upscale(
                list(images), prompt=prompt, negative_prompt=negative_prompt,
                rng=jax.random.fold_in(rng, 0x5d2),
            )
            timings["upscale_s"] = round(time.perf_counter() - t0, 3)
        elif upscale_fallback:
            # per-image calls: the 2x decode has 4x the activation footprint,
            # and a fallback path must not be the thing that OOMs the job
            t0 = time.perf_counter()
            out = []
            for im in images:
                px = jnp.asarray(_pil_to_array(im, width, height))[None]
                up = np.asarray(
                    self._latent2x_program(job_params["vae"], px)
                )
                out.append(Image.fromarray(up[0]))
            images = out
            timings["upscale_s"] = round(time.perf_counter() - t0, 3)

        # resumed passes (ISSUE 18) recomputed only steps >= from_step;
        # the cost stamp (and so the tenant ledger) bills that fraction,
        # not the full pass the FIRST delivery already burned
        resumed_info = None
        resume_at = getattr(self, "_last_resume_step", None)
        if resume_at is not None:
            resumed_info = {
                "from_step": int(resume_at),
                "recomputed_steps": int(steps - resume_at),
            }
        billed_flops = pass_flops_raw
        if resumed_info is not None and steps > t_start:
            billed_flops = int(round(
                pass_flops_raw * resumed_info["recomputed_steps"]
                / (steps - t_start)))
        # per-pass cost figures (ISSUE 17): a solo pass IS its own job,
        # so the job's flops equal the pass flops
        cost = costs.job_cost(
            costs.pass_cost(
                model=self.model_name,
                pass_flops=billed_flops,
                denoise_s=timings.get("denoise_decode_s"),
                chips=(self.chipset.chip_count() if self.chipset is not None
                       else 1),
                device=pass_mesh.devices.flat[0],
                geometry=geometry_label(pass_geometry["tensor"],
                                        pass_geometry["seq"]),
            ),
            billed_flops,
        )

        pipeline_config = {
            "model": self.model_name,
            "pipeline": pipeline_type,
            "scheduler": scheduler_type,
            "controlnet": controlnet_name,
            "mode": mode,
            "steps": steps,
            "size": [width, height],
            "guidance_scale": guidance_scale,
            **(
                {"image_guidance_scale": image_guidance}
                if mode == "pix2pix"
                else {}
            ),
            # a pix2pix job routed to a non-edit checkpoint degrades to plain
            # img2img — record the approximation so callers can tell
            **(
                {"approximated_as": "img2img"}
                if image_guidance is not None and mode == "img2img"
                else {}
            ),
            # `size` stays the requested canvas (reference parity); the
            # learned upscaler stage doubles the actual output
            **(
                {"output_size": [2 * width, 2 * height], "upscaled": True}
                if upscaler is not None or upscale_fallback
                else {}
            ),
            **(
                {"upscaler": "latent-resize-fallback"}
                if upscale_fallback
                else {}
            ),
            # analytic UNet FLOPs of the denoise loop -> MFU in the bench
            "unet_tflops": round(pass_flops_raw / 1e12, 4),
            # serving-path cost stamp (ISSUE 17): the job's own integer
            # FLOPs plus the pass's achieved TFLOP/s and MFU (null where
            # the platform has no peak entry — CPU smoke)
            "cost": cost,
            # adapter execution path (ISSUE 13): "delta" = runtime
            # per-row low-rank delta on the resident base tree,
            # "merged" = full merged-tree fallback copy
            **({"lora_mode": lora_mode} if lora is not None else {}),
            # per-pass prompt-embedding cache stats (tenant accounting:
            # the hive attributes these hits to the job's submitter)
            **({"embed_cache": {
                "hits": self.last_encode_stats[0],
                "misses": self.last_encode_stats[1]}}
               if getattr(self, "last_encode_stats", None) else {}),
            # operand-residency stats (ISSUE 16): bytes_saved is the
            # host->device upload the resident stacks spared this pass
            # (the tenant ledger attributes it to the job's submitter)
            **({"operand_cache": dict(self.last_operand_stats)}
               if getattr(self, "last_operand_stats", None) else {}),
            # the mesh view this pass STARTED under (ISSUE 12) — the
            # end-to-end proof that the class actually picked the
            # geometry; `resharded` records any chunk-seam migrations as
            # (from_geo, to_geo, step) triples
            "geometry": pass_geometry,
            **({"resharded": [
                {"from": list(f), "to": list(t), "step": int(s),
                 "compile_s": round(c, 3)}
                for f, t, s, c in self._last_reshards]}
               if getattr(self, "_last_reshards", None) else {}),
            # resume-on-redelivery (ISSUE 18): this pass rehydrated a
            # checkpoint at from_step and recomputed only the remainder
            **({"resumed": resumed_info} if resumed_info else {}),
            "timings": timings,
        }
        return images, pipeline_config

    def run_batched(self, requests: list[dict], *, height=None, width=None,
                    num_inference_steps: int = 30, guidance_scale: float = 7.5,
                    scheduler_type: str = "DPMSolverMultistepScheduler",
                    use_karras_sigmas: bool = False,
                    pipeline_type: str = "DiffusionPipeline",
                    strength: float = 0.75,
                    controlnet_model_name: str | None = None,
                    control_image=None,
                    controlnet_conditioning_scale: float = 1.0,
                    control_guidance_start: float = 0.0,
                    control_guidance_end: float = 1.0,
                    lora_slots_max: int | None = None):
        """Coalesced txt2img/img2img: N independent requests, ONE padded
        jitted denoise+decode invocation (batching.py design).

        requests: [{"prompt", "negative_prompt", "rng",
        "num_images_per_prompt", "image"?, "lora"?, "lora_scale"?}] —
        everything that must match across the batch (model, canvas,
        steps, scheduler, guidance, img2img strength, shared ControlNet)
        arrives as shared keyword arguments; the caller
        (workflows/diffusion.diffusion_batched_callback) groups by
        batching.coalesce_key so that invariant holds. When requests
        carry start images (img2img), EVERY request must: each image is
        resized to the shared canvas and VAE-encoded into a per-row stack
        of init latents ("batched_i2i" program variant), so each row
        denoises from ITS OWN image's noised latents — padding rows get
        zero latents and are discarded after decode.

        Adapters ride PER ROW (ISSUE 13): a request's resolved `lora`
        reference becomes a slot in the stacked low-rank factors the
        jitted program applies as runtime deltas — mixed-adapter (and
        adapter-free) requests share one pass with no param-tree copy.
        An adapter the delta path cannot express raises ValueError, so
        the worker's solo fallback serves the group via the merged path.

        A shared ControlNet (ISSUE 13 second rung) arrives as
        `controlnet_model_name` + ONE `control_image` common to the
        whole group (coalesce_key guarantees identity): the control
        residuals are computed once per group per step instead of once
        per job.

        Returns [(images_j, pipeline_config_j)] aligned with requests.
        Every row's noise derives only from its own request's rng (the
        batched program variants draw per-row via vmapped keys), so a
        request's images do not depend on who it was coalesced with. The
        total row count pads up to a power-of-two bucket so coalesce
        factors 3 and 4 share one compiled program; padding rows carry an
        empty prompt and are discarded after decode.
        """
        from .common import (
            clamp_strength,
            img2img_t_start,
            pad_bucket,
            split_by_counts,
        )

        base_params = self.params
        if base_params is None:
            raise Exception(
                f"pipeline {self.model_name} was evicted; resubmit the job"
            )
        timings: dict[str, float] = {}
        start_images = [r.get("image") for r in requests]
        i2i = any(im is not None for im in start_images)
        if i2i and not all(im is not None for im in start_images):
            # a mixed group means the grouping layer broke its invariant;
            # raising routes every member through the solo fallback
            raise ValueError("coalesced img2img group missing a start image")
        if i2i and len({im.size for im in start_images}) > 1:
            # the input path only bounds images DOWN to the job's dims, so
            # same-key jobs can still arrive at different native sizes —
            # and the solo path sizes each job's canvas to ITS image. One
            # shared program can't reproduce that; the solo fallback can.
            raise ValueError(
                "coalesced img2img group has mixed start-image sizes; "
                "serving members individually")
        if height is None and i2i:
            # all start images share one size (checked above), which is
            # the canvas the solo path would use for each of them
            width, height = start_images[0].size
        height = int(height or self.default_size)
        width = int(width or height)
        height, width = (max(64, (d // 64) * 64) for d in (height, width))
        lh, lw = height // self.latent_factor, width // self.latent_factor
        steps = int(num_inference_steps)
        t_start = (
            img2img_t_start(steps, clamp_strength(strength)) if i2i else 0
        )
        counts = [
            max(int(r.get("num_images_per_prompt", 1) or 1), 1)
            for r in requests
        ]
        total = sum(counts)
        padded = pad_bucket(total)
        pad_rows = padded - total

        # --- per-row adapters (ISSUE 13): distinct adapters become slots
        # in one stacked factor operand; rows map to their slot (0 = the
        # zero adapter for adapter-free rows and padding). This block
        # runs BEFORE the row counters: its refusals (deltas disabled,
        # ineligible adapters, slots-cap overflow) re-route members to
        # other paths, which must not read as batched rows — the
        # DeltaIneligible re-batch would double-count its survivors ---
        lora_operands, lora_sig, te_operands = None, None, None
        self.last_operand_stats = None  # adapter-free passes stamp nothing
        row_modes: list[str] = []
        if any(r.get("lora") for r in requests):
            from .. import lora_cache
            from .lora_runtime import DeltaIneligibleError, row_operands

            self._require_runtime_delta()
            slots_cap = self._adapter_slots_cap(lora_slots_max)
            # surface ALL delta-ineligible members in one typed refusal,
            # so the worker re-batches the eligible majority instead of
            # serializing the whole group behind one conv/over-rank
            # adapter
            factors_of, _distinct, ineligible = \
                self._scan_adapter_specs(requests)
            if ineligible:
                raise DeltaIneligibleError(ineligible)
            slot_of: dict[tuple, int] = {}
            adapters: list[dict] = []
            adapter_keys: list[tuple] = []  # slot order — the stack recipe
            row_slots: list[int] = []
            row_gains: list[float] = []
            for r, n in zip(requests, counts):
                lora = r.get("lora")
                if not lora:
                    slot, gain = 0, 0.0
                    row_modes.append("none")
                else:
                    akey = lora_cache.adapter_key(lora)
                    slot = slot_of.get(akey)
                    if slot is None:
                        factors = factors_of[akey]
                        if len(adapters) >= slots_cap:
                            # the grouping layers cap distinct adapters
                            # per gang; a group past the cap fell through
                            # an estimate — solo fallback, never OOM
                            raise ValueError(
                                f"group carries more than {slots_cap} "
                                "distinct adapters; serving members "
                                "individually")
                        adapters.append(factors)
                        adapter_keys.append(akey)
                        slot = slot_of[akey] = len(adapters)
                    gain = float(r.get("lora_scale", 1.0) or 0.0)
                    row_modes.append("delta")
                row_slots.extend([slot] * n)
                row_gains.extend([gain] * n)
            row_slots.extend([0] * pad_rows)
            row_gains.extend([0.0] * pad_rows)
            lora_operands, lora_sig = self._lora_operands(
                adapters, row_slots, row_gains,
                adapter_keys=tuple(adapter_keys))
            if any(":" in p for p in lora_sig[2]):
                # text-encoder content rides the pass (ISSUE 16): the
                # encode batch is [negs+pad | prompts+pad], so the TE
                # slot/gain layout is the row vector twice (pad rows
                # already carry slot 0 / gain 0 at the tail)
                te_operands = row_operands(
                    lora_operands["a"], lora_operands["b"],
                    row_slots + row_slots, row_gains + row_gains)
        else:
            row_modes = ["none"] * len(requests)

        _BATCH_ROWS.inc(total, kind="real")
        if pad_rows:
            _BATCH_ROWS.inc(pad_rows, kind="padding")

        # --- conditioning: rows [uncond*padded | cond*padded]; padding
        # rows are empty prompts whose outputs are discarded ---
        with Span("text_encode", timings):
            negs: list[str] = []
            prompts: list[str] = []
            for r, n in zip(requests, counts):
                negs.extend([r.get("negative_prompt") or ""] * n)
                prompts.extend([r.get("prompt") or ""] * n)
            texts = negs + [""] * pad_rows + prompts + [""] * pad_rows
            context, pooled = self.encode_prompts(
                texts, base_params, te_operands=te_operands)

            added = None
            if self.is_xl:
                ids = self._xl_time_ids(pooled.shape[-1], height, width)
                added = {
                    # already [uncond*padded | cond*padded]
                    "text_embeds": pooled,
                    "time_ids": jnp.asarray(
                        [ids] * (2 * padded), jnp.float32),
                }

        # --- per-row key pairs (init draw + ancestral step noise), each
        # derived only from the owning request's rng ---
        init_keys, step_keys = [], []
        row_sources = [
            (r.get("rng") if r.get("rng") is not None else jax.random.key(0), n)
            for r, n in zip(requests, counts)
        ] + [(jax.random.key(0x9AD), pad_rows)]
        for base, n in row_sources:
            for i in range(n):
                k_init, k_step = jax.random.split(jax.random.fold_in(base, i))
                init_keys.append(k_init)
                step_keys.append(k_step)
        init_rng = jnp.stack(init_keys)
        step_rng = jnp.stack(step_keys)

        # unused-mode placeholders, same rank trick as run()
        latent_c = self.latent_channels
        image_latents = jnp.zeros((1, 1, 1, latent_c), jnp.float32)
        mask = jnp.zeros((1, 1, 1, 1), jnp.float32)
        control_cond = jnp.zeros((1, 1, 1, 3), jnp.float32)
        if i2i:
            # per-row init latents: encode each request's start image
            # ONCE (already at the shared canvas, resized defensively
            # here; plus one zero frame covering every padding row), then
            # gather the latents into the padded row layout — a request
            # with n rows shares one encode instead of paying n, and
            # padding rows don't run the encoder at full resolution
            uniq = [_pil_to_array(im, width, height) for im in start_images]
            # the ENCODE batch pads to a power-of-two bucket too (jit
            # retraces per shape — distinct group sizes would otherwise
            # each pay a VAE-encode compile); the zero frames double as
            # the padding rows' init latents
            need = len(uniq) + (1 if pad_rows else 0)
            while len(uniq) < pad_bucket(need):
                uniq.append(np.zeros((height, width, 3), np.float32))
            uniq_latents = self._vae_encode_program(
                base_params["vae"],
                jnp.asarray(np.stack(uniq)).astype(self.dtype),
            )
            row_index = []
            for i, n in enumerate(counts):
                row_index.extend([i] * n)
            row_index.extend([len(start_images)] * pad_rows)
            image_latents = uniq_latents[jnp.asarray(row_index)]

        # --- shared ControlNet (ISSUE 13 second rung): one control image
        # conditions the whole group, so the branch's residuals are
        # computed once per group per step instead of once per job ---
        controlnet_module, cn_params, cn_key = None, {}, None
        cn_scale = float(controlnet_conditioning_scale)
        if controlnet_model_name:
            if control_image is None:
                raise ValueError(
                    "Controlnet specified but no control image provided")
            controlnet_module, cn_params = self._get_controlnet(
                controlnet_model_name)
            cond = (_pil_to_array(control_image, width, height) + 1.0) / 2.0
            control_cond = jnp.broadcast_to(
                jnp.asarray(cond)[None], (padded, height, width, 3))
            cg_lo = int(float(control_guidance_start) * steps)
            cn_key = (
                controlnet_model_name,
                cg_lo,
                max(int(np.ceil(float(control_guidance_end) * steps)),
                    cg_lo + 1),
            )

        context, image_latents, mask, control_cond = map(
            self._place_batch, (context, image_latents, mask, control_cond)
        )
        if added is not None:
            added = {k: self._place_batch(v) for k, v in added.items()}

        sched_cfg = SchedulerConfig(
            prediction_type=self.prediction_type,
            use_karras_sigmas=bool(use_karras_sigmas),
        )
        sched_key = (scheduler_type, tuple(sorted(dataclass_items(sched_cfg))))
        key = ("batched_i2i" if i2i else "batched",
               lh, lw, padded, steps, sched_key, t_start, cn_key)
        # analytic UNet FLOPs of the whole PADDED pass (padding rows
        # burn chip time too — the pass-level figure owns them; per-job
        # stamps below count only each job's real rows)
        from ..models.flops import denoise_flops

        pass_flops_raw = denoise_flops(
            self.unet.config, lh, lw, padded, steps - t_start, cfg_rows=2)
        with Span("compile", timings, key="trace_s"):
            runner = self._denoise_runner(
                key, controlnet_module, lora_sig=lora_sig,
                analytic_flops=pass_flops_raw)
        # coalesced passes ALWAYS run the default data-parallel view:
        # throughput traffic keeps the coalescing geometry while
        # interactive solos may shard (the class-aware split, ISSUE 12).
        # Counted AFTER the pass succeeds (below), like run(): a failed
        # batched pass falls back to solo runs that count themselves,
        # and a phantom batched count would skew the sharded_rate
        # exactly when an operator is debugging a misbehaving fleet.
        pass_geometry = {
            "data": self.mesh.shape.get("data", 1),
            "tensor": self.mesh.shape.get("tensor", 1),
            "seq": self.mesh.shape.get("seq", 1),
        }

        # per-ROW cancel tokens (ISSUE 10): each request carries its
        # job_id, so a hive revocation of ONE member marks just that row
        # — batchmates finish unharmed (the padded program's shapes are
        # fixed; the cancelled row keeps computing, its result is simply
        # flagged and never packaged). When EVERY member is cancelled
        # the probe aborts the whole pass, freeing the slice within one
        # denoise_chunk_steps boundary.
        row_ids = [r.get("job_id") for r in requests]
        cancelled_rows: set[int] = set()
        probe = None
        if any(row_ids):
            from ..cancel import JobCancelled, cancelled as _row_cancelled

            def probe():
                for idx, jid in enumerate(row_ids):
                    if (jid and idx not in cancelled_rows
                            and _row_cancelled(jid)):
                        cancelled_rows.add(idx)
                        logger.warning(
                            "coalesced row for job %s cancelled; "
                            "batchmates continue", jid)
                if cancelled_rows and len(cancelled_rows) == len(requests):
                    raise JobCancelled([j for j in row_ids if j])

        from ..ops.platform import mesh_scope

        compiled_before = programs.compile_seconds()
        with Span("denoise", timings, key="denoise_decode_s"):
            with mesh_scope(self.mesh):
                pixels = runner(
                    base_params,
                    init_rng,
                    context,
                    added,
                    jnp.float32(guidance_scale),
                    jnp.float32(0.0),
                    image_latents,
                    mask,
                    step_rng,
                    cn_params,
                    control_cond,
                    jnp.float32(cn_scale),
                    lora_operands or {},
                    cancel_probe=probe,
                )
            pixels = jax.block_until_ready(pixels)
        self._split_compile_time(timings, compiled_before)
        _SHARDED_PASSES.inc(geometry=geometry_label(
            pass_geometry["tensor"], pass_geometry["seq"]))
        if self.chipset is not None:
            self.chipset.note_geometry(**pass_geometry)
        from .lora_runtime import LORA_ROWS

        for mode, n in zip(row_modes, counts):
            LORA_ROWS.inc(n, mode=mode)

        with Span("readback", timings):
            groups = split_by_counts(_to_pil(np.asarray(pixels)), counts)

        # pass-level cost figures (ISSUE 17), counted ONCE for the
        # coalesced pass; each envelope below derives its own stamp with
        # its job's real-row FLOPs
        pass_cost_figures = costs.pass_cost(
            model=self.model_name,
            pass_flops=pass_flops_raw,
            denoise_s=timings.get("denoise_decode_s"),
            chips=(self.chipset.chip_count() if self.chipset is not None
                   else 1),
            device=self.mesh.devices.flat[0],
            geometry=geometry_label(pass_geometry["tensor"],
                                    pass_geometry["seq"]),
        )

        results = []
        offset = 0
        for row, (r, n, images) in enumerate(zip(requests, counts, groups)):
            results.append((images, {
                # a cancelled member's envelope is never built: the flag
                # tells the workflow/worker layers to drop this slot
                **({"cancelled": True} if row in cancelled_rows else {}),
                "model": self.model_name,
                "pipeline": pipeline_type,
                "scheduler": scheduler_type,
                "controlnet": controlnet_model_name,
                "mode": "img2img" if i2i else "txt2img",
                "steps": steps,
                "size": [width, height],
                "guidance_scale": guidance_scale,
                # adapter rows in this pass ran as runtime per-row
                # deltas (ISSUE 13); adapter-free rows stamp nothing
                **({"lora_mode": "delta"} if row_modes[row] == "delta"
                   else {}),
                **({"strength": clamp_strength(strength)} if i2i else {}),
                "batched_with": len(requests),
                "batch_rows": [offset, n],
                "padded_rows": padded,
                "unet_tflops": round(
                    denoise_flops(self.unet.config, lh, lw, n,
                                  steps - t_start, cfg_rows=2) / 1e12, 4,
                ),
                # per-envelope cost stamp (ISSUE 17): THIS job's real-row
                # FLOPs, then the shared pass figures (like embed_cache)
                "cost": costs.job_cost(
                    pass_cost_figures,
                    denoise_flops(self.unet.config, lh, lw, n,
                                  steps - t_start, cfg_rows=2)),
                # shared-pass embed-cache stats, copied per envelope
                # like the timings below (the per-job split is unknown
                # once rows stack — accounting treats them as the
                # pass-level figure they are)
                **({"embed_cache": {
                    "hits": self.last_encode_stats[0],
                    "misses": self.last_encode_stats[1]}}
                   if getattr(self, "last_encode_stats", None) else {}),
                # shared-pass operand-residency stats (ISSUE 16), copied
                # per envelope like embed_cache: bytes_saved is the
                # upload the resident stacks spared this pass
                **({"operand_cache": dict(self.last_operand_stats)}
                   if getattr(self, "last_operand_stats", None) else {}),
                # coalesced passes stamp the data-parallel view they ran
                # under, same key as the solo path (ISSUE 12)
                "geometry": dict(pass_geometry),
                # shared pass timings, copied per envelope: the envelope
                # must stand alone once the hive splits the batch apart
                "timings": dict(timings),
            }))
            offset += n
        return results


def dataclass_items(cfg) -> list[tuple]:
    import dataclasses

    return [(f.name, getattr(cfg, f.name)) for f in dataclasses.fields(cfg)]


@register_family("sd")
def _build_sd(model_name, chipset, **variant):
    return SDPipeline(model_name, chipset, **variant)


@register_family("sdxl")
def _build_sdxl(model_name, chipset, **variant):
    return SDPipeline(model_name, chipset, **variant)
