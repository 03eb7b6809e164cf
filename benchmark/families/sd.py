"""The `sd` / `sdxl` pipeline families: everything the benchmark knows of
this network. On-device seeded weights, what a job carries and how its
artifact is judged (`pictures.py`), the kernels' comparison, the denoiser's
half of `correct` 5 and the compile check's operands (README, "A family").

The program initialises `test/*` models eagerly on the host (~950 XLA:CPU
compiles, 150 s for SDXL: PERF.md section 5), a path no user pays. The
benchmark replaces ONLY that: `register(seed, record)` re-registers the two
families with a factory that builds an `SDPipeline` subclass overriding
`_load_params`. Shapes, names and structure come from the program's own
modules (`jax.eval_shape` over their `init`); values come from `--seed`,
made on the device as windows of one seeded normal pool, in the dtype
they are served in.

The weight factory leans on two private names, `SDPipeline._load_params`
and `_place` (PERF.md, Open questions: a public weight-provider seam). If
either is gone this module fails loudly instead of paying the host init in
silence. The denoiser's half and the compile operands read the pipeline as
it is (`unet`, `is_xl`, `_xl_time_ids`, `_denoise_program`'s key and
arguments): a program change that moves those is repaired here, by a
`benchmark` PR.
"""

from __future__ import annotations

import math
import time

from .. import checks
from . import pictures

FAMILIES = ("sd", "sdxl")
# the wire name the registry resolves these models by, from the model's
# name, where a job's `parameters` give none
PIPELINE_TYPE = "DiffusionPipeline"
# a job is a prompt and returns a picture at the configuration's canvas
check_artifact = pictures.check_artifact
job_fields = pictures.job_fields
# `correct` 4: the shared attention and GroupNorm dispatch, no kernel of
# this network's own
kernel_checks = checks.kernels
# Denoiser against the plain reference: relative L2 error of the predicted
# noise. bfloat16 weights and activations with float32 accumulation read
# 0.011-0.012 (SD2.1 768^2) and 0.013-0.015 (SDXL 1024^2) over nine seeds
# (my chip runs, PR 23); the bound is twice the worst. Rounding to int8
# (2^-7 a value against bfloat16's 2^-9) or accumulating in bfloat16 over
# contractions of 1280-10240 terms would multiply that error several
# times, by the same square-root-of-depth growth these readings show.
DENOISER_REL_L2_TOL = 0.03


def init_shapes(pipe):
    """The parameter tree the program's own seeded init would build, as
    shapes only (same calls as `SDPipeline._load_params`, abstractly)."""
    import jax
    import jax.numpy as jnp

    unet_cfg = pipe.unet.config
    n_down = len(unet_cfg.block_out_channels) - 1
    hw = 2 ** max(n_down, 2)

    def init():
        k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
        unet = pipe.unet.init(
            k1, jnp.zeros((1, hw, hw, unet_cfg.in_channels)), jnp.zeros((1,)),
            jnp.zeros((1, 77, unet_cfg.cross_attention_dim)),
            added_cond=pipe._dummy_added_cond(1))
        text = [enc.init(k2, jnp.zeros((1, 77), jnp.int32))
                for enc in pipe.text_encoders]
        vae = pipe.vae.init(k3, jnp.zeros(
            (1, hw * pipe.latent_factor, hw * pipe.latent_factor, 3)))
        return {"unet": unet["params"],
                "text": [t["params"] for t in text],
                "vae": vae["params"]}

    return jax.eval_shape(init)


def _leaf_rule(path, shape) -> tuple[float, float]:
    """(std, shift) of one leaf from its flax name: zeros for `bias`, ones
    for `scale`, else a normal scaled by fan-in (flax's lecun default has
    the same variance), embeddings by their width."""
    name = str(getattr(path[-1], "key", path[-1]))
    if name == "bias":
        return 0.0, 0.0
    if name == "scale":
        return 0.0, 1.0
    if len(shape) <= 1:
        return 0.02, 0.0
    if "embedding" in name:
        return 1.0 / math.sqrt(shape[-1]), 0.0
    return 1.0 / math.sqrt(math.prod(shape[:-1])), 0.0


def seeded_params(shapes, seed: int, dtype, phases: dict | None = None):
    """Fill every leaf of `shapes` on the default device from `seed`, in
    `dtype`. ONE normal draw makes a pool twice the largest leaf; every
    leaf is a window of it at an offset hashed from the leaf's index,
    scaled and shifted by `_leaf_rule` — one trivial program per distinct
    leaf shape (77 for SDXL's 2641 leaves) and no generator per leaf.
    Windows overlap, so leaves are correlated; each is still unit normal
    inside, which is all that speed and the reference comparison need.

    Why not one program for the tree, or a normal draw per shape: the TPU
    compiler took 20 minutes for the first and 1-4 s a shape for the
    second (PERF.md, PR 23). The persistent cache is asked to keep these
    however short their compile, so a warm run compiles none."""
    import jax
    import jax.numpy as jnp

    phases = {} if phases is None else phases
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    sizes = [math.prod(leaf.shape) for _, leaf in leaves]
    pool_size = 2 * max(sizes)
    takes: dict = {}

    def take(shape):
        if shape not in takes:
            size = math.prod(shape)

            def window(pool, offset, std, shift):
                flat = jax.lax.dynamic_slice(pool, (offset,), (size,))
                return (flat.reshape(shape) * std + shift).astype(dtype)

            takes[shape] = jax.jit(window)
        return takes[shape]

    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        started = time.perf_counter()
        pool = jax.jit(lambda key: jax.random.normal(
            key, (pool_size,), jnp.float32))(jax.random.key(seed))
        pool.block_until_ready()
        phases["pool_s"] = time.perf_counter() - started
        started = time.perf_counter()
        out = []
        for index, ((path, leaf), size) in enumerate(zip(leaves, sizes)):
            std, shift = _leaf_rule(path, leaf.shape)
            offset = (index * 2654435761) % (pool_size - size + 1)
            out.append(take(tuple(leaf.shape))(pool, offset, std, shift))
        jax.block_until_ready(out)
        phases["leaves_s"] = time.perf_counter() - started
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)
    return jax.tree_util.tree_unflatten(treedef, out)


def assert_same_tree(params, shapes) -> None:
    import jax

    got = jax.tree_util.tree_map(lambda x: tuple(x.shape), params)
    want = jax.tree_util.tree_map(lambda x: tuple(x.shape), shapes)
    if got != want:
        raise RuntimeError(
            "seeded parameter tree differs from eval_shape of the modules' "
            "init: the benchmark's weight seam no longer fits the program")


def make_pipeline_class():
    from chiaswarm_tpu.pipelines.stable_diffusion import SDPipeline

    for seam in ("_load_params", "_place"):
        if not callable(getattr(SDPipeline, seam, None)):
            raise RuntimeError(
                f"SDPipeline.{seam} is gone: benchmark/families/sd.py has "
                "no seam to hand the pipeline on-device weights through")

    class SeededSDPipeline(SDPipeline):
        """SDPipeline whose weights are made on the device from a seed."""

        weight_seed = 0

        def _load_params(self):
            import jax

            phases = self.weight_phases = {}
            started = time.perf_counter()
            shapes = init_shapes(self)
            phases["shapes_s"] = time.perf_counter() - started
            params = seeded_params(
                shapes, self.weight_seed, self.dtype, phases)
            assert_same_tree(params, shapes)
            started = time.perf_counter()
            placed = self._place(params)
            jax.block_until_ready(placed)
            phases["place_s"] = time.perf_counter() - started
            return placed

    return SeededSDPipeline


def register(seed: int, record: dict) -> None:
    """Re-register the `sd` and `sdxl` families in this process. `record`
    (the run's record) receives `weights_ready_s` per model built: the
    benchmark's own span around the program's registry building a
    pipeline (shapes + on-device fill + placement + tokenizers)."""
    from chiaswarm_tpu import registry

    registry._ensure_builtin_families()
    cls = make_pipeline_class()
    cls.weight_seed = int(seed)

    def factory(model_name, chipset, **variant):
        started = time.perf_counter()
        pipe = cls(model_name, chipset, **variant)
        record.setdefault("weights_ready_s", {})[model_name] = (
            time.perf_counter() - started)
        record.setdefault("weights_phases", {})[model_name] = dict(
            pipe.weight_phases)
        return pipe

    for family in FAMILIES:
        registry.register_family(family)(factory)


# --- the denoiser's half of `correct` 5 --------------------------------------


def denoiser_inputs(pipe, config: dict, seed: int) -> dict:
    """One seeded CFG pair at the cell's latent shape, rounded to the
    serving dtype (so the reference sees the values the system sees)."""
    import jax
    import jax.numpy as jnp

    height, width = int(config["job"]["height"]), int(config["job"]["width"])
    cfg = pipe.unet.config
    lh, lw = height // pipe.latent_factor, width // pipe.latent_factor
    keys = jax.random.split(jax.random.key(seed), 3)
    inputs = {
        "sample": jax.random.normal(
            keys[0], (2, lh, lw, cfg.in_channels)).astype(pipe.dtype),
        "timesteps": jnp.asarray([501.0, 501.0]),
        "context": jax.random.normal(
            keys[1], (2, 77, cfg.cross_attention_dim)).astype(pipe.dtype),
        "added": None}
    if pipe.is_xl:
        pooled = cfg.addition_embed_dim - 6 * cfg.addition_time_embed_dim
        inputs["added"] = {
            "text_embeds": jax.random.normal(
                keys[2], (2, pooled)).astype(pipe.dtype),
            "time_ids": jnp.asarray(
                [pipe._xl_time_ids(pooled, height, width)] * 2, jnp.float32)}
    return inputs


def denoiser_reference(pipe, inputs: dict):
    """The plain reference's predicted noise for the pair's second
    (conditional) row, computed on the host CPU. Rows of a batch are
    independent in this network, so one row of the pair is compared: the
    whole pair in float32 on the host takes a minute for SDXL, and every
    run of every check would pay it."""
    import jax

    from ..reference.unet2d import unet_forward

    def row(tree):
        return jax.tree_util.tree_map(lambda x: x[1:], tree)

    return unet_forward(
        pipe.params["unet"], pipe.unet.config, row(inputs["sample"]),
        row(inputs["timesteps"]), row(inputs["context"]),
        None if inputs["added"] is None else row(inputs["added"]),
        device=jax.local_devices(backend="cpu")[0])


def denoiser_serve(pipe, inputs: dict):
    """One evaluation of the resident UNet on the pair, in the serving
    dtype with the kernels as dispatched; returns the row the reference
    computed. The predicted noise is compared: a 30-step loop on random
    weights would amplify rounding."""
    import jax

    from chiaswarm_tpu.ops.platform import mesh_scope

    serve = jax.jit(lambda p, x, t, c, a: pipe.unet.apply(
        {"params": p}, x, t, c, added_cond=a))
    with mesh_scope(pipe.mesh):
        got = serve(pipe.params["unet"], inputs["sample"],
                    inputs["timesteps"], inputs["context"], inputs["added"])
    return got[1:]


# --- the compile check's operands --------------------------------------------


def compile_operands(spec: dict, devices):
    """The cell's denoise program (as `run_batched` / `run` would key it),
    its arguments as shapes on the described `devices`, and its rows."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from chiaswarm_tpu.pipelines.stable_diffusion import (
        SchedulerConfig,
        dataclass_items,
    )
    from chiaswarm_tpu.settings import load_settings

    if len(devices) != 1:
        raise ValueError(
            f"families/sd.py hands the compile check a one-chip program; "
            f"the cell asks for {len(devices)} chips")
    config, traffic = spec["config"], spec["traffic"]
    job = {**config["job"], **traffic["job"]}
    one = SingleDeviceSharding(devices[0])

    class Shapes(make_pipeline_class()):
        def _load_params(self):
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, self.dtype,
                                               sharding=one),
                init_shapes(self))

    pipe = Shapes(job["model_name"], dtype=jnp.dtype(config["kernel_dtype"]))
    rows = min(int(traffic["clients"]),
               int(load_settings().hive_max_jobs_per_poll))
    lh = int(job["height"]) // pipe.latent_factor
    lw = int(job["width"]) // pipe.latent_factor
    scheduler = config["job"].get("parameters", {}).get(
        "scheduler_type", "DPMSolverMultistepScheduler")
    sched_cfg = SchedulerConfig(prediction_type=pipe.prediction_type,
                                use_karras_sigmas=False)
    sched_key = (scheduler, tuple(sorted(dataclass_items(sched_cfg))))
    # a gang of one takes the worker's solo path (`run`), larger ones the
    # batched one (`run_batched`): their programs are keyed differently
    mode = "batched" if rows > 1 else "txt2img"
    key = (mode, lh, lw, rows, int(job["num_inference_steps"]), sched_key,
           0, None)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    rng = jax.eval_shape(lambda: jax.random.key(0))
    rngs = shape((rows,) + rng.shape, rng.dtype) if rows > 1 \
        else shape(rng.shape, rng.dtype)
    cross = pipe.unet.config.cross_attention_dim
    added = None
    if pipe.is_xl:
        pooled = (pipe.unet.config.addition_embed_dim
                  - 6 * pipe.unet.config.addition_time_embed_dim)
        added = {"text_embeds": shape((2 * rows, pooled), pipe.dtype),
                 "time_ids": shape((2 * rows, 6), jnp.float32)}
    scalar = shape((), jnp.float32)
    args = (pipe.params, rngs, shape((2 * rows, 77, cross), pipe.dtype),
            added, scalar, scalar, shape((1, 1, 1, 4), jnp.float32),
            shape((1, 1, 1, 1), jnp.float32), rngs, {},
            shape((1, 1, 1, 3), jnp.float32), scalar, {})
    return pipe._denoise_program(key), args, rows
