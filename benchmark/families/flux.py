"""The `flux` pipeline family: everything the benchmark knows of FLUX.1's
MMDiT. Seeded weights made already sharded, what a job carries and how its
artifact is judged (`pictures.py`), the kernels' comparison, the denoiser's
half of `correct` 5 and the compile check's operands (README, "A family").

It reads the program through public names only: `FluxPipeline(...,
weights=)`, `param_shapes()` / `param_shardings()` (the placed tree as
shapes and its `NamedSharding`s on the pipeline's mesh), `denoise_program`,
the attributes `params`, `config`, `transformer`, `mesh`, `dtype`,
`head_groups`, `latent_factor`, `latent_channels`, and
`models.flux.grouped_layout` / `patchify`.

**Weights.** The program would initialise a `test/*` model on the host in
float32 (16.6 B parameters) and then place it. `register` hands the
pipeline a `weights` provider instead: every leaf is made by one jitted
program whose output sharding is the leaf's own, as a window of one seeded
normal pool scaled by fan-in (`families/sd.py` has the scheme and its
reasons), so each chip only ever holds its shard and the host holds
nothing. One program per distinct (shape, sharding): ~60 for 1,800 leaves.

**`correct` 5** is one transformer evaluation of one row at the published
widths and depth: every block, the flash kernel at the per-chip head count,
all the collectives. `denoiser_canvas` in the configuration says at which
canvas. The reference runs on the host CPU in float32 with the blocks
pulled from the chips in turn (a double block is 1.36 GB in float32) and
back in the checkpoint's column order. At the cell's 1024^2 (4096 + 512
tokens, ~74 TFLOP) it took 272 s of a 30-core host (my chip run, PR 27):
under the five minutes ISSUE 27 drew the line at, but four and a half
minutes in which every run of every later check holds four chips for a
50 s window. So the run's own comparison is at 512^2 (1024 + 512 tokens,
still over the flash path's 1024-token gate; 70 s of the host), and the
full canvas is the builder's reading (0.0104 against the control's 0.0423:
PERF.md section 6, PR 27), which read as the small canvas does.

**The one-row program.** The harness warms passes of the traffic's gang
size and nothing else. `flux-pairs` has as many clients as a gang has rows,
so no queue stands behind a pass: when a worker poll falls between the two
resubmits, and the second is later than the batcher's linger, the first
runs alone, through the solo program. That is this deployment's behaviour
and not a fault, so `register`'s factory runs one solo job of the cell's
shape on the new pipeline (`solo_warm_s` in the record, inside set-up and
inside the program's `registry_build` span): nothing compiles in the window
whichever way a pass is formed.
"""

from __future__ import annotations

import math
import time
import weakref

from .. import checks
from . import pictures

FAMILY = "flux"
# the wire name the registry resolves this family by (the configuration's
# jobs send the same in `parameters`)
PIPELINE_TYPE = "FluxPipeline"
# a job is a prompt and returns a picture at the configuration's canvas
check_artifact = pictures.check_artifact
job_fields = pictures.job_fields
# `correct` 4: the shared attention dispatch at `attention_shapes`, no
# kernel of this network's own
kernel_checks = checks.kernels
# Velocity against the plain reference, relative L2, one row, every block.
# The rule of two readings (my chip runs, PR 27, four chips, one weight seed
# and three input seeds at 512^2, one at 1024^2): sound bfloat16 runs (bf16
# weights and activations, float32 accumulation) read 0.0103, 0.0139, 0.0123
# and 0.0104; the low-precision control (the same network served from
# weights rounded to 8 bits a tensor, `int8_control`) 0.0437, 0.0510, 0.0529
# and 0.0423. The bound is 1.8 times the largest of the first and 0.59 of
# the smallest of the second.
DENOISER_REL_L2_TOL = 0.025
TIMESTEP = 0.5  # mid-schedule flow time of the compared evaluation
TXT_LEN = 512


# --- seeded weights, made sharded --------------------------------------------


def _leaf_rule(path, shape) -> tuple[float, float]:
    """(std, shift) of one leaf from its flax name: zeros for `bias`, ones
    for a norm's `*scale`, tables by their width, else a normal scaled by
    fan-in (flax's lecun default has the same variance)."""
    name = str(getattr(path[-1], "key", path[-1]))
    if name == "bias":
        return 0.0, 0.0
    if name.endswith("scale"):
        return 0.0, 1.0
    if len(shape) <= 1:
        return 0.02, 0.0
    if "embedding" in name or name == "relative_attention_bias":
        return 1.0 / math.sqrt(shape[-1]), 0.0
    return 1.0 / math.sqrt(math.prod(shape[:-1])), 0.0


def seeded_leaves(shapes, shardings, seed: int, phases: dict | None = None):
    """Every leaf of `shapes` (`jax.ShapeDtypeStruct`s) from `seed`, each
    made with its own entry of `shardings` as the output sharding. The pool
    (twice the largest leaf, float32, whole on every chip while the leaves
    are made) is drawn once; a leaf is a window of it at an offset hashed
    from the leaf's index. Windows overlap, so leaves are correlated; each
    is still unit normal inside, which is all that speed and the reference
    comparison need."""
    import jax
    import jax.numpy as jnp

    phases = {} if phases is None else phases
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    places = jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: isinstance(x, jax.sharding.Sharding))
    sizes = [math.prod(leaf.shape) for _, leaf in leaves]
    pool_size = 2 * max(sizes)
    whole = jax.sharding.NamedSharding(
        places[0].mesh, jax.sharding.PartitionSpec())
    takes: dict = {}

    def take(shape, dtype, sharding):
        key = (shape, str(dtype), sharding)
        if key not in takes:
            size = math.prod(shape)

            def window(pool, offset, std, shift):
                flat = jax.lax.dynamic_slice(pool, (offset,), (size,))
                return (flat.reshape(shape) * std + shift).astype(dtype)

            takes[key] = jax.jit(window, out_shardings=sharding)
        return takes[key]

    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        started = time.perf_counter()
        pool = jax.jit(
            lambda key: jax.random.normal(key, (pool_size,), jnp.float32),
            out_shardings=whole)(jax.random.key(seed))
        pool.block_until_ready()
        phases["pool_s"] = time.perf_counter() - started
        started = time.perf_counter()
        out = []
        for index, ((path, leaf), size, place) in enumerate(
                zip(leaves, sizes, places)):
            std, shift = _leaf_rule(path, leaf.shape)
            offset = (index * 2654435761) % (pool_size - size + 1)
            out.append(take(tuple(leaf.shape), leaf.dtype, place)(
                pool, offset, std, shift))
        jax.block_until_ready(out)
        phases["leaves_s"] = time.perf_counter() - started
        phases["programs"] = len(takes)
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)
    return jax.tree_util.tree_unflatten(treedef, out)


def register(seed: int, record: dict) -> None:
    """Re-register the `flux` family in this process with a factory whose
    pipelines take their weights from `seeded_leaves`. `record` receives
    `weights_ready_s` and `weights_phases` per model built."""
    import inspect

    from chiaswarm_tpu import registry
    # importing the module registers the program's own factory first
    from chiaswarm_tpu.pipelines.flux import FluxPipeline

    from ..harness import RunFailure

    if "weights" not in inspect.signature(FluxPipeline).parameters:
        # a program from before the seam (the parent of PR 27): say so now,
        # not from inside the worker's first job
        raise RunFailure(
            "this program's FluxPipeline takes no `weights=`: it cannot be "
            "handed a model that no host and no one chip holds")

    def factory(model_name, chipset, **variant):
        started = time.perf_counter()
        phases: dict = {}
        pipe = FluxPipeline(
            model_name, chipset, **variant,
            weights=lambda shapes, shardings: seeded_leaves(
                shapes, shardings, int(seed), phases))
        record.setdefault("weights_ready_s", {})[model_name] = (
            time.perf_counter() - started)
        record.setdefault("weights_phases", {})[model_name] = phases
        _warm_solo(pipe, model_name, record)
        return pipe

    registry.register_family(FAMILY)(factory)


def _warm_solo(pipe, model_name: str, record: dict) -> None:
    """One solo job of the cell's shape on a pipeline just built (the module
    docstring, "The one-row program"); nothing where the record names no
    cell, or another model."""
    import jax

    spec = record.get("spec") or {}
    job = {**spec.get("config", {}).get("job", {}),
           **spec.get("traffic", {}).get("job", {})}
    if job.get("model_name") != model_name:
        return
    parameters = {**spec["config"]["job"].get("parameters", {}),
                  **spec["traffic"]["job"].get("parameters", {})}
    started = time.perf_counter()
    pipe.run(prompt="a one-row warm-up pass", rng=jax.random.key(0),
             height=int(job["height"]), width=int(job["width"]),
             num_inference_steps=int(job["num_inference_steps"]),
             guidance_scale=float(job["guidance_scale"]),
             max_sequence_length=int(
                 parameters.get("max_sequence_length", TXT_LEN)))
    record.setdefault("solo_warm_s", {})[model_name] = (
        time.perf_counter() - started)


# --- the denoiser's half of `correct` 5 --------------------------------------


def denoiser_inputs(pipe, config: dict, seed: int) -> dict:
    """One seeded row at the configuration's `denoiser_canvas` (else the
    job's own canvas): patchified latents, T5 states, CLIP's pooled vector,
    a mid-schedule flow time and the job's guidance, rounded to the serving
    dtype so that both sides see the same values."""
    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu.models.flux import patchify

    canvas = config.get("denoiser_canvas") or [
        config["job"]["height"], config["job"]["width"]]
    lh, lw = (int(side) // pipe.latent_factor for side in canvas)
    cfg = pipe.config
    keys = jax.random.split(jax.random.key(seed), 3)
    img, img_ids = patchify(jax.random.normal(
        keys[0], (1, lh, lw, pipe.latent_channels)).astype(pipe.dtype))
    txt_len = int(config.get("denoiser_txt_len", TXT_LEN))
    return {
        "img": img, "img_ids": img_ids,
        "txt": jax.random.normal(
            keys[1], (1, txt_len, cfg.context_dim)).astype(pipe.dtype),
        "txt_ids": jnp.zeros((1, txt_len, 3), jnp.int32),
        "timesteps": jnp.asarray([TIMESTEP], jnp.float32),
        "pooled": jax.random.normal(
            keys[2], (1, cfg.pooled_dim)).astype(pipe.dtype),
        "guidance": jnp.asarray(
            [float(config["job"].get("guidance_scale", 3.5))], jnp.float32)}


class HostBlocks:
    """The resident transformer's tree, one entry at a time on the host
    and in the checkpoint's column order: what `mmdit_forward` indexes."""

    def __init__(self, pipe):
        self.pipe = pipe

    def __getitem__(self, name: str):
        import jax

        from chiaswarm_tpu.models.flux import grouped_layout

        host = jax.device_get(self.pipe.params["flux"][name])
        return grouped_layout({name: host}, self.pipe.config,
                              self.pipe.head_groups, inverse=True)[name]


def denoiser_reference(pipe, inputs: dict):
    """The plain reference's velocity for the row, on the host CPU."""
    import jax

    from ..reference.mmdit import mmdit_forward

    return mmdit_forward(HostBlocks(pipe), pipe.config, **inputs,
                         device=jax.local_devices(backend="cpu")[0])


# one jitted evaluation a pipeline (the control reuses the compiled
# program); weak, so it never keeps a model alive
_SERVE_PROGRAMS: weakref.WeakKeyDictionary = weakref.WeakKeyDictionary()


def _serve_program(pipe):
    import jax

    if pipe not in _SERVE_PROGRAMS:
        transformer = pipe.transformer
        _SERVE_PROGRAMS[pipe] = jax.jit(lambda p, x: transformer.apply(
            {"params": p}, x["img"], x["img_ids"], x["txt"], x["txt_ids"],
            x["timesteps"], x["pooled"], guidance=x["guidance"]))
    return _SERVE_PROGRAMS[pipe]


def _serve(pipe, flux_params, inputs: dict):
    from chiaswarm_tpu.ops.platform import mesh_scope

    with mesh_scope(pipe.mesh):
        return _serve_program(pipe)(flux_params, inputs)


def denoiser_serve(pipe, inputs: dict):
    """One evaluation of the resident MMDiT on the row, in the serving
    dtype, sharded as it is served, the kernels as dispatched. The velocity
    is compared: a 28-step loop on random weights would amplify rounding."""
    return _serve(pipe, pipe.params["flux"], inputs)


def int8_control(pipe, inputs: dict):
    """The low-precision control of the tolerance's second reading (not
    part of a run): the same evaluation from weights rounded to 8 bits a
    tensor (symmetric, one scale a kernel), leaf by leaf where they lie."""
    import jax
    import jax.numpy as jnp

    def rounded(x):
        if x.ndim < 2:
            return x
        scale = jnp.max(jnp.abs(x.astype(jnp.float32))) / 127.0
        return (jnp.round(x.astype(jnp.float32) / scale)
                * scale).astype(x.dtype)

    coarse = jax.tree_util.tree_map(
        lambda x: jax.jit(rounded, out_shardings=x.sharding)(x),
        pipe.params["flux"])
    return _serve(pipe, coarse, inputs)


# --- the compile check's operands --------------------------------------------


class _Scoped:
    """A jitted program that has to be lowered inside `mesh_scope`."""

    def __init__(self, program, mesh):
        self.program, self.mesh = program, mesh

    def lower(self, *args):
        from chiaswarm_tpu.ops.platform import mesh_scope

        with mesh_scope(self.mesh):
            return self.program.lower(*args)


def compile_operands(spec: dict, devices):
    """The cell's denoise program (as `run_batched` / `run` would ask for
    it), its arguments as shapes sharded on the described `devices`, and
    its rows. The slice is laid out as the deployment's settings say."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from chiaswarm_tpu.chips.device import ChipSet
    from chiaswarm_tpu.pipelines.flux import FluxPipeline
    from chiaswarm_tpu.settings import load_settings

    config, traffic = spec["config"], spec["traffic"]
    job = {**config["job"], **traffic["job"]}
    tensor = int(config["deployment"]["env"].get(
        "SDAAS_TENSOR_PARALLELISM", 1))
    pipe = FluxPipeline(
        job["model_name"], ChipSet(list(devices), tensor=tensor),
        dtype=jnp.dtype(config["kernel_dtype"]),
        weights=lambda shapes, shardings: jax.tree_util.tree_map(
            lambda s, place: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=place), shapes, shardings))
    rows = min(int(traffic["clients"]),
               int(load_settings().hive_max_jobs_per_poll))
    whole = NamedSharding(pipe.mesh, PartitionSpec())

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=whole)

    lh = int(job["height"]) // pipe.latent_factor
    lw = int(job["width"]) // pipe.latent_factor
    txt_len = TXT_LEN  # the program's default, which the traffic keeps
    batched = rows > 1  # a gang of one takes the worker's solo path
    if batched:
        first = shape((rows, lh, lw, pipe.latent_channels), jnp.float32)
    else:
        rng = jax.eval_shape(lambda: jax.random.key(0))
        first = shape(rng.shape, rng.dtype)
    cfg = pipe.config
    args = (pipe.params, first,
            shape((rows, txt_len, cfg.context_dim), pipe.dtype),
            shape((rows, cfg.pooled_dim), pipe.dtype),
            shape((rows,), jnp.float32))
    program = pipe.denoise_program(
        int(job["height"]), int(job["width"]), rows,
        int(job["num_inference_steps"]), txt_len, batched=batched)
    return _Scoped(program, pipe.mesh), args, rows
