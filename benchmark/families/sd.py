"""On-device seeded weights for the `sd` / `sdxl` pipeline families.

The program initialises `test/*` models eagerly on the host (~950 XLA:CPU
compiles, 150 s for SDXL: PERF.md section 5), a path no user pays. The
benchmark replaces ONLY that: `register(seed, record)` re-registers the two
families with a factory that builds an `SDPipeline` subclass overriding
`_load_params`. Shapes, names and structure come from the program's own
modules (`jax.eval_shape` over their `init`); values come from `--seed`,
made on the device as windows of one seeded normal pool, in the dtype
they are served in.

Leans on two private names, `SDPipeline._load_params` and `_place`
(PERF.md, Open questions: a public weight-provider seam). If either is
gone this module fails loudly instead of paying the host init in silence.
"""

from __future__ import annotations

import math
import time

FAMILIES = ("sd", "sdxl")


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
