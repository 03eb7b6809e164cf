"""The comparisons that decide `correct` (README, "What correct means").

Each returns a list of failure strings; an empty list passes. Tolerances
are written here with their reason.
"""

from __future__ import annotations

import hashlib
import io

# Kernels against the float32 reference, max abs error on unit-normal
# bfloat16 inputs. The chip read 0.0005-0.0065 for attention (the largest
# at 77 keys and on the XLA path at 576 tokens, where each output averages
# few values) and 0.0078 for GroupNorm+SiLU, one bfloat16 rounding of the
# output (my chip runs, PR 23; PR 22's probe: 0.0005-0.0085 and 0.031 on
# other inputs). A float32-accumulating kernel stays under these bounds; a
# kernel that accumulated in bfloat16 over thousands of keys would not.
ATTENTION_TOL = 0.02
GROUP_NORM_TOL = 0.05
# Denoiser against the plain reference: relative L2 error of the predicted
# noise. bfloat16 weights and activations with float32 accumulation read
# 0.011-0.012 (SD2.1 768^2) and 0.013-0.015 (SDXL 1024^2) over nine seeds
# (my chip runs, PR 23); the bound is twice the worst. Rounding to int8
# (2^-7 a value against bfloat16's 2^-9) or accumulating in bfloat16 over
# contractions of 1280-10240 terms would multiply that error several
# times, by the same square-root-of-depth growth these readings show.
UNET_REL_L2_TOL = 0.03


def kernels(config: dict, dtype, interpret: bool = False) -> tuple[list, list]:
    """correct 4: the program's attention and GroupNorm dispatch against
    the plain references at the configuration's own shapes. Returns
    (failures, readings)."""
    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu.ops import dot_product_attention
    from chiaswarm_tpu.ops.group_norm import group_norm

    from .reference import kernels as ref

    failures, readings = [], []
    for n, (sq, skv, heads, dim) in enumerate(config["attention_shapes"]):
        keys = jax.random.split(jax.random.key(100 + n), 3)
        q = jax.random.normal(keys[0], (2, sq, heads, dim), dtype)
        k = jax.random.normal(keys[1], (2, skv, heads, dim), dtype)
        v = jax.random.normal(keys[2], (2, skv, heads, dim), dtype)
        got = jax.jit(dot_product_attention)(q, k, v)
        err = float(jnp.max(jnp.abs(
            jnp.asarray(got, jnp.float32) - ref.attention(q, k, v))))
        readings.append({"attention": [sq, skv, heads, dim], "max_abs": err})
        if not err <= ATTENTION_TOL:
            failures.append(f"attention {sq}x{skv}x{heads}x{dim}: max abs "
                            f"error {err:.4f} over {ATTENTION_TOL}")
    for n, (h, w, c) in enumerate(config["group_norm_shapes"]):
        keys = jax.random.split(jax.random.key(200 + n), 3)
        x = jax.random.normal(keys[0], (2, h, w, c), dtype)
        scale = 1.0 + 0.1 * jax.random.normal(keys[1], (c,), jnp.float32)
        bias = 0.1 * jax.random.normal(keys[2], (c,), jnp.float32)
        got = jax.jit(lambda x, s, b: group_norm(
            x, s, b, groups=32, eps=1e-5, act="silu", interpret=interpret))(
                x, scale, bias)
        err = float(jnp.max(jnp.abs(
            jnp.asarray(got, jnp.float32)
            - ref.group_norm_silu(x, scale, bias))))
        readings.append({"group_norm": [h, w, c], "max_abs": err})
        if not err <= GROUP_NORM_TOL:
            failures.append(f"group_norm {h}x{w}x{c}: max abs error "
                            f"{err:.4f} over {GROUP_NORM_TOL}")
    return failures, readings


def denoiser_inputs(pipe, height: int, width: int, seed: int) -> dict:
    """One seeded CFG pair at the cell's latent shape, rounded to the
    serving dtype (so the reference sees the values the system sees)."""
    import jax
    import jax.numpy as jnp

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

    from .reference.unet2d import unet_forward

    def row(tree):
        return jax.tree_util.tree_map(lambda x: x[1:], tree)

    return unet_forward(
        pipe.params["unet"], pipe.unet.config, row(inputs["sample"]),
        row(inputs["timesteps"]), row(inputs["context"]),
        None if inputs["added"] is None else row(inputs["added"]),
        device=jax.local_devices(backend="cpu")[0])


def denoiser(pipe, inputs: dict, want) -> tuple[list, dict]:
    """correct 5: one evaluation of the resident UNet on the pair, in the
    serving dtype with the kernels as dispatched, against the reference's
    row. Compares the predicted noise: a 30-step loop on random weights
    would amplify rounding."""
    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu.ops.platform import mesh_scope

    serve = jax.jit(lambda p, x, t, c, a: pipe.unet.apply(
        {"params": p}, x, t, c, added_cond=a))
    with mesh_scope(pipe.mesh):
        got = serve(pipe.params["unet"], inputs["sample"],
                    inputs["timesteps"], inputs["context"], inputs["added"])
    got = jax.device_put(jnp.asarray(got[1:], jnp.float32),
                         next(iter(want.devices())))
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    reading = {"rel_l2": rel,
               "max_abs": float(jnp.max(jnp.abs(got - want))),
               "ref_rms": float(jnp.sqrt(jnp.mean(want ** 2)))}
    failures = []
    if not rel <= UNET_REL_L2_TOL:
        failures.append(f"denoiser differs from the plain reference by "
                        f"{rel:.4f} relative L2, over {UNET_REL_L2_TOL}")
    return failures, reading


def artifact(blob: bytes, ref: dict, height: int, width: int) -> str | None:
    """correct 1 for one job's primary artifact: hashes to its name,
    decodes to the canvas, is not constant."""
    import numpy as np
    from PIL import Image

    if hashlib.sha256(blob).hexdigest() != ref.get("sha256"):
        return "artifact does not hash to its name"
    pixels = np.asarray(Image.open(io.BytesIO(blob)).convert("RGB"))
    if pixels.shape != (height, width, 3):
        return f"image decodes to {pixels.shape}, not {height}x{width}"
    if pixels.min() == pixels.max():
        return f"image is constant ({pixels.min()})"
    return None
