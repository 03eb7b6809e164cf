"""The comparisons that decide `correct` (README, "What correct means").

Each returns a list of failure strings; an empty list passes. Tolerances
are written with their reason: the shared kernels' here, the denoiser's in
the family module that knows the network (`families/<family>.py`). What a
job's artifact has to be is the family's too (`check_artifact`): the harness
knows no kind of artifact, so nothing here opens one.
"""

from __future__ import annotations

# Kernels against the float32 reference, max abs error on unit-normal
# bfloat16 inputs. The chip read 0.0005-0.0065 for attention (the largest
# at 77 keys and on the XLA path at 576 tokens, where each output averages
# few values) and 0.0078 for GroupNorm+SiLU, one bfloat16 rounding of the
# output (my chip runs, PR 23; PR 22's probe: 0.0005-0.0085 and 0.031 on
# other inputs). A float32-accumulating kernel stays under these bounds; a
# kernel that accumulated in bfloat16 over thousands of keys would not.
ATTENTION_TOL = 0.02
GROUP_NORM_TOL = 0.05


def kernels(config: dict, dtype, interpret: bool = False) -> tuple[list, list]:
    """correct 4, the part families share (a family's `kernel_checks`
    returns this, or adds the comparison of a kernel of its own): the
    program's attention and GroupNorm dispatch against the plain references
    at the configuration's own shapes (either list may be absent: a network
    without GroupNorm has none). Returns (failures, readings), a reading
    `{<kernel>: shape, "max_abs": number, "limit": its tolerance}`."""
    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu.ops import dot_product_attention
    from chiaswarm_tpu.ops.group_norm import group_norm

    from .reference import kernels as ref

    failures, readings = [], []
    for n, (sq, skv, heads, dim) in enumerate(
            config.get("attention_shapes", ())):
        keys = jax.random.split(jax.random.key(100 + n), 3)
        q = jax.random.normal(keys[0], (2, sq, heads, dim), dtype)
        k = jax.random.normal(keys[1], (2, skv, heads, dim), dtype)
        v = jax.random.normal(keys[2], (2, skv, heads, dim), dtype)
        got = jax.jit(dot_product_attention)(q, k, v)
        err = float(jnp.max(jnp.abs(
            jnp.asarray(got, jnp.float32) - ref.attention(q, k, v))))
        readings.append({"attention": [sq, skv, heads, dim], "max_abs": err,
                         "limit": ATTENTION_TOL})
        if not err <= ATTENTION_TOL:
            failures.append(f"attention {sq}x{skv}x{heads}x{dim}: max abs "
                            f"error {err:.4f} over {ATTENTION_TOL}")
    for n, (h, w, c) in enumerate(config.get("group_norm_shapes", ())):
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
        readings.append({"group_norm": [h, w, c], "max_abs": err,
                         "limit": GROUP_NORM_TOL})
        if not err <= GROUP_NORM_TOL:
            failures.append(f"group_norm {h}x{w}x{c}: max abs error "
                            f"{err:.4f} over {GROUP_NORM_TOL}")
    return failures, readings


def denoiser(family, pipe, inputs, want) -> tuple[list, dict]:
    """correct 5: what the family's resident network gives for `inputs`
    (`denoiser_serve`: the serving dtype, the kernels as dispatched)
    against the plain reference's `want`, by relative L2 error under the
    family's own tolerance."""
    import jax
    import jax.numpy as jnp

    got = family.denoiser_serve(pipe, inputs)
    got = jax.device_put(jnp.asarray(got, jnp.float32),
                         next(iter(want.devices())))
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    reading = {"rel_l2": rel,
               "max_abs": float(jnp.max(jnp.abs(got - want))),
               "ref_rms": float(jnp.sqrt(jnp.mean(want ** 2))),
               "limit": family.DENOISER_REL_L2_TOL}
    failures = []
    if not rel <= family.DENOISER_REL_L2_TOL:
        failures.append(f"denoiser differs from the plain reference by "
                        f"{rel:.4f} relative L2, over "
                        f"{family.DENOISER_REL_L2_TOL}")
    return failures, reading
