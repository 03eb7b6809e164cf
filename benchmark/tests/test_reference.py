"""The plain UNet reference against the program's flax module, float32, at
the `tiny` and `tiny-xl` presets."""

import jax
import jax.numpy as jnp
import pytest

from benchmark.reference.unet2d import unet_forward
from chiaswarm_tpu.models import configs as cfgs
from chiaswarm_tpu.models.unet2d import UNet2DConditionModel


@pytest.mark.parametrize("cfg", [cfgs.TINY_UNET, cfgs.TINY_XL_UNET],
                         ids=["tiny", "tiny-xl"])
def test_reference_matches_the_flax_module(cfg):
    model = UNet2DConditionModel(cfg, dtype=jnp.float32)
    keys = jax.random.split(jax.random.key(1), 5)
    sample = jax.random.normal(keys[0], (2, 16, 16, cfg.in_channels))
    timesteps = jnp.array([500.0, 20.0])
    context = jax.random.normal(keys[1], (2, 77, cfg.cross_attention_dim))
    added = None
    if cfg.addition_embed_dim:
        pooled = cfg.addition_embed_dim - 6 * cfg.addition_time_embed_dim
        added = {"text_embeds": jax.random.normal(keys[2], (2, pooled)),
                 "time_ids": jnp.asarray([[64, 64, 0, 0, 64, 64]] * 2,
                                         jnp.float32)}
    params = model.init(keys[3], sample, timesteps, context,
                        added_cond=added)["params"]
    # flax starts biases at 0 and scales at 1: move them so they count
    leaves, treedef = jax.tree_util.tree_flatten(params)
    noise = jax.random.split(keys[4], len(leaves))
    params = jax.tree_util.tree_unflatten(treedef, [
        leaf + 0.1 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, noise)])
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, sample, timesteps, context,
                          added_cond=added)
    want = unet_forward(params, cfg, sample, timesteps, context, added)
    # float32 both sides, other operation order: a few ulp of values ~5
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
    assert float(jnp.max(jnp.abs(want))) > 1.0
