"""The `flux` family at the tiny preset: the plain MMDiT reference against
the program's flax module, the seeded weights against the pipeline's own
description of its tree, and the three calls of `correct` 5 end to end (one
CPU device here; `tests/test_flux_sharded.py` runs them on a 4-way tensor
mesh, where the fused kernels are regrouped)."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from benchmark import checks
from benchmark.families import flux
from benchmark.reference.mmdit import mmdit_forward
from chiaswarm_tpu.models.flux import TINY_FLUX, FluxTransformer, patchify

CONFIG = {"job": {"height": 64, "width": 64}, "denoiser_canvas": [32, 32],
          "denoiser_txt_len": 8}


@pytest.mark.parametrize("cfg", [
    TINY_FLUX, dataclasses.replace(TINY_FLUX, guidance_embed=False)],
    ids=["dev", "schnell"])
def test_reference_matches_the_flax_module(cfg):
    model = FluxTransformer(cfg)
    keys = jax.random.split(jax.random.key(1), 6)
    img, img_ids = patchify(jax.random.normal(keys[0], (2, 8, 8, 4)))
    inputs = dict(
        img=img, img_ids=img_ids,
        txt=jax.random.normal(keys[1], (2, 8, cfg.context_dim)),
        txt_ids=jnp.zeros((2, 8, 3), jnp.int32),
        timesteps=jnp.asarray([0.3, 0.9]),
        pooled=jax.random.normal(keys[2], (2, cfg.pooled_dim)),
        guidance=jnp.asarray([3.5, 1.5]))
    apply = jax.jit(lambda p, x: model.apply(
        {"params": p}, x["img"], x["img_ids"], x["txt"], x["txt_ids"],
        x["timesteps"], x["pooled"], guidance=x["guidance"]))
    params = jax.jit(lambda: model.init(
        keys[3], img, img_ids, inputs["txt"], inputs["txt_ids"],
        inputs["timesteps"], inputs["pooled"],
        guidance=inputs["guidance"])["params"])()
    # flax starts biases at 0 and scales at 1: move them so they count
    leaves, treedef = jax.tree_util.tree_flatten(params)
    noise = jax.random.split(keys[4], len(leaves))
    params = jax.tree_util.tree_unflatten(treedef, [
        leaf + 0.1 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, noise)])
    with jax.default_matmul_precision("highest"):
        got = apply(params, inputs)
    want = mmdit_forward(params, cfg, **inputs)
    # float32 both sides, other operation order: a few ulp of values ~1
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
    assert float(jnp.max(jnp.abs(want))) > 0.5


@pytest.fixture(scope="module")
def pipe():
    from chiaswarm_tpu import registry

    record = {}
    flux.register(7, record)
    built = registry.get_pipeline("test/tiny-flux", "FluxPipeline")
    assert set(record["weights_phases"]["test/tiny-flux"]) == {
        "pool_s", "leaves_s", "programs"}
    return built


def test_seeded_tree_is_the_one_the_pipeline_describes(pipe):
    shapes = pipe.param_shapes()
    assert jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), pipe.params) \
        == jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), shapes)
    again = flux.seeded_leaves(shapes, pipe.param_shardings(), 7)
    other = flux.seeded_leaves(shapes, pipe.param_shardings(), 8)
    block = pipe.params["flux"]["double_blocks_0"]
    kernel = block["img_mlp_0"]["kernel"]
    assert bool((kernel == again["flux"]["double_blocks_0"]["img_mlp_0"]
                 ["kernel"]).all())
    assert not bool((kernel == other["flux"]["double_blocks_0"]["img_mlp_0"]
                     ["kernel"]).all())
    assert float(kernel.std()) == pytest.approx(
        kernel.shape[0] ** -0.5, rel=0.2)
    assert float(jnp.abs(block["img_mlp_0"]["bias"]).max()) == 0.0
    assert float(block["img_attn_norm"]["query_scale"].min()) == 1.0
    assert float(pipe.params["t5"]["final_norm"]["scale"].max()) == 1.0


def test_correct_5_end_to_end_and_the_control_breaks_it(pipe):
    inputs = flux.denoiser_inputs(pipe, CONFIG, 3)
    assert inputs["img"].shape == (1, 64, 16) and inputs["txt"].shape[1] == 8
    want = flux.denoiser_reference(pipe, inputs)
    assert next(iter(want.devices())).platform == "cpu"
    failures, reading = checks.denoiser(flux, pipe, inputs, want)
    # float32 serving here: the serve is the reference but for its order
    assert failures == [] and reading["rel_l2"] < 1e-5

    class Coarse:
        DENOISER_REL_L2_TOL = flux.DENOISER_REL_L2_TOL
        denoiser_serve = staticmethod(flux.int8_control)

    failures, control = checks.denoiser(Coarse, pipe, inputs, want)
    assert control["rel_l2"] > 100 * reading["rel_l2"]
