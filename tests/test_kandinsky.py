"""Kandinsky 2.x cascade: prior embedding diffusion + image-embed decoder.

Covers VERDICT missing #2 (Kandinsky prior/decoder): KandinskyV22Pipeline
wire names resolve and produce images on tiny configs, with the prior
running as the internal prepipeline stage (reference
swarm/diffusion/pipeline_steps.py:7-38 semantics).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chiaswarm_tpu import registry
from chiaswarm_tpu.models.prior import TINY_PRIOR, DiffusionPrior
from chiaswarm_tpu.pipelines.kandinsky import (
    KandinskyPipeline,
    KandinskyPriorPipeline,
    _prior_name_for,
)
from chiaswarm_tpu.weights import MissingWeightsError


def test_prior_model_forward():
    model = DiffusionPrior(TINY_PRIOR)
    cfg = TINY_PRIOR
    b = 2
    args = (
        jnp.zeros((b, cfg.embed_dim)),
        jnp.ones((b,)),
        jnp.zeros((b, cfg.text_seq, cfg.text_dim)),
        jnp.zeros((b, cfg.text_dim)),
    )
    params = model.init(jax.random.key(0), *args)
    out = model.apply(params, *args)
    assert out.shape == (b, cfg.embed_dim)
    assert np.isfinite(np.asarray(out)).all()


@pytest.fixture(scope="module")
def tiny_prior():
    return KandinskyPriorPipeline("test/tiny-kandinsky-prior")


@pytest.fixture(scope="module")
def tiny_decoder():
    return KandinskyPipeline("test/tiny-kandinsky")


def test_prior_generates_embeds(tiny_prior):
    embeds, neg = tiny_prior.generate(
        "a red fox", num_images=2, steps=3, rng=jax.random.key(0)
    )
    assert embeds.shape == (2, TINY_PRIOR.embed_dim)
    assert neg.shape == (2, TINY_PRIOR.embed_dim)
    assert not np.allclose(np.asarray(embeds), np.asarray(neg))


def test_prior_deterministic(tiny_prior):
    gen = lambda: np.asarray(
        tiny_prior.generate("same", steps=2, rng=jax.random.key(3))[0]
    )
    np.testing.assert_array_equal(gen(), gen())


def test_decoder_from_explicit_embeds(tiny_decoder):
    embeds = np.random.default_rng(0).standard_normal(
        (1, TINY_PRIOR.embed_dim)
    ).astype(np.float32)
    images, config = tiny_decoder.run(
        image_embeds=embeds, height=64, width=64, num_inference_steps=2,
        rng=jax.random.key(0),
    )
    assert images[0].size == (64, 64)
    assert "prior_s" not in config["timings"]  # prior stage skipped


def test_full_cascade_txt2img(tiny_decoder):
    images, config = tiny_decoder.run(
        prompt="a fox in the snow",
        height=64,
        width=64,
        num_inference_steps=2,
        prior_timesteps=2,
        rng=jax.random.key(0),
    )
    assert images[0].size == (64, 64)
    assert config["timings"]["prior_s"] > 0  # prior prepipeline ran
    assert config["timings"]["denoise_decode_s"] > 0


def test_embeds_condition_the_decoder(tiny_decoder):
    rng = np.random.default_rng(1)
    kw = dict(height=64, width=64, num_inference_steps=2, rng=jax.random.key(7))
    a = np.asarray(tiny_decoder.run(
        image_embeds=rng.standard_normal((1, TINY_PRIOR.embed_dim),
                                         ).astype(np.float32), **kw)[0][0])
    b = np.asarray(tiny_decoder.run(
        image_embeds=rng.standard_normal((1, TINY_PRIOR.embed_dim),
                                         ).astype(np.float32), **kw)[0][0])
    assert not np.array_equal(a, b)


def test_decoder_batch_follows_embeds(tiny_decoder):
    embeds = np.random.default_rng(2).standard_normal(
        (3, TINY_PRIOR.embed_dim)
    ).astype(np.float32)
    images, _ = tiny_decoder.run(
        image_embeds=embeds, height=64, width=64, num_inference_steps=2,
        rng=jax.random.key(0),
    )
    assert len(images) == 3  # batch from embeds, not num_images_per_prompt


def test_prior_typed_job_is_clean_error(tiny_prior):
    with pytest.raises(Exception, match="prepipeline stage"):
        tiny_prior.run(prompt="x")


def test_hint_on_non_controlnet_model_rejected(tiny_decoder):
    # a hint against a plain decoder checkpoint cannot condition anything
    with pytest.raises(Exception, match="not a ControlNet checkpoint"):
        tiny_decoder.run(
            prompt="x", pipeline_type="KandinskyV22ControlnetPipeline",
            hint=np.zeros((1, 8, 8, 3), np.float32), num_inference_steps=2,
        )


@pytest.fixture(scope="module")
def tiny_controlnet():
    return KandinskyPipeline("test/tiny-kandinsky-controlnet")


def test_controlnet_depth_hint_conditions(tiny_controlnet):
    """KandinskyV22ControlnetPipeline with a depth hint (reference
    job_arguments.py:386-388 passes `hint` instead of `image`)."""
    rng = np.random.default_rng(0)
    kw = dict(
        prompt="a robot, 4k photo",
        pipeline_type="KandinskyV22ControlnetPipeline",
        height=64, width=64, num_inference_steps=2, prior_timesteps=2,
        rng=jax.random.key(3),
    )
    a_hint = rng.random((64, 64, 3)).astype(np.float32)
    b_hint = rng.random((64, 64, 3)).astype(np.float32)
    a, cfg = tiny_controlnet.run(hint=a_hint, **kw)
    assert cfg["mode"] == "controlnet"
    assert a[0].size == (64, 64)
    b, _ = tiny_controlnet.run(hint=b_hint, **kw)
    assert not np.array_equal(np.asarray(a[0]), np.asarray(b[0]))


def test_controlnet_requires_hint(tiny_controlnet):
    with pytest.raises(Exception, match="requires a depth hint"):
        tiny_controlnet.run(prompt="x", num_inference_steps=2)


def test_registry_wire_names():
    pipe = registry.get_pipeline("test/tiny-kandinsky", "KandinskyV22Pipeline")
    assert isinstance(pipe, KandinskyPipeline)
    prior = registry.get_pipeline(
        "test/tiny-kandinsky-prior", "KandinskyV22PriorPipeline"
    )
    assert isinstance(prior, KandinskyPriorPipeline)


def test_prior_name_mapping():
    assert _prior_name_for("test/tiny-kandinsky") == "test/tiny-kandinsky-prior"
    assert (
        _prior_name_for("kandinsky-community/kandinsky-2-2-decoder")
        == "kandinsky-community/kandinsky-2-2-prior"
    )
    assert (
        _prior_name_for("kandinsky-community/kandinsky-2-1")
        == "kandinsky-community/kandinsky-2-1-prior"
    )


def test_real_kandinsky_requires_weights(sdaas_root):
    with pytest.raises(MissingWeightsError, match="Kandinsky"):
        KandinskyPipeline("kandinsky-community/kandinsky-2-2-decoder")


def test_kandinsky_job_through_callback():
    from chiaswarm_tpu.workflows.diffusion import (
        diffusion_callback,
        packaged,
    )

    artifacts, config = diffusion_callback(
        "cpu:0",
        "kandinsky-community/kandinsky-2-2-decoder",
        pipeline_type="KandinskyV22Pipeline",
        prompt="wire",
        height=64,
        width=64,
        num_inference_steps=2,
        prior_timesteps=2,
        test_tiny_model=True,
        rng=jax.random.key(0),
    )
    assert config["model"] == "test/tiny-kandinsky"
    assert packaged(artifacts)["primary"]["content_type"] == "image/jpeg"


def test_img2img_conditions_on_init_image(tiny_decoder):
    """Kandinsky img2img (reference swarm/test.py:100-113 schedules it via
    AutoPipelineForImage2Image): the init image sets the denoise start."""
    from PIL import Image as PILImage

    rng = np.random.default_rng(0)
    img_a = PILImage.fromarray((rng.random((64, 64, 3)) * 255).astype(np.uint8))
    img_b = PILImage.fromarray((rng.random((64, 64, 3)) * 255).astype(np.uint8))
    kw = dict(prompt="repaint", num_inference_steps=4, prior_timesteps=2,
              strength=0.5, rng=jax.random.key(9))
    a, cfg = tiny_decoder.run(image=img_a, **kw)
    assert cfg["mode"] == "img2img"
    assert a[0].size == (64, 64)
    b, _ = tiny_decoder.run(image=img_b, **kw)
    assert not np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
