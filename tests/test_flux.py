"""Flux MMDiT family: patchify/RoPE units, conversion mapping, pipeline e2e.

Covers VERDICT missing #2 (Flux family): FluxPipeline wire names resolve
and produce images on tiny configs. Conversion is validated by inverting
the tiny Flax tree into diffusers FluxTransformer2DModel / T5EncoderModel
naming and requiring an exact roundtrip (diffusers itself is not in this
image).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chiaswarm_tpu.models.flux import (
    TINY_FLUX,
    FluxTransformer,
    patchify,
    rope_frequencies,
    unpatchify,
)
from chiaswarm_tpu.models.t5 import TINY_T5, T5Encoder
from chiaswarm_tpu.pipelines.flux import FluxPipeline
from chiaswarm_tpu.weights import MissingWeightsError


def test_patchify_roundtrip():
    x = jnp.asarray(np.random.default_rng(0).random((2, 8, 6, 4)), jnp.float32)
    patches, ids = patchify(x)
    assert patches.shape == (2, 4 * 3, 16)
    assert ids.shape == (2, 12, 3)
    # ids are (0, y, x) per 2x2 patch
    assert ids[0, 0].tolist() == [0, 0, 0]
    assert ids[0, -1].tolist() == [0, 3, 2]
    back = unpatchify(patches, 8, 6)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


def test_rope_shapes_match_head_dim():
    ids = jnp.zeros((1, 5, 3), jnp.int32)
    cos, sin = rope_frequencies(ids, TINY_FLUX.axes_dims_rope, TINY_FLUX.theta)
    assert cos.shape == (1, 5, TINY_FLUX.head_dim // 2)
    assert sin.shape == cos.shape


def test_t5_encoder_forward():
    enc = T5Encoder(TINY_T5)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 999, (2, 16)))
    params = enc.init(jax.random.key(0), ids)
    out = enc.apply(params, ids)
    assert out.shape == (2, 16, TINY_T5.d_model)
    assert np.isfinite(np.asarray(out)).all()


def test_flux_transformer_forward():
    model = FluxTransformer(TINY_FLUX)
    b, s_img, s_txt = 2, 12, 8
    rng = jax.random.key(0)
    img = jnp.zeros((b, s_img, TINY_FLUX.in_channels))
    img_ids = jnp.zeros((b, s_img, 3), jnp.int32)
    txt = jnp.zeros((b, s_txt, TINY_FLUX.context_dim))
    txt_ids = jnp.zeros((b, s_txt, 3), jnp.int32)
    params = model.init(rng, img, img_ids, txt, txt_ids, jnp.ones((b,)),
                        jnp.zeros((b, TINY_FLUX.pooled_dim)),
                        guidance=jnp.ones((b,)))
    out = model.apply(params, img, img_ids, txt, txt_ids, jnp.ones((b,)),
                      jnp.zeros((b, TINY_FLUX.pooled_dim)),
                      guidance=jnp.ones((b,)))
    assert out.shape == (b, s_img, TINY_FLUX.in_channels)


@pytest.fixture(scope="module")
def tiny_flux():
    return FluxPipeline("test/tiny-flux")


def test_flux_txt2img(tiny_flux):
    images, config = tiny_flux.run(
        prompt="a fox", height=64, width=64, num_inference_steps=2,
        rng=jax.random.key(0),
    )
    assert len(images) == 1 and images[0].size == (64, 64)
    assert config["scheduler"] == "FlowMatchEulerScheduler"
    assert config["timings"]["denoise_decode_s"] > 0


def test_flux_deterministic(tiny_flux):
    run = lambda: np.asarray(
        tiny_flux.run(prompt="same", height=64, width=64,
                      num_inference_steps=2, rng=jax.random.key(5))[0][0]
    )
    np.testing.assert_array_equal(run(), run())


def test_flux_guidance_changes_output(tiny_flux):
    kw = dict(prompt="g", height=64, width=64, num_inference_steps=2,
              rng=jax.random.key(1))
    a = np.asarray(tiny_flux.run(guidance_scale=1.0, **kw)[0][0])
    b = np.asarray(tiny_flux.run(guidance_scale=8.0, **kw)[0][0])
    assert not np.array_equal(a, b)  # dev: distilled guidance embedding


def test_flux_schnell_ignores_guidance():
    pipe = FluxPipeline("test/tiny-flux-schnell")
    assert not pipe.config.guidance_embed
    kw = dict(prompt="g", height=64, width=64, num_inference_steps=2,
              rng=jax.random.key(1))
    a = np.asarray(pipe.run(guidance_scale=1.0, **kw)[0][0])
    b = np.asarray(pipe.run(guidance_scale=8.0, **kw)[0][0])
    np.testing.assert_array_equal(a, b)


def test_flux_run_batched_matches_solo(tiny_flux):
    """ISSUE 20 satellite: a coalesced flux pass reproduces each
    member's solo output to within one uint8 quantization step —
    per-request init latents are drawn from the request's own rng with
    the solo split + shape, and the programs are row-independent (XLA
    may vectorize the wider batch differently, so the last float bit
    can move a pixel by at most one level)."""
    shared = dict(height=64, width=64, num_inference_steps=2,
                  guidance_scale=4.0)
    reqs = [
        {"prompt": "a fox", "rng": jax.random.key(3),
         "num_images_per_prompt": 2},
        {"prompt": "a crab", "rng": jax.random.key(9)},
    ]
    outs = tiny_flux.run_batched([dict(r) for r in reqs], **shared)
    assert len(outs) == 2
    for r, (images, cfg) in zip(reqs, outs):
        solo_images, _ = tiny_flux.run(
            prompt=r["prompt"], rng=r["rng"],
            num_images_per_prompt=r.get("num_images_per_prompt", 1),
            **shared)
        assert len(images) == len(solo_images)
        for img, ref in zip(images, solo_images):
            np.testing.assert_allclose(
                np.asarray(img, np.int16), np.asarray(ref, np.int16),
                atol=1, rtol=0)
        assert cfg["batched_with"] == 2
        assert cfg["padded_rows"] == 4  # 3 real rows pad to the bucket
        assert cfg["scheduler"] == "FlowMatchEulerScheduler"


def test_flux_run_batched_refuses_adapter_rows(tiny_flux):
    with pytest.raises(ValueError):
        tiny_flux.run_batched(
            [{"prompt": "x", "lora": "style-a"}],
            height=64, width=64, num_inference_steps=2)


def test_flux_vae_has_no_quant_convs():
    from chiaswarm_tpu.models.configs import FLUX_VAE
    from chiaswarm_tpu.models.vae import AutoencoderKL

    vae = AutoencoderKL(FLUX_VAE)
    params = vae.init(jax.random.key(0), jnp.zeros((1, 16, 16, 3)))["params"]
    assert "quant_conv" not in params and "post_quant_conv" not in params
    # encoder moments still split into 16-ch mean/logvar and decode runs
    latents = vae.apply({"params": params}, jnp.zeros((1, 16, 16, 3)),
                        method=vae.encode)
    assert latents.shape == (1, 2, 2, 16)
    out = vae.apply({"params": params}, latents, method=vae.decode)
    assert out.shape == (1, 16, 16, 3)


def test_sigma_shift_per_variant():
    from chiaswarm_tpu.pipelines.flux import _sigma_shift

    assert _sigma_shift(4096, dynamic=False) == 1.0  # schnell: unshifted
    # dev at 1024px (4096 tokens): exp(1.15); at 256 tokens: exp(0.5)
    assert _sigma_shift(4096, dynamic=True) == pytest.approx(np.exp(1.15))
    assert _sigma_shift(256, dynamic=True) == pytest.approx(np.exp(0.5))


def test_flux_registry_wire_name():
    from chiaswarm_tpu import registry

    pipe = registry.get_pipeline("test/tiny-flux", "FluxPipeline")
    assert isinstance(pipe, FluxPipeline)


def test_flux_requires_weights(sdaas_root):
    with pytest.raises(MissingWeightsError):
        FluxPipeline("black-forest-labs/FLUX.1-dev")


def test_flux_tiny_job_through_callback():
    from chiaswarm_tpu.workflows.diffusion import (
        diffusion_callback,
        packaged,
    )

    artifacts, config = diffusion_callback(
        "cpu:0",
        "black-forest-labs/FLUX.1-schnell",
        pipeline_type="FluxPipeline",
        prompt="wire",
        height=64,
        width=64,
        num_inference_steps=2,
        test_tiny_model=True,
        rng=jax.random.key(0),
    )
    assert config["model"] == "test/tiny-flux-schnell"
    assert packaged(artifacts)["primary"]["content_type"] == "image/jpeg"


# --- conversion mapping (exact roundtrip through diffusers naming) ---


def _dense_to_torch(state, torch_name, tree):
    state[f"{torch_name}.weight"] = np.ascontiguousarray(
        np.asarray(tree["kernel"], np.float32).T
    )
    if "bias" in tree:
        state[f"{torch_name}.bias"] = np.asarray(tree["bias"], np.float32)


def _flux_flax_to_diffusers(p):
    cfg = TINY_FLUX
    state = {}
    _dense_to_torch(state, "x_embedder", p["img_in"])
    _dense_to_torch(state, "context_embedder", p["txt_in"])
    _dense_to_torch(state, "time_text_embed.timestep_embedder.linear_1",
                    p["time_in"]["in_layer"])
    _dense_to_torch(state, "time_text_embed.timestep_embedder.linear_2",
                    p["time_in"]["out_layer"])
    _dense_to_torch(state, "time_text_embed.text_embedder.linear_1",
                    p["vector_in"]["in_layer"])
    _dense_to_torch(state, "time_text_embed.text_embedder.linear_2",
                    p["vector_in"]["out_layer"])
    _dense_to_torch(state, "time_text_embed.guidance_embedder.linear_1",
                    p["guidance_in"]["in_layer"])
    _dense_to_torch(state, "time_text_embed.guidance_embedder.linear_2",
                    p["guidance_in"]["out_layer"])
    _dense_to_torch(state, "proj_out", p["final_layer_linear"])

    # my final_layer_mod kernel cols are (shift, scale); diffusers rows are
    # (scale, shift)
    k = np.asarray(p["final_layer_mod"]["kernel"], np.float32).T
    h = k.shape[0] // 2
    state["norm_out.linear.weight"] = np.ascontiguousarray(
        np.concatenate([k[h:], k[:h]], axis=0)
    )
    b = np.asarray(p["final_layer_mod"]["bias"], np.float32)
    state["norm_out.linear.bias"] = np.concatenate([b[h:], b[:h]])

    for i in range(cfg.depth_double):
        blk = p[f"double_blocks_{i}"]
        base = f"transformer_blocks.{i}"
        _dense_to_torch(state, f"{base}.norm1.linear", blk["img_mod"]["lin"])
        _dense_to_torch(state, f"{base}.norm1_context.linear",
                        blk["txt_mod"]["lin"])
        _dense_to_torch(state, f"{base}.attn.to_out.0", blk["img_attn_proj"])
        _dense_to_torch(state, f"{base}.attn.to_add_out", blk["txt_attn_proj"])
        _dense_to_torch(state, f"{base}.ff.net.0.proj", blk["img_mlp_0"])
        _dense_to_torch(state, f"{base}.ff.net.2", blk["img_mlp_2"])
        _dense_to_torch(state, f"{base}.ff_context.net.0.proj",
                        blk["txt_mlp_0"])
        _dense_to_torch(state, f"{base}.ff_context.net.2", blk["txt_mlp_2"])
        for stream, attn_prefix in (("img", ""), ("txt", "added_")):
            qkv_k = np.asarray(blk[f"{stream}_attn_qkv"]["kernel"], np.float32)
            qkv_b = np.asarray(blk[f"{stream}_attn_qkv"]["bias"], np.float32)
            third = qkv_k.shape[1] // 3
            names = (
                [f"{base}.attn.to_q", f"{base}.attn.to_k", f"{base}.attn.to_v"]
                if stream == "img"
                else [f"{base}.attn.add_q_proj", f"{base}.attn.add_k_proj",
                      f"{base}.attn.add_v_proj"]
            )
            for s, nm in enumerate(names):
                state[f"{nm}.weight"] = np.ascontiguousarray(
                    qkv_k[:, s * third:(s + 1) * third].T
                )
                state[f"{nm}.bias"] = qkv_b[s * third:(s + 1) * third]
            norm = blk[f"{stream}_attn_norm"]
            state[f"{base}.attn.norm_{attn_prefix}q.weight"] = np.asarray(
                norm["query_scale"], np.float32
            )
            state[f"{base}.attn.norm_{attn_prefix}k.weight"] = np.asarray(
                norm["key_scale"], np.float32
            )

    for i in range(cfg.depth_single):
        blk = p[f"single_blocks_{i}"]
        base = f"single_transformer_blocks.{i}"
        _dense_to_torch(state, f"{base}.norm.linear", blk["modulation"]["lin"])
        _dense_to_torch(state, f"{base}.proj_out", blk["linear2"])
        k = np.asarray(blk["linear1"]["kernel"], np.float32)
        b = np.asarray(blk["linear1"]["bias"], np.float32)
        hd3 = 3 * cfg.num_heads * cfg.head_dim
        third = hd3 // 3
        for s, nm in enumerate(["attn.to_q", "attn.to_k", "attn.to_v"]):
            state[f"{base}.{nm}.weight"] = np.ascontiguousarray(
                k[:, s * third:(s + 1) * third].T
            )
            state[f"{base}.{nm}.bias"] = b[s * third:(s + 1) * third]
        state[f"{base}.proj_mlp.weight"] = np.ascontiguousarray(k[:, hd3:].T)
        state[f"{base}.proj_mlp.bias"] = b[hd3:]
        state[f"{base}.attn.norm_q.weight"] = np.asarray(
            blk["norm"]["query_scale"], np.float32
        )
        state[f"{base}.attn.norm_k.weight"] = np.asarray(
            blk["norm"]["key_scale"], np.float32
        )
    return state


def _t5_flax_to_hf(p):
    state = {"shared.weight": np.asarray(p["token_embedding"]["embedding"],
                                         np.float32)}
    state["encoder.final_layer_norm.weight"] = np.asarray(
        p["final_norm"]["scale"], np.float32
    )
    for i in range(TINY_T5.num_layers):
        blk = p[f"block_{i}"]
        base = f"encoder.block.{i}.layer"
        for proj in "qkvo":
            state[f"{base}.0.SelfAttention.{proj}.weight"] = (
                np.ascontiguousarray(
                    np.asarray(blk["attention"][proj]["kernel"], np.float32).T
                )
            )
        if i == 0:
            state[f"{base}.0.SelfAttention.relative_attention_bias.weight"] = (
                np.asarray(blk["attention"]["relative_attention_bias"],
                           np.float32)
            )
        state[f"{base}.0.layer_norm.weight"] = np.asarray(
            blk["attn_norm"]["scale"], np.float32
        )
        for proj in ("wi_0", "wi_1", "wo"):
            state[f"{base}.1.DenseReluDense.{proj}.weight"] = (
                np.ascontiguousarray(
                    np.asarray(blk[proj]["kernel"], np.float32).T
                )
            )
        state[f"{base}.1.layer_norm.weight"] = np.asarray(
            blk["ff_norm"]["scale"], np.float32
        )
    return state


def _assert_trees_equal(converted, ref):
    flat_ref = jax.tree_util.tree_flatten_with_path(ref)[0]
    flat_conv = jax.tree_util.tree_flatten_with_path(converted)[0]
    assert len(flat_ref) == len(flat_conv), (
        len(flat_ref), len(flat_conv)
    )
    conv_map = {tuple(str(k) for k in kp): v for kp, v in flat_conv}
    for kp, v in flat_ref:
        key = tuple(str(k) for k in kp)
        assert key in conv_map, key
        np.testing.assert_allclose(conv_map[key], np.asarray(v), rtol=1e-6,
                                   err_msg=str(key))


def test_convert_flux_roundtrip_exact(tiny_flux):
    from chiaswarm_tpu.models.conversion import convert_flux

    ref = jax.tree_util.tree_map(
        lambda x: np.asarray(x, np.float32), dict(tiny_flux.params["flux"])
    )
    converted = convert_flux(_flux_flax_to_diffusers(ref))
    _assert_trees_equal(converted, ref)


def test_convert_t5_roundtrip_exact(tiny_flux):
    from chiaswarm_tpu.models.conversion import convert_t5

    ref = jax.tree_util.tree_map(
        lambda x: np.asarray(x, np.float32), dict(tiny_flux.params["t5"])
    )
    converted = convert_t5(_t5_flax_to_hf(ref))
    _assert_trees_equal(converted, ref)
