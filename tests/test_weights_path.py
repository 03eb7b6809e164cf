"""Real-weight serving path: disk safetensors -> conversion -> pipeline.

Covers VERDICT weak #3 / next-round #3: the loading path a production
worker takes (diffusers-layout safetensors under model_root_dir, converted
into the Flax trees at residency time), the fail-loud policy when weights
are absent, and the initialize CLI's convert+shape-check validation.

diffusers itself is not installed in this image, so the on-disk layout is
synthesized by inverting tiny Flax trees into torch tensor layout (the
exact inverse of models/conversion.py's rules) and writing real
safetensors files — the pipeline then loads them through the same
`load_torch_state_dict` path it uses for genuine HF checkpoints.
"""

import os

import numpy as np
import pytest
from safetensors.numpy import save_file

import jax
import jax.numpy as jnp

from chiaswarm_tpu.models import configs as cfgs
from chiaswarm_tpu.models.clip import CLIPTextEncoder
from chiaswarm_tpu.models.unet2d import UNet2DConditionModel
from chiaswarm_tpu.models.vae import AutoencoderKL
from chiaswarm_tpu.pipelines.stable_diffusion import SDPipeline
from chiaswarm_tpu.settings import Settings, save_settings
from chiaswarm_tpu.weights import MissingWeightsError


def flax_to_torch_layout(tree, prefix=""):
    """Invert conversion.py's layout rules: HWIO->OIHW convs, [I,O]->[O,I]
    linears, scale->weight norms. Values come back C-contiguous:
    safetensors' numpy writer silently serializes the raw buffer of a
    transposed view, corrupting the roundtrip otherwise."""
    flat = {
        k: np.ascontiguousarray(v)
        for k, v in _flax_to_torch_raw(tree, prefix).items()
    }
    return flat


def _flax_to_torch_raw(tree, prefix=""):
    flat = {}
    for k, v in tree.items():
        name = f"{prefix}.{k}" if prefix else k
        if isinstance(v, dict):
            flat.update(_flax_to_torch_raw(v, name))
        else:
            v = np.asarray(v, np.float32)
            if k == "kernel" and v.ndim == 4:
                flat[name.replace(".kernel", ".weight")] = v.transpose(3, 2, 0, 1)
            elif k == "kernel":
                flat[name.replace(".kernel", ".weight")] = v.T
            elif k == "scale":
                flat[name.replace(".scale", ".weight")] = v
            elif k == "embedding":
                flat[name.replace(".embedding", ".weight")] = v
            elif k == "position_embedding":
                # stored as a bare param in CLIPTextEncoder; HF keeps it at
                # embeddings.position_embedding.weight (clip_rename's input)
                flat["embeddings.position_embedding.weight"] = v
            else:
                flat[name] = v
    return flat


def seeded_params(module, seed, *args, **kwargs):
    return module.init(jax.random.key(seed), *args, **kwargs)["params"]


@pytest.fixture()
def tiny_model_on_disk(sdaas_root, tmp_path):
    """Write a tiny SD checkpoint in diffusers layout under a fresh model
    root; returns (model_name, root, reference_param_trees)."""
    model_root = tmp_path / "models"
    save_settings(Settings(model_root_dir=str(model_root)))
    name = "test/tiny-sd-disk"
    model_dir = model_root / name

    unet = UNet2DConditionModel(cfgs.TINY_UNET)
    vae = AutoencoderKL(cfgs.TINY_VAE)
    clip = CLIPTextEncoder(cfgs.TINY_CLIP)
    # seed 777: deliberately NOT the name-derived seed the random-init
    # fallback would use, so a value match proves weights came from disk
    unet_p = seeded_params(
        unet, 777, jnp.zeros((1, 8, 8, 4)), jnp.zeros((1,)),
        jnp.zeros((1, 77, cfgs.TINY_UNET.cross_attention_dim)),
    )
    vae_p = seeded_params(vae, 777, jnp.zeros((1, 16, 16, 3)))
    clip_p = seeded_params(clip, 777, jnp.zeros((1, 77), jnp.int32))

    for sub, tree in (("unet", unet_p), ("vae", vae_p), ("text_encoder", clip_p)):
        sub_dir = model_dir / sub
        sub_dir.mkdir(parents=True)
        save_file(flax_to_torch_layout(tree), str(sub_dir / "model.safetensors"))
    return name, model_root, {"unet": unet_p, "vae": vae_p, "text": clip_p}


def test_pipeline_loads_converted_weights_from_disk(tiny_model_on_disk):
    name, _, ref = tiny_model_on_disk
    pipe = SDPipeline(name)
    got = np.asarray(pipe.params["unet"]["conv_in"]["kernel"], np.float32)
    np.testing.assert_allclose(
        got, np.asarray(ref["unet"]["conv_in"]["kernel"]), rtol=1e-6
    )
    got_clip = np.asarray(
        pipe.params["text"][0]["token_embedding"]["embedding"], np.float32
    )
    np.testing.assert_allclose(
        got_clip, np.asarray(ref["text"]["token_embedding"]["embedding"]), rtol=1e-6
    )
    # and the loaded bundle actually serves a job
    images, config = pipe.run(
        prompt="from disk", height=64, width=64, num_inference_steps=2,
        rng=jax.random.key(0),
    )
    assert images[0].size == (64, 64)


def test_missing_weights_fatal_for_production_model(sdaas_root):
    with pytest.raises(MissingWeightsError, match="not present on this worker"):
        SDPipeline("stabilityai/stable-diffusion-2-1")


def test_missing_weights_is_value_error_hence_fatal_envelope():
    # worker.py:178 classifies ValueError as fatal_error=true for the hive
    assert issubclass(MissingWeightsError, ValueError)


def test_allow_random_init_policy():
    from chiaswarm_tpu.weights import random_init_permitted

    assert random_init_permitted("test/tiny-sd", False)
    assert random_init_permitted("segmind/tiny-sd", False)
    assert not random_init_permitted("stabilityai/stable-diffusion-2-1", False)
    # an explicit opt-in overrides the policy
    assert random_init_permitted("stabilityai/stable-diffusion-2-1", True)


def test_missing_controlnet_weights_fatal(tiny_model_on_disk):
    name, _, _ = tiny_model_on_disk
    pipe = SDPipeline(name)
    from PIL import Image

    control = Image.fromarray(np.zeros((64, 64, 3), np.uint8))
    with pytest.raises(MissingWeightsError, match="ControlNet"):
        pipe.run(
            prompt="x", control_image=control,
            controlnet_model_name="lllyasviel/control_v11p_sd15_canny",
            num_inference_steps=2, rng=jax.random.key(0),
        )


def test_initialize_check_validates_disk_model(tiny_model_on_disk):
    from chiaswarm_tpu.initialize import verify_local_model

    name, root, _ = tiny_model_on_disk
    report = verify_local_model(name, root)
    assert set(report) == {"unet", "vae", "text_encoder"}
    assert all(v > 0 for v in report.values())


def test_initialize_check_catches_shape_mismatch(tiny_model_on_disk):
    from chiaswarm_tpu.initialize import verify_local_model

    name, root, ref = tiny_model_on_disk
    bad = flax_to_torch_layout(ref["unet"])
    key = next(k for k in bad if k.endswith("conv_in.weight"))
    bad[key] = bad[key][:, :, :1, :1]  # truncate kernel spatial dims
    save_file(bad, str(root / name / "unet" / "model.safetensors"))
    with pytest.raises(ValueError, match="conversion mismatches"):
        verify_local_model(name, root)


def test_initialize_reset_and_silent(sdaas_root, capsys, monkeypatch):
    import asyncio

    from chiaswarm_tpu import initialize as init_mod
    from chiaswarm_tpu.settings import get_settings_full_path, settings_exist

    monkeypatch.setattr("sys.argv", ["chiaswarm-tpu-init", "--silent"])
    assert asyncio.run(init_mod.init()) == 0
    assert settings_exist()

    monkeypatch.setattr("sys.argv", ["chiaswarm-tpu-init", "--reset"])
    assert asyncio.run(init_mod.init()) == 0
    assert not get_settings_full_path().is_file()


def test_download_aux_list_covers_every_learned_detector(sdaas_root):
    """--download must fetch every checkpoint the preprocessor set needs
    to serve un-degraded (a worker that advertises detectors it never
    downloaded would silently serve approximations)."""
    from chiaswarm_tpu.initialize import _DOWNLOAD_PATTERNS, aux_model_names
    from chiaswarm_tpu.settings import Settings

    names = aux_model_names(Settings())
    assert "lllyasviel/Annotators" in names  # HED/MLSD/LineArt/PiDiNet
    assert "lllyasviel/ControlNet-openpose" in names
    assert "openmmlab/upernet-convnext-small" in names
    assert "Intel/zoedepth-nyu" in names
    assert any("motion-adapter" in n for n in names)
    assert len(names) == len(set(names))
    # the Annotators repo ships raw .pth pickles — the fetch patterns
    # must cover exactly the files the detector loaders glob (a blanket
    # *.pth would pull gigabytes of unrelated checkpoints)
    from chiaswarm_tpu.initialize import _PTH_PATTERNS_BY_KEYWORD

    assert "*.pth" not in _DOWNLOAD_PATTERNS
    ann = _PTH_PATTERNS_BY_KEYWORD["annotators"]
    for pattern in ("*HED*.pth", "*mlsd*.pth", "sk_model*.pth",
                    "*pidinet*.pth"):
        assert pattern in ann


def test_verify_annotators_repo_reports_present_detectors(sdaas_root,
                                                          tmp_path):
    """--check on the shared Annotators repo converts whichever detector
    checkpoints are present instead of failing through the SD verifier."""
    import sys

    import torch

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from torch_unet_ref import LineartGeneratorT

    from chiaswarm_tpu.initialize import verify_local_model
    from chiaswarm_tpu.settings import Settings, save_settings

    root = tmp_path / "models"
    repo = root / "lllyasviel/Annotators"
    repo.mkdir(parents=True)
    save_settings(Settings(model_root_dir=str(root)))
    torch.manual_seed(1)
    torch.save(LineartGeneratorT(base=8, n_res=1).state_dict(),
               str(repo / "sk_model.pth"))

    report = verify_local_model("lllyasviel/Annotators", root)
    assert report == {"lineart": report["lineart"]}
    assert report["lineart"] > 0

    import pytest

    with pytest.raises(FileNotFoundError):
        verify_local_model("lllyasviel/Annotators", tmp_path / "empty")
