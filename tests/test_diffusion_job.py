"""Job-level integration: hive job dict -> format_args -> ChipSet ->
diffusion_callback -> registry-resident pipeline -> base64 artifacts.

This is the hermetic version of the reference's manual `python -m swarm.test`
(swarm/test.py:295-311) — same path, real assertions, tiny weights.
"""

import asyncio
import base64

import jax
import pytest

from chiaswarm_tpu import registry
from chiaswarm_tpu.chips.device import ChipSet
from chiaswarm_tpu.job_arguments import format_args
from chiaswarm_tpu.settings import Settings
from chiaswarm_tpu.workflows.diffusion import packaged


@pytest.fixture(autouse=True)
def clean_registry():
    registry.clear_cache()
    yield
    registry.clear_cache()


def run_job(job: dict) -> dict:
    """Drive the full worker execution path synchronously."""
    settings = Settings(sdaas_token="t", sdaas_uri="http://fake")
    callback, kwargs = asyncio.run(format_args(job, settings, "cpu:0"))
    chipset = ChipSet(jax.devices()[:1])
    artifacts, pipeline_config = chipset(callback, **kwargs)
    return packaged(artifacts), pipeline_config


def test_txt2img_job_to_artifact():
    job = {
        "id": "job-1",
        "workflow": "txt2img",
        "model_name": "test/tiny-sd",
        "prompt": "an astronaut on a horse",
        "height": 64,
        "width": 64,
        "num_inference_steps": 2,
        "seed": 42,
        "parameters": {"pipeline_type": "StableDiffusionPipeline"},
        "content_type": "image/jpeg",
    }
    artifacts, config = run_job(job)
    assert config["seed"] == 42
    assert config["timings"]["job_s"] > 0
    primary = artifacts["primary"]
    blob = base64.b64decode(primary["blob"])
    assert blob[:3] == b"\xff\xd8\xff"  # JPEG magic
    assert primary["content_type"] == "image/jpeg"
    assert len(primary["sha256_hash"]) == 64


def test_job_pins_seed_reproducibly():
    job = {
        "id": "job-2",
        "workflow": "txt2img",
        "model_name": "test/tiny-sd",
        "prompt": "reproducible",
        "height": 64,
        "width": 64,
        "num_inference_steps": 2,
        "seed": 7,
        "parameters": {},
    }
    a1, _ = run_job(dict(job))
    a2, _ = run_job(dict(job))
    assert a1["primary"]["sha256_hash"] == a2["primary"]["sha256_hash"]


def test_unknown_pipeline_type_raises():
    job = {
        "id": "job-3",
        "workflow": "txt2img",
        "model_name": "test/tiny-sd",
        "prompt": "x",
        "parameters": {"pipeline_type": "EvilReflectionType"},
    }
    with pytest.raises(ValueError, match="Unknown pipeline type"):
        run_job(job)
