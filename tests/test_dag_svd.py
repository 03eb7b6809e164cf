"""ISSUE 20 satellite: svd img2vid as a golden-tested DAG workflow.

An img2vid submission WITHOUT a start image expands into
txt2img-renders-the-conditioning-frame -> svd-animates-it, handed off
through the spool (hive_server/dag.py `_expand_img2vid`). This file
executes that graph end to end through the REAL worker-side seams —
`format_args` stage routing, the encode/denoise callbacks, the
handoff="image" injection — with tiny models, and golden-checks the svd
stage against the monolithic baseline: `run_img2vid` handed the very
same conditioning frame by hand. The spool handoff must change nothing
but who carried the bytes.
"""

import asyncio
import base64
import hashlib
import io

import numpy as np
import pytest
from PIL import Image

import jax

from chiaswarm_tpu.hive_server import dag
from chiaswarm_tpu.job_arguments import format_args
from chiaswarm_tpu.settings import Settings
from chiaswarm_tpu.workflows.diffusion import packaged

PAYLOAD = {
    "workflow": "img2vid",
    "model_name": "stabilityai/stable-video-diffusion-img2vid",
    "test_tiny_model": True,
    "num_inference_steps": 2,
    "num_frames": 4,
    # GIF packaging is bit-deterministic (no container timestamps)
    "content_type": "image/gif",
    "seed": 7,
    "image_stage": {
        "model_name": "stabilityai/stable-diffusion-2-1",
        "prompt": "a lighthouse at dusk",
        "height": 64,
        "width": 64,
        "num_inference_steps": 2,
        "parameters": {"test_tiny_model": True},
        "seed": 3,
    },
}


def _hydrated_inputs(stage: dict, stages: list[dict], results: dict) -> list:
    """The worker-poll-loop stand-in: predecessor artifacts arrive with
    their blobs hydrated (worker.py `_resolve_stage_inputs` fetches each
    spool href and stamps the bytes back as `blob`)."""
    inputs = []
    for n in stage["needs"]:
        inputs.append({
            "stage": stages[n]["name"],
            "artifacts": {k: dict(a) for k, a in results[n].items()},
        })
    return inputs


def _run_stage(stage: dict, stages: list[dict], results: dict):
    job = dict(stage["job"])
    job["stage"] = dict(job["stage"])
    job["stage"]["inputs"] = _hydrated_inputs(stage, stages, results)
    return _run_job(job)


def _run_job(job: dict):
    """Execute one job the way a worker would: format, then call the
    routed callback with the ChipSet seed contract (pop `seed`, inject
    `rng`) but no chip — every tiny model runs on CPU."""
    func, kwargs = asyncio.run(format_args(job, Settings(), "cpu"))
    model_name = kwargs.pop("model_name", None)
    seed = kwargs.pop("seed", None)
    if seed is not None:
        kwargs["rng"] = jax.random.key(int(seed))
    kwargs.pop("chipset", None)
    artifacts, config = func("cpu", model_name, **kwargs)
    return packaged(artifacts), config


def _run_workflow(workflow_id: str, payload: dict = PAYLOAD):
    stages = dag.expand_workflow(dict(payload), workflow_id)
    results, configs = {}, {}
    for stage in stages:  # expansion order is topological
        artifacts, config = _run_stage(stage, stages, results)
        results[stage["index"]] = artifacts
        configs[stage["index"]] = config
    return stages, results, configs


def test_img2vid_expansion_shape():
    stages = dag.expand_workflow(dict(PAYLOAD), "wfv")
    assert [s["name"] for s in stages] == ["encode", "denoise", "svd"]
    assert [s["job_id"] for s in stages] == [
        "wfv-s0-encode", "wfv-s1-denoise", "wfv-s2-svd"]
    assert stages[2]["needs"] == [1]
    assert stages[2]["handoff"] == "image"
    # the conditioning-frame stage is plain txt2img on the image model
    assert stages[1]["job"]["workflow"] == "txt2img"
    assert stages[1]["job"]["model_name"] == PAYLOAD["image_stage"]["model_name"]
    assert stages[2]["job"]["model_name"] == PAYLOAD["model_name"]
    # graph-only keys never leak into stage-job content
    assert "image_stage" not in stages[2]["job"]


@pytest.fixture(scope="module")
def dag_run():
    return _run_workflow("wfv")


def test_dag_stages_execute_end_to_end(dag_run):
    stages, results, configs = dag_run
    assert "conditioning" in results[0]  # encode: jax-free prompt prep
    assert configs[0]["stage"] == "encode"
    # denoise (no handoff flag here) packages a full envelope: the svd
    # stage consumes its primary exactly like any image-consuming job
    assert "primary" in results[1]
    video = results[2]["primary"]
    assert video["content_type"] == "image/gif"
    assert base64.b64decode(video["blob"])[:3] == b"GIF"
    assert configs[2]["frames"] == PAYLOAD["num_frames"]


def test_svd_stage_consumed_the_spooled_frame(dag_run):
    stages, results, _ = dag_run
    # content-addressed handoff: the frame the svd stage worked from IS
    # the denoise stage's primary artifact, byte for byte
    primary = results[1]["primary"]
    blob = base64.b64decode(primary["blob"])
    assert hashlib.sha256(blob).hexdigest() == primary["sha256_hash"]


def test_svd_stage_matches_monolithic_baseline(dag_run):
    """Golden: the DAG's svd output equals `run_img2vid` handed the
    conditioning frame directly — the spool handoff is transport, not a
    numerics fork."""
    from chiaswarm_tpu.pipelines.video import run_img2vid

    stages, results, _ = dag_run
    frame = Image.open(io.BytesIO(
        base64.b64decode(results[1]["primary"]["blob"]))).convert("RGB")
    artifacts, config = run_img2vid(
        "cpu", PAYLOAD["model_name"],
        image=frame,
        test_tiny_model=True,
        num_inference_steps=PAYLOAD["num_inference_steps"],
        num_frames=PAYLOAD["num_frames"],
        content_type="image/gif",
        rng=jax.random.key(PAYLOAD["seed"]),
    )
    want = base64.b64decode(artifacts["primary"]["blob"])
    got = base64.b64decode(results[2]["primary"]["blob"])
    assert hashlib.sha256(got).hexdigest() == \
        hashlib.sha256(want).hexdigest()


def test_dag_workflow_is_deterministic(dag_run):
    stages, results, _ = dag_run
    _, rerun, _ = _run_workflow("wfv2")
    for index in results:
        a = {k: v.get("sha256_hash") for k, v in results[index].items()
             if isinstance(v, dict)}
        b = {k: v.get("sha256_hash") for k, v in rerun[index].items()
             if isinstance(v, dict)}
        assert a == b, f"stage {index} drifted across runs"


def test_txt2img_graph_hands_raw_rows_to_a_host_decode():
    """The txt2img chain as a graph: encode and decode are host stages, the
    denoise stage hands its rows over raw instead of packaging them, and
    what the decode stage packages from them is the monolithic job's
    artifact, byte for byte."""
    job = dict(PAYLOAD["image_stage"], workflow="txt2img",
               content_type="image/png")
    stages, results, configs = _run_workflow("wft", job)
    assert [(s["name"], s["needs"], s["handoff"]) for s in stages] == [
        ("encode", [], None), ("denoise", [0], "raw"), ("decode", [1], "raw")]
    assert configs[0]["stage"] == "encode"
    assert set(results[1]) == {"raw"}  # rows, not an envelope
    assert configs[2]["stage"] == "decode" and configs[2]["rows"] == 1

    whole, _ = _run_job(dict(job, id="mono"))
    assert results[2]["primary"]["content_type"] == "image/png"
    assert results[2]["primary"]["sha256_hash"] == \
        whole["primary"]["sha256_hash"]
