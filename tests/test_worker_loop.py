"""End-to-end worker loop against the in-process fake hive.

Exercises: poll -> job dispatch -> chip-slice execution -> artifact packaging
-> result upload, plus the fatal-vs-transient error policy (reference
swarm/worker.py:105-161 semantics) — all hermetic on CPU devices. The
fault-tolerance layer (outbox redelivery, slice watchdog + quarantine,
graceful drain) is driven through the deterministic injection points in
faults.py rather than sleeps-and-hope.
"""

import asyncio
import base64
import contextlib
import os
import signal
import threading
import time
import types

import pytest

from chiaswarm_tpu import faults
from chiaswarm_tpu import outbox as outbox_mod
from chiaswarm_tpu import worker as worker_mod
from chiaswarm_tpu.batching import SPANS
from chiaswarm_tpu.chips.allocator import SliceAllocator
from chiaswarm_tpu.settings import Settings
from chiaswarm_tpu.worker import Worker

from .fake_hive import FakeHive


@pytest.fixture(autouse=True)
def fast_poll(monkeypatch):
    monkeypatch.setattr(worker_mod, "POLL_SECONDS", 0.05)
    monkeypatch.setattr(worker_mod, "ERROR_BACKOFF_SECONDS", 0.2)


@pytest.fixture(autouse=True)
def disarm_faults():
    yield
    faults.configure("")


@pytest.fixture()
def fast_outbox_backoff(monkeypatch):
    monkeypatch.setattr(outbox_mod, "BACKOFF_BASE_S", 0.02)
    monkeypatch.setattr(outbox_mod, "BACKOFF_CAP_S", 0.1)


def echo_job(job_id: str) -> dict:
    return {"id": job_id, "workflow": "echo", "model_name": "none",
            "prompt": job_id}


def run_jobs(jobs, sdaas_root, n_results=None, chips_per_job=4):
    async def scenario():
        hive = await FakeHive().start()
        for job in jobs:
            hive.add_job(job)
        settings = Settings(sdaas_token="test-token", worker_name="test-worker")
        w = Worker(
            settings=settings,
            allocator=SliceAllocator(chips_per_job=chips_per_job),
            hive_uri=hive.uri,
        )
        runner = asyncio.create_task(w.run())
        try:
            # generous budget: tiny-model jit compiles alone can take
            # >30 s on low-core build hosts (observed 27 s for BLIP on 2
            # cores; 1-core hosts are slower still)
            results = await hive.wait_for_results(
                n_results or len(jobs), timeout=240.0
            )
        finally:
            w.stop()
            await asyncio.wait_for(runner, 10)
            await hive.stop()
        return hive, results

    return asyncio.run(scenario())


def test_echo_job_end_to_end(sdaas_root):
    hive, results = run_jobs(
        [{"id": "job-1", "workflow": "echo", "model_name": "none", "prompt": "hello"}],
        sdaas_root,
    )
    [result] = results
    assert result["id"] == "job-1"
    assert result["pipeline_config"]["echo"] is True
    assert "seed" in result["pipeline_config"]
    blob = base64.b64decode(result["artifacts"]["primary"]["blob"])
    assert blob.startswith(b"\xff\xd8")  # jpeg
    assert not result.get("fatal_error")


def test_capability_advertisement(sdaas_root):
    hive, _ = run_jobs(
        [{"id": "job-1", "workflow": "echo", "model_name": "none", "prompt": "x"}],
        sdaas_root,
    )
    req = hive.work_requests[0]
    assert req["worker_name"] == "test-worker"
    assert req["chips"] == "8"
    assert req["slices"] == "2"
    assert "memory" in req and "gpu" in req  # legacy keys still advertised
    # model-layer honesty: families with no conversion path are advertised
    # so a capability-aware hive stops sending un-runnable jobs — in
    # lockstep with the real keyword list, which is EMPTY as of round 4
    # (every served family converts; ",".join(()) wires through as "")
    from chiaswarm_tpu.weights import UNCONVERTED_FAMILY_KEYWORDS

    unconverted = [
        k for k in req["unconverted_families"].split(",") if k
    ]
    assert sorted(unconverted) == sorted(UNCONVERTED_FAMILY_KEYWORDS)
    assert "bark" not in unconverted and "kandinsky3" not in unconverted


def test_bad_args_produce_fatal_envelope(sdaas_root):
    # img2img with no input image: format_args raises -> fatal, don't resubmit
    hive, results = run_jobs(
        [{"id": "job-2", "workflow": "img2img", "model_name": "m"}], sdaas_root
    )
    [result] = results
    assert result["fatal_error"] is True
    assert "error" in result["pipeline_config"]


def test_missing_bark_weights_fatal(sdaas_root):
    # Bark is implemented now, so an unconverted real model name follows
    # the missing-weights policy: FATAL envelope (hive must not resubmit),
    # error rendered as an image artifact
    hive, results = run_jobs(
        [
            {
                "id": "job-3",
                "workflow": "txt2audio",
                "model_name": "suno/bark",
                "prompt": "x",
                "content_type": "image/jpeg",
            }
        ],
        sdaas_root,
    )
    [result] = results
    assert result["fatal_error"] is True
    assert "weights" in result["pipeline_config"]["error"]
    assert result["artifacts"]["primary"]["content_type"] == "image/jpeg"


def test_transient_error_renders_error_image(sdaas_root, monkeypatch):
    # a RUNTIME failure inside an otherwise-valid job stays transient:
    # error-image artifact, envelope NOT fatal, hive may resubmit
    from chiaswarm_tpu.pipelines import bark as bark_mod

    def boom(*a, **k):
        raise RuntimeError("chip fell over mid-job")

    monkeypatch.setattr(bark_mod.BarkPipeline, "run", boom)
    hive, results = run_jobs(
        [
            {
                "id": "job-3b",
                "workflow": "txt2audio",
                "model_name": "suno/bark",
                "prompt": "x",
                "content_type": "image/jpeg",
                "parameters": {"test_tiny_model": True},
            }
        ],
        sdaas_root,
    )
    [result] = results
    assert not result.get("fatal_error")
    assert "chip fell over" in result["pipeline_config"]["error"]
    assert result["artifacts"]["primary"]["content_type"] == "image/jpeg"


def test_img2txt_job_end_to_end(sdaas_root):
    """The FULL worker path for captioning (VERDICT missing #3): poll ->
    format_img2txt_args -> registry-resident BLIP -> greedy decode -> JSON
    text artifact."""
    import json

    from PIL import Image
    import numpy as np

    from chiaswarm_tpu import external_resources

    img = Image.fromarray(
        (np.random.default_rng(0).random((64, 64, 3)) * 255).astype(np.uint8)
    )

    async def fake_get_image(uri, size):
        return img if uri else None

    original = external_resources.get_image
    external_resources.get_image = fake_get_image
    # job_arguments imported get_image by name — patch there too
    from chiaswarm_tpu import job_arguments

    ja_original = job_arguments.get_image
    job_arguments.get_image = fake_get_image
    try:
        hive, results = run_jobs(
            [
                {
                    "id": "job-cap",
                    "workflow": "img2txt",
                    "model_name": "Salesforce/blip-image-captioning-base",
                    "start_image_uri": "fake://img",
                    "prompt": "a picture of",
                    "parameters": {"test_tiny_model": True},
                }
            ],
            sdaas_root,
        )
    finally:
        external_resources.get_image = original
        job_arguments.get_image = ja_original
    [result] = results
    assert not result.get("fatal_error")
    assert result["pipeline_config"]["caption"]
    art = result["artifacts"]["primary"]
    assert art["content_type"] == "application/json"
    payload = json.loads(base64.b64decode(art["blob"]))
    assert payload["caption"] == result["pipeline_config"]["caption"]


def test_missing_weights_job_is_fatal(sdaas_root):
    """A production model with no local weights must come back fatal with
    the remediation hint, not serve random-weight output (VERDICT weak #3)."""
    hive, results = run_jobs(
        [
            {
                "id": "job-nw",
                "workflow": "txt2img",
                "model_name": "stabilityai/stable-diffusion-2-1",
                "prompt": "x",
                "height": 64,
                "width": 64,
                "num_inference_steps": 2,
            }
        ],
        sdaas_root,
    )
    [result] = results
    assert result["fatal_error"] is True
    assert "not present on this worker" in result["pipeline_config"]["error"]


def test_multiple_jobs_across_slices(sdaas_root):
    jobs = [
        {"id": f"job-{i}", "workflow": "echo", "model_name": "none", "prompt": str(i)}
        for i in range(4)
    ]
    hive, results = run_jobs(jobs, sdaas_root, chips_per_job=2)
    assert {r["id"] for r in results} == {f"job-{i}" for i in range(4)}


def test_compatible_jobs_coalesce_into_one_batch(sdaas_root):
    """Cross-job micro-batching end to end (batching.py): 4 compatible
    tiny-model txt2img jobs arriving in one poll burst must execute as ONE
    padded denoise+decode pass on one slice, yet come back as 4 distinct
    result envelopes — correct ids, each job's own seed, no cross-job
    image leakage."""
    jobs = [
        {
            "id": f"job-b{i}",
            "workflow": "txt2img",
            "model_name": "stabilityai/stable-diffusion-2-1",
            "prompt": f"a photograph of test subject number {i}",
            "seed": 1000 + i,
            "height": 64,
            "width": 64,
            "num_inference_steps": 2,
            "parameters": {"test_tiny_model": True},
        }
        for i in range(4)
    ]
    # one slice spanning all chips: the whole group lands in one pass
    hive, results = run_jobs(jobs, sdaas_root, chips_per_job=8)
    assert {r["id"] for r in results} == {f"job-b{i}" for i in range(4)}

    by_id = {r["id"]: r for r in results}
    blobs = []
    for i in range(4):
        r = by_id[f"job-b{i}"]
        cfg = r["pipeline_config"]
        assert not r.get("fatal_error"), cfg
        # executed as one coalesced pass of all 4 jobs...
        assert cfg["batched_with"] == 4, cfg
        # ...but each envelope keeps ITS job's seed (independent noise)
        assert cfg["seed"] == 1000 + i
        blob = r["artifacts"]["primary"]["blob"]
        assert base64.b64decode(blob).startswith(b"\xff\xd8")  # jpeg
        blobs.append(blob)
    # no cross-job leakage: distinct seeds/prompts -> distinct images
    assert len(set(blobs)) == 4


def test_adapter_jobs_coalesce_with_runtime_deltas(sdaas_root, tmp_path):
    """ISSUE 13 end to end: two jobs carrying DISTINCT LoRA adapters
    plus an adapter-free batchmate — all one base model — coalesce into
    ONE padded pass served by runtime per-row deltas: 3 distinct
    envelopes, adapter rows stamped lora_mode=delta, no merged param
    tree ever built, distinct images per row. A 4th member whose adapter
    the delta can't express (conv module) rides the same group but is
    PARTITIONED OUT at the slice: it serves solo via the merged tree
    while the eligible trio keeps its coalesced pass."""
    import numpy as np
    from safetensors.numpy import save_file

    lora_root = tmp_path / "lora"
    lora_root.mkdir()
    dim = 32  # TINY_UNET block_out_channels[0]
    base_key = "unet.down_blocks.0.attentions.0.transformer_blocks.0.attn1"
    for i in range(2):
        rng = np.random.default_rng(40 + i)
        save_file({
            f"{base_key}.to_q.lora_A.weight":
                rng.standard_normal((2, dim)).astype(np.float32),
            f"{base_key}.to_q.lora_B.weight":
                rng.standard_normal((dim, 2)).astype(np.float32),
        }, str(lora_root / f"style-{i}.safetensors"))
    rng = np.random.default_rng(49)
    save_file({
        f"{base_key}.to_q.lora_A.weight":
            rng.standard_normal((2, dim)).astype(np.float32),
        f"{base_key}.to_q.lora_B.weight":
            rng.standard_normal((dim, 2)).astype(np.float32),
        # a 4D conv module the per-row Dense delta cannot express
        "unet.down_blocks.0.resnets_0.conv1.lora_A.weight":
            rng.standard_normal((2, 9)).astype(np.float32),
        "unet.down_blocks.0.resnets_0.conv1.lora_B.weight":
            rng.standard_normal((9, 2)).astype(np.float32),
    }, str(lora_root / "conv-style.safetensors"))

    def job(i, **over):
        out = {
            "id": f"job-l{i}",
            "workflow": "txt2img",
            "model_name": "stabilityai/stable-diffusion-2-1",
            "prompt": f"tenant {i}",
            "seed": 2000 + i,
            "height": 64,
            "width": 64,
            "num_inference_steps": 2,
            "parameters": {"test_tiny_model": True},
        }
        out.update(over)
        return out

    jobs = [
        job(0, lora="style-0.safetensors"),
        job(1, lora="style-1.safetensors"),
        job(2),
        job(3, lora="conv-style.safetensors"),
    ]

    async def scenario():
        hive = await FakeHive().start()
        for j in jobs:
            hive.add_job(j)
        settings = Settings(sdaas_token="test-token",
                            worker_name="test-worker",
                            lora_root_dir=str(lora_root))
        w = Worker(
            settings=settings,
            allocator=SliceAllocator(chips_per_job=8),
            hive_uri=hive.uri,
        )
        runner = asyncio.create_task(w.run())
        try:
            results = await hive.wait_for_results(4, timeout=300.0)
        finally:
            w.stop()
            await asyncio.wait_for(runner, 10)
            await hive.stop()
        return results

    results = asyncio.run(scenario())
    by_id = {r["id"]: r for r in results}
    assert set(by_id) == {"job-l0", "job-l1", "job-l2", "job-l3"}
    blobs = set()
    for i in range(3):
        r = by_id[f"job-l{i}"]
        cfg = r["pipeline_config"]
        assert not r.get("fatal_error"), cfg
        # the conv member was partitioned out; the eligible trio still
        # ran as ONE coalesced pass
        assert cfg["batched_with"] == 3, cfg
        if i < 2:
            assert cfg["lora_mode"] == "delta", cfg
        else:
            assert "lora_mode" not in cfg, cfg
        blobs.add(r["artifacts"]["primary"]["blob"])
    conv = by_id["job-l3"]
    assert not conv.get("fatal_error"), conv["pipeline_config"]
    assert conv["pipeline_config"]["lora_mode"] == "merged", \
        conv["pipeline_config"]
    assert "batched_with" not in conv["pipeline_config"], \
        conv["pipeline_config"]
    blobs.add(conv["artifacts"]["primary"]["blob"])
    assert len(blobs) == 4  # distinct adapters/seeds -> distinct images


def test_degraded_preprocessor_flag_in_envelope(sdaas_root):
    """A ControlNet job conditioned through a classical-CV stand-in
    annotator (mlsd) must carry `degraded_preprocessors` in its result
    envelope's pipeline_config — the hive can see the conditioning image
    is an approximation of the learned detector (VERDICT r03 item 3)."""

    async def scenario():
        hive = await FakeHive().start()
        image_uri = hive.uri[: -len("/api")] + "/image.png"
        hive.add_job({
            "id": "job-cn",
            "workflow": "txt2img",
            "model_name": "stabilityai/stable-diffusion-2-1",
            "prompt": "wireframe house",
            "height": 64,
            "width": 64,
            "num_inference_steps": 2,
            "parameters": {
                "test_tiny_model": True,
                "controlnet": {
                    "control_image_uri": image_uri,
                    "preprocessor": "mlsd",
                    "controlnet_model_name": "test/tiny-controlnet",
                },
            },
        })
        settings = Settings(sdaas_token="test-token", worker_name="test-worker")
        w = Worker(
            settings=settings,
            allocator=SliceAllocator(chips_per_job=4),
            hive_uri=hive.uri,
        )
        runner = asyncio.create_task(w.run())
        try:
            results = await hive.wait_for_results(1, timeout=240.0)
        finally:
            w.stop()
            await asyncio.wait_for(runner, 10)
            await hive.stop()
        return results

    results = asyncio.run(scenario())
    assert results[0].get("fatal_error") is not True, results[0].get(
        "pipeline_config"
    )
    cfg = results[0]["pipeline_config"]
    assert cfg["degraded_preprocessors"] == ["mlsd"]


def test_job_stage_spans_recorded_end_to_end(sdaas_root):
    """Telemetry acceptance: after a real (tiny) txt2img job runs the full
    poll -> coalesce -> denoise -> decode -> submit path, the process-wide
    `swarm_job_stage_seconds` histogram covers every lifecycle stage, the
    completion counter moved, and the envelope's timings carry the same
    span-sourced keys."""
    from chiaswarm_tpu import telemetry
    from chiaswarm_tpu.telemetry import STAGE_METRIC

    stages = telemetry.REGISTRY.get(STAGE_METRIC) or telemetry.histogram(
        STAGE_METRIC, "", ("stage",))
    completed = telemetry.REGISTRY.get("swarm_jobs_completed_total")
    required = ("queue_wait", "compile", "denoise", "readback", "decode",
                "submit", "pass", "load", "safety", "artifact_encode",
                "spool")
    before = {s: stages.count(stage=s) for s in required}
    ok_before = completed.value(outcome="ok") if completed else 0

    hive, results = run_jobs(
        [
            {
                "id": "job-tel",
                "workflow": "txt2img",
                "model_name": "stabilityai/stable-diffusion-2-1",
                "prompt": "a telemetry probe",
                "height": 64,
                "width": 64,
                "num_inference_steps": 2,
                "parameters": {"test_tiny_model": True},
            }
        ],
        sdaas_root,
    )
    [result] = results
    assert not result.get("fatal_error")

    # every required stage observed at least once more than before the job
    stages = telemetry.REGISTRY.get(STAGE_METRIC)
    for s in required:
        assert stages.count(stage=s) > before[s], f"stage {s} not recorded"
    completed = telemetry.REGISTRY.get("swarm_jobs_completed_total")
    assert completed.value(outcome="ok") > ok_before

    # the envelope carries the span-sourced per-stage timings (the hive's
    # view and the /metrics view come from the same measurements)
    timings = result["pipeline_config"]["timings"]
    for key in ("queue_wait_s", "trace_s", "denoise_decode_s", "decode_s"):
        assert key in timings, timings
    # ... and the same spans wall-stamped, children inside their parents
    # on the slice thread (the hive and the benchmark's readers rely on it)
    spans = {s["name"]: s for s in result["pipeline_config"]["spans"]}
    for name in ("pass", "queue_wait", "load", "text_encode", "compile",
                 "denoise", "readback", "decode", "safety",
                 "artifact_encode"):
        assert name in spans, sorted(spans)
    assert spans["queue_wait"]["thread"] == "wait"

    def end(name):
        return spans[name]["start_wall"] + spans[name]["seconds"]

    def inside(child, parent, slack=1e-4):
        return (spans[child]["thread"] == spans[parent]["thread"] == "slice"
                and spans[parent]["start_wall"] <= spans[child]["start_wall"]
                and end(child) <= end(parent) + slack)

    for child in ("load", "text_encode", "compile", "denoise", "readback",
                  "decode", "safety"):
        assert inside(child, "pass"), (child, spans)
    assert inside("safety", "decode"), spans
    assert end("denoise") <= spans["readback"]["start_wall"]
    # the pass ends with the read-back and the safety pass: the images are
    # packaged after the slice is let go, on a host thread
    assert spans["artifact_encode"]["thread"] == "host"
    assert spans["artifact_encode"]["start_wall"] >= end("pass") - 1e-4
    assert spans["queue_wait"]["start_wall"] <= spans["pass"]["start_wall"]
    assert timings["job_s"] == round(spans["pass"]["seconds"], 3)
    # capability heartbeat folded in the live-load snapshot
    req = hive.work_requests[0]
    assert "jobs_in_flight" in req and "busy_slices" in req


def test_submit_result_retries_transient_5xx(sdaas_root):
    """Satellite: one 502 from POST /results must not cost the artifacts —
    the client retries once after a short backoff and counts the retry."""
    from chiaswarm_tpu import hive as hive_mod
    from chiaswarm_tpu.hive import _RETRIES

    retries_before = _RETRIES.value(endpoint="results")
    original_backoff = hive_mod.SUBMIT_RETRY_BACKOFF_S
    hive_mod.SUBMIT_RETRY_BACKOFF_S = 0.01
    try:

        async def scenario():
            hive = await FakeHive().start()
            hive.fail_results_times = 1
            hive.add_job({"id": "job-r", "workflow": "echo",
                          "model_name": "none", "prompt": "x"})
            settings = Settings(sdaas_token="t", worker_name="w")
            w = Worker(
                settings=settings,
                allocator=SliceAllocator(chips_per_job=4),
                hive_uri=hive.uri,
            )
            runner = asyncio.create_task(w.run())
            try:
                results = await hive.wait_for_results(1, timeout=240.0)
            finally:
                w.stop()
                await asyncio.wait_for(runner, 10)
                await hive.stop()
            return hive, results

        hive, results = asyncio.run(scenario())
    finally:
        hive_mod.SUBMIT_RETRY_BACKOFF_S = original_backoff

    assert results[0]["id"] == "job-r"
    assert hive.result_attempts == 2  # 502 then success, ONE worker pass
    assert _RETRIES.value(endpoint="results") == retries_before + 1


# --- fault-tolerant job lifecycle (outbox / watchdog / drain) ---


def test_injected_submit_drops_never_lose_the_envelope(
        sdaas_root, fast_outbox_backoff):
    """Submit drop x3: more consecutive connection failures than the hive
    client's single in-call retry absorbs — the outbox keeps the envelope
    and redelivers until the hive ACKs. Zero silent drops."""
    faults.configure("drop_submit=3")
    hive, results = run_jobs([echo_job("job-drop")], sdaas_root)
    assert results[0]["id"] == "job-drop"
    assert faults.get_plan().fired("drop_submit") == 3
    assert hive.result_attempts == 1  # drops never reached the hive


def test_hive_connection_drops_never_lose_the_envelope(
        sdaas_root, fast_outbox_backoff):
    """Same contract with the failure on the hive side: the fake hive
    severs the TCP connection mid-request twice before accepting."""

    async def scenario():
        hive = await FakeHive().start()
        hive.drop_results_times = 2
        hive.add_job(echo_job("job-sever"))
        settings = Settings(sdaas_token="t", worker_name="w")
        w = Worker(settings=settings,
                   allocator=SliceAllocator(chips_per_job=4),
                   hive_uri=hive.uri)
        runner = asyncio.create_task(w.run())
        try:
            results = await hive.wait_for_results(1, timeout=240.0)
            # delivered AND acked: the spool entry is gone
            for _ in range(100):
                if w.outbox.depth == 0:
                    break
                await asyncio.sleep(0.05)
            assert w.outbox.depth == 0
        finally:
            w.stop()
            await asyncio.wait_for(runner, 10)
            await hive.stop()
        return hive, results

    hive, results = asyncio.run(scenario())
    assert results[0]["id"] == "job-sever"
    assert hive.result_attempts >= 3  # 2 severed + 1 accepted


def test_outbox_redelivery_across_worker_restart(sdaas_root):
    """kill-before-ack: the process dies after the hive accepted the POST
    but before the spool entry was unlinked. The next worker generation
    must redeliver it (at-least-once; the hive dedupes by job id)."""
    faults.configure("kill_before_ack=1")

    async def first_generation():
        hive = await FakeHive().start()
        hive.add_job(echo_job("job-redeliver"))
        settings = Settings(sdaas_token="t", worker_name="w")
        w = Worker(settings=settings,
                   allocator=SliceAllocator(chips_per_job=4),
                   hive_uri=hive.uri)
        runner = asyncio.create_task(w.run())
        try:
            await hive.wait_for_results(1, timeout=240.0)
            # the injected crash fired AFTER the ack, BEFORE the unlink:
            # the envelope must still be spooled
            assert w.outbox.depth == 1
        finally:
            w.stop()
            await asyncio.wait_for(runner, 10)
            await hive.stop()

    asyncio.run(first_generation())
    faults.configure("")

    async def second_generation():
        hive = await FakeHive().start()  # no new jobs queued
        settings = Settings(sdaas_token="t", worker_name="w")
        w = Worker(settings=settings,
                   allocator=SliceAllocator(chips_per_job=4),
                   hive_uri=hive.uri)
        runner = asyncio.create_task(w.run())
        try:
            results = await hive.wait_for_results(1, timeout=60.0)
            assert results[0]["id"] == "job-redeliver"
            for _ in range(100):
                if w.outbox.depth == 0:
                    break
                await asyncio.sleep(0.05)
            assert w.outbox.depth == 0  # unlinked on the real ACK
        finally:
            w.stop()
            await asyncio.wait_for(runner, 10)
            await hive.stop()

    asyncio.run(second_generation())


def test_watchdog_expiry_quarantines_then_probe_reinstates(sdaas_root):
    """A hung pass must not pin its slice forever: the watchdog returns
    the transient-error envelope at the deadline, quarantines the slice,
    and — once the hang clears and the smoke probe passes — returns it to
    service WITHOUT a worker restart."""
    faults.configure("hang_denoise=1", hang_timeout_s=60.0)

    async def scenario():
        hive = await FakeHive().start()
        hive.add_job(echo_job("job-hang"))
        settings = Settings(
            sdaas_token="t", worker_name="w",
            job_deadline_s=0.4, job_deadline_compile_scale=1.0,
            quarantine_probe_grace_s=10.0,
        )
        w = Worker(settings=settings,
                   allocator=SliceAllocator(chips_per_job=8),  # ONE slice
                   hive_uri=hive.uri)
        runner = asyncio.create_task(w.run())
        try:
            results = await hive.wait_for_results(1, timeout=60.0)
            r = results[0]
            assert r["id"] == "job-hang"
            assert not r.get("fatal_error")  # transient: hive may resubmit
            assert "watchdog" in r["pipeline_config"]["error"]
            assert w.allocator.quarantined_count == 1
            health = w._health()
            assert health["status"] == "degraded"
            assert any("quarantined" in reason
                       for reason in health["degraded_reasons"])
            assert health["slices"][0]["state"] == "quarantined"
            # advertised capacity shrank while the slice is out
            assert w.allocator.capabilities()["slices"] == 0

            # the hang clears -> probe runs -> slice returns to service
            faults.get_plan().release_hangs()
            for _ in range(200):
                if w.allocator.quarantined_count == 0:
                    break
                await asyncio.sleep(0.05)
            assert w.allocator.quarantined_count == 0

            # and it actually serves again, same process
            hive.add_job(echo_job("job-after"))
            results = await hive.wait_for_results(2, timeout=240.0)
            assert {r["id"] for r in results} == {"job-hang", "job-after"}
        finally:
            w.stop()
            await asyncio.wait_for(runner, 10)
            await hive.stop()

    asyncio.run(scenario())


def test_sigterm_drains_inflight_job_to_completion(sdaas_root):
    """SIGTERM mid-job: the worker stops polling, lets the in-flight
    denoise finish, flushes the outbox, and exits on its own — the round-6
    behavior cancelled the executing job and dropped its work."""
    faults.configure("hang_denoise=1", hang_timeout_s=60.0)

    async def scenario():
        hive = await FakeHive().start()
        hive.add_job(echo_job("job-drain"))
        settings = Settings(
            sdaas_token="t", worker_name="w",
            job_deadline_s=0.0,  # watchdog off: this hang is "a slow job"
            drain_deadline_s=60.0,
        )
        w = Worker(settings=settings,
                   allocator=SliceAllocator(chips_per_job=8),
                   hive_uri=hive.uri)
        runner = asyncio.create_task(w.run())
        try:
            # wait until the job is actually executing (blocked in-pass)
            plan = faults.get_plan()
            for _ in range(400):
                if plan.hanging:
                    break
                await asyncio.sleep(0.05)
            assert plan.hanging == 1

            os.kill(os.getpid(), signal.SIGTERM)
            await asyncio.sleep(0.3)
            # draining, not dead: the in-flight job is still running
            assert not runner.done()
            assert w._health()["draining"] is True
            assert hive.results == []

            plan.release_hangs()  # the job finishes normally
            await asyncio.wait_for(runner, 30.0)  # worker exits by itself
            assert [r["id"] for r in hive.results] == ["job-drain"]
            assert w.outbox.depth == 0  # flushed before exit
        finally:
            if not runner.done():
                w.stop()
                await asyncio.wait_for(runner, 10)
            await hive.stop()

    asyncio.run(scenario())


def test_batched_pass_oom_falls_back_per_job(sdaas_root):
    """Injected RESOURCE_EXHAUSTED on the coalesced pass: every member job
    must still come back clean through the per-job fallback path."""
    faults.configure("oom_batched=1")
    jobs = [
        {
            "id": f"job-oom{i}",
            "workflow": "txt2img",
            "model_name": "stabilityai/stable-diffusion-2-1",
            "prompt": f"fallback probe {i}",
            "seed": 2000 + i,
            "height": 64,
            "width": 64,
            "num_inference_steps": 2,
            "parameters": {"test_tiny_model": True},
        }
        for i in range(3)
    ]
    hive, results = run_jobs(jobs, sdaas_root, chips_per_job=8)
    assert {r["id"] for r in results} == {f"job-oom{i}" for i in range(3)}
    assert faults.get_plan().fired("oom_batched") == 1
    for r in results:
        cfg = r["pipeline_config"]
        assert not r.get("fatal_error"), cfg
        assert "error" not in cfg, cfg
        # served by the solo fallback, not the (failed) coalesced pass
        assert "batched_with" not in cfg


def test_poll_timeout_backs_off_with_jitter(sdaas_root):
    """Round-6 bug: the asyncio.TimeoutError branch never set the error
    backoff, so repeated timeouts hammered the hive at the poll cadence."""

    async def scenario():
        settings = Settings(sdaas_token="t", worker_name="w", metrics_port=0)
        w = Worker(settings=settings,
                   allocator=SliceAllocator(chips_per_job=8),
                   hive_uri="http://127.0.0.1:9/api")

        async def always_times_out(caps):
            raise asyncio.TimeoutError

        w.hive.ask_for_work = always_times_out
        poll = asyncio.create_task(w.poll_loop())
        try:
            for _ in range(200):
                if w._poll_backoff_s > worker_mod.POLL_SECONDS:
                    break
                await asyncio.sleep(0.01)
            assert w._poll_backoff_s > worker_mod.POLL_SECONDS
            assert w._poll_backoff_s <= worker_mod.ERROR_BACKOFF_SECONDS
        finally:
            poll.cancel()
            await asyncio.gather(poll, return_exceptions=True)
            w._executor.shutdown(wait=False)
        # decorrelated jitter: bounded by [cadence, cap], not a constant
        samples = {worker_mod._next_backoff(worker_mod.POLL_SECONDS)
                   for _ in range(50)}
        assert all(worker_mod.POLL_SECONDS <= s <= worker_mod.ERROR_BACKOFF_SECONDS
                   for s in samples)
        assert len(samples) > 10

    asyncio.run(scenario())


def test_healthz_degrades_on_stale_poll_and_outbox_saturation(sdaas_root):
    async def scenario():
        settings = Settings(sdaas_token="t", worker_name="w",
                            metrics_port=0, outbox_max_entries=2)
        w = Worker(settings=settings,
                   allocator=SliceAllocator(chips_per_job=8),
                   hive_uri="http://127.0.0.1:9/api")
        try:
            assert w._health()["status"] == "ok"  # age unknown at startup
            w._last_poll_monotonic = time.monotonic() - 1000.0
            h = w._health()
            assert h["status"] == "degraded"
            assert any("poll" in r for r in h["degraded_reasons"])

            # a stale poll while every slice is BUSY is the loop pausing
            # on purpose (mid-denoise), not a wedged worker
            held = await w.allocator.acquire()
            assert w._health()["status"] == "ok"
            w.allocator.release(held)
            assert w._health()["status"] == "degraded"

            w._last_poll_monotonic = time.monotonic()
            assert w._health()["status"] == "ok"

            w.outbox.spool({"id": "a"})
            w.outbox.spool({"id": "b"})
            h = w._health()
            assert h["outbox"]["saturated"]
            assert h["status"] == "degraded"
            assert any("outbox" in r for r in h["degraded_reasons"])
        finally:
            w._executor.shutdown(wait=False)

    asyncio.run(scenario())


# --- residency-aware placement (dispatch board, ISSUE 4 tentpole) ---


def test_placement_affinity_and_steal_across_two_slices(sdaas_root):
    """The acceptance scenario on 2 real (virtual-CPU) slices: the first
    tiny-SD job lands cold, a second same-model group lands on the slice
    where the model is now resident (affinity), and when two same-model
    groups arrive together the home slice takes one while the idle slice
    STEALS the other instead of waiting — all asserted through
    swarm_placement_total and the per-envelope placement stamp."""
    from chiaswarm_tpu import telemetry
    from chiaswarm_tpu.chips import allocator as alloc_mod

    placements = telemetry.REGISTRY.get(
        "swarm_placement_total") or telemetry.counter(
        "swarm_placement_total", "", ("outcome",))
    before = {o: placements.value(outcome=o)
              for o in ("affinity", "steal", "cold")}
    alloc_mod.reset_residency()

    def sd_job(jid: str, steps: int = 2) -> dict:
        return {"id": jid, "workflow": "txt2img",
                "model_name": "stabilityai/stable-diffusion-2-1",
                "prompt": jid, "height": 64, "width": 64,
                "num_inference_steps": steps,
                "parameters": {"test_tiny_model": True}}

    async def scenario():
        hive = await FakeHive().start()
        hive.add_job(sd_job("job-place1"))
        settings = Settings(sdaas_token="t", worker_name="w")
        w = Worker(settings=settings,
                   allocator=SliceAllocator(chips_per_job=4),  # 2 slices
                   hive_uri=hive.uri)
        runner = asyncio.create_task(w.run())
        try:
            await hive.wait_for_results(1, timeout=240.0)
            # model now resident where job-place1 ran; a second group
            # with both slices free must go HOME
            hive.add_job(sd_job("job-place2"))
            await hive.wait_for_results(2, timeout=240.0)
            # two same-model groups in one poll burst (distinct step
            # counts -> distinct coalesce keys -> two work items): the
            # home slice takes one, the idle slice steals the other
            hive.add_job(sd_job("job-place3"))
            hive.add_job(sd_job("job-place4", steps=3))
            results = await hive.wait_for_results(4, timeout=240.0)
        finally:
            w.stop()
            await asyncio.wait_for(runner, 10)
            await hive.stop()
        return results

    results = asyncio.run(scenario())
    by_id = {r["id"]: r for r in results}
    for r in results:
        assert not r.get("fatal_error"), r["pipeline_config"]
    assert by_id["job-place1"]["pipeline_config"]["placement"] == "cold"
    assert by_id["job-place2"]["pipeline_config"]["placement"] == "affinity"
    burst = {by_id["job-place3"]["pipeline_config"]["placement"],
             by_id["job-place4"]["pipeline_config"]["placement"]}
    assert burst == {"affinity", "steal"}, burst

    deltas = {o: placements.value(outcome=o) - before[o]
              for o in ("affinity", "steal", "cold")}
    assert deltas["cold"] == 1
    assert deltas["affinity"] == 2
    assert deltas["steal"] == 1


def test_compatible_img2img_jobs_coalesce_into_one_batch(sdaas_root):
    """Batched img2img end to end (ROADMAP "beyond plain txt2img"):
    3 compatible img2img jobs — per-request start images at a shared
    explicit canvas and strength — execute as ONE stacked-init-latent
    padded pass, each envelope keeping its own id, seed, and mode."""

    async def scenario():
        hive = await FakeHive().start()
        image_uri = hive.uri[: -len("/api")] + "/image.png"
        for i in range(3):
            hive.add_job({
                "id": f"job-i2i{i}",
                "workflow": "img2img",
                "model_name": "stabilityai/stable-diffusion-2-1",
                "prompt": f"repainted subject {i}",
                "seed": 3000 + i,
                "start_image_uri": image_uri,
                "strength": 0.5,
                "height": 64,
                "width": 64,
                "num_inference_steps": 4,
                "parameters": {"test_tiny_model": True},
            })
        settings = Settings(sdaas_token="t", worker_name="w")
        w = Worker(settings=settings,
                   allocator=SliceAllocator(chips_per_job=8),  # ONE slice
                   hive_uri=hive.uri)
        runner = asyncio.create_task(w.run())
        try:
            results = await hive.wait_for_results(3, timeout=240.0)
        finally:
            w.stop()
            await asyncio.wait_for(runner, 10)
            await hive.stop()
        return results

    results = asyncio.run(scenario())
    assert {r["id"] for r in results} == {f"job-i2i{i}" for i in range(3)}
    blobs = []
    for r in sorted(results, key=lambda r: r["id"]):
        cfg = r["pipeline_config"]
        assert not r.get("fatal_error"), cfg
        assert cfg["batched_with"] == 3, cfg  # ONE coalesced pass
        assert cfg["mode"] == "img2img"
        assert cfg["strength"] == 0.5
        assert cfg["seed"] == 3000 + int(r["id"][-1])
        blob = r["artifacts"]["primary"]["blob"]
        assert base64.b64decode(blob).startswith(b"\xff\xd8")  # jpeg
        blobs.append(blob)
    # distinct seeds/prompts -> distinct images (no cross-row leakage)
    assert len(set(blobs)) == 3


def test_envelope_echoes_hive_trace_context(sdaas_root):
    """ISSUE 8: the /work reply's trace context (stamped by the hive;
    the fake stamps the same field set, pinned by the conformance
    suite) rides back inside pipeline_config.trace, as it came, so the
    hive can merge this worker's stage spans into the job's timeline at
    the right dispatch attempt; the worker's receipt instant is the end
    of the `poll` span that brought the job (ISSUE 38), on the same
    wall clock, and `queue_wait` starts there."""
    hive, results = run_jobs([echo_job("traced-1")], sdaas_root)
    [result] = results
    trace = result["pipeline_config"]["trace"]
    assert trace["id"] == "traced-1"
    assert trace["attempt"] == 1
    assert isinstance(trace["dispatched_wall"], float)
    assert "received_wall" not in trace and "lingered_s" not in trace
    [poll] = spans_named(result, "poll")
    assert poll["thread"] == "poll"
    # the fake hive rounds its stamp to a millisecond
    assert (poll["start_wall"] - 0.001 <= trace["dispatched_wall"]
            <= span_end(poll) + 0.001)
    [waited] = spans_named(result, "queue_wait")
    assert waited["start_wall"] == pytest.approx(span_end(poll), abs=1e-6)
    # stage timings still ride next to it
    assert "queue_wait_s" in result["pipeline_config"]["timings"]


# --- packaging off the slice's critical path (ISSUE 28) ---------------------


def tiny_png_jobs(tag: str, n: int) -> list[dict]:
    return [{
        "id": f"{tag}{i}", "workflow": "txt2img",
        "model_name": "stabilityai/stable-diffusion-2-1",
        "prompt": f"packaging probe {i}", "seed": 4000 + i,
        "height": 64, "width": 64, "num_inference_steps": 2,
        "content_type": "image/png",
        "parameters": {"test_tiny_model": True},
    } for i in range(n)]


@contextlib.contextmanager
def scenario_root(tmp_path_factory):
    """What `sdaas_root` and `fast_poll` give a test, for a fixture that
    runs ONE worker for several tests (a worker run costs seconds)."""
    root = tmp_path_factory.mktemp("packaging")
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("SDAAS_ROOT", str(root / "sdaas"))
        for var in ("SDAAS_TOKEN", "SDAAS_URI", "SDAAS_WORKERNAME"):
            patch.delenv(var, raising=False)
        patch.setattr(worker_mod, "POLL_SECONDS", 0.05)
        yield patch


def stage_count(stage: str) -> int:
    from chiaswarm_tpu import telemetry

    return telemetry.REGISTRY.get(telemetry.STAGE_METRIC).count(stage=stage)


def span_end(span: dict) -> float:
    return span["start_wall"] + span["seconds"]


def spans_named(result: dict, name: str) -> list[dict]:
    return [span for span in result["pipeline_config"]["spans"]
            if span["name"] == name]


@pytest.fixture(scope="module")
def two_gangs_slow_packaging(tmp_path_factory):
    """Two gangs of two queued at the hive, one slice, `_package` slowed
    to 0.4 s a job: what the worker did with them, for the tests below."""
    from chiaswarm_tpu.workflows import diffusion

    real = diffusion._package
    calls, arrivals, settled = [], [], []

    class Stamped(list):
        def append(self, envelope):
            settled.append(time.time())
            super().append(envelope)

    def slow(images, outputs, content_type):
        calls.append(([image.copy() for image in images], outputs,
                      content_type))
        time.sleep(0.4)
        return real(images, outputs, content_type)

    async def scenario():
        hive = await FakeHive().start()
        hive.gang_max = 2
        hive.slow_results_s = 0.3  # a POST apart would be this far apart
        hive.results = Stamped()
        for job in tiny_png_jobs("gang-", 4):
            hive.add_job(job)
        w = Worker(settings=Settings(sdaas_token="t", worker_name="w"),
                   allocator=SliceAllocator(chips_per_job=8),
                   hive_uri=hive.uri)
        put = w.result_queue.put

        async def noting(entry):
            arrivals.append((time.time(), entry.job_id))
            await put(entry)

        w.result_queue.put = noting
        runner = asyncio.create_task(w.run())
        try:
            return list(await hive.wait_for_results(4, timeout=240.0))
        finally:
            w.stop()
            await asyncio.wait_for(runner, 10)
            await hive.stop()

    with scenario_root(tmp_path_factory) as patch:
        patch.setattr(diffusion, "_package", slow)
        results = asyncio.run(scenario())
    by_id = {result["id"]: result for result in results}
    settled_at = {result["id"]: at for result, at in zip(results, settled)}
    return types.SimpleNamespace(
        results=[by_id[f"gang-{i}"] for i in range(4)], calls=calls,
        arrivals=arrivals, package=real,
        settled_at=[settled_at[f"gang-{i}"] for i in range(4)])


def test_next_pass_runs_while_the_last_is_packaged(two_gangs_slow_packaging):
    """(a) The slice is let go before its pass's images are packaged: the
    second gang's `pass` span starts before the first gang's last
    `artifact_encode` span ends; how much of the packaging ran under the
    later pass is the overlap of the two kinds of span, on one clock."""
    first, second = (two_gangs_slow_packaging.results[:2],
                     two_gangs_slow_packaging.results[2:])
    for gang in (first, second):
        assert all(r["pipeline_config"]["batched_with"] == 2 for r in gang)
        assert len({r["pipeline_config"]["trace"]["gang"]["id"]
                    for r in gang}) == 1
    [next_pass] = spans_named(second[0], "pass")
    encodes = [span for r in first for span in spans_named(r, "artifact_encode")]
    assert len(encodes) == 2
    assert all(span["thread"] == "host" for span in encodes)
    assert next_pass["start_wall"] < max(map(span_end, encodes))
    [first_pass] = spans_named(first[0], "pass")
    assert span_end(first_pass) <= next_pass["start_wall"]
    overlapped_s = sum(
        max(min(span_end(span), span_end(next_pass))
            - max(span["start_wall"], next_pass["start_wall"]), 0.0)
        for span in encodes)
    # all of it where the next gang is fetched the instant the slice is
    # free; a span's end is a sum of two floats, hence the slack
    assert 0 < overlapped_s <= sum(
        span["seconds"] for span in encodes) + 1e-6


def test_artifacts_are_what_package_gives_for_the_same_images(
        two_gangs_slow_packaging):
    """(b) Same grid, same PNG parameters, same thumbnail, same hash: the
    envelope's artifacts are `_package`'s for the images the pass handed
    back, byte for byte."""
    run = two_gangs_slow_packaging
    assert len(run.calls) == 4  # packaged in pass order, one call a job
    for result, (images, outputs, content_type) in zip(run.results, run.calls):
        want = run.package(images, outputs, content_type)["primary"]
        got = result["artifacts"]["primary"]
        assert content_type == got["content_type"] == "image/png"
        for key in ("blob", "thumbnail", "sha256_hash"):
            assert got[key] == want[key], (result["id"], key)
        assert base64.b64decode(got["blob"]).startswith(b"\x89PNG")


def test_a_gangs_envelopes_are_enqueued_together_and_in_order(
        two_gangs_slow_packaging):
    """(e) A pass's envelopes reach the result queue after the last of its
    jobs is packaged, in order and together, never one encode apart."""
    run = two_gangs_slow_packaging
    assert [job_id for _, job_id in run.arrivals] == [
        f"gang-{i}" for i in range(4)]
    for gang in (0, 2):
        (first_at, _), (second_at, _) = run.arrivals[gang:gang + 2]
        last_encoded = max(
            span_end(span) for r in run.results[gang:gang + 2]
            for span in spans_named(r, "artifact_encode"))
        assert first_at >= last_encoded - 1e-3
        assert second_at - first_at < 0.2  # an encode is 0.4 s here


def test_a_gangs_envelopes_are_uploaded_together(two_gangs_slow_packaging):
    """(g) A pass's envelopes are posted at once, not one after the other's
    ACK: the hive (0.3 s a POST here) settles a gang's jobs at one instant,
    so its clients see them together."""
    at = two_gangs_slow_packaging.settled_at
    for gang in (0, 2):
        assert abs(at[gang + 1] - at[gang]) < 0.15
    assert at[2] - at[0] > 0.15  # the two passes are apart all the same


@pytest.fixture(scope="module")
def drained_over_a_failed_encode(tmp_path_factory):
    """One gang of three; the second member's `_package` raises; a drain
    is asked for while the first member is being packaged."""
    from chiaswarm_tpu.workflows import diffusion

    real = diffusion._package
    calls = []
    packaging = threading.Event()

    def failing(images, outputs, content_type):
        calls.append(len(calls))
        packaging.set()
        time.sleep(0.5)
        if calls[-1] == 1:
            raise RuntimeError("injected: the encoder failed")
        return real(images, outputs, content_type)

    async def scenario():
        hive = await FakeHive().start()
        for job in tiny_png_jobs("enc-", 3):
            hive.add_job(job)
        w = Worker(settings=Settings(sdaas_token="t", worker_name="w",
                                     drain_deadline_s=60.0),
                   allocator=SliceAllocator(chips_per_job=8),
                   hive_uri=hive.uri)
        runner = asyncio.create_task(w.run())
        try:
            for _ in range(4800):
                if packaging.is_set():
                    break
                await asyncio.sleep(0.05)
            assert packaging.is_set()
            delivered_before = len(hive.results)
            in_flight = w._health()["jobs_in_flight"]
            w.stop(drain=True)
            await asyncio.wait_for(runner, 60.0)  # exits by itself
            return types.SimpleNamespace(
                results=list(hive.results), in_flight=in_flight,
                delivered_before=delivered_before,
                spooled_left=w.outbox.depth)
        finally:
            if not runner.done():
                w.stop()
                await asyncio.wait_for(runner, 10)
            await hive.stop()

    with scenario_root(tmp_path_factory) as patch:
        patch.setattr(diffusion, "_package", failing)
        before = {stage: stage_count(stage) for stage in ("denoise", "pass")}
        run = asyncio.run(scenario())
        run.passes = {stage: stage_count(stage) - n
                      for stage, n in before.items()}
    return run


def test_drain_waits_for_packaging_in_flight(drained_over_a_failed_encode):
    """(c) A job is outstanding until its envelope is spooled: a drain
    asked for over unencoded images delivers every envelope first."""
    run = drained_over_a_failed_encode
    assert run.delivered_before == 0 and run.in_flight == 3
    assert [r["id"] for r in run.results] == ["enc-0", "enc-1", "enc-2"]
    assert run.spooled_left == 0


def test_a_failed_encode_is_that_jobs_own_envelope(
        drained_over_a_failed_encode):
    """(d) An exception while packaging one member is that member's error
    envelope by the usual attribution; its batchmates are delivered and
    nothing is denoised again (before, the whole callback failed and every
    member was rerun solo)."""
    run = drained_over_a_failed_encode
    failed, fine = run.results[1], [run.results[0], run.results[2]]
    assert failed["pipeline_config"]["error"] == "injected: the encoder failed"
    assert not failed.get("fatal_error")
    assert base64.b64decode(
        failed["artifacts"]["primary"]["blob"]).startswith(b"\x89PNG")
    # the failed job's envelope still says where its pass went
    assert spans_named(failed, "denoise") and spans_named(failed, "pass")
    for result in fine:
        config = result["pipeline_config"]
        assert "error" not in config and config["batched_with"] == 3
        assert len(spans_named(result, "artifact_encode")) == 1
    assert run.passes == {"denoise": 1, "pass": 1}


def test_a_slice_waits_while_two_of_its_passes_are_undelivered(sdaas_root):
    """(f) The one bound on what waits, a rule and no setting: with two
    earlier passes of a slice undelivered, its third pass does not start
    until the older one is; the wait is the third job's `package_wait`
    span, in the stage histogram and in its envelope."""
    from chiaswarm_tpu import telemetry
    from chiaswarm_tpu.chips.device import _EXECUTE_SECONDS

    def held_s():
        return telemetry.REGISTRY.histogram(
            telemetry.STAGE_METRIC, labelnames=("stage",)).sum(
                stage="package_wait")

    async def scenario():
        hive = await FakeHive().start()
        for i in range(3):
            hive.add_job(echo_job(f"job-q{i}"))
        w = Worker(settings=Settings(sdaas_token="t", worker_name="w"),
                   allocator=SliceAllocator(chips_per_job=8),
                   hive_uri=hive.uri)
        gate = asyncio.Event()
        enqueue = w._enqueue_result

        async def gated(result):
            await gate.wait()
            await enqueue(result)

        w._enqueue_result = gated
        passes_before = _EXECUTE_SECONDS.count(kind="solo")
        held_before = held_s()
        runner = asyncio.create_task(w.run())
        try:
            for _ in range(200):
                if _EXECUTE_SECONDS.count(kind="solo") - passes_before >= 2:
                    break
                await asyncio.sleep(0.05)
            await asyncio.sleep(0.5)  # room for a third pass to start
            assert _EXECUTE_SECONDS.count(kind="solo") - passes_before == 2
            # the two passes that ran waited microseconds; the third's
            # wait is stamped when it ends
            assert hive.results == [] and held_s() - held_before < 0.05
            assert w._health()["jobs_in_flight"] == 3
            gate.set()
            results = await hive.wait_for_results(3, timeout=60.0)
            assert [r["id"] for r in results] == [
                "job-q0", "job-q1", "job-q2"]
            assert _EXECUTE_SECONDS.count(kind="solo") - passes_before == 3
            assert held_s() - held_before >= 0.4
            waits = [spans_named(r, "package_wait")[0] for r in results]
            assert waits[0]["seconds"] < 0.05 and waits[1]["seconds"] < 0.05
            assert waits[2]["seconds"] == pytest.approx(
                held_s() - held_before, abs=0.05)
            [claim] = spans_named(results[2], "claim")
            assert waits[2]["start_wall"] == pytest.approx(
                span_end(claim), abs=1e-6)
        finally:
            w.stop()
            await asyncio.wait_for(runner, 10)
            await hive.stop()

    asyncio.run(scenario())


# --- the poll's wait ends at the timer or at new capacity (ISSUE 39) ---


def polls_by_cause() -> dict:
    return {cause: worker_mod._POLLS.value(cause=cause)
            for cause in ("capacity", "timer", "heartbeat")}


def polls_since(before: dict) -> dict:
    return {cause: n - before[cause]
            for cause, n in polls_by_cause().items()}


@contextlib.asynccontextmanager
async def polling_worker(slices: int = 1, hold: bool = True):
    """A worker of which only `poll_loop` runs, against a fake hive: the
    test plays the slice workers (`hold`: every slice taken before the
    loop starts, so nothing is asked until the test lets one go). Yields
    the hive, the worker, the held slices and `sent`, the monotonic
    instant and `cancel_only` flag of every poll the worker sends."""
    hive = await FakeHive().start()
    w = Worker(settings=Settings(sdaas_token="t", worker_name="w",
                                 metrics_port=0),
               allocator=SliceAllocator(chips_per_job=8 // slices),
               hive_uri=hive.uri)
    assert len(w.allocator) == slices
    sent: list[tuple[float, bool]] = []
    ask = w.hive.ask_for_work

    async def noting(caps):
        sent.append((time.monotonic(), bool(caps.get("cancel_only"))))
        return await ask(caps)

    w.hive.ask_for_work = noting
    held = [w.allocator.try_acquire() for _ in range(slices)] if hold else []
    w._note_capacity()
    w._capabilities()  # a process's first costs seconds of imports
    poll = asyncio.create_task(w.poll_loop())
    await asyncio.sleep(0.01)  # the loop's first turn is behind it
    try:
        yield hive, w, held, sent
    finally:
        poll.cancel()
        await asyncio.gather(poll, return_exceptions=True)
        await w.hive.close()
        w._executor.shutdown(wait=False)
        await hive.stop()


async def until(condition, timeout: float = 2.0) -> float:
    """Seconds until `condition()` held (asked every millisecond)."""
    started = time.monotonic()
    while not condition():
        assert time.monotonic() - started < timeout, "never happened"
        await asyncio.sleep(0.001)
    return time.monotonic() - started


# what "at once" may cost on a busy test host; every cadence below is
# several times this, so a timer cannot be what passed a test
AT_ONCE_S = 0.25


def test_a_release_with_a_job_at_the_hive_is_followed_by_a_poll_at_once(
        sdaas_root, monkeypatch):
    monkeypatch.setattr(worker_mod, "POLL_SECONDS", 30.0)

    async def scenario():
        async with polling_worker() as (hive, w, [chipset], sent):
            hive.add_job(echo_job("standing"))
            await asyncio.sleep(0.3)
            assert sent == []  # no room: nothing asked, no heartbeat owed
            before = polls_by_cause()
            released = time.time()
            w.allocator.release(chipset)
            assert await until(lambda: sent) < AT_ONCE_S
            [job] = await asyncio.wait_for(w.batcher.get(), 1.0)
            w.batcher.task_done(job)
            await asyncio.sleep(0.2)
            assert len(sent) == 1  # and the timer's turn is 30 s away
            return job, released, polls_since(before)

    job, released, polls = asyncio.run(scenario())
    assert job["id"] == "standing"
    spans = {span["name"]: span for span in job[SPANS]}
    # the wait for the tick is the loop's wake-up, begun at the release
    assert spans["tick_wait"]["seconds"] < AT_ONCE_S
    assert spans["tick_wait"]["start_wall"] == pytest.approx(
        released, abs=0.01)
    assert polls == {"capacity": 1, "timer": 0, "heartbeat": 0}


def test_a_release_does_not_cut_a_poll_errors_back_off_short(
        sdaas_root, monkeypatch):
    # cadence == cap, so the jittered back-off is exactly one second
    monkeypatch.setattr(worker_mod, "POLL_SECONDS", 1.0)
    monkeypatch.setattr(worker_mod, "ERROR_BACKOFF_SECONDS", 1.0)

    async def scenario():
        async with polling_worker() as (hive, w, [chipset], sent):
            hive.refuse_with = "not now"
            before = polls_by_cause()
            w.allocator.release(chipset)
            await until(lambda: w._poll_failed)
            assert w._poll_backoff_s == 1.0
            hive.refuse_with = None
            chipset = w.allocator.try_acquire()
            w._note_capacity()
            assert w._able_since is None
            hive.add_job(echo_job("behind-the-back-off"))
            w.allocator.release(chipset)  # capacity, new, mid back-off
            assert w._capacity.is_set()
            await asyncio.sleep(0.7)
            assert len(sent) == 1
            await until(lambda: len(sent) == 2, timeout=1.0)
            [job] = await asyncio.wait_for(w.batcher.get(), 1.0)
            w.batcher.task_done(job)
            # the back-off's own poll answered the wake-up: no second one
            await asyncio.sleep(0.5)
            assert len(sent) == 2 and not w._poll_failed
            return sent, polls_since(before)

    sent, polls = asyncio.run(scenario())
    assert sent[1][0] - sent[0][0] >= 1.0
    assert polls == {"capacity": 1, "timer": 1, "heartbeat": 0}


def test_a_release_with_nothing_at_the_hive_makes_one_poll_then_the_timer(
        sdaas_root, monkeypatch):
    monkeypatch.setattr(worker_mod, "POLL_SECONDS", 1.0)

    async def scenario():
        async with polling_worker() as (hive, w, [chipset], sent):
            await asyncio.sleep(0.3)
            assert sent == []
            before = polls_by_cause()
            w.allocator.release(chipset)
            assert await until(lambda: sent) < AT_ONCE_S
            # it brought nothing: the worker is as able as it was, so
            # nothing wakes the loop again and the hive is left alone
            # until the timer, which counts from the early poll
            await asyncio.sleep(0.8)
            assert len(sent) == 1 and w._able_since is not None
            assert not w._capacity.is_set()
            await until(lambda: len(sent) == 2, timeout=1.0)
            await asyncio.sleep(0.8)
            assert len(sent) == 2
            return sent, polls_since(before), len(hive.work_requests)

    sent, polls, requests = asyncio.run(scenario())
    assert 1.0 <= sent[1][0] - sent[0][0] < 1.0 + AT_ONCE_S
    assert polls == {"capacity": 1, "timer": 1, "heartbeat": 0}
    assert requests == 2


def test_two_slices_released_together_make_one_poll(sdaas_root, monkeypatch):
    monkeypatch.setattr(worker_mod, "POLL_SECONDS", 30.0)

    async def scenario():
        async with polling_worker(slices=2) as (hive, w, held, sent):
            before = polls_by_cause()
            for chipset in held:
                w.allocator.release(chipset)
            assert await until(lambda: sent) < AT_ONCE_S
            await asyncio.sleep(0.3)
            assert len(sent) == 1
            # one slice busy and free again while the other stayed free:
            # the worker never stopped being able, nothing new to ask
            chipset = w.allocator.try_acquire()
            w._note_capacity()
            w.allocator.release(chipset)
            await asyncio.sleep(0.3)
            assert len(sent) == 1
            return polls_since(before)

    assert asyncio.run(scenario()) == {
        "capacity": 1, "timer": 0, "heartbeat": 0}


def test_a_draining_worker_is_not_woken(sdaas_root, monkeypatch):
    monkeypatch.setattr(worker_mod, "POLL_SECONDS", 30.0)

    async def scenario():
        async with polling_worker() as (hive, w, [chipset], sent):
            hive.add_job(echo_job("not-for-a-leaver"))
            w.stop(drain=True)
            w.allocator.release(chipset)
            assert w._able_since is None and not w._capacity.is_set()
            await asyncio.sleep(0.4)
            return sent, len(hive.pending_jobs)

    assert asyncio.run(scenario()) == ([], 1)


def test_a_busy_workers_heartbeat_keeps_the_timers_cadence(
        sdaas_root, monkeypatch):
    """A wake-up whose capacity is gone by the time the loop runs (the
    slice went straight to work that was waiting on the board) asks
    nothing and leaves the timer as it was: the `cancel_only` heartbeat
    comes a cadence after the one before, neither early nor late."""
    monkeypatch.setattr(worker_mod, "POLL_SECONDS", 0.6)

    async def scenario():
        async with polling_worker(hold=False) as (hive, w, _, sent):
            # one job in a slice's hands: outstanding, nothing ready
            await w.batcher.put(echo_job("in-a-pass"))
            _, chipset, _ = await asyncio.wait_for(
                w.batcher.claim(w.allocator), 2.0)
            w._note_capacity()
            await until(lambda: sent and sent[-1][1])  # a heartbeat
            first = len(sent)
            before = polls_by_cause()
            await asyncio.sleep(0.2)
            w.allocator.release(chipset)  # free ...
            assert w._capacity.is_set()
            assert w.allocator.try_acquire() is chipset  # ... and taken
            w._note_capacity()
            await until(lambda: len(sent) == first + 2, timeout=3.0)
            return sent[first - 1:], polls_since(before)

    sent, polls = asyncio.run(scenario())
    assert all(heartbeat for _, heartbeat in sent)
    for (earlier, _), (later, _) in zip(sent, sent[1:]):
        assert 0.6 <= later - earlier < 0.6 + AT_ONCE_S
    assert polls == {"capacity": 0, "timer": 0, "heartbeat": 2}
