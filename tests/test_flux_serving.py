"""Flux through hive -> worker -> batcher -> registry -> FluxPipeline
(ISSUE 27): the envelope's spans on the batched and on the solo path, and
the solo fallback after a failed batched pass keeping the job's canvas."""

import base64
import io

import pytest
from PIL import Image

from .test_worker_loop import run_jobs


@pytest.fixture(autouse=True)
def fast_poll(monkeypatch):
    from chiaswarm_tpu import worker

    monkeypatch.setattr(worker, "POLL_SECONDS", 0.05)


def _jobs(tag):
    return [{
        "id": f"{tag}-{i}", "workflow": "txt2img",
        "model_name": "test/tiny-flux", "prompt": f"flux probe {i}",
        "seed": 3000 + i, "height": 64, "width": 64,
        "num_inference_steps": 2, "guidance_scale": 3.5,
        "content_type": "image/png",
        "parameters": {"pipeline_type": "FluxPipeline"},
    } for i in range(2)]


@pytest.mark.parametrize("batched_fails", [False, True],
                         ids=["batched", "solo-fallback"])
def test_envelope_spans_and_canvas(batched_fails, monkeypatch, sdaas_root):
    """PR 26's dry run on four chips saw a failed batched pass come back as
    512 x 512 images for 64 x 64 jobs: the solo retry had failed too (its
    programs ran outside `mesh_scope`, as the batched one's), and what
    came back was `exception_image`'s 512 x 512 rendering of the error,
    settled `done`. With `run_batched` made to raise, the fallback serves
    each member on its own at the job's canvas."""
    from chiaswarm_tpu.pipelines.flux import FluxPipeline

    if batched_fails:
        def refuse(self, requests, **shared):
            raise RuntimeError("injected: the batched pass failed")

        monkeypatch.setattr(FluxPipeline, "run_batched", refuse)
    jobs = _jobs("fb" if batched_fails else "b")
    _, results = run_jobs(jobs, sdaas_root, chips_per_job=8)
    assert {r["id"] for r in results} == {job["id"] for job in jobs}
    for result in results:
        config = result["pipeline_config"]
        assert not result.get("fatal_error") and "error" not in config, config
        assert ("batched_with" in config) == (not batched_fails)
        assert config["size"] == [64, 64]
        blob = base64.b64decode(result["artifacts"]["primary"]["blob"])
        assert Image.open(io.BytesIO(blob)).size == (64, 64)

        # nothing hand-stamped: the stages are spans, children of `pass`
        # on the slice thread, and the timings come from them
        spans = {s["name"]: s for s in config["spans"]}
        for name in ("pass", "load", "text_encode", "compile", "denoise",
                     "decode", "artifact_encode"):
            assert name in spans, sorted(spans)
        held = spans["pass"]
        for name in ("text_encode", "compile", "denoise"):
            child = spans[name]
            assert child["thread"] == held["thread"] == "slice"
            assert held["start_wall"] <= child["start_wall"]
            assert child["start_wall"] + child["seconds"] \
                <= held["start_wall"] + held["seconds"] + 1e-4
        timings = config["timings"]
        assert timings["denoise_decode_s"] == round(
            spans["denoise"]["seconds"], 3)
        assert timings["trace_s"] == round(spans["compile"]["seconds"], 3)
        assert "text_encode_s" in timings


def test_local_swarm_slices_take_their_geometry_from_the_settings(sdaas_root):
    """`LocalSwarm` (the benchmark's and the e2e tests' swarm) built its
    workers' slices with the default geometry whatever the settings said:
    a four-chip cell at `SDAAS_TENSOR_PARALLELISM=4` ran `[data=4,
    tensor=1]`, and FLUX.1-dev was refused by admission on every job
    (ISSUE 27, my chip run)."""
    import asyncio

    from chiaswarm_tpu.hive_server.harness import LocalSwarm
    from chiaswarm_tpu.settings import Settings

    async def scenario():
        swarm = LocalSwarm(
            n_workers=0, chips_per_job=4,
            settings=Settings(sdaas_token="t", worker_name="w", hive_port=0,
                              metrics_port=0, tensor_parallelism=4))
        await swarm.start()
        try:
            worker = swarm.add_worker("geometry")
            return [(len(s.devices), s.tensor, dict(s.mesh().shape))
                    for s in worker.allocator.slices]
        finally:
            await swarm.stop()

    slices = asyncio.run(scenario())
    assert slices == [(4, 4, {"data": 1, "tensor": 4, "seq": 1})] * 2


def test_jobs_a_poll_apart_ride_one_pass_and_their_envelopes_say_so(
        monkeypatch, sdaas_root):
    """Two users resubmit a few ms apart and a worker poll falls between
    them (`flux-backlog`, my chip run, PR 27): the first job lingers long
    enough for the next poll to bring its batchmate (here the linger a long
    pass would earn, `BatchScheduler.linger_for`), they ride one batched
    pass, and both envelopes echo one `trace.gang` that the worker made,
    which is how a reader of envelopes tells the passes that ran."""
    import asyncio

    from chiaswarm_tpu.batching import BatchScheduler
    from chiaswarm_tpu.chips.allocator import SliceAllocator
    from chiaswarm_tpu.settings import Settings
    from chiaswarm_tpu.worker import Worker

    from .fake_hive import FakeHive

    monkeypatch.setattr(BatchScheduler, "linger_for", lambda self, key: 0.6)
    first, second = _jobs("apart")

    async def scenario():
        hive = await FakeHive().start()
        w = Worker(settings=Settings(sdaas_token="test-token",
                                     worker_name="test-worker"),
                   allocator=SliceAllocator(chips_per_job=8),
                   hive_uri=hive.uri)
        runner = asyncio.create_task(w.run())
        try:
            hive.add_job(first)
            while w.batcher.pending_jobs == 0:  # taken alone, lingering
                await asyncio.sleep(0.01)
            hive.add_job(second)
            results = await hive.wait_for_results(2, timeout=240.0)
            while w.batcher.outstanding_jobs:  # the pass booked as done
                await asyncio.sleep(0.01)
            return results, dict(w.batcher._pass_s)
        finally:
            w.stop()
            await asyncio.wait_for(runner, 10)
            await hive.stop()

    results, passes_seen = asyncio.run(scenario())
    # the pass is booked under the key its jobs were grouped by (the slice
    # worker takes `trace` and its own stamps off a job, nothing of the key)
    from chiaswarm_tpu.batching import coalesce_key

    assert list(passes_seen) == [coalesce_key(_jobs("apart")[0])]
    assert passes_seen[coalesce_key(first)][0] == 1
    configs = [r["pipeline_config"] for r in results]
    assert all(c["batched_with"] == 2 for c in configs), configs
    gangs = [c["trace"]["gang"] for c in configs]
    assert gangs[0]["id"] == gangs[1]["id"]
    assert {g["index"] for g in gangs} == {0, 1}
    assert all(g["by"] == "worker" and g["size"] == 2 for g in gangs)
