"""Priority-aware multi-chip sharding (ISSUE 12): one job, many chips.

Covers the elastic slice geometry end to end on the 8-virtual-device CPU
mesh: per-pass geometry selection (an interactive solo fans one image
over the whole slice as a tensor-sharded program while coalesced batch
traffic keeps the data-parallel view), the chunk-boundary re-shard seam
(a pass migrated sharded->replicated — or back — mid-denoise equals an
undisturbed pass), cancellation probing under a sharded mesh, the
worker-side class routing, and the hive-side shard-capable dispatch
preference.
"""

import asyncio
import base64

import numpy as np
import pytest

import jax

from chiaswarm_tpu import cancel as cancel_mod
from chiaswarm_tpu import worker as worker_mod
from chiaswarm_tpu.cancel import JobCancelled
from chiaswarm_tpu.chips.allocator import SliceAllocator
from chiaswarm_tpu.chips.device import ChipSet
from chiaswarm_tpu.pipelines.stable_diffusion import (
    SDPipeline,
    geometry_label,
)
from chiaswarm_tpu.settings import Settings
from chiaswarm_tpu.telemetry import trace_job
from chiaswarm_tpu.worker import Worker

from .fake_hive import FakeHive


@pytest.fixture(autouse=True)
def fast_poll(monkeypatch):
    monkeypatch.setattr(worker_mod, "POLL_SECONDS", 0.05)
    monkeypatch.setattr(worker_mod, "ERROR_BACKOFF_SECONDS", 0.2)


@pytest.fixture(scope="module")
def slice8():
    return ChipSet(jax.devices())  # 8 virtual CPU chips, one slice


@pytest.fixture(scope="module")
def pipe8(slice8):
    return SDPipeline("test/tiny-sd", chipset=slice8)


KW = dict(prompt="geometry test", height=64, width=64,
          num_inference_steps=4)


# --- geometry resolution ----------------------------------------------------


def test_chipset_resolve_geometry():
    cs = ChipSet(jax.devices())
    assert cs.shard_capable
    # auto leaves a data axis for the CFG pair: 8 chips -> tensor=4
    assert cs.resolve_geometry(0, 1) == (4, 1)
    assert cs.resolve_geometry(None, None) == (4, 1)
    assert cs.resolve_geometry(2, 1) == (2, 1)
    assert cs.resolve_geometry(8, 1) == (8, 1)
    assert cs.resolve_geometry(0, 2) == (2, 2)  # auto under a seq axis
    assert cs.resolve_geometry(3, 1) is None  # 3 does not divide 8
    assert cs.resolve_geometry(2, 3) is None
    solo = ChipSet(jax.devices()[:1])
    assert not solo.shard_capable
    assert solo.resolve_geometry(0, 1) == (1, 1)
    assert solo.resolve_geometry(2, 1) is None


def test_geometry_label():
    assert geometry_label(1, 1) == "replicated"
    assert geometry_label(2, 1) == "tensor2"
    assert geometry_label(1, 2) == "seq2"
    assert geometry_label(2, 2) == "tensor2_seq2"


# --- per-pass geometry selection -------------------------------------------


@pytest.mark.parametrize("tensor", [2, 4])
def test_sharded_pass_matches_replicated_and_stamps(pipe8, tensor):
    ref, cfg0 = pipe8.run(rng=jax.random.key(3), **KW)
    assert cfg0["geometry"] == {"data": 8, "tensor": 1, "seq": 1}

    data = 8 // tensor
    imgs, cfg = pipe8.run(rng=jax.random.key(3),
                          geometry={"tensor": tensor}, **KW)
    assert cfg["geometry"] == {"data": data, "tensor": tensor, "seq": 1}
    diff = np.abs(np.asarray(ref[0], np.int16)
                  - np.asarray(imgs[0], np.int16))
    assert diff.max() <= 2, f"max pixel diff {diff.max()}"
    # the same work over the same slice, whatever the view
    assert cfg["cost"]["flops"] == cfg0["cost"]["flops"] > 0
    assert cfg["cost"]["chips"] == 8 and cfg["cost"]["mfu"] is None
    # the slice remembers the view its latest pass ran under
    assert pipe8.chipset.last_geometry == (data, tensor, 1)
    assert pipe8.chipset.geometry_str() == f"data{data}·tensor{tensor}·seq1"


def test_unmeshable_geometry_falls_back_to_default(pipe8):
    imgs, cfg = pipe8.run(rng=jax.random.key(3),
                          geometry={"tensor": 3}, **KW)
    assert cfg["geometry"] == {"data": 8, "tensor": 1, "seq": 1}
    assert len(imgs) == 1


def test_sharded_pass_counter(pipe8):
    from chiaswarm_tpu import telemetry

    before = telemetry.REGISTRY.render()
    pipe8.run(rng=jax.random.key(4), geometry={"tensor": 2}, **KW)
    after = telemetry.REGISTRY.render()
    line = 'swarm_sharded_passes_total{geometry="tensor2"}'
    count = lambda text: next(
        (float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
         if ln.startswith(line)), 0.0)
    assert count(after) == count(before) + 1


# --- the chunk-seam re-shard ------------------------------------------------


def test_reshard_midpass_matches_undisturbed(pipe8, sdaas_root, monkeypatch):
    monkeypatch.setenv("CHIASWARM_DENOISE_CHUNK_STEPS", "1")
    ref, _ = pipe8.run(rng=jax.random.key(9), **KW)

    # sharded -> replicated after the first boundary
    down, cfg_down = pipe8.run(rng=jax.random.key(9),
                               geometry={"tensor": 2},
                               reshard_probe=lambda: "default", **KW)
    assert cfg_down["resharded"], cfg_down
    assert cfg_down["resharded"][0]["from"] == [2, 1]
    assert cfg_down["resharded"][0]["to"] == [1, 1]
    diff = np.abs(np.asarray(ref[0], np.int16)
                  - np.asarray(down[0], np.int16))
    assert diff.max() <= 2, f"down-migrated diff {diff.max()}"

    # replicated -> sharded (the reverse seam)
    up, cfg_up = pipe8.run(rng=jax.random.key(9),
                           reshard_probe=lambda: {"tensor": 2}, **KW)
    assert cfg_up["resharded"][0]["to"] == [2, 1]
    diff = np.abs(np.abs(np.asarray(ref[0], np.int16)
                         - np.asarray(up[0], np.int16)))
    assert diff.max() <= 2, f"up-migrated diff {diff.max()}"


def test_reshard_probe_none_keeps_geometry(pipe8, sdaas_root, monkeypatch):
    monkeypatch.setenv("CHIASWARM_DENOISE_CHUNK_STEPS", "2")
    imgs, cfg = pipe8.run(rng=jax.random.key(10),
                          geometry={"tensor": 2},
                          reshard_probe=lambda: None, **KW)
    assert "resharded" not in cfg
    assert cfg["geometry"]["tensor"] == 2
    assert len(imgs) == 1


def test_cancel_probed_at_chunk_boundary_under_mesh(pipe8, sdaas_root,
                                                    monkeypatch):
    """ISSUE 12 satellite: the cancel token is still probed at chunk
    boundaries when the pass runs under a sharded mesh — a revoked
    interactive job frees its whole-slice sharded pass within one
    chunk, exactly like a replicated one."""
    monkeypatch.setenv("CHIASWARM_DENOISE_CHUNK_STEPS", "1")
    cancel_mod.cancel("doomed-sharded")
    try:
        with trace_job("doomed-sharded"):
            with pytest.raises(JobCancelled) as err:
                pipe8.run(rng=jax.random.key(5),
                          geometry={"tensor": 2}, **KW)
        assert err.value.job_ids == ["doomed-sharded"]
    finally:
        cancel_mod.discard("doomed-sharded")


# --- worker-side class routing ---------------------------------------------


def test_worker_interactive_shards_batch_coalesces(sdaas_root):
    """The class picks the view end-to-end on ONE allocator: an
    interactive job executes under a tensor>1 mesh (geometry stamped in
    its envelope) while a concurrent batch group keeps data-parallel
    coalescing on the same 8-chip slice."""

    def sd_job(jid: str, **extra) -> dict:
        job = {"id": jid, "workflow": "txt2img",
               "model_name": "stabilityai/stable-diffusion-2-1",
               "prompt": f"subject {jid}", "height": 64, "width": 64,
               "num_inference_steps": 2,
               "parameters": {"test_tiny_model": True}}
        job.update(extra)
        return job

    jobs = [sd_job(f"batch-{i}") for i in range(3)]
    # distinct step count -> its own coalesce bucket, so the interactive
    # job dispatches solo instead of riding the batch group
    jobs.append(sd_job("vip", num_inference_steps=4,
                       priority="interactive"))

    async def scenario():
        hive = await FakeHive().start()
        for job in jobs:
            hive.add_job(job)
        settings = Settings(sdaas_token="test-token",
                            worker_name="shard-worker",
                            shard_interactive=True, shard_tensor=2)
        w = Worker(settings=settings,
                   allocator=SliceAllocator(chips_per_job=0),
                   hive_uri=hive.uri)
        runner = asyncio.create_task(w.run())
        try:
            results = await hive.wait_for_results(4, timeout=240.0)
        finally:
            w.stop()
            await asyncio.wait_for(runner, 10)
            await hive.stop()
        return hive, results

    hive, results = asyncio.run(scenario())
    by_id = {r["id"]: r for r in results}
    vip = by_id["vip"]["pipeline_config"]
    assert vip["geometry"]["tensor"] == 2, vip
    assert vip["geometry"]["data"] == 4, vip
    for i in range(3):
        cfg = by_id[f"batch-{i}"]["pipeline_config"]
        assert cfg["geometry"] == {"data": 8, "tensor": 1, "seq": 1}, cfg
        assert cfg["batched_with"] == 3, cfg
        blob = by_id[f"batch-{i}"]["artifacts"]["primary"]["blob"]
        assert base64.b64decode(blob).startswith(b"\xff\xd8")
    # the worker advertised its slice geometry on /work
    req = hive.work_requests[0]
    assert req["chips_per_slice"] == "8"
    assert req["shard_capable"] == "1"


def test_worker_shard_geometry_gates(sdaas_root):
    """No sharding without the knob, on single-chip slices, or when the
    resolved view equals the slice default."""
    alloc = SliceAllocator(chips_per_job=0)
    w = Worker(settings=Settings(sdaas_token="t"), allocator=alloc,
               hive_uri="http://127.0.0.1:1")
    assert w._shard_geometry(alloc.slices[0]) is None  # knob off

    w2 = Worker(settings=Settings(sdaas_token="t", shard_interactive=True),
                allocator=alloc, hive_uri="http://127.0.0.1:1")
    assert w2._shard_geometry(alloc.slices[0]) == (4, 1)  # auto

    solo_alloc = SliceAllocator(chips_per_job=1)
    w3 = Worker(settings=Settings(sdaas_token="t", shard_interactive=True),
                allocator=solo_alloc, hive_uri="http://127.0.0.1:1")
    assert w3._shard_geometry(solo_alloc.slices[0]) is None


def test_localswarm_interactive_sharded_e2e(sdaas_root):
    """ISSUE 12 acceptance: on a LocalSwarm with an 8-device slice, an
    interactive job demonstrably executes under a tensor>1 mesh —
    geometry stamped in its settled envelope — while concurrently
    submitted batch jobs keep data-parallel coalescing (gang-dispatched
    by the hive, batched_with in their envelopes)."""
    from chiaswarm_tpu.hive_server.harness import LocalSwarm

    def sd_job(jid: str, **extra) -> dict:
        job = {"id": jid, "workflow": "txt2img",
               "model_name": "stabilityai/stable-diffusion-2-1",
               "prompt": f"swarm subject {jid}", "height": 64, "width": 64,
               "num_inference_steps": 2,
               "parameters": {"test_tiny_model": True}}
        job.update(extra)
        return job

    async def scenario():
        swarm = LocalSwarm(
            n_workers=1,
            settings=Settings(
                sdaas_token="local-swarm", worker_name="swarm-worker",
                hive_port=0, metrics_port=0,
                shard_interactive=True, shard_tensor=2))
        async with swarm:
            batch_ids = [await swarm.submit(sd_job(f"bulk-{i}"))
                         for i in range(2)]
            vip_id = await swarm.submit(
                sd_job("vip", num_inference_steps=4,
                       priority="interactive"))
            vip = await swarm.wait_done(vip_id)
            done = [await swarm.wait_done(j) for j in batch_ids]
        vip_cfg = vip["result"]["pipeline_config"]
        assert vip_cfg["geometry"]["tensor"] == 2, vip_cfg
        for status in done:
            cfg = status["result"]["pipeline_config"]
            assert cfg["geometry"]["tensor"] == 1, cfg
            assert cfg["geometry"]["data"] == 8, cfg
        return True

    assert asyncio.run(scenario())


# --- hive-side dispatch preference -----------------------------------------


def _observe(directory, name, **extra):
    query = {"worker_name": name, "worker_version": "0.1.0", "chips": "8",
             "slices": "1", "busy_slices": "0", "queue_depth": "0",
             "resident_models": ""}
    query.update({k: str(v) for k, v in extra.items()})
    return directory.observe(query)


def test_dispatch_prefers_shard_capable_for_interactive_seeds():
    from chiaswarm_tpu.hive_server.dispatch import (
        Dispatcher,
        WorkerDirectory,
    )
    from chiaswarm_tpu.hive_server.queue import PriorityJobQueue

    directory = WorkerDirectory(ttl_s=45.0)
    dispatcher = Dispatcher(directory, affinity_hold_s=60.0,
                            max_jobs_per_poll=4)
    q = PriorityJobQueue()
    q.submit({"id": "vip", "workflow": "txt2img",
              "model_name": "m", "priority": "interactive"})
    plain = _observe(directory, "plain", chips_per_slice=8, shard_capable=0)
    capable = _observe(directory, "capable", chips_per_slice=8,
                       shard_capable=1)
    # the non-capable poller is held while a shard-capable worker is live
    assert dispatcher.select(plain, q) == []
    handed = dispatcher.select(capable, q)
    assert [r.job_id for r, _, _ in handed] == ["vip"]
    assert capable.shard_capable and capable.chips_per_slice == 8


def test_shard_hold_never_starves():
    """Outside the hold window — or with no shard-capable worker live —
    any poller takes the interactive seed (preference, not a gate)."""
    from chiaswarm_tpu.hive_server.dispatch import (
        Dispatcher,
        WorkerDirectory,
    )
    from chiaswarm_tpu.hive_server.queue import PriorityJobQueue

    directory = WorkerDirectory(ttl_s=45.0)
    dispatcher = Dispatcher(directory, affinity_hold_s=0.0,
                            max_jobs_per_poll=4)
    q = PriorityJobQueue()
    q.submit({"id": "vip", "workflow": "txt2img",
              "model_name": "m", "priority": "interactive"})
    plain = _observe(directory, "plain", shard_capable=0)
    _observe(directory, "capable", shard_capable=1)
    # hold window 0: the window has lapsed by the time the poll lands
    assert [r.job_id for r, _, _ in dispatcher.select(plain, q)] == ["vip"]

    q2 = PriorityJobQueue()
    q2.submit({"id": "vip2", "workflow": "txt2img",
               "model_name": "m", "priority": "interactive"})
    lonely_dir = WorkerDirectory(ttl_s=45.0)
    lonely = _observe(lonely_dir, "plain", shard_capable=0)
    d2 = Dispatcher(lonely_dir, affinity_hold_s=60.0, max_jobs_per_poll=4)
    assert [r.job_id for r, _, _ in d2.select(lonely, q2)] == ["vip2"]


def test_shard_hold_excludes_straggler_targets():
    """A straggler-flagged shard-capable worker is NOT a shard_hold
    target: straggler_hold already withholds the seed from it, so
    counting it would make the two rules defer to each other and park
    the seed for the whole hold window while both workers poll."""
    from chiaswarm_tpu.hive_server.dispatch import (
        Dispatcher,
        WorkerDirectory,
    )
    from chiaswarm_tpu.hive_server.queue import PriorityJobQueue

    class FlagCapable:
        def note(self, *a):
            pass

        def forget(self, *a):
            pass

        def refresh_metrics(self, *a):
            pass

        def is_outlier(self, name, live):
            return name == "capable"

    directory = WorkerDirectory(ttl_s=45.0, fleet=FlagCapable())
    dispatcher = Dispatcher(directory, affinity_hold_s=60.0,
                            max_jobs_per_poll=4)
    q = PriorityJobQueue()
    q.submit({"id": "vip", "workflow": "txt2img",
              "model_name": "m", "priority": "interactive"})
    plain = _observe(directory, "plain", shard_capable=0)
    _observe(directory, "capable", shard_capable=1)
    # the only shard-capable worker is flagged: the healthy plain poller
    # takes the seed instead of waiting out the window
    assert [r.job_id for r, _, _ in dispatcher.select(plain, q)] == ["vip"]


def test_batch_jobs_ignore_shard_preference():
    from chiaswarm_tpu.hive_server.dispatch import (
        Dispatcher,
        WorkerDirectory,
    )
    from chiaswarm_tpu.hive_server.queue import PriorityJobQueue

    directory = WorkerDirectory(ttl_s=45.0)
    dispatcher = Dispatcher(directory, affinity_hold_s=60.0,
                            max_jobs_per_poll=4)
    q = PriorityJobQueue()
    q.submit({"id": "bulk", "workflow": "txt2img", "model_name": "m",
              "priority": "batch"})
    plain = _observe(directory, "plain", shard_capable=0)
    _observe(directory, "capable", shard_capable=1)
    assert [r.job_id for r, _, _ in dispatcher.select(plain, q)] == ["bulk"]
