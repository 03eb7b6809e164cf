"""A job's life between passes, in the program's own spans (ISSUE 38).

A real hive and a pristine worker (`LocalSwarm`) run the CPU's tiny model;
what is asserted is the chain every settled envelope holds, with the hive's
timeline on the same wall clock: `tick_wait`, `poll`, `queue_wait` (tiled
by `linger`, `claim`, `package_wait`), `format_args`, `pass`, `handoff`,
`artifact_encode`, then the `settle` event's `received_wall` and stamp,
with nothing of the job's admit -> settle left unnamed."""

import asyncio
import threading
import time

import pytest

from chiaswarm_tpu import telemetry
from chiaswarm_tpu import worker as worker_mod
from chiaswarm_tpu.batching import SPANS
from chiaswarm_tpu.chips.allocator import SliceAllocator
from chiaswarm_tpu.settings import Settings
from chiaswarm_tpu.worker import Worker

from .fake_hive import FakeHive

POLL_S = 0.05
# a span's end is a wall stamp plus a duration taken on another clock
SLACK_S = 0.0001
CHAIN = ("tick_wait", "poll", "queue_wait", "format_args", "pass",
         "handoff", "artifact_encode")


@pytest.fixture(autouse=True)
def fast_poll(monkeypatch):
    monkeypatch.setattr(worker_mod, "POLL_SECONDS", POLL_S)


def tiny_job(job_id: str) -> dict:
    return {"id": job_id, "workflow": "txt2img",
            "model_name": "stabilityai/stable-diffusion-2-1",
            "prompt": f"a life in spans {job_id}", "seed": 38,
            "height": 64, "width": 64, "num_inference_steps": 2,
            "content_type": "image/png",
            "parameters": {"test_tiny_model": True}}


def end(span: dict) -> float:
    return span["start_wall"] + span["seconds"]


def named(envelope: dict, name: str) -> dict:
    [span] = [s for s in envelope["pipeline_config"]["spans"]
              if s["name"] == name]
    return span


def stamp(trace: dict, event: str) -> dict:
    return [e for e in trace["events"] if e["event"] == event][-1]


def union_seconds(intervals) -> float:
    total, reach = 0.0, float("-inf")
    for lo, hi in sorted(intervals):
        total += max(hi - max(lo, reach), 0.0)
        reach = max(reach, hi)
    return total


def assert_chain(envelope: dict, trace: dict) -> None:
    """One job's chain: every link there, in order, each starting no
    earlier than the one before it ends (less the slack), `queue_wait`
    tiled to a millisecond, and admit -> settle covered to a poll period."""
    links = [named(envelope, name) for name in CHAIN]
    for before, after in zip(links, links[1:]):
        assert after["start_wall"] >= end(before) - SLACK_S, (
            before["name"], after["name"])
    lanes = {span["name"]: span["thread"] for span in links}
    assert lanes == {
        "tick_wait": "wait", "poll": "poll", "queue_wait": "wait",
        "format_args": "wait", "pass": "slice", "handoff": "deliver",
        "artifact_encode": "host"}

    waited = named(envelope, "queue_wait")
    parts = [named(envelope, name)
             for name in ("linger", "claim", "package_wait")]
    assert all(span["thread"] == "wait" for span in parts)
    assert parts[0]["start_wall"] == waited["start_wall"]
    for before, after in zip(parts, parts[1:]):
        assert after["start_wall"] == pytest.approx(end(before), abs=1e-6)
    assert sum(span["seconds"] for span in parts) == pytest.approx(
        waited["seconds"], abs=0.001)
    assert end(parts[-1]) == pytest.approx(end(waited), abs=0.001)
    # the poll's end is where the wait begins, the pass's where the
    # hand-off does
    assert waited["start_wall"] == pytest.approx(
        end(named(envelope, "poll")), abs=1e-6)
    assert named(envelope, "handoff")["start_wall"] == pytest.approx(
        end(named(envelope, "pass")), abs=1e-6)
    assert envelope["pipeline_config"]["timings"]["queue_wait_s"] == round(
        waited["seconds"], 3)

    admit, settle = stamp(trace, "admit"), stamp(trace, "settle")
    dispatch = stamp(trace, "dispatch")
    poll = named(envelope, "poll")
    assert poll["start_wall"] <= dispatch["wall"] <= end(poll)
    assert end(links[-1]) <= settle["received_wall"] <= settle["wall"]
    # the named stretches: the hive's queue up to the poll that took the
    # job, the worker's chain, the delivery up to the hive's handler, and
    # the hive's own settle
    named_stretches = [(admit["wall"], max(poll["start_wall"], admit["wall"]))]
    named_stretches += [(s["start_wall"], end(s)) for s in links[1:]]
    named_stretches += [(end(links[-1]), settle["received_wall"]),
                        (settle["received_wall"], settle["wall"])]
    clipped = [(max(lo, admit["wall"]), min(hi, settle["wall"]))
               for lo, hi in named_stretches]
    unnamed = settle["wall"] - admit["wall"] - union_seconds(clipped)
    assert 0 <= unnamed < POLL_S, unnamed


def run_swarm(jobs: list[dict], sdaas_root):
    from chiaswarm_tpu.hive_server.harness import LocalSwarm

    async def scenario():
        settings = Settings(sdaas_token="lifecycle", worker_name="w",
                            hive_port=0, metrics_port=0)
        swarm = LocalSwarm(n_workers=0, chips_per_job=0, settings=settings)
        async with swarm:
            for job in jobs:  # queued before the worker exists: one gang
                await swarm.submit(job)
            swarm.add_worker("lifecycle-worker")
            out = []
            for job in jobs:
                status = await swarm.wait_done(job["id"], timeout=240.0)
                async with swarm._session.get(
                        f"{swarm.hive.api_uri}/jobs/{job['id']}/trace",
                        headers=swarm._headers()) as reply:
                    out.append((status["result"], await reply.json()))
            return out

    return asyncio.run(scenario())


def test_one_jobs_life_is_named_from_admit_to_settle(sdaas_root):
    [(envelope, trace)] = run_swarm([tiny_job("life-1")], sdaas_root)
    assert "error" not in envelope["pipeline_config"]
    assert_chain(envelope, trace)
    # the operator's view of the same spans: clipped to the gap they
    # carve, so nothing is pushed past it, and what they leave is split
    # at the handler's stamp
    executing = [g for g in trace["gaps"]
                 if g["attribution"] == "executing"][-1]
    assert executing["worker_total_s"] <= executing["seconds"]
    stages = {s["stage"] for s in executing["worker_stages"]}
    assert {"poll", "queue_wait", "format_args", "handoff",
            "artifact_encode"} <= stages
    assert not stages & {"tick_wait", "linger", "claim", "package_wait",
                         "pass"}
    assert executing["unattributed_s"] == pytest.approx(
        executing["unattributed_wire_s"] + executing["unattributed_hive_s"],
        abs=0.0015)
    assert {s["stage"] for s in trace["worker"]["stages"]} >= {"tick_wait"}


@pytest.mark.parametrize("period", ["a-tenth", "default"])
def test_a_lone_job_at_a_quiet_hive_does_not_linger(
        sdaas_root, monkeypatch, period):
    """ISSUE 58: no poll is due before the 50 ms linger's end, at a 0.1 s
    period as at the shipped one, so the one job a reply brought is on the
    board once the reply is admitted: its `linger` is the admission, the
    release counts under `no_poll_due`, and `queue_wait` is still tiled."""
    from chiaswarm_tpu.batching import _RELEASES

    monkeypatch.delenv("CHIASWARM_POLL_SECONDS", raising=False)
    monkeypatch.setattr(
        worker_mod, "POLL_SECONDS",
        0.1 if period == "a-tenth" else worker_mod._env_poll_seconds())
    counted = {cause: _RELEASES.value(cause=cause)
               for cause in ("full", "timer", "no_poll_due")}
    [(envelope, trace)] = run_swarm([tiny_job(f"lone-{period}")], sdaas_root)
    assert "error" not in envelope["pipeline_config"]
    assert named(envelope, "linger")["seconds"] < 0.005
    assert_chain(envelope, trace)
    counted = {cause: _RELEASES.value(cause=cause) - before
               for cause, before in counted.items()}
    assert counted == {"full": 0, "timer": 0, "no_poll_due": 1}


def test_a_gangs_members_share_the_wait_and_have_their_own_handoff(
        sdaas_root):
    jobs = [tiny_job(f"gang-life-{i}") for i in range(3)]
    settled = run_swarm(jobs, sdaas_root)
    envelopes = [envelope for envelope, _ in settled]
    assert all(e["pipeline_config"]["batched_with"] == 3 for e in envelopes)
    for envelope, trace in settled:
        assert_chain(envelope, trace)
    for name in ("tick_wait", "poll", "linger", "claim", "package_wait",
                 "queue_wait", "format_args", "pass"):
        found = {(s["start_wall"], s["seconds"])
                 for s in (named(e, name) for e in envelopes)}
        assert len(found) == 1, name
    # a job's images wait for its batchmates' encodes: its own hand-off
    handoffs = [named(e, "handoff") for e in envelopes]
    encodes = [named(e, "artifact_encode") for e in envelopes]
    assert len({h["start_wall"] for h in handoffs}) == 1
    for earlier, later, encode in zip(handoffs, handoffs[1:], encodes):
        assert later["seconds"] >= earlier["seconds"] + encode["seconds"]


def test_tick_wait_is_the_sleep_a_free_slice_spent_not_the_busy_time(
        sdaas_root):
    """`tick_wait` starts at the later of the last poll's end and the
    instant the worker became able to take work: a poll right after a
    release waited for nothing, one after a sleep with a free slice
    waited the sleep; every poll lands in the stage histogram."""
    stages = telemetry.REGISTRY.histogram(
        telemetry.STAGE_METRIC, labelnames=("stage",))

    async def scenario():
        hive = await FakeHive().start()
        w = Worker(settings=Settings(sdaas_token="t", worker_name="w",
                                     metrics_port=0),
                   allocator=SliceAllocator(chips_per_job=8),
                   hive_uri=hive.uri)

        async def polled(job_id: str) -> dict:
            hive.add_job({"id": job_id, "workflow": "echo",
                          "model_name": "none", "prompt": job_id})
            await w._poll_once(POLL_S)
            [job] = await asyncio.wait_for(w.batcher.get(), 1.0)
            w.batcher.task_done(job)
            return {s["name"]: s for s in job[SPANS]}

        try:
            polls = stages.count(stage="poll")
            ticks = stages.count(stage="tick_wait")
            await w._poll_once(POLL_S)  # brings nothing
            assert stages.count(stage="poll") == polls + 1
            assert stages.count(stage="tick_wait") == ticks
            await asyncio.sleep(0.2)
            slept = await polled("slept")  # free through the sleep
            assert stages.count(stage="tick_wait") == ticks + 1

            chipset = w.allocator.try_acquire()
            w._note_capacity()
            assert w._able_since is None
            await asyncio.sleep(0.2)  # busy: nothing to ask for
            w.allocator.release(chipset)
            assert w._able_since is not None
            at_once = await polled("at-once")
            return slept, at_once
        finally:
            await w.hive.close()
            w._executor.shutdown(wait=False)
            await hive.stop()

    slept, at_once = asyncio.run(scenario())
    assert 0.2 <= slept["tick_wait"]["seconds"] < 0.5
    assert end(slept["tick_wait"]) == pytest.approx(
        slept["poll"]["start_wall"], abs=1e-6)
    assert at_once["tick_wait"]["seconds"] < 0.1  # not the 0.2 s it was busy
    assert list(at_once) == ["poll", "tick_wait", "linger", "claim"]


def test_a_gang_standing_at_the_hive_is_fetched_the_instant_a_slice_is_free(
        sdaas_root, monkeypatch):
    """ISSUE 39: with the cadence at half a minute, the job that stood at
    the hive while the slice was held is asked for as the slice is let
    go: its `tick_wait` is the poll loop's wake-up, begun where the pass
    before it ended, and the poll is counted under `capacity`."""
    monkeypatch.setattr(worker_mod, "POLL_SECONDS", 30.0)
    gate = threading.Event()

    async def scenario():
        hive = await FakeHive().start()
        hive.add_job({"id": "holds-the-slice", "workflow": "echo",
                      "model_name": "none", "prompt": "first"})
        w = Worker(settings=Settings(sdaas_token="t", worker_name="w",
                                     metrics_port=0),
                   allocator=SliceAllocator(chips_per_job=8),
                   hive_uri=hive.uri)
        work = w.synchronous_do_work

        def gated(chipset, function, kwargs):
            if kwargs.get("id") == "holds-the-slice":
                gate.wait(30.0)
            return work(chipset, function, kwargs)

        w.synchronous_do_work = gated
        by_capacity = worker_mod._POLLS.value(cause="capacity")
        runner = asyncio.create_task(w.run())
        try:
            while not w._executing_ids:  # the start-up poll's job, held
                await asyncio.sleep(0.01)
            hive.add_job({"id": "stood-at-the-hive", "workflow": "echo",
                          "model_name": "none", "prompt": "second"})
            await asyncio.sleep(0.3)
            assert len(hive.pending_jobs) == 1
            opened = time.monotonic()
            gate.set()
            results = await hive.wait_for_results(2, timeout=20.0)
            return (results, time.monotonic() - opened,
                    worker_mod._POLLS.value(cause="capacity") - by_capacity)
        finally:
            gate.set()
            w.stop()
            await asyncio.wait_for(runner, 10)
            await hive.stop()

    results, took, by_capacity = asyncio.run(scenario())
    first, second = sorted(results, key=lambda r: r["id"] != "holds-the-slice")
    assert second["id"] == "stood-at-the-hive"
    # a poll a pass: the second's brought nothing and no third followed
    assert took < 5.0 and by_capacity == 2
    tick_wait, poll = named(second, "tick_wait"), named(second, "poll")
    assert tick_wait["seconds"] < 0.25
    assert end(tick_wait) == pytest.approx(poll["start_wall"], abs=1e-6)
    # the slice came free where the first job's pass ended (the loop
    # learning of it in between)
    assert 0 <= tick_wait["start_wall"] - end(named(first, "pass")) < 0.25
