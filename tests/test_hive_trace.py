"""End-to-end job tracing (ISSUE 8): the per-job timeline at
GET /api/jobs/{id}/trace and its durability.

The tentpole claims are pinned here at the wire level:

- a settled job answers with ONE ordered, gap-attributed timeline —
  hive lifecycle events (admit/dispatch/lease/settle) merged with the
  worker's stage spans from the result envelope;
- the timeline is WAL-durable: a job redelivered across a hive
  kill/restart (and one across standby promotion) still yields a single
  complete timeline with no duplicated or reordered events;
- shed submissions are visible (the refusal IS trace data) and fold
  into the record's timeline if the id is later admitted;
- the labeled hive latency histograms (queue wait / dispatch-to-settle,
  per class) fill from the same instants the timeline records.
"""

import asyncio
import json

import aiohttp
import pytest

from chiaswarm_tpu import telemetry
from chiaswarm_tpu.hive_server.trace import trace_missing
from chiaswarm_tpu.settings import Settings

TOKEN = "trace-test-token"


def _hive_settings(**overrides) -> Settings:
    fields = dict(sdaas_token=TOKEN, hive_port=0, metrics_port=0)
    fields.update(overrides)
    return Settings(**fields)


def _headers() -> dict:
    return {"Authorization": f"Bearer {TOKEN}",
            "Content-type": "application/json"}


async def _poll(session, api_uri, name, **extra):
    params = {"worker_version": "0.1.0", "worker_name": name,
              "chips": "4", "slices": "4", "busy_slices": "0",
              "queue_depth": "0", "resident_models": ""}
    params.update({k: str(v) for k, v in extra.items()})
    async with session.get(f"{api_uri}/work", params=params,
                           headers=_headers()) as r:
        return r.status, (await r.json() if r.status == 200 else None)


async def _post(session, url, payload):
    async with session.post(url, data=json.dumps(payload),
                            headers=_headers()) as r:
        try:
            return r.status, await r.json()
        except (aiohttp.ContentTypeError, json.JSONDecodeError):
            return r.status, None


async def _get_trace(session, api_uri, job_id):
    async with session.get(f"{api_uri}/jobs/{job_id}/trace",
                           headers=_headers()) as r:
        return r.status, await r.json()


def _echo(job_id: str, **extra) -> dict:
    return {"id": job_id, "workflow": "echo", "model_name": "none",
            "prompt": job_id, **extra}


def _envelope(job, timings=None) -> dict:
    """A worker-shaped result envelope: stage timings + the wire trace
    context echoed as it came, what Worker._finish_result produces (the
    worker's receipt instant is the end of its `poll` span, not a key of
    the context)."""
    return {
        "id": job["id"], "artifacts": {}, "nsfw": False,
        "worker_name": "trace-w",
        "pipeline_config": {
            "trace": dict(job.get("trace") or {}),
            "timings": timings or {"queue_wait_s": 0.01,
                                   "denoise_s": 0.2, "decode_s": 0.05},
        },
    }


def _events(trace: dict) -> list[str]:
    return [e["event"] for e in trace["events"]]


# --- the timeline, live ------------------------------------------------------


def test_settled_job_answers_complete_ordered_timeline(sdaas_root):
    from chiaswarm_tpu.hive_server import HiveServer

    async def scenario():
        async with HiveServer(_hive_settings(), port=0) as hive, \
                aiohttp.ClientSession() as session:
            status, _ = await _post(session, f"{hive.api_uri}/jobs",
                                    _echo("t1"))
            assert status == 200
            _, payload = await _poll(session, hive.api_uri, "w1")
            [job] = payload["jobs"]
            # the wire trace context rides the /work reply
            assert job["trace"]["id"] == "t1"
            assert job["trace"]["attempt"] == 1
            assert isinstance(job["trace"]["dispatched_wall"], float)
            status, _ = await _post(session, f"{hive.api_uri}/results",
                                    _envelope(job))
            assert status == 200

            status, trace = await _get_trace(session, hive.api_uri, "t1")
            assert status == 200
            assert _events(trace) == ["admit", "dispatch", "lease", "settle"]
            # monotonically ordered, t_s anchored at admit
            walls = [e["wall"] for e in trace["events"]]
            assert walls == sorted(walls)
            assert trace["events"][0]["t_s"] == 0.0
            # dispatch carries placement outcome + worker identity
            dispatch = trace["events"][1]
            assert dispatch["worker"] == "w1"
            assert dispatch["outcome"] in ("cold", "affinity", "steal")
            # settle names the sender and the echoed attempt
            settle = trace["events"][-1]
            assert settle["worker"] == "trace-w"
            assert settle["attempt"] == 1
            # the handler's entry, before the body was read: what follows
            # it up to the settle stamp is the hive's own work
            lease = trace["events"][2]
            assert lease["wall"] <= settle["received_wall"] <= settle["wall"]
            assert trace["worker"]["trace"] == job["trace"]
            # every inter-event gap is attributed; the executing gap
            # carries the worker's stage breakdown + honest remainder
            assert [g["attribution"] for g in trace["gaps"]] == \
                ["hive_queue", "hive_grant", "executing"]
            executing = trace["gaps"][-1]
            assert {s["stage"] for s in executing["worker_stages"]} == \
                {"queue_wait", "denoise", "decode"}
            assert executing["worker_total_s"] == pytest.approx(0.26)
            assert executing["unattributed_s"] >= 0.0
            assert trace["worker"]["trace"]["attempt"] == 1
            assert not trace["open"]
            assert trace_missing(trace) == []

            # 404 for an id the hive never saw
            status, _ = await _get_trace(session, hive.api_uri, "nope")
            assert status == 404

            # the labeled latency histograms filled from the same instants
            qw = telemetry.REGISTRY.get("swarm_hive_queue_wait_seconds")
            assert qw.count(**{"class": "default"}) >= 1
            d2s = telemetry.REGISTRY.get(
                "swarm_hive_dispatch_to_settle_seconds")
            assert d2s.count(**{"class": "default"}) >= 1

    asyncio.run(scenario())


def _settled_record(pipeline_config: dict, lease: float, settle: float):
    """A settled JobRecord as build_trace reads one."""
    from types import SimpleNamespace

    return SimpleNamespace(
        job_id="spans-1", job_class="default", state="done", attempts=1,
        placement="cold", queue_wait_s=0.5, job={},
        result={"id": "spans-1", "pipeline_config": pipeline_config},
        timeline=[{"event": "admit", "wall": lease - 0.5},
                  {"event": "dispatch", "wall": lease, "outcome": "cold"},
                  {"event": "lease", "wall": lease},
                  {"event": "settle", "wall": settle}])


def _span(name, start, seconds, thread="slice"):
    return {"name": name, "thread": thread, "start_wall": start,
            "seconds": seconds}


def test_spans_carve_the_executing_gap_and_leave_the_hole_visible():
    """The worker's wall-stamped spans are the stages: `pass` (and so
    `job_s`) is their parent and never one of them, children of a stage
    are its detail, and what no span covers is reported."""
    from chiaswarm_tpu.hive_server.trace import build_trace

    t = 1_000_000.0
    config = {
        # the durations an old hive summed: job_s alone fills the gap
        "timings": {"queue_wait_s": 0.1, "load_s": 0.0, "text_encode_s": 0.2,
                    "trace_s": 0.0, "denoise_decode_s": 2.0, "decode_s": 0.5,
                    "job_s": 3.0},
        "spans": [
            _span("text_encode", t + 0.2, 0.2),
            _span("denoise", t + 0.4, 2.0),
            _span("safety", t + 2.7, 0.1),
            _span("artifact_encode", t + 2.8, 0.4),
            # a hole of 0.3 s between denoise and decode
            _span("decode", t + 2.7, 0.5),
            _span("pass", t + 0.2, 3.0),
            _span("queue_wait", t + 0.1, 0.1, thread="wait"),
            # another thread's span overlapping the pass is its own stage
            _span("ship", t + 1.0, 0.5, thread="shipper_0"),
            {"name": "torn"},
        ],
    }
    trace = build_trace(_settled_record(config, t, t + 3.5), t + 4.0)
    executing = trace["gaps"][-1]
    assert executing["attribution"] == "executing"
    assert executing["seconds"] == 3.5
    stages = executing["worker_stages"]
    assert [s["stage"] for s in stages] == [
        "queue_wait", "text_encode", "denoise", "ship", "decode"]
    assert [s["start_wall"] for s in stages] == sorted(
        s["start_wall"] for s in stages)
    # the union, not the sum: `ship` ran while `denoise` did
    assert executing["worker_total_s"] == pytest.approx(2.8)
    assert executing["worker_total_s"] <= executing["seconds"]
    # 0.1 s lease -> poll reply, the 0.3 s hole, 0.3 s spool and upload
    assert executing["unattributed_s"] == pytest.approx(0.7)
    assert trace["worker"]["total_s"] == executing["worker_total_s"]
    assert trace_missing(trace) == []


def test_spans_are_clipped_to_the_gap_and_the_rest_is_split_at_receipt():
    """ISSUE 38: a worker's `tick_wait` and `poll` begin before the
    dispatch they brought. Unclipped they would push `worker_total_s`
    past the executing gap and `unattributed_s` to a clamped 0; clipped,
    what the spans leave is real, and the `settle` event's
    `received_wall` parts it into the wire's and the hive's."""
    from chiaswarm_tpu.hive_server.trace import build_trace

    t = 1_000_000.0
    config = {"spans": [
        _span("tick_wait", t - 0.09, 0.05, thread="wait"),  # all before
        _span("poll", t - 0.04, 0.06, thread="poll"),  # dispatch inside
        _span("queue_wait", t + 0.02, 0.08, thread="wait"),
        _span("linger", t + 0.02, 0.05, thread="wait"),  # its detail
        _span("claim", t + 0.07, 0.03, thread="wait"),
        _span("format_args", t + 0.1, 0.1, thread="wait"),
        _span("denoise", t + 0.2, 2.0),
        _span("pass", t + 0.2, 2.0),
        _span("handoff", t + 2.2, 0.1, thread="deliver"),
        _span("artifact_encode", t + 2.3, 0.2, thread="host"),
    ]}
    record = _settled_record(config, t, t + 3.0)
    record.timeline[-1]["received_wall"] = t + 2.9
    trace = build_trace(record, t + 4.0)
    executing = trace["gaps"][-1]
    stages = executing["worker_stages"]
    assert [s["stage"] for s in stages] == [
        "poll", "queue_wait", "format_args", "denoise", "handoff",
        "artifact_encode"]
    # `poll` keeps what lies after the lease, 0.02 of its 0.06 s
    assert stages[0]["start_wall"] == t
    assert stages[0]["seconds"] == pytest.approx(0.02)
    assert executing["worker_total_s"] == pytest.approx(2.5)
    assert executing["unattributed_s"] == pytest.approx(0.5)
    assert executing["unattributed_wire_s"] == pytest.approx(0.4)
    assert executing["unattributed_hive_s"] == pytest.approx(0.1)
    # the operator's list is unclipped: every span on the one clock
    assert [s["stage"] for s in trace["worker"]["stages"]][:2] == [
        "tick_wait", "poll"]
    assert trace["worker"]["stages"][1]["seconds"] == 0.06
    assert trace["worker"]["total_s"] == pytest.approx(2.59)

    # a settle stamp without the receipt (an older hive's WAL): no split
    del record.timeline[-1]["received_wall"]
    executing = build_trace(record, t + 4.0)["gaps"][-1]
    assert executing["unattributed_s"] == pytest.approx(0.5)
    assert "unattributed_wire_s" not in executing


def test_an_old_envelope_without_spans_still_builds():
    from chiaswarm_tpu.hive_server.trace import build_trace

    t = 1_000_000.0
    config = {"timings": {"queue_wait_s": 0.1, "load_s": 0.05,
                          "text_encode_s": 0.2, "denoise_decode_s": 2.0,
                          "decode_s": 0.5, "job_s": 3.0, "rows": 4}}
    trace = build_trace(_settled_record(config, t, t + 3.5), t + 4.0)
    executing = trace["gaps"][-1]
    assert [s["stage"] for s in executing["worker_stages"]] == [
        "queue_wait", "load", "text_encode", "denoise_decode", "decode"]
    assert executing["worker_total_s"] == pytest.approx(2.85)
    assert executing["unattributed_s"] == pytest.approx(0.65)
    assert trace_missing(trace) == []


def test_shed_submission_is_traced_and_folds_into_admit(sdaas_root):
    from chiaswarm_tpu.hive_server import HiveServer

    async def scenario():
        # depth limit 1: the default-class watermark (0.85 -> ceil = 1)
        # sheds the second submission
        async with HiveServer(_hive_settings(hive_queue_depth_limit=1),
                              port=0) as hive, \
                aiohttp.ClientSession() as session:
            status, _ = await _post(session, f"{hive.api_uri}/jobs",
                                    _echo("keeper"))
            assert status == 200
            status, _ = await _post(session, f"{hive.api_uri}/jobs",
                                    _echo("shed-me"))
            assert status == 429
            status, _ = await _post(session, f"{hive.api_uri}/jobs",
                                    _echo("shed-me"))
            assert status == 429

            # the refusals are visible as trace data even though the job
            # was never admitted — with the backoff between them
            # attributed, not flattened to zero
            status, trace = await _get_trace(session, hive.api_uri,
                                             "shed-me")
            assert status == 200
            assert trace["status"] == "shed"
            assert [e["event"] for e in trace["events"]] == ["shed", "shed"]
            assert trace["events"][0]["class"] == "default"
            [gap] = trace["gaps"]
            assert gap["attribution"] == "resubmit_backoff"
            assert trace["total_s"] >= 0.0
            assert trace["events"][-1]["t_s"] == pytest.approx(
                trace["total_s"])

            # drain the queue, then the retry is admitted — and its
            # timeline leads with the shed attempt, gap attributed as
            # the submitter's backoff
            await _poll(session, hive.api_uri, "w1")
            status, _ = await _post(session, f"{hive.api_uri}/jobs",
                                    _echo("shed-me"))
            assert status == 200
            status, trace = await _get_trace(session, hive.api_uri,
                                             "shed-me")
            assert status == 200
            assert _events(trace) == ["shed", "shed", "admit"]
            assert [g["attribution"] for g in trace["gaps"]] == \
                ["resubmit_backoff", "resubmit_backoff"]

    asyncio.run(scenario())


# --- durability --------------------------------------------------------------


def test_timeline_survives_redelivery_across_hive_kill_restart(sdaas_root):
    """THE acceptance scenario: a job leased, the hive killed, a fresh
    instance replaying the WAL over the same root, the lease expiring,
    the job redelivered to a second worker and settled — one complete
    timeline, no duplicated or reordered events."""
    from chiaswarm_tpu.hive_server import HiveServer

    async def scenario():
        settings = _hive_settings(hive_lease_deadline_s=0.2)
        async with HiveServer(settings, port=0) as hive, \
                aiohttp.ClientSession() as session:
            await _post(session, f"{hive.api_uri}/jobs", _echo("durable"))
            _, payload = await _poll(session, hive.api_uri, "doomed-w")
            assert [j["id"] for j in payload["jobs"]] == ["durable"]
            # hive dies here (context exit = stop; state is the WAL's)

        async with HiveServer(settings, port=0) as revived, \
                aiohttp.ClientSession() as session:
            record = revived.queue.records["durable"]
            for _ in range(100):
                if record.state == "queued":
                    break
                await asyncio.sleep(0.05)
            assert record.state == "queued", "recovered lease never expired"
            _, payload = await _poll(session, revived.api_uri, "second-w")
            [job] = payload["jobs"]
            assert job["trace"]["attempt"] == 2
            status, _ = await _post(session, f"{revived.api_uri}/results",
                                    _envelope(job))
            assert status == 200

            status, trace = await _get_trace(session, revived.api_uri,
                                             "durable")
            assert status == 200
            events = _events(trace)
            # one admit, both dispatch attempts, the redelivery, one
            # settle — nothing duplicated, nothing lost to the restart
            assert events == ["admit", "dispatch", "lease", "redeliver",
                              "dispatch", "lease", "settle"]
            attempts = [e["attempt"] for e in trace["events"]
                        if e["event"] == "dispatch"]
            assert attempts == [1, 2]
            assert trace["events"][3]["worker"] == "doomed-w"
            walls = [e["wall"] for e in trace["events"]]
            assert walls == sorted(walls)
            # lease -> redeliver is the lost worker's deadline; the
            # requeued wait is hive_queue again
            assert [g["attribution"] for g in trace["gaps"]] == [
                "hive_queue", "hive_grant", "lease_lost", "hive_queue",
                "hive_grant", "executing"]
            assert trace_missing(trace) == []

    asyncio.run(scenario())


def test_timeline_survives_compaction_and_restart(sdaas_root):
    from chiaswarm_tpu.hive_server import HiveServer

    async def scenario():
        settings = _hive_settings()
        async with HiveServer(settings, port=0) as hive, \
                aiohttp.ClientSession() as session:
            await _post(session, f"{hive.api_uri}/jobs", _echo("compact"))
            _, payload = await _poll(session, hive.api_uri, "w1")
            [job] = payload["jobs"]
            await _post(session, f"{hive.api_uri}/results", _envelope(job))
            pre_status, pre = await _get_trace(session, hive.api_uri,
                                               "compact")
            assert pre_status == 200
            # compaction folds the stream to minimal events; the
            # timeline must ride the fold verbatim
            hive.journal.compact(hive.journal.snapshot_fn())

        async with HiveServer(settings, port=0) as revived, \
                aiohttp.ClientSession() as session:
            status, post = await _get_trace(session, revived.api_uri,
                                            "compact")
            assert status == 200
            assert post["events"] == pre["events"]
            assert trace_missing(post) == []

    asyncio.run(scenario())


def test_timeline_survives_standby_promotion(sdaas_root):
    """The replicated half of the acceptance bar: a timeline started on
    the primary completes on the promoted standby — the replication
    stream carries it event for event, and the promotion's lease
    re-grant is VISIBLE in the timeline rather than hidden."""
    import dataclasses

    from chiaswarm_tpu.hive_server import HiveServer
    from chiaswarm_tpu.hive_server.replication import StandbyHive

    async def scenario():
        base = _hive_settings(hive_wal_dir="wal_trace_primary")
        primary = await HiveServer(base, port=0).start()
        standby = StandbyHive(
            dataclasses.replace(base, hive_wal_dir="wal_trace_standby"),
            primary_uri=primary.uri, port=0)
        await standby.server.start()
        try:
            async with aiohttp.ClientSession() as session:
                await _post(session, f"{primary.api_uri}/jobs",
                            _echo("promoted"))
                _, payload = await _poll(session, primary.api_uri, "w1")
                [job] = payload["jobs"]
                await standby.sync_once()
                await primary.stop()
                server = await standby.promote()

                status, _ = await _post(
                    session, f"{server.api_uri}/results", _envelope(job))
                assert status == 200
                status, trace = await _get_trace(
                    session, server.api_uri, "promoted")
                assert status == 200
                # original admit/dispatch/lease replicated; promotion
                # re-granted the lease (fresh deadline) and the worker's
                # result settled on the new primary
                assert _events(trace) == \
                    ["admit", "dispatch", "lease", "lease", "settle"]
                assert trace["gaps"][2]["attribution"] == "lease_regrant"
                walls = [e["wall"] for e in trace["events"]]
                assert walls == sorted(walls)
                assert trace_missing(trace) == []
        finally:
            await standby.stop()

    asyncio.run(scenario())


# --- parked jobs -------------------------------------------------------------


def test_exhausted_redelivery_timeline_ends_in_park(sdaas_root):
    from chiaswarm_tpu.hive_server import HiveServer

    async def scenario():
        settings = _hive_settings(hive_lease_deadline_s=0.1,
                                  hive_max_redeliveries=0)
        async with HiveServer(settings, port=0) as hive, \
                aiohttp.ClientSession() as session:
            await _post(session, f"{hive.api_uri}/jobs", _echo("poison"))
            await _poll(session, hive.api_uri, "w1")
            record = hive.queue.records["poison"]
            for _ in range(100):
                if record.state == "failed":
                    break
                await asyncio.sleep(0.05)
            assert record.state == "failed"
            status, trace = await _get_trace(session, hive.api_uri,
                                             "poison")
            assert status == 200
            assert _events(trace) == ["admit", "dispatch", "lease", "park"]
            assert trace["gaps"][-1]["attribution"] == "lease_lost"
            assert not trace["open"]

    asyncio.run(scenario())


def test_affinity_hold_is_visible_and_deduped_in_timeline():
    """A job skipped for a cold poller while its warm worker's affinity
    window runs gets ONE `hold` event (not one per skipped poll), and
    the hold -> dispatch gap is attributed as affinity_hold."""
    from chiaswarm_tpu.hive_server.clock import CLOCK
    from chiaswarm_tpu.hive_server.dispatch import (
        Dispatcher,
        WorkerDirectory,
    )
    from chiaswarm_tpu.hive_server.queue import PriorityJobQueue
    from chiaswarm_tpu.hive_server.trace import build_trace

    directory = WorkerDirectory(ttl_s=60.0)
    directory.observe({"worker_name": "warm-w", "worker_version": "1",
                       "resident_models": "m/a", "slices": "1",
                       "busy_slices": "1"})
    queue = PriorityJobQueue()
    record = queue.submit(
        {"id": "held-1", "workflow": "txt2img", "model_name": "m/a"})
    dispatcher = Dispatcher(directory, affinity_hold_s=60.0,
                            max_jobs_per_poll=4)

    cold = directory.observe({"worker_name": "cold-w", "worker_version": "1",
                              "slices": "1", "busy_slices": "0"})
    assert dispatcher.select(cold, queue) == []
    assert [e["event"] for e in record.timeline] == ["admit", "hold"]
    assert record.timeline[1]["warm_on"] == ["warm-w"]
    assert dispatcher.select(cold, queue) == []  # second skipped poll
    assert [e["event"] for e in record.timeline] == ["admit", "hold"]

    warm = directory.observe({"worker_name": "warm-w", "worker_version": "1",
                              "resident_models": "m/a", "slices": "1",
                              "busy_slices": "0"})
    [(handed, outcome, _)] = dispatcher.select(warm, queue)
    assert handed is record and outcome == "affinity"
    queue.take(record, "warm-w", outcome)
    trace = build_trace(record, CLOCK.wall())
    assert [g["attribution"] for g in trace["gaps"]] == \
        ["hive_queue", "affinity_hold"]


def test_shed_trace_is_bounded_per_id():
    """A client hammering ONE id against a saturated hive must not grow
    an unbounded shed history (it would ride every later WAL event):
    the first shed (backoff start) and the most recent ones are kept."""
    from chiaswarm_tpu.hive_server.queue import (
        _SHED_EVENTS_PER_ID,
        PriorityJobQueue,
        QueueFull,
    )

    queue = PriorityJobQueue(depth_limit=1)
    queue.submit({"id": "filler"})
    for _ in range(3 * _SHED_EVENTS_PER_ID):
        with pytest.raises(QueueFull):
            queue.submit({"id": "storm"})
    events = queue.shed_traces["storm"]
    assert len(events) == _SHED_EVENTS_PER_ID
    walls = [e["wall"] for e in events]
    assert walls == sorted(walls)  # first kept, middle dropped, tail kept

    # an id-LESS shed submission gets a generated uuid that can never
    # recur: remembering it would only evict correlatable entries
    with pytest.raises(QueueFull):
        queue.submit({"workflow": "echo"})
    assert set(queue.shed_traces) == {"storm"}
