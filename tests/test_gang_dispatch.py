"""Hive-side gang scheduling (ISSUE 9): the coalesce-key secondary
index, the dispatcher's gang formation rules, the wire/WAL plumbing
through the real HiveServer, and the worker-side put_gang intake.

Quick tier: everything here is jax-free (queue/dispatch units and the
HiveServer driven without sockets) or pure-asyncio (BatchScheduler).
"""

import asyncio

import pytest

from chiaswarm_tpu.batching import BatchScheduler
from chiaswarm_tpu.coalesce import coalesce_key
from chiaswarm_tpu.hive_server.dispatch import Dispatcher, WorkerDirectory
from chiaswarm_tpu.hive_server.queue import PriorityJobQueue
from chiaswarm_tpu.settings import Settings


def gang_job(i: int, prompt: str | None = None, **extra) -> dict:
    job = {"id": f"g{i}", "workflow": "txt2img",
           "model_name": "stabilityai/stable-diffusion-2-1",
           "prompt": prompt or f"member {i}", "height": 64, "width": 64,
           "num_inference_steps": 2,
           "parameters": {"test_tiny_model": True}}
    job.update(extra)
    return job


def observe(directory, name, **extra):
    query = {"worker_name": name, "worker_version": "0.1.0", "chips": "4",
             "slices": "1", "busy_slices": "0", "queue_depth": "0",
             "gang_rows": "8", "resident_models": ""}
    query.update({k: str(v) for k, v in extra.items()})
    return directory.observe(query)


# --- queue secondary index --------------------------------------------------


def test_queued_peers_fifo_same_key_only():
    q = PriorityJobQueue()
    records = [q.submit(gang_job(i)) for i in range(4)]
    q.submit({"id": "echo", "workflow": "echo", "model_name": "none"})
    other_canvas = q.submit(gang_job(9, height=128, width=128))
    peers = list(q.queued_peers(records[0]))
    assert [p.job_id for p in peers] == ["g1", "g2", "g3"]
    assert other_canvas.job_id not in [p.job_id for p in peers]


def test_queued_peers_excludes_taken_and_is_tombstone_aware():
    q = PriorityJobQueue()
    records = [q.submit(gang_job(i)) for i in range(4)]
    q.take(records[1], "w", "cold")  # leased: tombstoned in the index
    q.discard_queued(records[2])
    records[2].state = "failed"
    assert [p.job_id for p in q.queued_peers(records[0])] == ["g3"]


def test_queued_peers_requeue_front_reappears_first():
    q = PriorityJobQueue()
    records = [q.submit(gang_job(i)) for i in range(3)]
    q.take(records[2], "w", "cold")
    q.requeue_front(records[2])  # lease expired -> front of class
    # g2 now leads the class FIFO, so it leads the peers of g0 too...
    assert [p.job_id for p in q.queued_peers(records[0])] == ["g2", "g1"]
    # ...and the class-queue iteration agrees (no divergent orders)
    assert [r.job_id for r in q.iter_queued()] == ["g2", "g0", "g1"]


def test_queued_peers_never_cross_priority_classes():
    q = PriorityJobQueue()
    seed = q.submit(gang_job(0))
    q.submit(gang_job(1, priority="interactive"))
    q.submit(gang_job(2, priority="batch"))
    same = q.submit(gang_job(3))
    assert [p.job_id for p in q.queued_peers(seed)] == [same.job_id]


def test_index_rebuilds_from_wal_replay(sdaas_root):
    """The gang index is derived state: a replayed hive gangs exactly
    like the pre-crash one did (it is rebuilt inside _enqueue, which
    every restore path goes through)."""
    from chiaswarm_tpu.hive_server import HiveServer

    settings = Settings(sdaas_token="t", hive_port=0,
                        hive_max_jobs_per_poll=8)
    server = HiveServer(settings)
    revived = None
    try:
        for i in range(3):
            job = gang_job(i)
            record = server.queue.submit(job)
            from chiaswarm_tpu.hive_server.journal import ev_admit

            server._journal(ev_admit(record))
        server.journal.close()
        revived = HiveServer(settings)  # same $SDAAS_ROOT -> WAL replay
        seed = revived.queue.records["g0"]
        assert seed.coalesce == coalesce_key(gang_job(0))
        assert [p.job_id for p in revived.queue.queued_peers(seed)] \
            == ["g1", "g2"]
        # and the revived dispatcher hands them out as one gang
        worker = observe(revived.directory, "w-after")
        handed = revived.dispatcher.select(worker, revived.queue)
        assert [g["index"] for _, _, g in handed] == [0, 1, 2]
    finally:
        if server.journal:
            server.journal.close()
        if revived is not None and revived.journal:
            revived.journal.close()


# --- dispatcher gang formation ---------------------------------------------


def test_gang_respects_gang_max_and_stamps_context():
    directory = WorkerDirectory(ttl_s=45.0)
    dispatcher = Dispatcher(directory, affinity_hold_s=0.0,
                            max_jobs_per_poll=8, gang_max=3)
    q = PriorityJobQueue()
    for i in range(5):
        q.submit(gang_job(i))
    worker = observe(directory, "w1")
    handed = dispatcher.select(worker, q)
    assert [(r.job_id, o) for r, o, _ in handed] == \
        [("g0", "cold"), ("g1", "gang"), ("g2", "gang")]
    gangs = [g for _, _, g in handed]
    assert len({g["id"] for g in gangs}) == 1
    assert [g["index"] for g in gangs] == [0, 1, 2]
    assert all(g["size"] == 3 for g in gangs)


def test_gang_rows_cap_counts_multi_image_jobs():
    directory = WorkerDirectory(ttl_s=45.0)
    dispatcher = Dispatcher(directory, affinity_hold_s=0.0,
                            max_jobs_per_poll=8, gang_max=8)
    q = PriorityJobQueue()
    # 4-image jobs: an appetite of 8 rows fits exactly two of them
    for i in range(4):
        job = gang_job(i)
        job["parameters"]["num_images_per_prompt"] = 4
        q.submit(job)
    worker = observe(directory, "w1", gang_rows=8)
    handed = dispatcher.select(worker, q)
    assert [r.job_id for r, _, _ in handed] == ["g0", "g1"]
    assert handed[0][2]["size"] == 2


def test_gang_caps_distinct_adapters_at_lora_slots():
    """ISSUE 13: mixed-adapter jobs gang together, but at most
    `lora_slots` DISTINCT adapters leave in one gang (the worker's
    stacked-factor program has that many slots). Repeats of an adapter
    already aboard — and adapter-free batchmates — still ride."""
    directory = WorkerDirectory(ttl_s=45.0)
    dispatcher = Dispatcher(directory, affinity_hold_s=0.0,
                            max_jobs_per_poll=8, gang_max=8, lora_slots=2)
    q = PriorityJobQueue()
    adapters = ["style-a", "style-b", "style-a", "style-c", None]
    for i, adapter in enumerate(adapters):
        job = gang_job(i)
        if adapter is not None:
            job["lora"] = adapter
        q.submit(job)
    worker = observe(directory, "w1", gang_rows=8)
    handed = dispatcher.select(worker, q)
    gang_ids = [r.job_id for r, _, g in handed
                if g is not None and g["id"] == handed[0][2]["id"]]
    # g3 (third distinct adapter) stops the pull — stop-don't-skip keeps
    # the class FIFO, so the adapter-free g4 behind it waits too
    assert gang_ids == ["g0", "g1", "g2"]


def test_adapter_jobs_gang_with_plain_jobs():
    """Adapter identity is per-row data: a LoRA job and a plain job on
    one base model share a key and leave as one gang."""
    directory = WorkerDirectory(ttl_s=45.0)
    dispatcher = Dispatcher(directory, affinity_hold_s=0.0,
                            max_jobs_per_poll=8, gang_max=8)
    q = PriorityJobQueue()
    lora_job = gang_job(0)
    lora_job["lora"] = "style-a"
    q.submit(lora_job)
    q.submit(gang_job(1))
    worker = observe(directory, "w1", gang_rows=8)
    handed = dispatcher.select(worker, q)
    assert [r.job_id for r, _, _ in handed] == ["g0", "g1"]
    assert all(g is not None and g["size"] == 2 for _, _, g in handed)


def test_no_job_dispatched_twice_in_one_reply():
    """A gang member handed behind an earlier seed is still queue-live
    until app.py takes it AFTER select() returns — the peer pull must
    skip already-handed ids or one job leases twice in one poll."""
    directory = WorkerDirectory(ttl_s=45.0)
    dispatcher = Dispatcher(directory, affinity_hold_s=0.0,
                            max_jobs_per_poll=8, gang_max=2)
    q = PriorityJobQueue()
    for i in range(4):
        q.submit(gang_job(i))
    worker = observe(directory, "w1", slices=2, gang_rows=2)
    handed = dispatcher.select(worker, q)
    ids = [r.job_id for r, _, _ in handed]
    assert len(ids) == len(set(ids)), f"job dispatched twice: {ids}"
    assert ids == ["g0", "g1", "g2", "g3"]  # two gangs of two
    assert [g["size"] for _, _, g in handed] == [2, 2, 2, 2]


def test_legacy_budget_counts_jobs_not_rows():
    """A legacy poller (no gang_rows) budgets in JOBS — a multi-image
    job must not eat several of its per-poll slots."""
    directory = WorkerDirectory(ttl_s=45.0)
    dispatcher = Dispatcher(directory, affinity_hold_s=0.0,
                            max_jobs_per_poll=4, gang_max=8)
    q = PriorityJobQueue()
    for i in range(2):
        job = gang_job(i)
        job["parameters"]["num_images_per_prompt"] = 4
        q.submit(job)
    legacy_query = {"worker_name": "legacy", "worker_version": "0.1.0",
                    "slices": "2", "busy_slices": "0", "queue_depth": "0"}
    legacy = directory.observe(legacy_query)
    handed = dispatcher.select(legacy, q)
    assert [r.job_id for r, _, _ in handed] == ["g0", "g1"]
    assert all(g is None for _, _, g in handed)


def test_gang_disabled_by_gang_max_one():
    directory = WorkerDirectory(ttl_s=45.0)
    dispatcher = Dispatcher(directory, affinity_hold_s=0.0,
                            max_jobs_per_poll=4, gang_max=1)
    q = PriorityJobQueue()
    for i in range(4):
        q.submit(gang_job(i))
    worker = observe(directory, "w1", slices=4)
    handed = dispatcher.select(worker, q)
    assert len(handed) == 4
    assert all(g is None for _, _, g in handed)


def test_gang_prefers_warm_worker_via_seed_affinity():
    """The affinity/hold machinery sees the SEED, so the whole gang
    follows the seed's placement: a cold poll inside the hold window
    leaves the gang queued for the warm worker."""
    directory = WorkerDirectory(ttl_s=45.0)
    dispatcher = Dispatcher(directory, affinity_hold_s=300.0,
                            max_jobs_per_poll=8, gang_max=8)
    q = PriorityJobQueue()
    for i in range(3):
        q.submit(gang_job(i))
    model = q.records["g0"].job["model_name"]
    from chiaswarm_tpu.coalesce import placement_model

    resident = placement_model(q.records["g0"].job)
    observe(directory, "warm", resident_models=resident)
    cold = observe(directory, "cold")
    assert dispatcher.select(cold, q) == []  # held for the warm worker
    warm = observe(directory, "warm", resident_models=resident)
    handed = dispatcher.select(warm, q)
    assert [(r.job_id, o) for r, o, _ in handed] == \
        [("g0", "affinity"), ("g1", "gang"), ("g2", "gang")]
    assert model  # silence unused warning paths


def test_adapter_affinity_prefers_operand_warm_worker():
    """ISSUE 16: a model-warm poller whose operand cache also holds the
    job's adapter places as `adapter_affinity` (and its gang riders
    follow the seed); a model-warm poller WITHOUT the operands defers
    while an operand-warm model-warm peer is live inside the hold
    window. The dict job form ({'lora': ...}) and the advertised string
    must agree via the canonical ref."""
    from chiaswarm_tpu.coalesce import placement_model

    directory = WorkerDirectory(ttl_s=45.0)
    dispatcher = Dispatcher(directory, affinity_hold_s=300.0,
                            max_jobs_per_poll=8, gang_max=8)
    q = PriorityJobQueue()
    q.submit(gang_job(0, lora={"lora": "style-a"}))
    q.submit(gang_job(1, lora={"lora": "style-a"}))
    resident = placement_model(q.records["g0"].job)
    # both workers are model-warm; only "warm-op" holds the operands
    observe(directory, "warm-op", resident_models=resident,
            resident_adapters="style-a,style-b")
    plain = observe(directory, "plain", resident_models=resident)
    assert dispatcher.select(plain, q) == []  # held for the operand peer
    warm = observe(directory, "warm-op", resident_models=resident,
                   resident_adapters="style-a,style-b")
    handed = dispatcher.select(warm, q)
    assert [(r.job_id, o) for r, o, _ in handed] == \
        [("g0", "adapter_affinity"), ("g1", "gang")]


def test_adapter_affinity_never_starves():
    """Residency prefers, never starves: with NO operand-warm peer a
    model-warm poller takes the adapter job as plain affinity, and once
    the hold window lapses it takes it even when a peer advertises the
    operands. Adapter-free jobs never enter the operand machinery."""
    from chiaswarm_tpu.coalesce import placement_model

    directory = WorkerDirectory(ttl_s=45.0)
    dispatcher = Dispatcher(directory, affinity_hold_s=300.0,
                            max_jobs_per_poll=8, gang_max=8)
    q = PriorityJobQueue()
    q.submit(gang_job(0, lora="style-a"))
    resident = placement_model(q.records["g0"].job)
    # nobody advertises the operands -> plain affinity, no deferral
    plain = observe(directory, "plain", resident_models=resident)
    handed = dispatcher.select(plain, q)
    assert [(r.job_id, o) for r, o, _ in handed] == [("g0", "affinity")]

    # window lapsed (hold 0): the operand-warm peer does not block
    lapsed = Dispatcher(directory, affinity_hold_s=0.0,
                        max_jobs_per_poll=8, gang_max=8)
    q2 = PriorityJobQueue()
    q2.submit(gang_job(5, lora="style-a"))
    observe(directory, "warm-op", resident_models=resident,
            resident_adapters="style-a")
    plain = observe(directory, "plain", resident_models=resident)
    handed = lapsed.select(plain, q2)
    assert [(r.job_id, o) for r, o, _ in handed] == [("g5", "affinity")]

    # adapter-free job on an operand-warm worker: plain affinity
    q3 = PriorityJobQueue()
    q3.submit(gang_job(7))
    warm = observe(directory, "warm-op", resident_models=resident,
                   resident_adapters="style-a")
    handed = dispatcher.select(warm, q3)
    assert [(r.job_id, o) for r, o, _ in handed] == [("g7", "affinity")]


def test_gang_timeline_and_wire_context_through_hive_server(sdaas_root):
    """Through the real HiveServer surface: each member is leased and
    journaled individually, the dispatch timeline event carries the gang
    context (WAL-durable), and wire_trace_context stamps trace.gang."""
    from chiaswarm_tpu.hive_server import HiveServer
    from chiaswarm_tpu.hive_server.trace import wire_trace_context

    server = HiveServer(Settings(sdaas_token="t", hive_port=0,
                                 hive_max_jobs_per_poll=8,
                                 hive_wal_dir=""))
    for i in range(3):
        server.queue.submit(gang_job(i))
    worker = observe(server.directory, "w1")
    handed = server.dispatcher.select(worker, server.queue)
    for record, outcome, gang in handed:
        server.queue.take(record, worker.name, outcome, gang=gang)
        server.leases.grant(record, worker.name)
    assert len(server.leases) == 3  # one lease PER member, no gang lease
    for record, _, gang in handed:
        dispatch = [e for e in record.timeline
                    if e.get("event") == "dispatch"][-1]
        assert dispatch["gang"] == gang["id"]
        assert dispatch["gang_size"] == 3
        wire = wire_trace_context(record, gang=gang)
        assert wire["gang"] == gang
        assert wire["id"] == record.job_id


# --- worker-side put_gang ---------------------------------------------------


def run(coro):
    return asyncio.run(coro)


def test_put_gang_flushes_immediately_with_gang_reason():
    from chiaswarm_tpu.batching import _FLUSHES

    async def scenario():
        b = BatchScheduler(linger_s=60.0, max_coalesce=8)
        before = _FLUSHES.value(reason="gang")
        jobs = [gang_job(i, trace={"gang": {"id": "x", "size": 3,
                                            "index": i}}) for i in range(3)]
        await b.put_gang(jobs)
        assert b.pending_jobs == 0  # nothing lingers
        group = await asyncio.wait_for(b.get(), 1.0)
        assert [j["id"] for j in group] == ["g0", "g1", "g2"]
        assert _FLUSHES.value(reason="gang") == before + 1
        # the no-linger attribution: one `linger` span the members share
        # (nobody waited for batchmates), then one `claim` span
        from chiaswarm_tpu.batching import SPANS

        for name in ("linger", "claim"):
            found = [[s for s in j[SPANS] if s["name"] == name]
                     for j in group]
            assert all(len(spans) == 1 for spans in found)
            assert len({(s["start_wall"], s["seconds"])
                        for [s] in found}) == 1
            assert found[0][0]["thread"] == "wait"
        assert all(s["seconds"] < 0.01 for j in group for s in j[SPANS])
        assert all(j["trace"]["coalesced_with"] == 2 for j in group)
        assert all("lingered_s" not in j["trace"] for j in group)

    run(scenario())


def test_put_gang_chunks_past_max_coalesce_and_solo_fallback():
    async def scenario():
        b = BatchScheduler(linger_s=60.0, max_coalesce=2)
        jobs = [gang_job(i) for i in range(3)]
        jobs.append({"id": "odd", "workflow": "echo", "model_name": "none"})
        await b.put_gang(jobs)
        first = await asyncio.wait_for(b.get(), 1.0)
        second = await asyncio.wait_for(b.get(), 1.0)
        third = await asyncio.wait_for(b.get(), 1.0)
        assert [j["id"] for j in first] == ["g0", "g1"]  # chunked at 2
        assert [j["id"] for j in second] == ["g2"]
        assert [j["id"] for j in third] == ["odd"]  # solo fallback
        assert b.outstanding_jobs == 4

    run(scenario())


def test_put_gang_respects_rows_limit():
    async def scenario():
        b = BatchScheduler(linger_s=60.0, max_coalesce=8,
                           rows_limit=lambda job: 2)
        await b.put_gang([gang_job(i) for i in range(3)])
        first = await asyncio.wait_for(b.get(), 1.0)
        second = await asyncio.wait_for(b.get(), 1.0)
        assert [len(first), len(second)] == [2, 1]

    run(scenario())


def test_outstanding_rows_tracks_lifecycle():
    async def scenario():
        b = BatchScheduler(linger_s=60.0, max_coalesce=8)
        multi = gang_job(0)
        multi["parameters"]["num_images_per_prompt"] = 3
        await b.put_gang([multi, gang_job(1)])
        assert b.outstanding_rows == 4  # ready: 3 + 1
        group = await asyncio.wait_for(b.get(), 1.0)
        assert b.outstanding_rows == 4  # executing now
        for job in group:
            b.task_done(job)
        assert b.outstanding_rows == 0
        assert b.outstanding_jobs == 0

    run(scenario())


def test_put_gang_closed_degrades_to_put():
    async def scenario():
        b = BatchScheduler(linger_s=60.0, max_coalesce=8)
        b.close()
        await b.put_gang([gang_job(i) for i in range(2)])
        first = await asyncio.wait_for(b.get(), 1.0)
        second = await asyncio.wait_for(b.get(), 1.0)
        assert len(first) == 1 and len(second) == 1

    run(scenario())


# --- settings knobs ---------------------------------------------------------


@pytest.mark.parametrize("env,attr,value,expect", [
    ("CHIASWARM_HIVE_GANG_MAX", "hive_gang_max", "16", 16),
    ("CHIASWARM_EMBED_CACHE_MB", "embed_cache_mb", "128", 128),
])
def test_new_knobs_env_overrides(monkeypatch, sdaas_root, env, attr,
                                 value, expect):
    from chiaswarm_tpu.settings import load_settings

    monkeypatch.setenv(env, value)
    assert getattr(load_settings(), attr) == expect
