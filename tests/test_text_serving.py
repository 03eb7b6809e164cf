"""`txt2txt` jobs through the serving plane (ISSUE 32): what a text job's
coalesce key and rows are, admission by weights held and a row's cache
bytes, the appetite a worker advertises for a family whose rows are
sequences and what the hive's dispatcher makes of it, and four multi-row
jobs end to end through `LocalSwarm` on `test/tiny-kimi`: one gang, one
pass, four JSON artifacts, the same ids for the same seed among other
batchmates."""

import asyncio
import hashlib
import json

import numpy as np
import pytest

from chiaswarm_tpu.chips import requirements
from chiaswarm_tpu.coalesce import coalesce_key, job_rows, prompt_slots


def _job(number, seed, rows=3, new_tokens=6, low=3, high=16, **extra):
    rng = np.random.default_rng(number)
    return {"id": f"text-{number}", "workflow": "txt2txt",
            "model_name": "test/tiny-kimi",
            "prompt_ids": [rng.integers(0, 128, int(rng.integers(
                low, high))).tolist() for _ in range(rows)],
            "max_new_tokens": new_tokens, "temperature": 1.0, "seed": seed,
            **extra}


# --- the key and the rows ----------------------------------------------------


def test_a_text_jobs_key_is_model_bucket_new_tokens_and_sampling():
    a, b = _job(1, 5), _job(2, 9, rows=7)
    assert coalesce_key(a) == coalesce_key(b) == (
        "test/tiny-kimi", "kimi_k2", "txt2txt", 16, 6, 1.0)
    assert job_rows(a) == 3 and job_rows(b) == 7


@pytest.mark.parametrize("change, same", [
    ({"seed": 77}, True),
    ({"max_new_tokens": 7}, False),
    ({"temperature": 0.0}, False),
    ({"model_name": "test/tiny-kimi-b"}, False),
    ({"prompt_ids": [[1] * 17]}, False),  # the 32-slot bucket
    ({"prompt_ids": [[1] * 16, [2]]}, True),
    ({"parameters": {"max_new_tokens": 6}}, True),
], ids=["seed", "new_tokens", "temperature", "model", "longer_prompt",
        "other_rows", "in_parameters"])
def test_what_splits_a_text_bucket_and_what_rides_per_row(change, same):
    base = _job(1, 5)
    other = {**base, **change}
    assert (coalesce_key(other) == coalesce_key(base)) is same


@pytest.mark.parametrize("broken", [
    {"prompt_ids": []}, {"prompt_ids": [[]]}, {"prompt_ids": "ids"},
    {"prompt_ids": None}, {"model_name": "test/tiny-sd"},
    {"parameters": {"scheduler_args": {}}}, {"max_new_tokens": 0},
], ids=["no_rows", "empty_row", "not_rows", "none", "no_text_family",
        "unknown_parameter", "no_new_tokens"])
def test_a_text_job_that_cannot_batch_has_no_key(broken):
    assert coalesce_key({**_job(1, 5), **broken}) is None


def test_prompt_slots_are_powers_of_two_from_sixteen():
    assert [prompt_slots(n) for n in (1, 16, 17, 128, 129, 256)] == [
        16, 16, 32, 128, 256, 256]


# --- admission ---------------------------------------------------------------


class _Slice:
    platform, tensor, seq = "tpu", 1, 1

    def __init__(self, gib=15.75, chips=1):
        self._bytes, self._chips = int(gib * (1 << 30)) * chips, chips

    def hbm_bytes(self):
        return self._bytes

    def chip_count(self):
        return self._chips


def test_admission_is_the_weights_held_and_a_rows_cache_bytes():
    name = "test/Kimi-K2.6"
    costs = requirements.SEQUENCE_FAMILIES["kimi_k2"]
    # a position is 576 values x 2 bytes x 7 layers
    assert costs["cache_bytes_per_position"] == 576 * 2 * 7
    free = 15.75 - costs["params_gb"] - costs["working_gb"]
    for positions in (512, 4096, 32768):
        per_row = positions * 8064 / (1 << 30)
        fit = requirements.fit_batch(_Slice(), name, 10 ** 9, positions)
        assert fit == int(free / per_row)
    assert requirements.fit_batch(_Slice(), name, 200, 512) == 200
    # the pow2 budget a pass is held to, under the default ceiling
    assert requirements.coalesce_rows_limit(_Slice(), name, 512) == 256
    assert requirements.coalesce_rows_limit(_Slice(), name, 32768) == 8
    # a chip the weights do not fit: the single-job gate says so
    assert requirements.fit_batch(_Slice(gib=8), name, 4, 512) == 0
    with pytest.raises(ValueError, match="does not fit"):
        requirements.check_capacity(_Slice(gib=8), name, 4, 512)
    # more chips of a slice hold no more: every chip holds the share whole
    assert requirements.fit_batch(_Slice(chips=4), name, 10 ** 9, 512) \
        == requirements.fit_batch(_Slice(), name, 10 ** 9, 512)
    # the tiny stand-in is a few MB whatever the table says
    assert requirements.fit_batch(_Slice(gib=1), "test/tiny-kimi", 64,
                                  512) == 64
    assert requirements.required_hbm_gb(name, 256, 512) == pytest.approx(
        costs["params_gb"] + costs["working_gb"] + 256 * 512 * 8064 / 2 ** 30)


# --- the appetite between worker and hive ------------------------------------


def test_the_dispatcher_sizes_a_text_gang_by_the_familys_own_appetite():
    from chiaswarm_tpu.hive_server.dispatch import (
        Dispatcher,
        WorkerDirectory,
    )
    from chiaswarm_tpu.hive_server.queue import PriorityJobQueue

    directory = WorkerDirectory(ttl_s=45.0)
    dispatcher = Dispatcher(directory, affinity_hold_s=0.0,
                            max_jobs_per_poll=4)
    queue = PriorityJobQueue()
    for n in range(6):
        queue.submit(_job(n, n, rows=64, low=20, high=60))
    poll = {"worker_name": "w", "slices": "1", "busy_slices": "0",
            "queue_depth": "0", "gang_rows": "8"}
    # no appetite of the family's own: 64 rows are over the 8-row appetite
    # of image jobs, and one job leaves alone
    alone = dispatcher.select(directory.observe(poll), queue)
    assert [gang for _, _, gang in alone] == [None]
    info = directory.observe({**poll, "family_gang_rows": "kimi_k2:256"})
    assert info.family_rows == {"kimi_k2": 256}
    assert info.rows_per_pass(("m", "kimi_k2")) == 256
    assert info.rows_per_pass(("m", "sdxl")) == info.rows_per_pass(None) == 8
    handed = dispatcher.select(info, queue)
    assert len(handed) == 4 and {g["size"] for _, _, g in handed} == {4}
    assert len({g["id"] for _, _, g in handed}) == 1
    # a pass of 256 in flight leaves a text job nothing, 128 leave two
    busy = directory.observe({**poll, "family_gang_rows": "kimi_k2:256",
                              "queue_depth": "256"})
    assert dispatcher.select(busy, queue) == []
    half = directory.observe({**poll, "family_gang_rows": "kimi_k2:256",
                              "queue_depth": "128"})
    assert len(dispatcher.select(half, queue)) == 2
    # what does not parse is left out
    assert directory.observe(
        {**poll, "family_gang_rows": "x:,:3,y:0,z:5"}).family_rows == {"z": 5}


def test_a_worker_advertises_the_sequence_families_appetite(sdaas_root):
    from chiaswarm_tpu.chips.allocator import SliceAllocator
    from chiaswarm_tpu.settings import Settings
    from chiaswarm_tpu.worker import Worker

    worker = Worker(settings=Settings(sdaas_token="t", worker_name="w"),
                    allocator=SliceAllocator(chips_per_job=8),
                    hive_uri="http://127.0.0.1:1")
    caps = worker._capabilities()
    # not HBM on the CPU: the ceiling; the job cap stays what it was
    assert caps["family_gang_rows"] == "kimi_k2:256"
    assert caps["gang_rows"] == 8
    # the batcher's own budget is the job's true positions
    assert worker._coalesce_rows_limit(_job(1, 2)) == 256


# --- end to end ---------------------------------------------------------------


def test_four_text_jobs_are_one_gang_one_pass_and_seeded(sdaas_root,
                                                         monkeypatch):
    from chiaswarm_tpu import worker as worker_module
    from chiaswarm_tpu.hive_server.harness import LocalSwarm
    from chiaswarm_tpu.settings import Settings

    monkeypatch.setattr(worker_module, "POLL_SECONDS", 0.1)

    async def scenario():
        swarm = LocalSwarm(n_workers=0, settings=Settings(
            sdaas_token="t", worker_name="w", hive_port=0, metrics_port=0))
        await swarm.start()
        try:
            first = [await swarm.submit(_job(n, 100 + n)) for n in range(4)]
            swarm.add_worker("text-worker")
            done = [await swarm.wait_done(i, timeout=300) for i in first]
            # the first job again, among other batchmates of other sizes
            again = [await swarm.submit(dict(_job(0, 100), id="again"))] + [
                await swarm.submit(dict(_job(n, n, rows=2), id=f"other-{n}"))
                for n in range(5, 8)]
            done.append(await swarm.wait_done(again[0], timeout=300))
            blobs = [await swarm.artifact(
                status["result"]["artifacts"]["primary"]["href"])
                for status in done]
            return done, blobs
        finally:
            await swarm.stop()

    done, blobs = asyncio.run(scenario())
    configs = [status["result"]["pipeline_config"] for status in done]
    assert all(status["status"] == "done" and status["attempts"] == 1
               for status in done)
    # one gang of four left the hive, and ran as one pass of 12 rows
    gangs = [config["trace"]["gang"] for config in configs[:4]]
    assert len({gang["id"] for gang in gangs}) == 1
    assert [gang["size"] for gang in gangs] == [4] * 4
    assert {config["pass_rows"] for config in configs[:4]} == {12}
    assert [config["batch_rows"] for config in configs[:4]] == [
        [0, 3], [3, 3], [6, 3], [9, 3]]
    for config in configs:
        names = [span["name"] for span in config["spans"]]
        assert {"pass", "prefill", "decode", "readback",
                "artifact_encode"} <= set(names)
        assert {"prefill_s", "decode_s", "readback_s"} <= set(
            config["timings"])
        routing = config["routing"]
        assert routing["pairs"] == (routing["prefill"]["pairs"]
                                    + routing["decode"]["pairs"])
        assert 0 < routing["pairs"] < routing["routed"]
    host = [span for span in configs[0]["spans"]
            if span["name"] == "artifact_encode"]
    assert host[0]["thread"] == "host"
    # four JSON artifacts: 3 rows of 6 ids of the vocabulary each
    for status, blob in zip(done, blobs):
        ref = status["result"]["artifacts"]["primary"]
        assert ref["content_type"] == "application/json"
        assert hashlib.sha256(blob).hexdigest() == ref["sha256"]
        rows = json.loads(blob)["token_ids"]
        assert len(rows) == 3 and all(len(row) == 6 for row in rows)
        assert all(0 <= i < 128 for row in rows for i in row)
    assert len({blob for blob in blobs[:4]}) == 4
    # one job, one seed: the same bytes in a pass of 9 rows as of 12
    assert configs[4]["pass_rows"] == 9
    assert blobs[4] == blobs[0]
