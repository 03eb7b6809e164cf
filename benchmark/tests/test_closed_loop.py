"""The closed-loop generator's think times: a list in the traffic file is
one set for every seed, in an order the seed shuffles."""

import asyncio

import pytest

from benchmark import harness
from benchmark.generators import closed_loop

THINKS = [0.0, 0.002, 0.004, 0.006]


class InstantHive:
    """Every job is done at its first status poll."""

    async def submit(self, job):
        return job["id"]

    async def status(self, job_id):
        return {"status": "done"}


def thinks_taken(seed: int, think, jobs: int = 9) -> list[float]:
    made = []

    def make_job():
        made.append({"id": f"cell-{seed}-{len(made) + 1:05d}"})
        return made[-1]

    class Window:
        def closed(self):
            return len(made) >= jobs

    traffic = {"clients": 1, "status_poll_s": 0.001, "think_s": think}
    records = asyncio.run(closed_loop.run(
        InstantHive(), traffic, make_job, Window(), lambda record: None))
    assert [r["previous"] for r in records[1:]] == [
        r["id"] for r in records[:-1]]
    return [r["think_s"] for r in records]


def test_a_think_list_is_one_set_in_a_seeded_order():
    one, again, other = (thinks_taken(seed, THINKS) for seed in (7, 7, 8))
    assert one == again and one != other
    for taken in (one, other):
        # no think before the first job; then every value once before any
        # comes again, whatever the seed
        assert taken[0] == 0.0
        assert sorted(taken[1:5]) == sorted(taken[5:9]) == THINKS


def test_one_think_time_is_taken_before_every_job_but_the_first():
    assert thinks_taken(7, 0.003, jobs=4) == [0.0, 0.003, 0.003, 0.003]
    assert thinks_taken(7, 0, jobs=3) == [0.0, 0.0, 0.0]


def test_generator_lateness_leaves_out_the_think_time():
    def job(n, previous, submit, settle, think):
        return {"id": f"j{n}", "previous": previous, "in_window": True,
                "think_s": think, "submit_wall": submit,
                "accepted_wall": submit + 0.002,
                "trace": {"events": [{"event": "settle", "wall": settle}]}}

    jobs = [job(0, None, 100.0, 110.0, 0.0),
            job(1, "j0", 110.058, 120.0, 0.05)]
    read = harness.load_reader("layer_metrics", "generator_late_ms")
    assert read({"jobs": jobs}) == pytest.approx(10.0)
