"""Closed-loop load: `clients` callers, each with one job in flight.

A client submits a job, polls its status every `status_poll_s` until the
hive calls it terminal, thinks for `think_s`, and submits the next. It
starts before the window opens (so that a standing queue exists when it
does) and stops submitting when the window closes; a job still queued at
the close is withdrawn (cancelled, counted apart), one already leased is
awaited, so every job a worker took is checked.

`think_s` is one number, or a list of them: then a client takes the
list's values in an order shuffled from the seed (its first job's id
carries it), every value once before any comes again, so every seed gets
the same set of think times in another order. A lone awaiting client
with no think time locks onto the worker's poll cadence (each submit
falls at one fixed phase of it, so the wait for the next poll is one
value all run long, set by the work's length modulo the poll period); a
list that spans one poll period walks the phase through the period.

Parameters (the traffic file): `clients`, `think_s`, `status_poll_s`.
"""

from __future__ import annotations

import asyncio
import random
import time

from benchmark.harness import TERMINAL


async def run(client, traffic: dict, make_job, window, observe) -> list[dict]:
    """Drive the load until `window.closed()`; returns one record per job
    in submit order. `observe(record)` is called as each job ends."""
    records: list[dict] = []
    poll_s = float(traffic.get("status_poll_s", 0.02))
    think = traffic.get("think_s", 0.0)
    thinks = [float(t) for t in (think if isinstance(think, list) else [think])]

    async def one_client(number: int) -> None:
        previous, thought, order, upcoming = None, 0.0, None, []
        while not window.closed():
            job = make_job()
            record = {"id": job["id"], "client": number, "previous": previous,
                      "think_s": thought,
                      "submit_wall": time.time(), "withdrawn": False}
            records.append(record)
            await client.submit(job)
            record["accepted_wall"] = time.time()
            while True:
                status = await client.status(job["id"])
                if status["status"] in TERMINAL:
                    break
                if window.closed() and status["status"] == "queued":
                    await client.cancel(job["id"])
                    record["withdrawn"] = True
                await asyncio.sleep(poll_s)
            record["seen_wall"] = time.time()
            record["status"] = status
            observe(record)
            previous = job["id"]
            if order is None:
                order = random.Random(f"think {number} {job['id']}")
            if not upcoming:
                upcoming = order.sample(thinks, len(thinks))
            thought = upcoming.pop()
            if thought:
                await asyncio.sleep(thought)

    tasks = [asyncio.create_task(one_client(n), name=f"client_{n}")
             for n in range(int(traffic["clients"]))]
    try:
        await asyncio.gather(*tasks)
    finally:
        for task in tasks:
            task.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
    return records
