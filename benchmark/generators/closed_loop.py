"""Closed-loop load: `clients` callers, each with one job in flight.

A client submits a job, polls its status every `status_poll_s` until the
hive calls it terminal, thinks for `think_s`, and submits the next. It
starts before the window opens (so that a standing queue exists when it
does) and stops submitting when the window closes; a job still queued at
the close is withdrawn (cancelled, counted apart), one already leased is
awaited, so every job a worker took is checked.

Parameters (the traffic file): `clients`, `think_s`, `status_poll_s`.
"""

from __future__ import annotations

import asyncio
import time

from benchmark.harness import TERMINAL


async def run(client, traffic: dict, make_job, window, observe) -> list[dict]:
    """Drive the load until `window.closed()`; returns one record per job
    in submit order. `observe(record)` is called as each job ends."""
    records: list[dict] = []
    poll_s = float(traffic.get("status_poll_s", 0.02))
    think_s = float(traffic.get("think_s", 0.0))

    async def one_client(number: int) -> None:
        previous = None
        while not window.closed():
            job = make_job()
            record = {"id": job["id"], "client": number, "previous": previous,
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
            if think_s:
                await asyncio.sleep(think_s)

    tasks = [asyncio.create_task(one_client(n), name=f"client_{n}")
             for n in range(int(traffic["clients"]))]
    try:
        await asyncio.gather(*tasks)
    finally:
        for task in tasks:
            task.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
    return records
