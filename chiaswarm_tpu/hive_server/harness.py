"""In-process swarm: a real HiveServer plus real Workers on real sockets.

The worker tests fake the hive; the hive tests fake the worker. This
harness is the seam where neither is faked: it stands up a HiveServer on
an ephemeral loopback port and N pristine `Worker` instances pointed at
it via HTTP, then drives jobs through POST /api/jobs. Used by the e2e
tests (tests/test_hive_server.py), the chaos lease-takeover scenario
(tools/chaos_smoke.py), and anything else that needs the whole swarm
loop without subprocesses.

Note: in-process workers share one registry/residency map, so two
LocalSwarm workers always advertise identical resident models. Scenarios
that need residency to DIFFER per worker (the affinity acceptance test,
the bench row) use worker subprocesses or simulated pollers instead.
"""

from __future__ import annotations

import asyncio
import dataclasses
from typing import TYPE_CHECKING, Any

import aiohttp

from ..settings import Settings
from .app import HiveServer
from .replication import StandbyHive

if TYPE_CHECKING:  # worker-side types only; see lazy import below
    from ..worker import Worker


class LocalSwarm:
    def __init__(self, n_workers: int = 1, chips_per_job: int = 0,
                 settings: Settings | None = None,
                 worker_overrides: dict[str, Any] | None = None,
                 standby: bool = False):
        self.settings = settings or Settings(
            sdaas_token="local-swarm", worker_name="swarm-worker",
            hive_port=0, metrics_port=0)
        self.n_workers = n_workers
        self.chips_per_job = chips_per_job
        self.worker_overrides = worker_overrides or {}
        # standby=True stands a WAL-shipped standby hive next to the
        # primary (replication.py) and gives every worker BOTH endpoints,
        # so failover scenarios — kill_primary(), promote() — run in
        # process. The standby journals to its own WAL dir; the
        # content-addressed artifact spool is shared by design.
        self.with_standby = standby
        self.standby: StandbyHive | None = None
        self.hive: HiveServer | None = None
        self.workers: list["Worker"] = []
        self._worker_tasks: list[asyncio.Task] = []
        self._session: aiohttp.ClientSession | None = None

    async def __aenter__(self) -> "LocalSwarm":
        return await self.start()

    async def __aexit__(self, *exc) -> None:
        await self.stop()

    async def start(self) -> "LocalSwarm":
        self.hive = await HiveServer(self.settings, port=0).start()
        if self.with_standby:
            wal = str(getattr(self.settings, "hive_wal_dir", "hive_wal"))
            self.standby = await StandbyHive(
                dataclasses.replace(
                    self.settings, hive_port=0,
                    hive_wal_dir=f"{wal}_standby" if wal else ""),
                primary_uri=self.hive.uri).start()
        for i in range(self.n_workers):
            self.add_worker(f"swarm-worker-{i}")
        self._session = aiohttp.ClientSession()
        return self

    @property
    def active_hive(self) -> HiveServer:
        """The hive currently entitled to serve: the promoted standby
        once promote()/failover happened, the primary before."""
        if self.standby is not None and self.standby.promoted:
            return self.standby.server
        return self.hive

    def worker_endpoints(self) -> list[str] | str:
        if self.standby is not None:
            return [self.hive.api_uri, self.standby.api_uri]
        return self.hive.api_uri

    def add_worker(self, name: str) -> "Worker":
        """Start one more pristine Worker against the hive (the
        second-worker half of takeover scenarios). Workers inherit the
        swarm's settings (a caller tuning e.g. job_deadline_s or
        batch_linger_ms configures the whole swarm, not just the hive),
        with per-worker identity and `worker_overrides` on top."""
        fields = {"metrics_port": 0}
        # overrides win over the harness defaults (a scenario that wants
        # a live worker /metrics endpoint passes metrics_port explicitly)
        # — except worker_name: per-worker identity keys the hive's
        # directory and lease attribution, so a shared override would
        # silently conflate every worker in the swarm
        fields.update(self.worker_overrides)
        fields["worker_name"] = name
        # lazy: the worker half pulls jax; a chip-less host must be able
        # to import hive_server.harness for its hive-only surface (SW001)
        from ..chips.allocator import SliceAllocator
        from ..worker import Worker

        worker = Worker(
            settings=dataclasses.replace(self.settings, **fields),
            # the slice geometry is the settings', as in `worker.main`
            # (ISSUE 27: this allocator ignored them, so a swarm asked for
            # `tensor_parallelism=4` got `[data=4, tensor=1]` slices)
            allocator=SliceAllocator(
                chips_per_job=self.chips_per_job,
                tensor_parallelism=self.settings.tensor_parallelism,
                sequence_parallelism=self.settings.sequence_parallelism),
            hive_uri=self.worker_endpoints(),
        )
        self.workers.append(worker)
        self._worker_tasks.append(
            asyncio.create_task(worker.run(), name=f"swarm_{name}"))
        return worker

    async def kill_primary(self) -> None:
        """Hard-stop the primary hive: sockets close, in-flight requests
        die — externally indistinguishable from SIGKILLing its process
        (workers see refused connections, the standby sees stream+health
        silence and eventually promotes itself)."""
        await self.hive.stop()

    async def promote(self) -> HiveServer:
        """Promote the standby explicitly (the operator seam; the
        health-check loop does the same on its own after
        hive_failover_grace_s of primary silence)."""
        return await self.standby.promote()

    async def restart_hive(self) -> HiveServer:
        """Hard-stop the hive and stand a fresh instance up over the same
        $SDAAS_ROOT and port — the in-process analog of a coordinator
        restart. With the WAL enabled (the default) the new instance
        replays to the pre-stop queue + lease state; workers keep polling
        the same URI and never learn a restart happened beyond a few
        refused connections."""
        port = self.hive.port
        await self.hive.stop()
        self.hive = await HiveServer(self.settings, port=port).start()
        return self.hive

    async def stop_worker(self, worker: "Worker") -> None:
        """Hard-stop one worker (no drain) — 'the worker died mid-lease'."""
        idx = self.workers.index(worker)
        worker.stop()
        task = self._worker_tasks[idx]
        await asyncio.wait_for(
            asyncio.gather(task, return_exceptions=True), 10)

    async def stop(self) -> None:
        for worker in self.workers:
            worker.stop()
        await asyncio.gather(*self._worker_tasks, return_exceptions=True)
        self.workers.clear()
        self._worker_tasks.clear()
        if self._session is not None:
            await self._session.close()
            self._session = None
        if self.standby is not None:
            await self.standby.stop()
        if self.hive is not None:
            await self.hive.stop()

    # --- client surface (real HTTP against the hive) ---

    def _headers(self) -> dict:
        return {"Authorization": f"Bearer {self.settings.sdaas_token}",
                "Content-type": "application/json"}

    async def submit(self, job: dict) -> str:
        import json

        async with self._session.post(
                f"{self.active_hive.api_uri}/jobs", data=json.dumps(job),
                headers=self._headers()) as resp:
            resp.raise_for_status()
            payload = await resp.json()
            return payload["id"]

    async def cancel(self, job_id: str) -> dict:
        """POST /api/jobs/{id}/cancel against the active hive (the
        submitter-side revoke the cancellation scenarios drive)."""
        async with self._session.post(
                f"{self.active_hive.api_uri}/jobs/{job_id}/cancel",
                headers=self._headers()) as resp:
            resp.raise_for_status()
            return await resp.json()

    async def job_status(self, job_id: str) -> dict:
        async with self._session.get(
                f"{self.active_hive.api_uri}/jobs/{job_id}",
                headers=self._headers()) as resp:
            resp.raise_for_status()
            return await resp.json()

    async def wait_done(self, job_id: str, timeout: float = 240.0,
                        accept_failed: bool = False) -> dict:
        """Poll until the job reaches a terminal state; returns the
        status snapshot (result included, blobs as spool refs)."""
        deadline = asyncio.get_running_loop().time() + timeout
        while True:
            status = await self.job_status(job_id)
            if status["status"] == "done":
                return status
            if status["status"] == "failed":
                if accept_failed:
                    return status
                raise AssertionError(
                    f"job {job_id} failed at the hive: {status['error']}")
            if asyncio.get_running_loop().time() >= deadline:
                raise asyncio.TimeoutError(
                    f"job {job_id} still {status['status']} "
                    f"after {timeout:.0f}s")
            await asyncio.sleep(0.05)

    async def artifact(self, href_or_digest: str) -> bytes:
        path = (href_or_digest if href_or_digest.startswith("/")
                else f"/api/artifacts/{href_or_digest}")
        async with self._session.get(f"{self.active_hive.uri}{path}",
                                     headers=self._headers()) as resp:
            resp.raise_for_status()
            return await resp.read()
