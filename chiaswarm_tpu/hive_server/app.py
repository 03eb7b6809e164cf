"""The hive HTTP server: the wire protocol of hive.py, served.

Protocol parity with the client in `chiaswarm_tpu/hive.py` (itself at
parity with reference swarm/hive.py:9-88):

  GET  /api/work?worker_version&worker_name&<capabilities>
       -> 200 {"jobs": [...]} | 400 {"message": ...} (refusal)
  POST /api/results  <- result envelope -> 200 ack JSON (idempotent)
  GET  /api/models   -> {"models": [...], "language_models": [...]}

plus the coordinator's own surface, which the reference hive kept
closed-source:

  POST /api/jobs            submit a job (admission control; 429 on a
                            full queue), returns {"id", "class"}
  POST /api/jobs/{id}/cancel  revoke a queued or leased job (WAL-durable;
                            leased cancels ride the next /work reply's
                            `cancels` piggyback to the lessee)
  GET  /api/jobs/{id}       lifecycle snapshot + spooled result
  GET  /api/usage           per-tenant usage ledger (accounting.py)
  GET  /api/tenants/{t}/usage  one tenant's bucket
  GET  /api/slo             per-class SLO compliance + burn rates (slo.py)
  GET  /api/artifacts/{d}   content-addressed artifact bytes
  GET  /metrics, /healthz   same telemetry registry the worker uses

Auth is the same bearer token workers are provisioned with
(`Settings.sdaas_token`); an empty token disables the check (dev mode).
`GET /api/models` alone is unauthenticated — the reference hive serves
its catalog publicly and the worker's `initialize --download` probe
relies on that (tests/fake_hive.py pins the same exception).
`refuse_with` mirrors tests/fake_hive.py: set it and /work answers 400
with the message — the hive-side drain switch (workers back off and
retry, nothing errors).
"""

from __future__ import annotations

import asyncio
import json
import logging

from aiohttp import web

from .. import faults, telemetry
from ..settings import Settings, get_settings_dir, load_settings, resolve_path
from . import accounting
from .dag import DagTable, WorkflowError
from .dispatch import Dispatcher, WorkerDirectory
from .fleet import FleetStats
from .slo import SLOEngine, parse_slo
from .journal import (
    HiveJournal,
    apply_events,
    ev_admit,
    ev_cancel,
    ev_checkpoint,
    ev_dag,
    ev_expire,
    ev_lease,
    ev_park,
    ev_requeue,
    ev_retire,
    ev_settle,
    snapshot_events,
)
from .leases import LeaseTable
from .queue import (PriorityJobQueue, QueueFull, job_class,
                    parse_shed_watermarks)
from .spool import ArtifactSpool
from .trace import (
    build_shed_trace,
    build_trace,
    envelope_trace,
    wire_trace_context,
)

logger = logging.getLogger(__name__)

_RESULTS = telemetry.counter(
    "swarm_hive_results_total",
    "Result envelopes POSTed to the hive, by disposition "
    "(ok | duplicate | late | unknown | cancelled | expired)",
    ("status",),
)
_CANCEL_REVOCATIONS = telemetry.gauge(
    "swarm_hive_cancel_revocations_pending",
    "Leased-job cancels awaiting delivery to their lessee via the next "
    "/work reply's `cancels` piggyback (lease already revoked hive-side)")
_POLLS = telemetry.counter(
    "swarm_hive_polls_total",
    "GET /work polls answered, by reply (jobs | empty | refused)",
    ("reply",),
)
# registered by leases.py (imported above); same-name counter() returns it
_JOBS_FAILED = telemetry.counter("swarm_hive_jobs_failed_total")
_CHECKPOINTS = telemetry.counter(
    "swarm_hive_checkpoints_total",
    "Mid-pass checkpoint blobs POSTed to the hive (ISSUE 18), by outcome "
    "(stored = spooled + WAL-journaled; superseded = an older checkpoint "
    "blob of the same job dropped; rejected = sender is not the lessee "
    "or the job is not leased)",
    ("outcome",),
)
_PREVIEWS_STORED = telemetry.counter(
    "swarm_hive_previews_total",
    "Progressive preview artifacts POSTed to the hive (ISSUE 18), by "
    "outcome (stored | rejected)",
    ("outcome",),
)
_RESUME_OFFERS = telemetry.counter(
    "swarm_hive_resume_offers_total",
    "Redelivered jobs whose /work reply carried a `resume` offer "
    "(checkpoint href + step + program signature) to a resume-capable "
    "worker (ISSUE 18)",
)
_STALE_EPOCH = telemetry.counter(
    "swarm_hive_stale_epoch_total",
    "Requests refused with 409 because the caller has seen a newer hive "
    "epoch — this hive is a deposed primary (split-brain fencing)",
)
_EPOCH = telemetry.gauge(
    "swarm_hive_epoch",
    "This hive's fencing epoch (bumped by every standby promotion)")
_ROLE = telemetry.gauge(
    "swarm_hive_standby",
    "1 while this hive is a standby replicating from a primary, 0 once "
    "primary (born-primary or promoted)")

# served when no models.json exists under $SDAAS_ROOT — enough for a
# worker's `initialize --download` probe to succeed against a dev hive
_DEFAULT_CATALOG = {
    "models": [{"id": "stabilityai/stable-diffusion-2-1"}],
    "language_models": [],
}


class HiveServer:
    """One coordinator instance; start()/stop() or `async with`."""

    def __init__(self, settings: Settings | None = None,
                 host: str | None = None, port: int | None = None,
                 standby: bool = False):
        self.settings = settings or load_settings()
        g = lambda name, default: getattr(self.settings, name, default)  # noqa: E731
        self.host = host if host is not None else g("hive_host", "127.0.0.1")
        self.port = port if port is not None else int(g("hive_port", 9511))
        self.token = str(g("sdaas_token", ""))
        # standby role (replication.py): refuse dispatch/results/submits
        # with a 409 not-primary until promoted; epoch is the split-brain
        # fence — a request stamped with a NEWER epoch than ours proves a
        # standby was promoted over us, so we answer 409 rather than
        # double-dispatch or double-settle (see _fenced)
        self.standby = bool(standby)
        self.epoch = 0
        # fleet observability plane (ISSUE 11): per-tenant usage is pure
        # derived state over the records (accounting.py); the SLO engine
        # and fleet straggler stats are live-traffic views created here
        # so _new_state (also the replication reset path) can rewire the
        # queue's observation hook into the same engine
        self.tenant_topk = int(g("hive_tenant_topk", 10))
        self.slo = SLOEngine(
            parse_slo(g("hive_slo", "")),
            fast_window_s=float(g("hive_slo_fast_window_s", 60.0)),
            slow_window_s=float(g("hive_slo_slow_window_s", 600.0)))
        self.fleet = FleetStats(factor=float(g("hive_straggler_factor", 2.5)))
        self.queue, self.leases = self._new_state()
        # workflow graphs (ISSUE 20): stage-jobs live in the queue as
        # ordinary records; the dag table only owns the edges between
        # them and the parent aggregation — reset alongside the queue on
        # a replication reset (see replication._reset_state)
        self.dag = self._new_dag()
        self.directory = WorkerDirectory(
            ttl_s=float(g("hive_worker_ttl_s", 45.0)), fleet=self.fleet)
        # flap detection (ISSUE 18): the dispatcher queries the LIVE
        # lease table through self (a standby's replication reset swaps
        # self.leases, and the closure must follow it)
        self.flap_threshold = int(g("hive_flap_threshold", 3))
        self.dispatcher = Dispatcher(
            self.directory,
            affinity_hold_s=float(g("hive_affinity_hold_s", 15.0)),
            max_jobs_per_poll=int(g("hive_max_jobs_per_poll", 4)),
            gang_max=int(g("hive_gang_max", 8)),
            lora_slots=int(g("lora_slots_max", 8)),
            flap_threshold=self.flap_threshold,
            flapping_fn=lambda: self.leases.flapping(self.flap_threshold),
        )
        self.spool = ArtifactSpool(
            resolve_path(g("hive_spool_dir", "hive_spool")))
        self.spool_max_bytes = int(g("hive_spool_max_bytes", 0))
        self.spool_max_age_s = float(g("hive_spool_max_age_s", 0.0))
        self.refuse_with: str | None = None
        # optional health augmentation (replication.py installs the
        # standby's lag view); returns a dict merged into health(),
        # with its "degraded_reasons" list folded into the verdict
        self.extra_health = None
        self.started_at = self.queue.clock.mono()
        self._last_spool_sweep = self.queue.clock.mono()
        self._runner: web.AppRunner | None = None
        self._reaper: asyncio.Task | None = None
        # write-ahead journal: recover the pre-crash queue + lease state
        # BEFORE serving a single request ("" disables — pure in-memory,
        # the pre-WAL behavior). Replay happens here in __init__, not
        # start(), so tests and tools that drive the state machine
        # without a socket get the same durability semantics.
        self.journal: HiveJournal | None = None
        self.recovery: dict | None = None
        wal_dir = str(g("hive_wal_dir", "hive_wal"))
        if wal_dir:
            self.journal = HiveJournal(
                resolve_path(wal_dir),
                fsync=bool(g("hive_wal_fsync", False)),
                compact_every=int(g("hive_wal_compact_every", 512)))
            events = self.journal.recover()
            if events:
                self.recovery = apply_events(
                    events, self.queue, self.leases, dag=self.dag)
                self.epoch = max(
                    self.epoch, int(self.recovery.get("epoch", 0)))
                logger.warning(
                    "hive WAL replayed %d event(s) -> %s (recovered leases "
                    "get a fresh %gs deadline)", len(events), self.recovery,
                    self.leases.deadline_s)
                # repair the graph edges against the replayed records: a
                # crash between a stage settle and its ev_dag append left
                # the workflow behind its own stages — re-derive states
                # and re-admit ready successors (deterministic stage ids
                # make this exactly-once)
                for readmitted in self.dag.reconcile(self.queue):
                    self._journal(ev_admit(readmitted))
            # compact now: the stream shrinks to live state, and a
            # crash-restart-crash loop cannot grow it without bound
            self.journal.compact(
                snapshot_events(self.queue, self.leases, self.epoch,
                                dag=self.dag))
            self.journal.snapshot_fn = (
                lambda: snapshot_events(self.queue, self.leases, self.epoch,
                                        dag=self.dag))
        # leased-job cancels awaiting their lessee's next poll:
        # worker name -> job ids, delivered as the /work reply's
        # `cancels` piggyback. Volatile by design (the durable fact is
        # the record's `cancelled` state) — rebuilt from the records
        # after WAL replay and standby promotion, so a worker mid-denoise
        # across a hive crash still hears about the revocation
        self._cancel_notify: dict[str, set[str]] = {}
        self.rebuild_cancel_notify()
        # the tenant ledger is derived from the records, so a WAL replay
        # (or a fresh start) prices in here — the gauges agree with
        # GET /api/usage from the first scrape
        self.refresh_usage_metrics()
        self.note_role_change()

    def refresh_usage_metrics(self) -> dict:
        """Recompute the per-tenant usage summary from the records and
        re-export the top-K gauges; returns the raw summary (micro-unit
        buckets) for the callers that render it. O(retained history) —
        settles only mark the gauges dirty and the reaper (or the next
        /api/usage read) pays this, never the result hot path."""
        summary = accounting.usage_summary(self.queue.records.values())
        accounting.refresh_tenant_metrics(summary, self.tenant_topk)
        self._usage_dirty = False
        return summary

    def rebuild_cancel_notify(self) -> None:
        """Re-derive the pending-revocation map from record state (WAL
        recovery, standby promotion). A cancelled-while-leased record
        whose lessee never answered is re-notified on that worker's next
        poll; re-notifying a worker that already dropped the job is a
        harmless no-op on its side."""
        self._cancel_notify = {}
        for record in self.queue.records.values():
            if (record.state == "cancelled"
                    and record.cancel_stage == "leased" and record.worker):
                self._cancel_notify.setdefault(
                    record.worker, set()).add(record.job_id)
        self._refresh_cancel_gauge()

    def _refresh_cancel_gauge(self) -> None:
        _CANCEL_REVOCATIONS.set(
            sum(len(ids) for ids in self._cancel_notify.values()))

    def note_role_change(self) -> None:
        """Refresh the role/epoch gauges (called again on promotion)."""
        _EPOCH.set(self.epoch)
        _ROLE.set(1 if self.standby else 0)

    def _new_state(self) -> tuple[PriorityJobQueue, LeaseTable]:
        """Fresh queue + lease tables with this hive's knobs. Split out
        of __init__ because a standby performing a replication RESET
        (its position was compacted away on the primary) rebuilds state
        from the snapshot rather than patching the divergent copy."""
        g = lambda name, default: getattr(self.settings, name, default)  # noqa: E731
        queue = PriorityJobQueue(
            depth_limit=int(g("hive_queue_depth_limit", 256)),
            history_limit=int(g("hive_job_history_limit", 1000)),
            shed_watermarks=parse_shed_watermarks(
                g("hive_shed_watermarks", None)),
            job_ttl_s=float(g("hive_job_ttl_s", 0.0)))
        leases = LeaseTable(
            deadline_s=float(g("hive_lease_deadline_s", 300.0)),
            max_redeliveries=int(g("hive_max_redeliveries", 3)),
        )
        # rewired on every reset so a standby's rebuilt queue keeps
        # feeding the same live SLO windows
        queue.slo = self.slo
        return queue, leases

    def _new_dag(self) -> DagTable:
        g = lambda name, default: getattr(self.settings, name, default)  # noqa: E731
        return DagTable(self.queue.clock,
                        history_limit=int(g("hive_dag_history", 256)))

    # --- lifecycle ---

    @property
    def uri(self) -> str:
        return f"http://{self.host}:{self.port}"

    @property
    def api_uri(self) -> str:
        return f"{self.uri}/api"

    def build_app(self) -> web.Application:
        app = web.Application(client_max_size=256 * 1024 * 1024)
        app.router.add_get("/api/work", self._work)
        app.router.add_post("/api/results", self._results)
        app.router.add_get("/api/models", self._models)
        app.router.add_post("/api/jobs", self._submit)
        app.router.add_post("/api/workflows", self._workflow_submit)
        app.router.add_get("/api/workflows/{workflow_id}",
                           self._workflow_status)
        app.router.add_get("/api/workflows/{workflow_id}/trace",
                           self._workflow_trace)
        app.router.add_post("/api/jobs/{job_id}/cancel", self._cancel)
        app.router.add_post("/api/jobs/{job_id}/checkpoint", self._checkpoint)
        app.router.add_post("/api/jobs/{job_id}/preview", self._preview)
        app.router.add_get("/api/jobs/{job_id}", self._job_status)
        app.router.add_get("/api/jobs/{job_id}/trace", self._job_trace)
        app.router.add_get("/api/usage", self._usage)
        app.router.add_get("/api/tenants/{tenant}/usage", self._tenant_usage)
        app.router.add_get("/api/slo", self._slo)
        app.router.add_get("/api/artifacts/{digest}", self._artifact)
        app.router.add_get("/api/replication/stream", self._replication_stream)
        app.router.add_get("/metrics", self._metrics)
        app.router.add_get("/healthz", self._healthz)
        return app

    async def start(self) -> "HiveServer":
        self._runner = web.AppRunner(self.build_app())
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        # port 0 binds an ephemeral port; report the real one
        self.port = site._server.sockets[0].getsockname()[1]
        self._reaper = asyncio.create_task(
            self._reap_loop(), name="hive_lease_reaper")
        logger.info("hive coordinator on %s (lease %.0fs, queue limit %d)",
                    self.uri, self.leases.deadline_s,
                    self.queue.depth_limit)
        return self

    async def stop(self) -> None:
        if self._reaper is not None:
            self._reaper.cancel()
            await asyncio.gather(self._reaper, return_exceptions=True)
            self._reaper = None
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None
        if self.journal is not None:
            self.journal.close()

    def _journal(self, event: dict) -> None:
        """Append one transition; a journal WRITE failure (full disk,
        bad mount) is logged loudly but never takes serving down — the
        hive degrades to the pre-WAL in-memory semantics it had for five
        PRs rather than refusing jobs it can still run. Injected faults
        (kill_before_journal_sync) DO propagate: they simulate the
        process dying at this exact line."""
        if self.journal is None:
            return
        try:
            self.journal.append(event)
        except OSError:
            logger.exception(
                "hive WAL append failed; this transition is NOT "
                "restart-durable")

    async def __aenter__(self) -> "HiveServer":
        return await self.start()

    async def __aexit__(self, *exc) -> None:
        await self.stop()

    async def _reap_loop(self) -> None:
        """Expire overdue leases on a cadence well inside the deadline,
        so a redelivery waits ~one deadline, not up to two."""
        interval = min(1.0, max(self.leases.deadline_s / 4.0, 0.05))
        while True:
            await asyncio.sleep(interval)
            if self.standby:
                # replicated leases are the PRIMARY's to expire; a
                # standby reaping them would diverge from the stream it
                # is applying (promotion re-grants them fresh instead)
                continue
            try:
                for record in self.leases.reap(self.queue):
                    if record.state == "failed":
                        self._drop_partials(record)
                        self._journal(ev_park(record))
                        for pruned in self.queue.retire(record):
                            self._journal(ev_retire(pruned))
                        self._note_stage_terminal(record, "failed")
                        logger.error("job %s failed: %s",
                                     record.job_id, record.error)
                    else:
                        self._journal(ev_requeue(record))
                        logger.warning(
                            "lease expired for job %s (attempt %d); "
                            "re-queued at the front of class %s",
                            record.job_id, record.attempts,
                            record.job_class)
                self._expire_due()
                self._park_unplaceable()
                self._sweep_spool_if_due()
                # keep the burn-rate gauges fresh between scrapes: the
                # windows slide whether or not anyone polls /api/slo
                self.slo.refresh_metrics()
                if self._usage_dirty:
                    # settles defer the O(history) tenant-gauge refresh
                    # here: once per reaper tick, not once per result
                    self.refresh_usage_metrics()
            except Exception:
                # the reaper is the only thing that frees a dead
                # worker's lease; it must survive any single bad pass
                logger.exception("lease reaper pass failed; continuing")

    def _park_unplaceable(self) -> None:
        """Park queued jobs no live worker can run. A job whose model
        family every live worker advertises as unconverted is skipped by
        dispatch on every poll — it never leases, so the redelivery
        budget never engages, yet it occupies admission depth; enough of
        them wedge the queue at 429 until a restart. Give each one a
        full lease deadline of queue time for a capable worker to show
        up, then fail it with the same parking machinery an exhausted
        lease uses."""
        cutoff = self.queue.clock.mono() - self.leases.deadline_s
        for record in self.queue.iter_queued():
            if record.submitted_at > cutoff:
                continue
            if not self.dispatcher.unplaceable(record):
                continue
            self.queue.discard_queued(record)
            record.state = "failed"
            record.error = (
                "unplaceable: every live worker advertises this job's "
                "model family as unconverted "
                f"(waited {self.leases.deadline_s:g}s)")
            record.timeline.append({
                "event": "park", "wall": self.queue.clock.wall(),
                "reason": "unplaceable"})
            self._drop_partials(record)
            self._journal(ev_park(record))
            for pruned in self.queue.retire(record):
                self._journal(ev_retire(pruned))
            self._note_stage_terminal(record, "failed")
            _JOBS_FAILED.inc()
            logger.error("job %s failed: %s", record.job_id, record.error)

    # artifact-retention cadence: the sweep globs the whole spool tree,
    # so it rides the reaper at most this often, not every pass
    SPOOL_SWEEP_INTERVAL_S = 30.0

    def _sweep_spool_if_due(self) -> None:
        if self.spool_max_bytes <= 0 and self.spool_max_age_s <= 0:
            return
        now = self.queue.clock.mono()
        if now - self._last_spool_sweep < self.SPOOL_SWEEP_INTERVAL_S:
            return
        self._last_spool_sweep = now
        self.sweep_spool()

    def sweep_spool(self) -> int:
        """Age/size-bound the artifact spool. Blobs referenced by a live
        record — any record still answering GET /api/jobs/{id}, i.e. not
        yet pruned from history — are protected: a status poll must keep
        resolving its hrefs. Everything else is fair game — content
        addressing means a re-submitted duplicate simply re-stores the
        blob."""
        protected: set[str] = set()
        for record in self.queue.records.values():
            if not isinstance(record.result, dict):
                continue
            artifacts = record.result.get("artifacts")
            if not isinstance(artifacts, dict):
                continue
            for art in artifacts.values():
                if isinstance(art, dict) and isinstance(
                        art.get("sha256"), str):
                    protected.add(art["sha256"])
        # live mid-pass state (ISSUE 18): a checkpoint awaiting its
        # resume, or previews a poll can still reference, must survive
        # the sweep whatever their age
        protected |= self.queue.partial_digests()
        return self.spool.sweep(self.spool_max_bytes, self.spool_max_age_s,
                                protected)

    # --- auth ---

    def _authorized(self, request: web.Request) -> bool:
        if not self.token:
            return True
        return request.headers.get(
            "Authorization", "") == f"Bearer {self.token}"

    @staticmethod
    def _unauthorized() -> web.Response:
        return web.json_response({"message": "unauthorized"}, status=401)

    # --- replication role + split-brain fencing ---

    def _epoch_headers(self) -> dict[str, str]:
        """Every hive answer advertises the fencing epoch; workers track
        the maximum they have seen and echo it back (X-Hive-Epoch), which
        is what lets a deposed primary discover it was deposed."""
        return {"X-Hive-Epoch": str(self.epoch)}

    def _refuse_not_primary(self) -> web.Response | None:
        if not self.standby:
            return None
        return web.json_response(
            {"message": "not primary: standby replicating "
                        "(fail over to the promoted hive)"},
            status=409, headers=self._epoch_headers())

    def _refuse_stale_epoch(self, request: web.Request) -> web.Response | None:
        """409 any request stamped with a NEWER epoch than ours: the
        caller has talked to a hive promoted over us, so we are a deposed
        primary and our dispatches/ACKs must not count — accepting them
        would double-dispatch the job we think is queued or double-settle
        the one the true primary already owns."""
        raw = request.headers.get("X-Hive-Epoch", "")
        try:
            seen = int(raw)
        except ValueError:
            return None
        if seen <= self.epoch:
            return None
        _STALE_EPOCH.inc()
        logger.error(
            "stale-epoch request refused: caller at epoch %d, this hive "
            "at %d — a standby was promoted over this (deposed) primary",
            seen, self.epoch)
        return web.json_response(
            {"message": f"not primary: stale hive epoch {self.epoch} "
                        f"(the swarm is at epoch {seen}; this hive was "
                        "deposed)"},
            status=409, headers=self._epoch_headers())

    def _refused(self, request: web.Request) -> web.Response | None:
        # explicit None checks: web.Response is a MutableMapping and an
        # empty one is FALSY, so `a or b` would drop a real refusal
        refused = self._refuse_not_primary()
        if refused is not None:
            return refused
        return self._refuse_stale_epoch(request)

    # --- wire-protocol handlers ---

    async def _work(self, request: web.Request) -> web.Response:
        if not self._authorized(request):
            return self._unauthorized()
        refused = self._refused(request)
        if refused is not None:
            _POLLS.inc(reply="refused")
            return refused
        if self.refuse_with is not None:
            _POLLS.inc(reply="refused")
            return web.json_response(
                {"message": self.refuse_with}, status=400)
        query = dict(request.query)
        if not query.get("worker_version"):
            # 400-with-message refusal, reference swarm/hive.py:39-44
            _POLLS.inc(reply="refused")
            return web.json_response(
                {"message": "worker_version is required"}, status=400)
        worker = self.directory.observe(query)
        # park TTL-lapsed queued jobs BEFORE the dispatcher looks: an
        # expired job must never waste this poll's dispatch budget
        self._expire_due()
        if query.get("cancel_only"):
            # heartbeat from a saturated worker (every slice busy): it
            # cannot take work but must still hear about revocations of
            # the leases it is executing — and the observe() above keeps
            # it live in the directory through a long denoise
            handed = []
        else:
            handed = self.dispatcher.select(worker, self.queue)
        for record, outcome, gang in handed:
            # a gang is a dispatch-time grouping, NOT a new lifecycle:
            # each member is taken, leased, and journaled individually —
            # redelivery/settle semantics per job are unchanged, and a
            # lost gang degrades to singles through the normal reaper
            self.queue.take(record, worker.name, outcome, gang=gang)
            self.leases.grant(record, worker.name)
            self._journal(ev_lease(record))
            logger.info("dispatched job %s to %s (%s, attempt %d%s)",
                        record.job_id, worker.name, outcome, record.attempts,
                        f", gang {gang['id']} {gang['index'] + 1}/"
                        f"{gang['size']}" if gang else "")
        # chaos hook: the hive 'dies' after leasing + journaling but
        # before the reply leaves — the worker never sees the jobs, and
        # recovery + lease expiry must redeliver them
        faults.fire("crash_after_lease")
        _POLLS.inc(reply="jobs" if handed else "empty")
        # every handed job carries its trace context on the wire (a copy
        # — the stored job dict stays pristine in the WAL): the worker
        # echoes it back inside the envelope's pipeline_config.trace so
        # its stage spans attach to the right dispatch attempt, and gang
        # members carry trace.gang so they arrive pre-batched. Field
        # set pinned by the protocol-conformance suite.
        jobs_payload = []
        for record, _, gang in handed:
            job = dict(record.job,
                       trace=wire_trace_context(record, gang=gang))
            ck = record.checkpoint
            if (ck and ck.get("sha256") and worker.resume_capable
                    and record.attempts > 1):
                # resume-on-redelivery (ISSUE 18): a redelivered job
                # whose previous lessee shipped a mid-pass checkpoint
                # carries the offer — href to the spooled blob, the
                # step it was cut at, and the program signature the
                # worker validates before rehydrating. Only attached
                # for resume-capable pollers (capability-advertised),
                # so legacy workers see the pre-resume wire shape.
                job["resume"] = {
                    "href": f"/api/artifacts/{ck['sha256']}",
                    "step": int(ck.get("step", 0)),
                    "signature": ck.get("signature"),
                }
                _RESUME_OFFERS.inc()
                record.timeline.append({
                    "event": "resume_offer",
                    "wall": self.queue.clock.wall(),
                    "worker": worker.name,
                    "step": int(ck.get("step", 0))})
            jobs_payload.append(job)
        reply = {"jobs": jobs_payload}
        # piggyback pending lease revocations for THIS worker: the ids
        # of its live leases cancelled since its last poll. Popped on
        # delivery — a reply lost in flight degrades to the job running
        # to completion and its late result earning the `cancelled`
        # disposition (the durable state, not this hint, is the truth).
        # Legacy workers ignore the unknown key; the key is absent when
        # there is nothing to revoke, so the pre-cancel wire shape is
        # byte-identical (conformance-pinned).
        cancels = self._cancel_notify.pop(worker.name, None)
        if cancels:
            reply["cancels"] = sorted(cancels)
            self._refresh_cancel_gauge()
            logger.info("revoking %d cancelled lease(s) from %s: %s",
                        len(cancels), worker.name, sorted(cancels))
        return web.json_response(reply, headers=self._epoch_headers())

    async def _results(self, request: web.Request) -> web.Response:
        # before the body is read and parsed: what follows until the
        # settle stamp is the hive's own work, what came before it the
        # worker's spool, its queue to the uploader and the wire
        received_wall = self.queue.clock.wall()
        if not self._authorized(request):
            return self._unauthorized()
        refused = self._refused(request)
        if refused is not None:
            return refused
        body = await request.read()
        try:
            # a result envelope can be hundreds of MB of base64 blobs
            # (client_max_size above); parsing that on the event loop
            # would stall every other handler and the lease reaper
            result = await asyncio.to_thread(json.loads, body)
        except json.JSONDecodeError:
            return web.json_response(
                {"message": "result envelope is not JSON"}, status=400)
        if not isinstance(result, dict):
            return web.json_response(
                {"message": "result envelope must be a JSON object"},
                status=400)
        job_id = str(result.get("id", ""))
        record = self.queue.records.get(job_id)
        if record is None:
            # a job this hive never issued (e.g. another hive's outbox
            # redelivery): ACK it anyway — a 4xx would make the worker
            # park an envelope the operator may still want
            _RESULTS.inc(status="unknown")
            return web.json_response({"status": "ok", "unknown_job": True})
        if record.state in ("done", "settling"):
            # duplicate submit (outbox redelivery after a lost ACK, or a
            # concurrent POST racing the spool write): idempotent ACK,
            # nothing re-stored
            _RESULTS.inc(status="duplicate")
            return web.json_response({"status": "ok", "duplicate": True})
        if record.state in ("cancelled", "expired"):
            # the cancel/TTL won the race: the result is not stored, but
            # the ACK names the disposition so the worker's outbox can
            # PARK the envelope (reason visible in outbox_inspect)
            # instead of retrying a submission this hive will never
            # accept. The cancel-vs-result race is pinned: whichever
            # settled first wins, this side is an idempotent no-op.
            disposition = record.state
            _RESULTS.inc(status=disposition)
            # only the CURRENT lessee's own envelope proves it knows: a
            # late result from a PREVIOUS lessee (expired lease, job
            # redelivered, then cancelled) must not silence the pending
            # revocation the live lessee still needs to abort its pass
            sender = str(result.get("worker_name") or "") or None
            if record.worker and sender == record.worker:
                pending = self._cancel_notify.get(record.worker)
                if pending and job_id in pending:
                    pending.discard(job_id)
                    if not pending:
                        del self._cancel_notify[record.worker]
                    self._refresh_cancel_gauge()
            return web.json_response(
                {"status": "ok", disposition: True},
                headers=self._epoch_headers())
        # the envelope's own worker_name (stamped by the worker's outbox
        # path; optional on the wire) identifies the true sender — the
        # current lease does NOT: a late result from an expired lessee
        # can arrive while the redelivered copy is leased to someone else
        sender = str(result.get("worker_name") or "") or None
        lease = self.leases.settle(job_id)
        if record.state == "queued":
            # the original lessee answered after expiry, while the
            # redelivered copy was still queued: take the result, cancel
            # the redelivery
            self.queue.discard_queued(record)
            status = "late"
        elif record.state == "failed":
            status = "late"  # better late than parked
        elif sender and lease and sender != lease.worker:
            status = "late"  # an earlier lessee beat the current one
        else:
            status = "ok"
        # "settling" (set with no await point since the state checks
        # above) routes a concurrent duplicate POST to the idempotent
        # ACK; the blob decode/hash/write itself runs in a thread so a
        # multi-MB envelope never stalls /work polls or the lease reaper
        record.state = "settling"
        try:
            stored = await asyncio.to_thread(self.spool.store_result, result)
        except Exception:
            # the spool is an optimization, never a gate on accepting a
            # result: a full/read-only disk keeps the blobs inline rather
            # than wedging the record in "settling" (where the worker's
            # retry would be ACKed as a duplicate and the result lost)
            logger.exception("artifact spool failed for job %s; "
                             "keeping blobs inline", job_id)
            stored = result
        record.result = stored
        record.error = None
        record.done_at = self.queue.clock.mono()
        record.completed_by = (
            sender or (lease.worker if lease else record.worker))
        record.state = "done"
        # the final artifact supersedes every partial (ISSUE 18)
        self._drop_partials(record)
        settle_event = {
            "event": "settle", "wall": self.queue.clock.wall(),
            "worker": record.completed_by, "disposition": status,
            "received_wall": received_wall,
        }
        # the worker echoes the wire trace context; its attempt number
        # ties the envelope's stage spans to the dispatch that produced
        # them (a late result names the EARLIER attempt, visibly)
        echoed_attempt = envelope_trace(stored).get("attempt")
        if isinstance(echoed_attempt, int):
            settle_event["attempt"] = echoed_attempt
        record.timeline.append(settle_event)
        self.queue.observe_settle(record)
        self._journal(ev_settle(record))
        for pruned in self.queue.retire(record):
            self._journal(ev_retire(pruned))
        # stage-graph advance (ISSUE 20): a settled stage-job admits its
        # ready successors (with the settled stage's spool artifacts
        # injected as handoff inputs) and may complete the workflow;
        # records journal before the graph so replay never restores a
        # graph pointing at jobs the WAL has not admitted yet. A
        # monolithic job returns (None, []) and journals nothing extra.
        wf, stage_admitted = self.dag.note_settle(record, self.queue)
        if wf is not None:
            for stage_record in stage_admitted:
                self._journal(ev_admit(stage_record))
            self._journal(ev_dag(wf))
        # tenant accounting (accounting.py): bill this settle. An
        # envelope with no usable stage timings (older worker, a parked-
        # then-requeued outbox redelivery) is billed its wall-clock
        # dispatch-to-settle and COUNTED — approximate beats silently
        # absent from the tenant's ledger. Counted live only; replay
        # rebuilds the ledger without re-counting.
        usage = accounting.job_usage(record)
        if usage is not None and usage["fallback"]:
            accounting.note_fallback()
            logger.warning(
                "job %s settled without pipeline_config.timings; tenant "
                "%s billed wall-clock %.3fs (fallback)", job_id,
                usage["tenant"], usage["chip_us"] / 1e6)
        # the gauge refresh re-scans the retained records (O(history));
        # deferring it to the next reaper tick keeps the settle path
        # O(1) however deep the history runs — /api/usage itself always
        # refreshes, so readers never see the deferral
        self._usage_dirty = True
        _RESULTS.inc(status=status)
        return web.json_response(
            {"status": "ok"}, headers=self._epoch_headers())

    async def _cancel(self, request: web.Request) -> web.Response:
        """POST /api/jobs/{id}/cancel: revoke a job. A QUEUED job is
        tombstoned from its class queue (and the gang index) on the spot;
        a LEASED one has its lease revoked hive-side and the lessee is
        told on its next /work poll (`cancels` piggyback) so a chunked
        denoise can abort within one chunk. Races are pinned: whichever
        settles first wins — cancelling a done/settling job is an
        idempotent no-op (cancelled=False, the result stands), and a
        result arriving after a cancel earns the `cancelled` disposition
        (the worker's outbox parks it instead of retrying forever).
        Every real transition is WAL-journaled before the response
        leaves, so a cancel survives SIGKILL recovery and standby
        promotion exactly like lease state."""
        if not self._authorized(request):
            return self._unauthorized()
        refused = self._refused(request)
        if refused is not None:
            return refused
        job_id = request.match_info["job_id"]
        record = self.queue.records.get(job_id)
        if record is None:
            return web.json_response(
                {"message": "unknown job id"}, status=404)

        def reply(cancelled: bool) -> web.Response:
            return web.json_response({
                "id": job_id,
                "status": record.state,
                "cancelled": cancelled,
            }, headers=self._epoch_headers())

        if record.state == "cancelled":
            return reply(True)  # idempotent repeat
        if record.state in ("done", "settling", "failed", "expired"):
            # the other side of the race already settled; no-op
            return reply(False)
        if record.state == "queued":
            self.queue.mark_cancelled(record, "queued")
            self._drop_partials(record)
            self._journal(ev_cancel(record))
            for pruned in self.queue.retire(record):
                self._journal(ev_retire(pruned))
            self._note_stage_terminal(record, "cancelled")
            logger.info("job %s cancelled while queued", job_id)
            return reply(True)
        # leased: revoke the lease (the reaper must not redeliver a job
        # nobody wants) and queue the revocation for the lessee's next
        # poll; the denoise chunk boundary does the actual abort
        self.leases.settle(job_id)
        self.queue.mark_cancelled(record, "leased")
        self._drop_partials(record)
        self._journal(ev_cancel(record))
        for pruned in self.queue.retire(record):
            self._journal(ev_retire(pruned))
        self._note_stage_terminal(record, "cancelled")
        if record.worker:
            self._cancel_notify.setdefault(
                record.worker, set()).add(job_id)
            self._refresh_cancel_gauge()
        logger.warning(
            "job %s cancelled while leased to %s (attempt %d); lease "
            "revoked, worker notified on its next poll",
            job_id, record.worker, record.attempts)
        return reply(True)

    # --- mid-pass durability (ISSUE 18) ---

    def _note_stage_terminal(self, record, outcome: str) -> None:
        """Stage-graph fail-closed (ISSUE 20): a stage-job that ended
        without settling (cancelled / expired / parked failed) fails its
        workflow — blocked descendants never admit, still-queued siblings
        are cancelled and journaled here, and the updated graph state
        rides ONE ev_dag. No-op for monolithic jobs."""
        wf, cascaded = self.dag.note_terminal(record, outcome, self.queue)
        if wf is None:
            return
        for sibling in cascaded:
            self._drop_partials(sibling)
            self._journal(ev_cancel(sibling))
            for pruned in self.queue.retire(sibling):
                self._journal(ev_retire(pruned))
        self._journal(ev_dag(wf))

    def _drop_partials(self, record) -> None:
        """Terminal states keep no mid-pass state: clear the record's
        checkpoint + previews and delete their now-unreferenced spool
        blobs (the final artifact supersedes every partial)."""
        for digest in self.queue.clear_partial(record):
            self.spool.drop(digest)

    async def _partial_body(self, request: web.Request
                            ) -> tuple[dict | None, bytes | None,
                                       web.Response | None]:
        """Shared validation for checkpoint/preview POSTs: the sender
        must be the job's CURRENT lessee and the job must still be
        leased — a blob from an expired lessee (or for a settled job)
        is refused so stale state can never shadow live state. Returns
        (record_meta, blob, error_response)."""
        import base64
        import binascii

        job_id = request.match_info["job_id"]
        record = self.queue.records.get(job_id)
        if record is None:
            return None, None, web.json_response(
                {"message": "unknown job id"}, status=404)
        try:
            body = json.loads(await request.text())
        except json.JSONDecodeError:
            return None, None, web.json_response(
                {"message": "body is not JSON"}, status=400)
        if not isinstance(body, dict) or not isinstance(
                body.get("blob"), str):
            return None, None, web.json_response(
                {"message": "body must carry a base64 `blob`"}, status=400)
        sender = str(body.get("worker_name") or "") or None
        lease = self.leases.get(job_id)
        if record.state != "leased" or lease is None or (
                sender is not None and sender != lease.worker):
            return {"record": record}, None, web.json_response(
                {"message": f"job is {record.state}; only the current "
                            "lessee may ship mid-pass state",
                 "status": record.state},
                status=409, headers=self._epoch_headers())
        try:
            blob = base64.b64decode(body["blob"])
        except (binascii.Error, ValueError):
            return None, None, web.json_response(
                {"message": "blob is not base64"}, status=400)
        return {"record": record, "body": body}, blob, None

    async def _checkpoint(self, request: web.Request) -> web.Response:
        """POST /api/jobs/{id}/checkpoint: the lessee's mid-pass state
        at a chunk boundary. Spooled content-addressed, recorded on the
        job as ONE WAL event (replayed, compacted, replicated), and only
        the newest kept — a superseded blob is dropped on the spot."""
        if not self._authorized(request):
            return self._unauthorized()
        refused = self._refused(request)
        if refused is not None:
            return refused
        meta, blob, error = await self._partial_body(request)
        if error is not None:
            _CHECKPOINTS.inc(outcome="rejected")
            return error
        record, body = meta["record"], meta["body"]
        digest = await asyncio.to_thread(self.spool.put, blob)
        superseded = self.queue.note_checkpoint(record, {
            "step": int(body.get("step", 0)),
            "sha256": digest,
            "signature": str(body.get("signature", "")),
            "bytes": len(blob),
        })
        if superseded:
            self.spool.drop(superseded)
            _CHECKPOINTS.inc(outcome="superseded")
        self._journal(ev_checkpoint(record))
        _CHECKPOINTS.inc(outcome="stored")
        return web.json_response({
            "status": "ok", "step": int(body.get("step", 0)),
            "sha256": digest,
        }, headers=self._epoch_headers())

    async def _preview(self, request: web.Request) -> web.Response:
        """POST /api/jobs/{id}/preview: an intermediate decode of the
        live latents. Appends to the record's `partial` disposition
        (GET /api/jobs/{id}) and rides the same WAL event as the
        checkpoint meta."""
        if not self._authorized(request):
            return self._unauthorized()
        refused = self._refused(request)
        if refused is not None:
            return refused
        meta, blob, error = await self._partial_body(request)
        if error is not None:
            _PREVIEWS_STORED.inc(outcome="rejected")
            return error
        record, body = meta["record"], meta["body"]
        digest = await asyncio.to_thread(self.spool.put, blob)
        self.queue.note_preview(record, {
            "step": int(body.get("step", 0)),
            "sha256": digest,
            "bytes": len(blob),
            "href": f"/api/artifacts/{digest}",
            **({"content_type": str(body["content_type"])}
               if body.get("content_type") else {}),
        })
        self._journal(ev_checkpoint(record))
        _PREVIEWS_STORED.inc(outcome="stored")
        return web.json_response({
            "status": "ok", "step": int(body.get("step", 0)),
            "href": f"/api/artifacts/{digest}",
        }, headers=self._epoch_headers())

    def _expire_due(self) -> None:
        """Park queued jobs whose admission-time TTL lapsed. Runs before
        every dispatch decision (an expired job must not waste a
        dispatch) and on every reaper pass (so expiry fires even with no
        worker polling)."""
        for record in self.queue.expired_queued():
            self.queue.mark_expired(record)
            self._drop_partials(record)
            self._journal(ev_expire(record))
            for pruned in self.queue.retire(record):
                self._journal(ev_retire(pruned))
            self._note_stage_terminal(record, "expired")
            logger.warning("job %s expired after %.0fs queued (TTL)",
                           record.job_id,
                           self.queue.clock.mono() - record.submitted_at)

    async def _models(self, request: web.Request) -> web.Response:
        # deliberately unauthenticated: public catalog, reference parity
        # (see module docstring) — keep job data and metrics off it
        catalog = _DEFAULT_CATALOG
        path = get_settings_dir() / "models.json"
        try:
            # off-loop (read AND parse): an operator-supplied catalog can
            # be arbitrarily large, and this handler shares the loop
            # with dispatch
            data = await asyncio.to_thread(
                lambda: json.loads(path.read_text()))
            if isinstance(data, dict) and "models" in data:
                catalog = {
                    "models": data.get("models", []),
                    "language_models": data.get("language_models", []),
                }
        except (OSError, json.JSONDecodeError):
            pass
        return web.json_response(catalog)

    # --- coordinator surface ---

    async def _submit(self, request: web.Request) -> web.Response:
        if not self._authorized(request):
            return self._unauthorized()
        refused = self._refused(request)
        if refused is not None:
            return refused
        try:
            job = json.loads(await request.text())
        except json.JSONDecodeError:
            return web.json_response(
                {"message": "job is not JSON"}, status=400)
        if not isinstance(job, dict):
            return web.json_response(
                {"message": "job must be a JSON object"}, status=400)
        known = str(job.get("id") or "") in self.queue.records
        try:
            record = self.queue.submit(job)
        except QueueFull as e:
            return web.json_response({"message": str(e)}, status=429)
        if not known:
            self._journal(ev_admit(record))
        return web.json_response({
            "id": record.job_id,
            "class": record.job_class,
            "tenant": record.tenant,
            "status": record.state,
            "depth": self.queue.depth,
        })

    async def _workflow_submit(self, request: web.Request) -> web.Response:
        """POST /api/workflows: expand a multi-stage submission into its
        stage-job DAG (hive_server/dag.py). The ready stages are admitted
        immediately as ordinary records; successors admit as their needs
        settle. WAL order is records-then-graph (ev_admit per stage, then
        ONE ev_dag carrying the whole workflow state) so replay always
        sees the jobs a restored graph refers to; the reconcile pass in
        __init__ repairs a crash that landed between the two."""
        if not self._authorized(request):
            return self._unauthorized()
        refused = self._refused(request)
        if refused is not None:
            return refused
        try:
            payload = json.loads(await request.text())
        except json.JSONDecodeError:
            return web.json_response(
                {"message": "workflow is not JSON"}, status=400)
        if not isinstance(payload, dict):
            return web.json_response(
                {"message": "workflow must be a JSON object"}, status=400)
        try:
            wf, admitted = self.dag.submit(payload, self.queue)
        except WorkflowError as e:
            return web.json_response({"message": str(e)}, status=400)
        except QueueFull as e:
            return web.json_response({"message": str(e)}, status=429)
        for record in admitted:
            self._journal(ev_admit(record))
        # unconditional: an idempotent resubmit re-appends the same graph
        # state, and restore-by-replacement makes that a no-op on replay
        self._journal(ev_dag(wf))
        return web.json_response({
            "id": wf.workflow_id,
            "workflow": wf.job.get("workflow"),
            "class": job_class(wf.job),
            "tenant": wf.tenant,
            "status": wf.state,
            "stages": [{"stage": s["name"], "index": s["index"],
                        "id": s["job_id"], "status": s["state"]}
                       for s in wf.stages],
            "depth": self.queue.depth,
        }, headers=self._epoch_headers())

    async def _workflow_status(self, request: web.Request) -> web.Response:
        """GET /api/workflows/{id}: the parent aggregation — per-stage
        lifecycle + attempts + worker, the pooled usage totals, and (once
        done) the final stage's result envelope."""
        if not self._authorized(request):
            return self._unauthorized()
        wf = self.dag.workflows.get(request.match_info["workflow_id"])
        if wf is None:
            return web.json_response(
                {"message": "unknown workflow id"}, status=404)
        return web.json_response(self.dag.status(wf, self.queue))

    async def _workflow_trace(self, request: web.Request) -> web.Response:
        """GET /api/workflows/{id}/trace: every stage's timeline merged
        on one wall clock, with the settle->admit seams attributed as
        `stage_handoff` — shaped to pass the same trace_missing oracle a
        monolithic trace does."""
        if not self._authorized(request):
            return self._unauthorized()
        wf = self.dag.workflows.get(request.match_info["workflow_id"])
        if wf is None:
            return web.json_response(
                {"message": "unknown workflow id"}, status=404)
        return web.json_response(
            self.dag.build_trace(wf, self.queue, self.queue.clock.wall()))

    async def _job_status(self, request: web.Request) -> web.Response:
        if not self._authorized(request):
            return self._unauthorized()
        record = self.queue.records.get(request.match_info["job_id"])
        if record is None:
            return web.json_response(
                {"message": "unknown job id"}, status=404)
        return web.json_response(record.status())

    async def _job_trace(self, request: web.Request) -> web.Response:
        """One ordered, gap-attributed timeline per job: hive lifecycle
        events (admit/shed/dispatch/lease/redeliver/settle, WAL-durable)
        merged with the worker's stage spans from the settled envelope
        (a job's life between passes included: `tick_wait`, `poll`,
        `queue_wait`, `format_args`, `handoff`; the `settle` event's
        `received_wall` parts what they leave into the wire's and the
        hive's). See hive_server/trace.py for the assembly contract."""
        if not self._authorized(request):
            return self._unauthorized()
        job_id = request.match_info["job_id"]
        record = self.queue.records.get(job_id)
        if record is None:
            shed = self.queue.shed_traces.get(job_id)
            if shed:
                # never admitted, but we watched it being shed: the
                # refusals ARE its timeline so far
                return web.json_response(build_shed_trace(job_id, shed))
            return web.json_response(
                {"message": "unknown job id"}, status=404)
        return web.json_response(
            build_trace(record, self.queue.clock.wall()))

    async def _usage(self, request: web.Request) -> web.Response:
        """GET /api/usage: the per-tenant ledger — chip-seconds, rows,
        coalesce savings, embed-cache hits, artifact bytes, and fallback
        counts per submitter, plus grand totals. Derived on demand from
        the settled records (accounting.py), so it is exactly as
        crash-consistent and replication-consistent as the records
        themselves; standbys answer it like any other read. Window =
        whatever history the hive retains (hive_job_history_limit), the
        same window GET /api/jobs/{id} answers from."""
        if not self._authorized(request):
            return self._unauthorized()
        summary = self.refresh_usage_metrics()
        return web.json_response(
            accounting.render_usage(summary, self.tenant_topk))

    async def _tenant_usage(self, request: web.Request) -> web.Response:
        """GET /api/tenants/{id}/usage: one tenant's bucket (zeroed when
        the retained history holds nothing for it — an unknown tenant is
        indistinguishable from an idle one by design)."""
        if not self._authorized(request):
            return self._unauthorized()
        return web.json_response(accounting.render_tenant_reply(
            accounting.usage_summary(self.queue.records.values()),
            request.match_info["tenant"]))

    async def _slo(self, request: web.Request) -> web.Response:
        """GET /api/slo: per-class objective compliance and fast/slow
        burn rates over the sliding windows (slo.py). Shape is
        conformance-pinned; with no hive_slo configured the reply
        carries enabled=false and an empty classes map."""
        if not self._authorized(request):
            return self._unauthorized()
        return web.json_response(self.slo.refresh_metrics())

    async def _artifact(self, request: web.Request) -> web.Response:
        if not self._authorized(request):
            return self._unauthorized()
        path = self.spool.path_for(request.match_info["digest"])
        if path is None:
            return web.json_response(
                {"message": "unknown artifact"}, status=404)
        # FileResponse streams via sendfile — a multi-hundred-MB blob
        # neither blocks the event loop nor lands in memory whole
        return web.FileResponse(
            path, headers={"Content-Type": "application/octet-stream"})

    # --- replication (hive_server/replication.py tails this) ---

    async def _replication_stream(self, request: web.Request) -> web.Response:
        """WAL event stream for a standby: events past `since`, or the
        full compacted snapshot with `reset` when the requested position
        was compacted away. Served from the journal's in-memory mirror,
        so a torn tail on disk never reaches a replica."""
        if not self._authorized(request):
            return self._unauthorized()
        if self.journal is None:
            return web.json_response(
                {"message": "replication requires a WAL "
                            "(hive_wal_dir is disabled on this hive)"},
                status=400)
        try:
            since = int(request.query.get("since", "0"))
        except ValueError:
            return web.json_response(
                {"message": "since must be an integer replication "
                            "sequence"}, status=400)
        events, reset = self.journal.stream_since(since)
        return web.json_response({
            "events": events,
            "seq": self.journal.last_rs,
            "reset": reset,
            "epoch": self.epoch,
            "standby": self.standby,
        }, headers=self._epoch_headers())

    # --- telemetry ---

    async def _metrics(self, request: web.Request) -> web.Response:
        return web.Response(
            text=telemetry.REGISTRY.render(),
            headers={
                "Content-Type": "text/plain; version=0.0.4; charset=utf-8"},
        )

    def health(self) -> dict:
        states: dict[str, int] = {}
        for record in self.queue.records.values():
            states[record.state] = states.get(record.state, 0) + 1
        reasons = []
        if (self.queue.depth_limit > 0
                and self.queue.depth >= self.queue.depth_limit):
            reasons.append(
                f"queue full ({self.queue.depth}/{self.queue.depth_limit}): "
                "admission refusing new jobs")
        for cls in self.queue.shedding():
            threshold = self.queue.shed_threshold(cls)
            if threshold < self.queue.depth_limit:
                # partial, class-aware degradation; the full queue is
                # already reported above
                reasons.append(
                    f"shedding {cls} jobs ({self.queue.depth} queued >= "
                    f"{cls} watermark {threshold})")
        if self.refuse_with is not None:
            reasons.append(f"draining: refusing workers ({self.refuse_with})")
        # SLO fast-burn breaches are degraded reasons: a class burning
        # its error budget >FAST_BURN_DEGRADED x over the fast window is
        # exactly what an orchestrator probe should react to
        slo_report = self.slo.refresh_metrics()
        reasons.extend(self.slo.degraded_reasons(slo_report))
        extra: dict = {}
        if self.extra_health is not None:
            # replication.py installs its tail-side view here: a standby
            # reports its lag and goes degraded when the stream stalls
            try:
                extra = dict(self.extra_health() or {})
                reasons.extend(extra.pop("degraded_reasons", []))
            except Exception:  # a broken probe must not break /healthz
                logger.exception("extra health probe failed")
        payload = {
            "status": "degraded" if reasons else "ok",
            "degraded_reasons": reasons,
            "role": "standby" if self.standby else "primary",
            "epoch": self.epoch,
            "uptime_s": round(self.queue.clock.mono() - self.started_at, 1),
            "queue_depth": self.queue.depths(),
            "leases_active": len(self.leases),
            "jobs": states,
            # stage-graph serving (ISSUE 20): workflow counts by state +
            # ready-stage depth — the swarm_top `workflows` line
            "workflows": self.dag.summary(),
            "workers": self.directory.snapshot(),
            # fleet observability plane (ISSUE 11): compact SLO verdict
            # per class, straggler flags per live reporter, and the
            # top-K tenant cut — the swarm_top frames read these
            "slo": {
                cls: {"fast_burn": view["fast_burn"],
                      "slow_burn": view["slow_burn"],
                      "compliance": view["compliance"],
                      "breaching": view["breaching"]}
                for cls, view in slo_report["classes"].items()
            },
            "stragglers": self.fleet.snapshot(self.directory.live_names()),
            # flap detection (ISSUE 18): workers currently preferred-
            # against for fresh seeds (consecutive lease expiries >=
            # hive_flap_threshold), plus the raw streaks behind them
            "flapping": sorted(self.leases.flapping(self.flap_threshold)),
            "flap_streaks": dict(self.leases.flaps),
        }
        if self.journal is not None:
            payload["wal"] = {
                "dir": str(self.journal.root),
                "appends_since_compact": self.journal.appends_since_compact,
                "replayed_events": self.journal.replayed_events,
                "torn_lines": self.journal.torn_lines,
                "recovery": self.recovery,
            }
        payload.update(extra)
        return payload

    async def _healthz(self, request: web.Request) -> web.Response:
        payload = self.health()
        status = 200 if payload.get("status") == "ok" else 503
        return web.json_response(payload, status=status)


async def serve(settings: Settings | None = None, host: str | None = None,
                port: int | None = None) -> None:
    """Run a hive until SIGTERM/SIGINT (tools/hive_serve.py and
    `python -m chiaswarm_tpu.hive_server`). With `hive_standby_of` /
    CHIASWARM_HIVE_STANDBY_OF set, runs as a WAL-shipped STANDBY of that
    primary instead: replicating, health-checking, and self-promoting
    after `hive_failover_grace_s` of primary silence."""
    import signal

    settings = settings or load_settings()
    standby_of = str(getattr(settings, "hive_standby_of", "") or "")
    if standby_of:
        from .replication import StandbyHive

        server = await StandbyHive(
            settings, primary_uri=standby_of, host=host, port=port).start()
        print(f"hive STANDBY on {server.uri} replicating from {standby_of} "
              f"(auto-promotes after "
              f"{getattr(settings, 'hive_failover_grace_s', 10.0)}s of "
              "primary silence)")
    else:
        server = await HiveServer(settings, host=host, port=port).start()
        print(f"hive coordinator listening on {server.uri} "
              f"(workers poll {server.api_uri}/work)")
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop.set)
        except (NotImplementedError, RuntimeError, ValueError):
            pass
    try:
        await stop.wait()
    finally:
        await server.stop()
