"""Worker runtime: poll the hive, fan jobs out to chip slices, upload results.

Loop-shape parity with reference swarm/worker.py:38-196 — 11 s poll cadence
(the longest a worker with room goes without asking: it also asks the
instant it becomes able to take work, `poll_loop`),
121 s backoff on poll errors, bounded work queue, per-slice consumer tasks,
a result-upload task, and the same error policy (transient exceptions become
error-image artifacts and the job "succeeds"; ValueError/TypeError mark the
envelope `fatal_error` so the hive won't resubmit; bad input args take the
fatal path before execution, swarm/worker.py:105-115).

Differences by design:
- `Worker` is a class with injected settings/allocator, so tests run it
  against an in-process fake hive (the reference used module globals and was
  untestable without a live hive).
- The GPU semaphore is replaced by the SliceAllocator; capability
  advertisement aggregates the whole pool (fixing swarm/worker.py:45-62
  which advertised only the last device).
- Jobs execute in a thread pool sized to the slice count, so one slice's
  denoise loop never blocks another slice's or the event loop.
- Between the poll loop and the slice workers sits a BatchScheduler
  (batching.py): compatible txt2img/img2img jobs for the same model and
  shape bucket coalesce — after a short linger window — into ONE padded
  denoise+decode pass per slice, each job keeping its own id, seed, and
  result envelope. Anything the batched program can't express dispatches
  solo, exactly as before. Jobs that arrive pre-batched from a
  gang-scheduling hive (trace.gang on the /work reply, ISSUE 9) skip
  the linger window entirely and flush as one group immediately.
- Released work items land on the scheduler's dispatch board and are
  matched to slices by MODEL RESIDENCY (batching.BatchScheduler.claim +
  chips/allocator residency map): groups route to the slice whose HBM
  and program cache are already warm (affinity), first loads prefer
  unclaimed slices (cold), and an idle slice steals a busy home's group
  rather than idling (cross-slice batch stealing). Outcomes land in
  swarm_placement_total and each envelope's pipeline_config.placement.
- The job lifecycle is fault-tolerant end to end: result envelopes go
  through a durable disk outbox (outbox.py — spooled before upload,
  retried with backoff, redelivered after a restart, unlinked only on
  hive ACK), a per-pass watchdog deadline quarantines-and-probes a slice
  whose execution hangs instead of pinning it forever, SIGTERM drains
  (finish in-flight slices, flush the outbox) instead of cancelling
  mid-denoise, and every failure path is deterministically injectable
  via faults.py.
"""

from __future__ import annotations

import asyncio
import base64
import json
import logging
import os
import random
import signal
import time
from concurrent.futures import ThreadPoolExecutor

from . import __version__, faults, telemetry
from . import cancel as cancel_mod
from . import outbox as outbox_mod
from .batching import ARRIVED, SPANS, BatchScheduler
from .cancel import JobCancelled
from .chips.allocator import SliceAllocator
from .faults import FaultInjected
from .hive import HiveClient, HiveError, hive_endpoints
from .job_arguments import format_args
from .log_setup import setup_logging
from .outbox import Outbox, OutboxEntry
from .post_processors.output_processor import (
    exception_image,
    exception_message,
    fatal_exception_response,
)
from .settings import Settings, load_settings, resolve_path
from .telemetry import Span, trace_job

logger = logging.getLogger(__name__)

# reference cadence is 11 s: the longest a worker that could take work goes
# without asking, not the only time it asks (poll_loop); the env knob
# exists for worker SUBPROCESSES driven by the bench/e2e harness, which
# cannot monkeypatch the module the way the in-process tests do


def _env_poll_seconds() -> float:
    raw = os.environ.get("CHIASWARM_POLL_SECONDS", "")
    try:
        value = float(raw)
    except ValueError:
        if raw:
            logger.warning(
                "CHIASWARM_POLL_SECONDS=%r is not a number; using 11", raw)
        return 11.0
    if value <= 0:  # a zero/negative cadence would busy-loop the hive
        logger.warning(
            "CHIASWARM_POLL_SECONDS=%r must be positive; using 11", raw)
        return 11.0
    return value


POLL_SECONDS = _env_poll_seconds()
ERROR_BACKOFF_SECONDS = 121


def _next_backoff(prev: float) -> float:
    """Poll-error backoff with decorrelated jitter (sleep ~ U(cadence,
    3*prev), capped): repeated failures walk up toward the cap instead of
    hammering the hive at the 11 s cadence, and a fleet that lost the
    hive together does not re-poll in lockstep when it returns."""
    base = float(POLL_SECONDS)
    prev = max(float(prev), base)
    return min(float(ERROR_BACKOFF_SECONDS), random.uniform(base, prev * 3))

_JOBS_POLLED = telemetry.counter(
    "swarm_jobs_polled_total", "Jobs received from hive /work polls")
_POLL_ERRORS = telemetry.counter(
    "swarm_poll_errors_total", "ask_for_work calls that raised")
_JOBS_COMPLETED = telemetry.counter(
    "swarm_jobs_completed_total",
    "Result envelopes produced, by outcome (ok | error | fatal)",
    ("outcome",),
)
_LAST_POLL = telemetry.gauge(
    "swarm_last_poll_unixtime",
    "Wall-clock time of the last successful hive poll")
_SLICES_TOTAL = telemetry.gauge(
    "swarm_slices_total", "Chip slices this worker serves jobs on")
_SLICES_BUSY = telemetry.gauge(
    "swarm_slices_busy", "Chip slices currently executing a job")
_JOBS_IN_FLIGHT = telemetry.gauge(
    "swarm_jobs_in_flight",
    "Jobs accepted from the hive and not yet uploaded")
_QUEUE_DEPTH = telemetry.gauge(
    "swarm_queue_depth",
    "Jobs per internal queue (lingering = open coalescing groups, "
    "ready = released to slice workers, results = awaiting upload)",
    ("queue",),
)
_WATCHDOG_EXPIRED = telemetry.counter(
    "swarm_watchdog_expired_total",
    "Jobs whose execution exceeded the slice watchdog deadline",
    ("kind",),
)
_WATCHDOG_PROBES = telemetry.counter(
    "swarm_watchdog_probe_total",
    "Quarantined-slice smoke probes, by outcome (ok | failed | wedged)",
    ("outcome",),
)
_SLICE_STATE = telemetry.gauge(
    "swarm_slice_state",
    "Chip slices by lifecycle state (active | quarantined)",
    ("state",),
)
_CHECKPOINTS = telemetry.counter(
    "swarm_checkpoints_total",
    "Mid-pass checkpoints cut at denoise chunk boundaries, by outcome "
    "(shipped = the hive stored it; oversize = bigger than "
    "checkpoint_max_bytes, skipped; error = pack or upload failed)",
    ("outcome",),
)
_PREVIEWS = telemetry.counter(
    "swarm_previews_total",
    "Progressive preview frames decoded at denoise chunk boundaries, "
    "by outcome (shipped | error)",
    ("outcome",),
)
_RESUMES = telemetry.counter(
    "swarm_resume_total",
    "Redelivered jobs that arrived with a resume offer, by outcome "
    "(resumed = checkpoint fetched+unpacked and handed to the pipeline; "
    "fetch_failed | unpack_failed degrade to a full pass)",
    ("outcome",),
)
_POLL_OVERSHOOT = telemetry.counter(
    "swarm_poll_overshoot_seconds_total",
    "Seconds the poll loop's timed-out waits lasted beyond what was asked "
    "(a wait cut short by new capacity overshoots nothing): the lag of the "
    "event loop that also carries the uploads, measured where it delays a "
    "poll (per poll: swarm_job_stage_seconds_count{stage=\"poll\"})")
_POLLS = telemetry.counter(
    "swarm_polls_total",
    "Hive /work polls sent, by what ended the wait before them (capacity = "
    "the worker became able to take work: a slice released or reinstated, "
    "the batcher no longer full, a held job cancelled; timer = the cadence "
    "or an error back-off ran out with room for work; heartbeat = the "
    "timer's cancel_only poll of a worker with none)",
    ("cause",),
)
_JOBS_CANCELLED = telemetry.counter(
    "swarm_jobs_cancelled_total",
    "Hive-revoked jobs this worker dropped, by where the cancel caught "
    "them (held = still lingering/on the dispatch board, no envelope "
    "ever produced; executing = aborted or row-dropped mid-denoise at a "
    "chunk boundary; unknown = already delivered or never held)",
    ("stage",),
)


def _deadline_cap_of(job: dict) -> float:
    """The job's own watchdog cap from its `deadline_s` field; 0 = none.
    `deadline_s` is submitter-controlled and forwarded un-validated by
    the hive (its own TTL parse is just as tolerant), so garbage must
    degrade to "no cap", never kill the slice worker task."""
    try:
        cap = float(job.get("deadline_s") or 0.0)
    except (TypeError, ValueError):
        return 0.0
    return cap if cap > 0 else 0.0


class Worker:
    def __init__(
        self,
        settings: Settings | None = None,
        allocator: SliceAllocator | None = None,
        hive_uri: str | None = None,
    ):
        self.settings = settings or load_settings()
        # hive_uri (str or list) pins the endpoints explicitly (tests,
        # LocalSwarm); otherwise Settings decides — sdaas_uris names the
        # primary+standby set for client-side failover, sdaas_uri the
        # classic single hive
        self.hive_uri = (
            hive_uri if hive_uri is not None
            else hive_endpoints(self.settings))
        if isinstance(self.hive_uri, list) and len(self.hive_uri) == 1:
            self.hive_uri = self.hive_uri[0]
        self.allocator = allocator or SliceAllocator(
            chips_per_job=self.settings.chips_per_job,
            tensor_parallelism=self.settings.tensor_parallelism,
            sequence_parallelism=self.settings.sequence_parallelism,
        )
        self.hive = HiveClient(self.settings, self.hive_uri)
        coalesce = max(int(getattr(self.settings, "max_coalesce", 8)), 1)
        self.batcher = BatchScheduler(
            linger_s=float(getattr(self.settings, "batch_linger_ms", 50.0))
            / 1000.0,
            max_coalesce=coalesce,
            # released (ready) work keeps the round-5 work-queue bound, so
            # unbatchable traffic never hoards jobs other workers could
            # take; only jobs lingering toward a coalesced pass get the
            # extra in-flight allowance
            maxsize=len(self.allocator) * coalesce,
            ready_maxsize=len(self.allocator),
            rows_limit=self._coalesce_rows_limit,
            # interactive preemption probe: other lingering groups flush
            # when an interactive dispatch finds slices contended
            free_slices=lambda: self.allocator.free_count,
            # distinct-adapter cap per coalesced group (ISSUE 13) — the
            # stacked-factor slot dimension run_batched enforces
            lora_slots=int(getattr(self.settings, "lora_slots_max", 8) or 8),
        )
        # a slice returning to the free pool re-runs the placement match,
        # so a board entry blocked on "no slice free" dispatches the
        # moment release()/reinstate() happens
        self.allocator.add_free_listener(self.batcher.notify)
        # since when (wall) this worker could have taken work and had not
        # asked, None while it cannot; and when its last poll ended: what
        # `tick_wait` is reckoned from (_note_capacity, poll_loop)
        self._able_since: float | None = None
        self._polled_at = time.time()
        # set where `_able_since` goes from None to an instant: what ends
        # the poll loop's wait ahead of its timer (_wait_to_poll)
        self._capacity = asyncio.Event()
        # the last poll raised: its back-off is slept whole
        self._poll_failed = False
        self.allocator.add_free_listener(self._note_capacity)
        self.result_queue: asyncio.Queue = asyncio.Queue()
        # durable result spool: envelopes land here BEFORE the first
        # upload attempt and are unlinked only on hive ACK (outbox.py)
        self.outbox = Outbox(
            resolve_path(getattr(self.settings, "outbox_dir", "outbox")),
            max_entries=int(getattr(self.settings, "outbox_max_entries", 512)),
        )
        if getattr(self.settings, "fault_injection", ""):
            faults.configure(self.settings.fault_injection)
        self._executor = ThreadPoolExecutor(
            max_workers=len(self.allocator), thread_name_prefix="chipslice"
        )
        self._stopping = asyncio.Event()
        self._draining = asyncio.Event()
        self._probe_tasks: set[asyncio.Task] = set()
        # slice id -> its passes whose results are not spooled yet, oldest
        # first: a pass's images are packaged after the slice is free,
        # by a task that waits for the one before it (_pass_ended)
        self._deliveries: dict[int, list[asyncio.Task]] = {}
        self._delivering = 0  # entries popped from result_queue, not yet acked
        # job ids currently claimed by a slice (the cancel router's
        # "executing" test: a hive revocation for one of these marks the
        # process-wide cancel registry the chunked denoise probes)
        self._executing_ids: set[str] = set()
        # host-path stage lane (ISSUE 20): encode/decode/postprocess
        # stage-jobs bypass the BatchScheduler and the slice allocator —
        # they run on the default executor, so the decode of pass N
        # overlaps the denoise of pass N+1 instead of holding its slice
        self._stage_queue: asyncio.Queue = asyncio.Queue()
        self._stage_inflight = 0
        self._stage_queued_ids: set[str] = set()
        self._stage_cancelled: set[str] = set()
        self._metrics_runner = None
        self._runtime: dict = {}  # runtime_report(), filled at startup()
        self._profiling = False  # one on-demand profiler capture at a time
        # per-stage EWMA of this worker's OWN envelope stage timings
        # (stage -> [ewma_seconds, samples]), piggybacked on every /work
        # poll as the `stats` query param so the hive's fleet view
        # (hive_server/fleet.py) can spot a straggler slice that looks
        # healthy in isolation. Per-instance state, fed from the settled
        # envelopes in _finish_result — deliberately NOT the process-
        # global stage histogram, so in-process multi-worker harnesses
        # report per-worker truth.
        self._stage_stats: dict[str, list] = {}
        self._stats_alpha = min(max(float(getattr(
            self.settings, "hive_stats_ewma_alpha", 0.2) or 0.2), 0.01), 1.0)
        # monotonic time of the last SUCCESSFUL hive poll (healthz age)
        self._last_poll_monotonic: float | None = None
        self._poll_backoff_s = float(POLL_SECONDS)

    # --- lifecycle ---

    async def run(self) -> None:
        self.startup()
        await self._start_metrics_server()
        loop = asyncio.get_running_loop()
        sigterm_installed = False
        try:
            # rolling restarts send SIGTERM: drain instead of dropping work
            loop.add_signal_handler(signal.SIGTERM, self.stop, True)
            sigterm_installed = True
        except (NotImplementedError, RuntimeError, ValueError):
            pass  # non-unix / nested loop: stop(drain=True) still works
        # redeliver envelopes a previous process spooled but never got
        # ACKed (outbox contract: at-least-once across restarts)
        recovered = self.outbox.recover()
        for entry in recovered:
            self.result_queue.put_nowait(entry)
        if recovered:
            logger.warning(
                "outbox: redelivering %d spooled result(s) from a previous run",
                len(recovered))
        tasks = [
            asyncio.create_task(self.slice_worker(), name=f"slice_worker_{i}")
            for i in range(len(self.allocator))
        ]
        for i in range(int(getattr(self.settings, "stage_workers", 2) or 0)):
            tasks.append(asyncio.create_task(
                self.stage_worker(), name=f"stage_worker_{i}"))
        tasks.append(asyncio.create_task(self.result_worker(), name="result_worker"))
        tasks.append(asyncio.create_task(self.poll_loop(), name="poll_loop"))
        tasks.append(asyncio.create_task(self._drain_watcher(), name="drain_watcher"))
        try:
            await self._stopping.wait()
        finally:
            if sigterm_installed:
                try:
                    loop.remove_signal_handler(signal.SIGTERM)
                except (NotImplementedError, RuntimeError, ValueError):
                    pass
            tasks += [t for ts in self._deliveries.values() for t in ts]
            for t in [*tasks, *self._probe_tasks]:
                t.cancel()
            await asyncio.gather(
                *tasks, *self._probe_tasks, return_exceptions=True)
            await self.hive.close()
            if self._metrics_runner is not None:
                await self._metrics_runner.cleanup()
                self._metrics_runner = None
            self._executor.shutdown(wait=False, cancel_futures=True)

    def stop(self, drain: bool = False) -> None:
        """Stop the worker. drain=False (default) cancels immediately —
        spooled envelopes survive on disk for the next start. drain=True
        (the SIGTERM path) stops polling, finishes in-flight slices, and
        flushes the outbox up to Settings.drain_deadline_s first, so a
        rolling restart loses zero work."""
        if drain:
            self._draining.set()
        else:
            self._stopping.set()

    async def _drain_watcher(self) -> None:
        await self._draining.wait()
        deadline = time.monotonic() + max(
            float(getattr(self.settings, "drain_deadline_s", 120.0)), 0.0)
        logger.warning(
            "drain: polls stopped; flushing %d in-flight job(s) and the outbox",
            self.batcher.outstanding_jobs)
        # lingering coalescing groups dispatch now; nothing new lingers
        self.batcher.close()
        while time.monotonic() < deadline:
            # deliverable work = executing jobs + queued/in-flight uploads;
            # NOT outbox.depth, which also counts parked (permanently
            # refused) envelopes that only a restart may retry
            if (self.batcher.outstanding_jobs == 0
                    and self._stage_queue.qsize() == 0
                    and self._stage_inflight == 0
                    and self.result_queue.qsize() == 0
                    and self._delivering == 0):
                logger.warning("drain complete: no in-flight work remains")
                break
            await asyncio.sleep(0.05)
        else:
            logger.error(
                "drain deadline hit with %d job(s) in flight and %d spooled "
                "envelope(s); exiting — spooled results redeliver on restart",
                self.batcher.outstanding_jobs, self.outbox.depth)
        self._stopping.set()

    def startup(self) -> None:
        setup_logging(
            resolve_path(self.settings.log_filename),
            self.settings.log_level,
            getattr(self.settings, "log_format", "plain"),
        )
        logger.info("chiaSWARM-TPU worker %s", __version__)
        caps = self.allocator.capabilities()
        from .chips.device import runtime_report

        # what this worker actually serves on, said out loud: a worker
        # that found no chip serves on the CPU (tests do), but never
        # without a word
        self._runtime = runtime_report()
        banner = (
            "serving on platform={platform} device_kind={device_kind} "
            "devices={device_count} (jax {jax}, jaxlib {jaxlib}, "
            "libtpu {libtpu})".format(**self._runtime))
        logger.info(banner)
        print(
            f"Found {caps['chips']} chips ({caps['topology']}), "
            f"{len(self.allocator)} job slice(s); {banner}"
        )
        _SLICES_TOTAL.set(len(self.allocator))
        self._enable_compilation_cache()
        self._start_profiler_server()
        telemetry.mark_startup("worker_started")

    async def _start_metrics_server(self) -> None:
        """Local telemetry endpoint (telemetry.py): GET /metrics in
        Prometheus text format, GET /healthz with last-poll age, resident
        models, and per-slice busy state. Sits next to the jax.profiler
        server; Settings.metrics_port / CHIASWARM_METRICS_PORT picks the
        port, 0 disables. Never fatal — a busy port costs the scrape, not
        the worker."""
        port = int(getattr(self.settings, "metrics_port", 0) or 0)
        if not port:
            return
        try:
            from . import memory_census, programs
            from .telemetry import start_metrics_server

            self._metrics_runner = await start_metrics_server(
                port,
                health=self._health,
                host=getattr(self.settings, "metrics_host", "127.0.0.1"),
                profile=self._capture_profile,
                # the profile hook mutates; it requires the same bearer
                # token the worker itself is provisioned with
                token=str(getattr(self.settings, "sdaas_token", "")),
                # ISSUE 17 cost plane: the compiled-program ledger and the
                # fleet byte census, both read-only snapshots
                programs=programs.snapshot,
                memory=memory_census.census,
            )
            logger.info("metrics server on :%d", port)
        except Exception as e:  # observability is an add-on, never fatal
            logger.warning("metrics server unavailable: %s", e)

    async def _capture_profile(self, seconds: float) -> dict:
        """On-demand jax.profiler capture (POST /debug/profile?seconds=N
        on the metrics app): traces this process for `seconds` and writes
        a perfetto/TensorBoard trace bundle under $SDAAS_ROOT/profiles/.
        Gated by Settings.profiler_capture (off by default — a profile
        exposes prompts and timings, so arming it is an operator
        decision), and serialized: jax keeps one global tracer, so a
        second concurrent capture answers 409 instead of corrupting the
        first."""
        if not bool(getattr(self.settings, "profiler_capture", False)):
            raise PermissionError(
                "profiler capture is disabled; set profiler_capture=true "
                "(CHIASWARM_PROFILER_CAPTURE=1) to arm it")
        if self._profiling:
            raise RuntimeError("a profiler capture is already running")
        import jax.profiler

        # nanosecond suffix: two captures starting in the same wall-clock
        # second must not interleave their bundles in one directory
        out_dir = resolve_path("profiles") / (
            time.strftime("trace_%Y%m%d_%H%M%S")
            + f"_{time.time_ns() % 1_000_000_000:09d}")
        self._profiling = True
        try:
            def run() -> None:
                with jax.profiler.trace(str(out_dir)):
                    time.sleep(seconds)

            # off-loop: the capture sleeps for the whole window and the
            # metrics app must keep answering scrapes meanwhile
            await asyncio.get_running_loop().run_in_executor(None, run)
        finally:
            self._profiling = False
        logger.warning("profiler capture (%.1fs) written under %s",
                       seconds, out_dir)
        return {"path": str(out_dir), "seconds": seconds}

    def _health(self) -> dict:
        """/healthz snapshot: is this worker polling, what is resident,
        which slices serve. Reports `degraded` (telemetry.py answers 503)
        when polling has stalled, a slice is quarantined, or the outbox is
        saturated — so an orchestrator can act instead of trusting an
        unconditional "ok"."""
        from .registry import resident_models

        age = None
        if self._last_poll_monotonic is not None:
            age = round(time.monotonic() - self._last_poll_monotonic, 1)
        reasons = []
        # a stale poll only means trouble when the worker SHOULD be
        # polling — the loop intentionally pauses while draining, while
        # every slice is busy, or while the batcher is full, and a worker
        # mid-denoise must not probe as unhealthy
        expects_polls = (not self._draining.is_set()
                         and self.allocator.has_free_slice()
                         and not self.batcher.full())
        if expects_polls and age is not None and age > 3 * POLL_SECONDS:
            reasons.append(
                f"last successful poll {age:.0f}s ago "
                f"(cadence {POLL_SECONDS}s)")
        quarantined = self.allocator.quarantined_count
        if quarantined:
            reasons.append(f"{quarantined} slice(s) quarantined")
        if self.outbox.saturated:
            reasons.append(
                f"outbox saturated ({self.outbox.depth} spooled envelopes)")
        # ISSUE 17: HBM squeeze probe. Opt-in (threshold 0 = off) because
        # a healthy steady state legitimately keeps HBM near-full on some
        # fleets; CPU smoke reports no bytes_limit -> headroom None ->
        # never fires
        headroom = None
        threshold = float(
            getattr(self.settings, "memory_headroom_degraded", 0.0) or 0.0)
        if threshold > 0:
            from . import memory_census

            headroom = memory_census.device_headroom()
            if headroom is not None and headroom < threshold:
                reasons.append(
                    f"device HBM headroom {headroom:.1%} below "
                    f"{threshold:.1%}")
        oldest = self.outbox.oldest_age_s()
        return {
            "status": "degraded" if reasons else "ok",
            "degraded_reasons": reasons,
            "worker_version": __version__,
            # platform / device_kind / device_count as jax reports them,
            # plus the jax, jaxlib and libtpu versions (chips/device.py)
            "runtime": self._runtime,
            "last_poll_age_s": age,
            "memory_headroom_ratio": headroom,
            "draining": self._draining.is_set(),
            "jobs_in_flight": self.batcher.outstanding_jobs,
            "results_pending": self.result_queue.qsize(),
            # host-path stage lane (ISSUE 20)
            "stage_lane": {
                "queued": self._stage_queue.qsize(),
                "inflight": self._stage_inflight,
                "workers": int(getattr(
                    self.settings, "stage_workers", 2) or 0),
            },
            "outbox": {
                "depth": self.outbox.depth,
                "oldest_age_s": round(oldest, 1) if oldest else 0,
                "saturated": self.outbox.saturated,
            },
            # multi-hive failover view (hive.py): which endpoint this
            # worker is pinned to, and how often it has had to move
            "hive": {
                "active_endpoint": self.hive.hive_uri,
                "endpoints": list(self.hive.endpoints),
                "failovers": self.hive.failovers,
                "epoch": self.hive.epoch,
            },
            "resident_models": resident_models(),
            "slices": [
                {
                    "slice_id": s.slice_id,
                    "chips": s.chip_count(),
                    "busy": s.busy,
                    "state": ("quarantined"
                              if self.allocator.is_quarantined(s)
                              else "active"),
                    # per-slice warm models (the placement layer's view):
                    # which slice the dispatch board would route each
                    # model's next group to
                    "resident": s.resident_models(),
                    # the mesh view of the slice's most recent pass
                    # (ISSUE 12): data-parallel for coalesced batch
                    # traffic, tensor/seq-sharded for interactive solos
                    "geometry": s.geometry_str(),
                }
                for s in self.allocator.slices
            ],
        }

    def _update_queue_gauges(self) -> None:
        _JOBS_IN_FLIGHT.set(self.batcher.outstanding_jobs)
        _SLICES_BUSY.set(len(self.allocator) - self.allocator.free_count)
        _QUEUE_DEPTH.set(self.batcher.pending_jobs, queue="lingering")
        _QUEUE_DEPTH.set(self.batcher.ready_jobs, queue="ready")
        _QUEUE_DEPTH.set(self.result_queue.qsize(), queue="results")
        _QUEUE_DEPTH.set(
            self._stage_queue.qsize() + self._stage_inflight, queue="stage")
        quarantined = self.allocator.quarantined_count
        _SLICE_STATE.set(len(self.allocator) - quarantined, state="active")
        _SLICE_STATE.set(quarantined, state="quarantined")
        self.outbox.refresh_gauges()

    def _start_profiler_server(self) -> None:
        """jax.profiler trace endpoint (SURVEY §5 'tracing/profiling:
        absent' in the reference — rebuilt as a first-class worker
        capability). Connect with TensorBoard's profile plugin or
        `jax.profiler.trace_function` tooling against localhost:PORT;
        0 disables."""
        port = int(getattr(self.settings, "profiler_port", 0) or 0)
        if not port:
            return
        try:
            import jax.profiler

            jax.profiler.start_server(port)
            logger.info("jax profiler server on :%d", port)
        except Exception as e:  # profiling is an optimization, never fatal
            logger.warning("profiler server unavailable: %s", e)

    def _enable_compilation_cache(self) -> None:
        """Persistent XLA compilation cache — the TPU analog of the reference's
        warm HF model cache (SURVEY §5 'checkpoint/resume'). Placed from
        outside (compile_cache.py): JAX_COMPILATION_CACHE_DIR, else the
        fixed in-checkout path. An unwritable directory stops the worker
        at start-up instead of serving every restart cold."""
        from .compile_cache import enable_compile_cache

        logger.info("persistent compile cache at %s", enable_compile_cache())

    def _capabilities(self) -> dict:
        """Chip capabilities plus the model-layer honesty key: families
        with no real-weight conversion path are advertised as unconverted
        so a capability-aware hive stops scheduling jobs this worker can
        only fail (VERDICT r03 weak #7); legacy hives ignore the key."""
        from .chips.requirements import (
            coalesce_rows_limit,
            flux_admissible,
            min_chips,
            SEQUENCE_REFERENCE_POSITIONS,
            pass_positions_limit,
        )
        from .text_families import TEXT_FAMILIES
        from .weights import UNCONVERTED_FAMILY_KEYWORDS

        caps = dict(self.allocator.capabilities())
        caps["platform"] = self.allocator.slices[0].platform
        caps["device_kind"] = self.allocator.slices[0].name()
        caps["unconverted_families"] = ",".join(UNCONVERTED_FAMILY_KEYWORDS)
        # flux cannot fit one 16 GB chip resident (VERDICT r03 item 4), but
        # weight streaming serves it there anyway (VERDICT r04 missing #2).
        # flux_admissible IS the job gate (check_capacity routes flux
        # through it), evaluated on an actual job slice, so the hive's
        # placement decision matches admission exactly.
        flux = "black-forest-labs/FLUX.1-dev"
        job_slice = self.allocator.slices[0]
        allowed, _ = flux_admissible(job_slice, 1, 1024, model_name=flux)
        caps["flux_runnable"] = int(bool(allowed))
        if job_slice.platform == "tpu":
            per_chip = job_slice.hbm_bytes() / (1 << 30) / max(
                job_slice.chip_count(), 1
            )
            # chips a slice would need at full TP — the remediation the
            # hive/operator can act on when flux_runnable is 0
            caps["flux_min_chips"] = min_chips(flux, max(per_chip, 1e-6))
        # slice geometry advertisement (ISSUE 12): how many chips one job
        # slice spans, and whether this worker will run an interactive
        # job as ONE sharded program over them (shard_interactive AND a
        # multi-chip slice). A geometry-aware hive prefers a
        # shard-capable worker for interactive seeds; legacy hives
        # ignore both keys.
        caps["chips_per_slice"] = job_slice.chip_count()
        caps["shard_capable"] = int(
            bool(getattr(self.settings, "shard_interactive", False))
            and job_slice.shard_capable)
        # live-load snapshot riding the heartbeat: a capability-aware hive
        # can place by actual occupancy instead of round-robin (legacy
        # hives ignore unknown query params)
        caps["jobs_in_flight"] = self.batcher.outstanding_jobs
        caps["busy_slices"] = len(self.allocator) - self.allocator.free_count
        # in-flight IMAGE ROWS (lingering + ready + executing; ISSUE 9):
        # the hive's gang budget is row-denominated — counting jobs, or
        # skipping executing work, would let a gang reply oversubscribe
        # a slice that is mid-coalesce. Versioning note: a pre-gang hive
        # reads this with the old jobs-excl-executing semantics and
        # under-feeds this worker while a coalesced batch executes —
        # transient, conservative (never oversubscribes), and gone once
        # the coordinator is upgraded (it keys the new arithmetic off
        # the gang_rows param below)
        caps["queue_depth"] = self.batcher.outstanding_rows
        # per-slice coalescing appetite: how many rows this worker will
        # merge into ONE pass (the hive sizes gangs by it; 1 = solo-only).
        # max_coalesce is a JOB cap, so for multi-image jobs this
        # under-states the slice's true row capacity — deliberately
        # conservative: gangs under-fill rather than oversubscribe, and
        # put_gang re-chunks anything that still doesn't fit
        caps["gang_rows"] = max(self.batcher.max_coalesce, 1)
        # a family whose rows are sequences has an appetite of its own,
        # from admission (the weights it holds and a row's cache bytes,
        # chips/requirements.py) and not from the job cap: a text job is
        # dozens of rows and a pass worth running hundreds (a family's key
        # resolves to itself as a model's name, `_family_key`)
        caps["family_gang_rows"] = ",".join(
            f"{family}:" + str(coalesce_rows_limit(
                job_slice, family, SEQUENCE_REFERENCE_POSITIONS))
            for family in TEXT_FAMILIES)
        # ... and that number is the appetite at a reference length; the
        # cached positions a pass may hold let the hive reckon a gang at
        # the job's own prompt slots + new tokens, as the batcher here
        # does (`_coalesce_rows_limit`), so the hive's gang is the pass
        caps["family_gang_positions"] = ",".join(
            f"{family}:{pass_positions_limit(job_slice, family)}"
            for family in TEXT_FAMILIES)
        # preemption tolerance (ISSUE 18): a chunked, checkpoint-armed
        # worker can rehydrate a redelivered job from a hive-held
        # checkpoint; the hive attaches `resume` offers only to workers
        # advertising this (legacy hives ignore the key)
        caps["resume_capable"] = int(
            int(getattr(self.settings, "denoise_chunk_steps", 0) or 0) > 0
            and int(getattr(
                self.settings, "checkpoint_every_chunks", 0) or 0) > 0)
        # stage-typed placement (ISSUE 20): the stage names this worker
        # serves. A stage-graph hive gates stage-job hand-outs on this;
        # omitting the key entirely (stage_roles="none") keeps the
        # legacy wire shape — such a worker sees only monolithic jobs.
        stages = self._stage_roles()
        if stages is not None:
            caps["stages"] = ",".join(sorted(stages))
        caps["jobs_completed"] = int(_JOBS_COMPLETED.total())
        if self._last_poll_monotonic is not None:
            caps["last_poll_age_s"] = round(
                time.monotonic() - self._last_poll_monotonic, 1)
        # compact per-stage EWMA blob for the hive's straggler detector
        # (hive_server/fleet.py): {"a": alpha, "s": {stage: [ewma, n]}}.
        # Sent only once samples exist; legacy hives ignore the key.
        if self._stage_stats:
            caps["stats"] = json.dumps(
                {"a": self._stats_alpha,
                 "s": {stage: [round(ewma, 4), n]
                       for stage, (ewma, n) in self._stage_stats.items()}},
                separators=(",", ":"))
        return caps

    def _stage_roles(self) -> frozenset[str] | None:
        """Stage names to advertise on /work, or None for the legacy
        (no `stages` param) shape. "auto": a chip-bearing worker serves
        every stage; the host (CPU) stages are advertised only while the
        stage lane has consumers. An explicit csv passes through, minus
        the CPU stages when the lane is disabled — advertising a stage
        no coroutine will ever pop would strand its jobs until lease
        expiry."""
        from .coalesce import CHIP_STAGES, CPU_STAGES

        raw = str(getattr(self.settings, "stage_roles", "auto")
                  or "auto").strip()
        if raw.lower() == "none":
            return None
        host_ok = int(getattr(self.settings, "stage_workers", 2) or 0) > 0
        if raw.lower() == "auto":
            roles = set(CHIP_STAGES)
            if host_ok:
                roles |= CPU_STAGES
            return frozenset(roles)
        roles = {s.strip() for s in raw.split(",") if s.strip()}
        if not host_ok:
            roles -= CPU_STAGES
        return frozenset(roles)

    def _note_stage_stats(self, timings: dict) -> None:
        """Fold one PASS's stage spans into the per-stage EWMAs the
        `stats` poll param advertises. Called once per physical pass
        (a coalesced group's envelopes share copied timings — folding
        each would fake the hive's min-samples confidence gate with one
        observation), and waiting stages are excluded: queue_wait
        measures THIS worker's backlog, which is load, not slowness —
        folding it in would let the hive's own uneven dispatch
        manufacture a 'straggler'."""
        for key, value in timings.items():
            if not (isinstance(key, str) and key.endswith("_s")):
                continue
            if key == "queue_wait_s":
                continue
            try:
                v = float(value)
            except (TypeError, ValueError):
                continue
            if v < 0:
                continue
            stage = key[:-2]
            entry = self._stage_stats.get(stage)
            if entry is None:
                self._stage_stats[stage] = [v, 1]
            else:
                entry[0] += self._stats_alpha * (v - entry[0])
                entry[1] += 1

    # --- producer: poll the hive ---

    def _note_capacity(self) -> None:
        """Called wherever the answer to "could this worker take work"
        may have changed (a slice released or claimed, a batch done, a
        held job cancelled, every tick of the poll), always on the event
        loop: keeps the instant it last became yes, and on becoming yes
        wakes the poll loop (once a transition, however many slices came
        free with it; never while draining)."""
        if (self._draining.is_set() or self.batcher.full()
                or not self.allocator.has_free_slice()):
            self._able_since = None
        elif self._able_since is None:
            self._able_since = time.time()
            self._capacity.set()

    async def poll_loop(self) -> None:
        sleep_seconds, cause = POLL_SECONDS, "timer"
        while True:
            sleep_seconds = await self._poll_once(sleep_seconds, cause)
            self._poll_backoff_s = sleep_seconds
            self._update_queue_gauges()
            cause = await self._wait_to_poll(sleep_seconds)

    async def _wait_to_poll(self, seconds: float) -> str:
        """The wait between two polls: over at the timer ("timer") or at
        the instant the worker becomes able to take work ("capacity"),
        whichever comes first, so the cadence is the longest a worker
        with room goes without asking. A poll that brings nothing leaves
        `_able_since` set, so no second wake-up follows it: an empty hive
        sees one poll a capacity transition and then the timer. A poll
        error's back-off is slept whole (the hive is struggling), and a
        wake-up whose capacity is gone again by the time the loop runs
        (the board had work for the slice) leaves the timer as it was,
        which keeps a busy worker's heartbeat on its cadence."""
        asked = time.monotonic()
        if self._poll_failed:
            await asyncio.sleep(seconds)
        else:
            while (left := asked + seconds - time.monotonic()) > 0:
                try:
                    await asyncio.wait_for(self._capacity.wait(), left)
                except asyncio.TimeoutError:
                    break
                self._capacity.clear()
                self._note_capacity()
                if self._able_since is not None:
                    return "capacity"
        _POLL_OVERSHOOT.inc(max(time.monotonic() - asked - seconds, 0.0))
        return "timer"

    async def _poll_once(self, sleep_seconds: float,
                         cause: str = "timer") -> float:
        """One turn of the poll: ask the hive if there is reason to, feed
        what comes to the batcher; returns the seconds to wait next.
        `cause`: what ended the wait before it (`swarm_polls_total`)."""
        self._note_capacity()
        # this poll answers every wake-up so far, its own included
        self._capacity.clear()
        can_take = self._able_since is not None
        # cancel-only heartbeat (ISSUE 10): a worker whose every
        # slice is busy used to go silent for the whole denoise —
        # exactly the window in which a cancel matters most. It now
        # keeps polling with `cancel_only=1`: the hive skips dispatch
        # (and a legacy hive that hands jobs anyway just feeds the
        # batcher early), keeps the worker live in its directory,
        # and piggybacks lease revocations for the executing slices.
        heartbeat = (not can_take and not self._draining.is_set()
                     and self.batcher.outstanding_jobs > 0)
        if not (can_take or heartbeat):
            return sleep_seconds
        try:
            caps = self._capabilities()
            if heartbeat:
                caps["cancel_only"] = 1
            _POLLS.inc(cause="heartbeat" if heartbeat else cause)
            telemetry.mark_startup("first_poll")
            sent = time.time()
            # span "tick_wait": the worker could have asked and had not,
            # from the later of its last poll's end and the instant it
            # became able to take work to this request: the loop's
            # wake-up latency where new capacity ended the wait, the
            # sleep (and what it overshot) where the timer did; stamped
            # once the poll has brought jobs, on them
            idle_from = (sent if self._able_since is None
                         else min(max(self._polled_at, self._able_since),
                                  sent))
            jobs = await self.hive.ask_for_work(caps)
            received = self._polled_at = time.time()
            self._last_poll_monotonic = time.monotonic()
            _LAST_POLL.set(received)
            # span "poll": request sent -> reply parsed, the hive's
            # dispatch and gang-forming inside; it ran across an await, so
            # it is recorded and opens no annotation. Every poll lands in
            # the stage histogram, one that brought jobs in their
            # envelopes too
            brought: list[dict] = []
            Span("poll", thread="poll",
                 spans=brought if jobs else None).record(
                     sent, received - sent)
            if jobs:
                Span("tick_wait", thread="wait", spans=brought).record(
                    idle_from, sent - idle_from)
            # a gang-scheduling hive groups same-key jobs in one
            # reply and marks them with trace.gang; same-gang
            # jobs enter the BatchScheduler as ONE pre-formed
            # group (immediate flush, no linger — the hive
            # already did the waiting). Everything else takes
            # the classic per-job put() path.
            gangs: dict[str, list[dict]] = {}
            intake: list[tuple[str, object]] = []
            for job in jobs:
                print(f"Got job {job['id']}")
                _JOBS_POLLED.inc()
                # queue_wait starts where the poll ended; the slice
                # worker takes the stamp and the spans off the job when
                # it picks it up
                job[ARRIVED], job[SPANS] = received, list(brought)
                gang_id = None
                if isinstance(job.get("trace"), dict):
                    gang = job["trace"].get("gang")
                    if isinstance(gang, dict) and gang.get("id"):
                        gang_id = str(gang["id"])
                # stage-jobs (ISSUE 20): hydrate the predecessor
                # handoff artifacts through the authed client,
                # then route host stages to the stage lane — they
                # never touch the batcher or claim a chip slice
                if isinstance(job.get("stage"), dict):
                    await self._resolve_stage_inputs(job)
                if self._is_host_stage(job):
                    intake.append(("stage", job))
                elif gang_id is None:
                    intake.append(("job", job))
                else:
                    if gang_id not in gangs:
                        intake.append(("gang", gang_id))
                    gangs.setdefault(gang_id, []).append(job)
            for kind, item in intake:
                if kind == "gang":
                    await self.batcher.put_gang(gangs[item])
                elif kind == "stage":
                    self._stage_queued_ids.add(str(item.get("id")))
                    self._stage_queue.put_nowait(item)
                else:
                    await self.batcher.put(item)
            # the reply is in whole, and the timer's next poll goes out
            # POLL_SECONDS from here at the earliest: a group whose linger
            # ends before that is waiting for nobody
            self.batcher.reply_admitted(
                asyncio.get_running_loop().time() + POLL_SECONDS)
            # lease revocations piggybacked on this reply: route
            # each to wherever the job currently lives (batcher
            # -> dropped outright; executing slice -> cancel
            # token probed at the next denoise chunk boundary)
            for job_id in self.hive.last_cancels:
                self._cancel_job(job_id)
            self._poll_failed = False
            return POLL_SECONDS
        except asyncio.TimeoutError:
            # a timeout IS a poll failure: back off like one (the
            # round-6 branch forgot, re-polling a struggling hive
            # at the full cadence)
            logger.warning("hive poll timeout")
            _POLL_ERRORS.inc()
        except Exception as e:
            logger.exception("ask_for_work error")
            print(f"ask_for_work error {e}")
            _POLL_ERRORS.inc()
        self._poll_failed = True
        return _next_backoff(sleep_seconds)

    def _cancel_job(self, job_id: str) -> None:
        """Route one hive-revoked job id. Held (lingering / on the
        board): dropped outright, no envelope ever produced. Executing:
        the cancel registry is marked and the chunked denoise aborts the
        row (or the whole pass) at its next chunk boundary. Anything
        else — already delivered, or never ours — is a no-op; a late
        result earns the hive's `cancelled` disposition and parks."""
        job_id = str(job_id)
        if self.batcher.cancel(job_id):
            stage = "held"
        elif job_id in self._executing_ids:
            cancel_mod.cancel(job_id)
            stage = "executing"
            logger.warning(
                "hive cancelled executing job %s; the slice aborts at "
                "its next denoise chunk boundary", job_id)
        elif job_id in self._stage_queued_ids:
            # sitting in the stage lane: tombstone it — the consumer
            # drops it on pickup, no envelope is ever produced
            self._stage_cancelled.add(job_id)
            stage = "held"
        else:
            stage = "unknown"
        _JOBS_CANCELLED.inc(stage=stage)
        self._note_capacity()
        self._update_queue_gauges()

    # --- host-path stage lane (ISSUE 20) ---

    @staticmethod
    def _is_host_stage(job: dict) -> bool:
        """True for a stage-job whose stage name is host work (encode/
        decode/postprocess/...): it runs on the stage lane, jax-free,
        and never claims a chip slice."""
        from .coalesce import CPU_STAGES, stage_of

        return stage_of(job) in CPU_STAGES

    async def _resolve_stage_inputs(self, job: dict) -> None:
        """Hydrate a stage-job's handoff: predecessors' outputs arrive
        as content-addressed spool references ({sha256, bytes, href});
        fetch each blob through the AUTHED artifact client and stamp it
        back as base64 so the stage callback works from bytes. Fetch
        failures degrade — the callback reports the missing input as a
        fatal envelope instead of the worker dying here."""
        stage = job.get("stage")
        if not isinstance(stage, dict):
            return
        for entry in stage.get("inputs") or []:
            artifacts = (entry.get("artifacts")
                         if isinstance(entry, dict) else None)
            if not isinstance(artifacts, dict):
                continue
            for art in artifacts.values():
                if not isinstance(art, dict) or art.get("blob"):
                    continue
                href = art.get("href")
                if not href:
                    continue
                blob = await self.hive.fetch_artifact(str(href))
                if blob is not None:
                    art["blob"] = base64.b64encode(blob).decode("ascii")

    async def stage_worker(self) -> None:
        """One consumer of the stage lane: pops a host stage-job, runs
        its callback on the default executor (device "cpu" — no slice,
        no jax), and ships the envelope through the same finish/outbox
        path a slice pass uses. N of these run concurrently
        (Settings.stage_workers), so decode of pass N overlaps denoise
        of pass N+1 on the chip slices."""
        while True:
            job = await self._stage_queue.get()
            picked_up = time.time()
            job_id = str(job.get("id"))
            self._stage_queued_ids.discard(job_id)
            if job_id in self._stage_cancelled:
                self._stage_cancelled.discard(job_id)
                self._stage_queue.task_done()
                continue
            self._stage_inflight += 1
            self._executing_ids.add(job_id)
            queue_wait = {}
            self._picked_up(job, picked_up, queue_wait)
            trace = job.pop("trace", None)
            job.pop("resume", None)
            stage_name = str((job.get("stage") or {}).get("name", ""))
            traces = ({job.get("id"): trace}
                      if isinstance(trace, dict) else {})
            self._update_queue_gauges()
            try:
                worker_function, kwargs = await self.get_args(job, "cpu")
                if worker_function is not None:
                    result = await asyncio.get_running_loop().run_in_executor(
                        None, self.synchronous_do_work,
                        _HostLane(stage_name), worker_function, kwargs)
                    if result is not None:
                        self._finish_result(result, queue_wait, "cold", traces)
                        self._note_stage_stats(
                            result["pipeline_config"].get("timings") or {})
                        await self._enqueue_result(result)
            except Exception as e:
                logger.exception("stage_worker error")
                print(f"stage_worker {e}")
            finally:
                self._stage_inflight -= 1
                self._executing_ids.discard(job_id)
                cancel_mod.discard(job_id)
                self._stage_queue.task_done()
                self._update_queue_gauges()

    # --- consumers: one logical worker per chip slice ---

    def _coalesce_rows_limit(self, job: dict) -> int | None:
        """Advisory image budget for one coalesced group (BatchScheduler
        rows_limit): the representative slice's capacity for this job's
        model at its canvas, so groups arrive already admissible."""
        from .chips.requirements import coalesce_rows_limit, default_canvas
        from .coalesce import text_shape

        model = job.get("model_name", "")
        if job.get("workflow") == "txt2txt":
            # rows are sequences: what a row costs is its cached positions
            shape = text_shape(job)
            if shape is None:
                return None
            return coalesce_rows_limit(
                self.allocator.slices[0], model, sum(shape))
        params = job.get("parameters") or {}
        height = job.get("height", params.get("default_height"))
        width = job.get("width", params.get("default_width"))
        height = int(height or default_canvas(model))
        width = int(width or height)
        return coalesce_rows_limit(self.allocator.slices[0], model, height, width)

    async def slice_worker(self) -> None:
        while True:
            # placement-aware dispatch (batching.py board): the work item
            # and the slice are matched by model residency — affinity to
            # the warm slice, stealing by an idle one when the warm slice
            # is busy — and the chipset arrives already acquired
            batch, chipset, outcome = await self.batcher.claim(self.allocator)
            self._note_capacity()
            await self._wait_for_packaging(chipset)
            # queue_wait: hive handoff -> a slice actually starting the work
            picked_up = time.time()
            # whole-pass slice occupancy feeds the "pass" stage EWMA for
            # the hive's straggler detector: unlike the envelope's
            # job_s, this wall clock covers EVERYTHING that holds the
            # slice (arg formatting, a wedged busy lock, an injected
            # hang) — exactly the time a silently sick slice inflates
            pass_started = time.monotonic()
            queue_wait = {}
            traces = {}
            resume_offers = {}
            batch_ids = [str(job["id"]) for job in batch if "id" in job]
            self._executing_ids.update(batch_ids)
            # a job-level deadline (`deadline_s`, the hive TTL's per-job
            # override) caps the slice watchdog for its pass: the
            # submitter's promise outranks the worker-side default. A
            # COALESCED pass is capped only when EVERY member opted in,
            # and then by the loosest promise — a watchdog expiry kills
            # the whole pass, and one job's tight deadline must never
            # cost its batchmates their denoise (observed: a 0.5s
            # deadline ganged with a normal job quarantined the slice)
            caps_by_id = {str(job.get("id")): _deadline_cap_of(job)
                          for job in batch}
            caps = list(caps_by_id.values())
            batch_cap = max(caps) if caps and all(
                c > 0 for c in caps) else None
            for job in batch:
                self._picked_up(job, picked_up, queue_wait)
                # hive trace context comes OFF the job before formatting
                # and rides the envelope back (pipeline_config.trace) so
                # the hive attaches this worker's stage spans to the
                # right dispatch attempt
                trace = job.pop("trace", None)
                if isinstance(trace, dict) and "id" in job:
                    traces[job["id"]] = trace
                # a redelivery's resume offer (ISSUE 18) comes off the
                # job the same way — it is dispatch metadata, not a
                # pipeline argument; the solo path rehydrates from it
                offer = job.pop("resume", None)
                if isinstance(offer, dict) and "id" in job:
                    resume_offers[str(job["id"])] = offer
            self._update_queue_gauges()
            # the jobs no pass has ended for yet, by id
            waiting = {str(job.get("id")): job for job in batch}
            delivery = (queue_wait, outcome, traces)
            # span "format_args" starts here for the pass's jobs, and for
            # a later solo of the batch where the solo before it ended
            since = picked_up
            try:
                prepared = []
                for job in batch:
                    worker_function, kwargs = await self.get_args(
                        job, chipset.identifier()
                    )
                    if worker_function is not None:
                        prepared.append((worker_function, kwargs))
                if len(prepared) > 1 and self._batchable(prepared):
                    results = await self.do_batched_work(
                        chipset, prepared, batch_cap)
                    self._pass_ended(
                        chipset,
                        [waiting.pop(str(kw.get("id"))) for _, kw in prepared],
                        results, since, *delivery)
                else:
                    for worker_function, kwargs in prepared:
                        # read now: the executor thread pops it
                        job_id = str(kwargs.get("id"))
                        solo_cap = caps_by_id.get(job_id) or None
                        # class-aware geometry (ISSUE 12): an interactive
                        # solo on a multi-chip slice fans ONE image over
                        # every chip as a sharded program; batch solos
                        # (and every coalesced pass) keep the default
                        # data-parallel view
                        self._apply_shard_geometry(
                            waiting.get(job_id),
                            worker_function, kwargs, chipset)
                        # mid-pass durability (ISSUE 18): arm the solo
                        # pass with checkpoint/preview callbacks and,
                        # for a redelivery carrying an offer, the
                        # rehydrated resume state
                        await self._apply_checkpointing(
                            worker_function, kwargs,
                            resume_offers.get(job_id))
                        await self._wait_for_packaging(chipset)
                        # None: the pass was aborted by a cancel
                        result = await self.do_work(
                            chipset, worker_function, kwargs, solo_cap
                        )
                        self._pass_ended(
                            chipset, [waiting.pop(job_id)], [result], since,
                            *delivery)
                        since = time.time()
            except Exception as e:
                logger.exception("slice_worker error")
                print(f"slice_worker {e}")
            finally:
                # the slice is free from here: what the passes produced
                # is packaged and delivered by _deliver_pass while the
                # next pass runs
                self.allocator.release(chipset)
                self._note_stage_stats(
                    {"pass_s": round(time.monotonic() - pass_started, 4)})
                for job in waiting.values():
                    # no pass ended for these (refused by format_args,
                    # or the loop above raised): nothing is delivered
                    self.batcher.task_done(job)
                for job_id in batch_ids:
                    # tokens die with the pass: a later resubmission of
                    # the same id must start with a clean slate
                    self._executing_ids.discard(job_id)
                    cancel_mod.discard(job_id)
                self._note_capacity()
                self._update_queue_gauges()

    # --- packaging off the slice's critical path ---

    async def _wait_for_packaging(self, chipset) -> None:
        """The one bound on what waits to be packaged, a rule and no
        setting: a slice does not start a pass while two earlier passes
        of its own are still undelivered; it waits for the older one
        (span `package_wait`, stamped at pick-up)."""
        pending = self._deliveries.get(chipset.slice_id, ())
        while len(pending) >= 2:
            await asyncio.wait({pending[0]})

    def _picked_up(self, job: dict, picked_up: float,
                   queue_wait: dict) -> None:
        """Take what the poll and the batcher stamped off a job a slice
        (or the stage lane) has just picked up, and close its wait: span
        `package_wait` from the end of `claim` (the wait for the slice's
        earlier passes to be delivered; none on the stage lane, which has
        no claim), and `queue_wait` around `linger`, `claim` and it. Kept
        by job id for the envelope (`_finish_result`), with the timings
        the wait adds to it."""
        arrived, spans = job.pop(ARRIVED, None), job.pop(SPANS, [])
        if arrived is None or "id" not in job:
            return
        claimed = [_span_end(s) for s in spans if s["name"] == "claim"]
        if claimed:
            Span("package_wait", thread="wait", spans=spans).record(
                claimed[-1], picked_up - claimed[-1])
        timings: dict = {}
        # span "queue_wait": poll reply -> slice pick-up; it ran on no
        # thread, and the pass's trace closes with the pass, so it joins
        # the envelope's spans directly
        Span("queue_wait", timings, thread="wait", spans=spans).record(
            arrived, picked_up - arrived)
        queue_wait[job["id"]] = (spans, timings)

    def _pass_ended(self, chipset, jobs: list[dict],
                    results: list[dict | None], since: float,
                    queue_wait: dict, placement: str, traces: dict) -> None:
        """One executor call has returned with `results` for `jobs`
        (None: a cancelled member, no envelope exists and none is
        delivered — the hive tombstoned the job, batchmates unharmed).
        The jobs want no slice any more; they stay outstanding until a
        task of the pass's own has packaged, finished and spooled their
        envelopes, behind the slice's earlier passes. `since` is where
        the jobs' arguments began to be formatted."""
        ended = time.time()
        for job in jobs:
            # pass the job so the row accounting (advertised
            # queue_depth) subtracts its true image count
            self.batcher.pass_done(job)
        results = [result for result in results if result is not None]
        for result in results:
            waited = queue_wait.get(result.get("id"))
            held = _pass_span(result)
            if waited is not None and held is not None:
                # span "format_args": pick-up -> the `pass` span opens
                # (the arguments formatted, the executor's pick-up, the
                # busy lock taken and the job's key drawn)
                Span("format_args", thread="wait", spans=waited[0]).record(
                    since, max(held["start_wall"] - since, 0.0))
        if results:
            # ONE pass = one stats sample; a coalesced pass's envelopes
            # all carry the same copied timings
            self._note_stage_stats(
                results[0]["pipeline_config"].get("timings") or {})
        pending = self._deliveries.setdefault(chipset.slice_id, [])
        task = asyncio.create_task(self._deliver_pass(
            results, len(jobs), ended, queue_wait, placement, traces,
            after=pending[-1] if pending else None))
        pending.append(task)
        task.add_done_callback(pending.remove)

    async def _deliver_pass(self, results: list[dict], n_jobs: int,
                            ended: float, queue_wait: dict, placement: str,
                            traces: dict, after: asyncio.Task | None) -> None:
        """Package one pass's images on a host thread, then finish and
        enqueue its envelopes together and in order: a gang's clients
        see their jobs settle together (two that resubmit at once are
        one gang again; envelopes spaced by an encode would split them
        over polls). `ended`: when the worker's loop learned the pass was
        over, where `handoff` starts for a result without a `pass` span."""
        from .workflows.diffusion import Unpackaged

        try:
            if after is not None:
                await asyncio.wait({after})  # per slice, in pass order
            if any(isinstance(result["artifacts"], Unpackaged)
                   for result in results):
                results = await asyncio.get_running_loop().run_in_executor(
                    None, self._package_pass, results, ended)
            else:
                for result in results:
                    _stamp_handoff(result, ended)
            for result in results:
                self._finish_result(result, queue_wait, placement, traces)
            await self._enqueue_result(*results)
        except Exception as e:
            logger.exception("delivering a pass's results failed")
            print(f"deliver_pass {e}")
        finally:
            for _ in range(n_jobs):
                self.batcher.job_delivered()
            self._update_queue_gauges()

    def _package_pass(self, results: list[dict], ended: float) -> list[dict]:
        """On a host thread: the `artifacts` of every result that left
        its pass `Unpackaged`, in order. A failure is that job's own
        error envelope; its batchmates are delivered and nothing is
        denoised again."""
        from .workflows.diffusion import Unpackaged

        packaged = []
        for result in results:
            _stamp_handoff(result, ended)
            unpackaged = result["artifacts"]
            if isinstance(unpackaged, Unpackaged):
                spans = result["pipeline_config"].setdefault("spans", [])
                try:
                    result["artifacts"] = unpackaged.package(spans)
                except Exception as e:
                    logger.exception(
                        "packaging job %s failed", result["id"])
                    result = _error_envelope(
                        e, result["id"], unpackaged.content_type, spans)
            packaged.append(result)
        return packaged

    def _finish_result(self, result: dict, queue_wait: dict,
                       placement: str | None = None,
                       traces: dict | None = None) -> None:
        """Stamp worker-side stage timings (and the placement outcome that
        routed the work item to its slice) into the envelope and count the
        job by outcome — ONE place, so solo, coalesced, and fallback paths
        all report identically. (The `stats` EWMAs are fed separately,
        once per physical pass — see _note_stage_stats.)"""
        cfg = result.setdefault("pipeline_config", {})
        if placement is not None:
            cfg["placement"] = placement
        trace = (traces or {}).get(result.get("id"))
        if isinstance(trace, dict):
            # echo the hive's trace context (attempt, dispatch instant)
            # back through the envelope
            cfg["trace"] = trace
        timings = cfg.setdefault("timings", {})
        waited = queue_wait.get(result.get("id"))
        if waited is not None:
            # what the job's life held before its pass (`_picked_up`,
            # `_pass_ended`): tick_wait, poll, linger, claim, package_wait,
            # queue_wait, format_args
            spans, waits = waited
            cfg.setdefault("spans", []).extend(spans)
            timings.update(waits)
        if result.get("fatal_error"):
            outcome = "fatal"
        elif "error" in cfg:
            outcome = "error"
        else:
            outcome = "ok"
        _JOBS_COMPLETED.inc(outcome=outcome)

    # --- priority-aware multi-chip sharding (ISSUE 12) ---

    def _shard_geometry(self, chipset) -> tuple[int, int] | None:
        """The (tensor, seq) view an interactive solo should run under on
        `chipset`, or None when sharding is off / impossible / identical
        to the slice's default view. shard_tensor=0 resolves to the
        chipset's auto degree (largest power-of-two leaving a data axis
        for the CFG pair)."""
        s = self.settings
        if not getattr(s, "shard_interactive", False):
            return None
        if not getattr(chipset, "shard_capable", False):
            return None
        geo = chipset.resolve_geometry(
            int(getattr(s, "shard_tensor", 0) or 0),
            int(getattr(s, "shard_seq", 1) or 1))
        if geo is None or geo == (chipset.tensor, chipset.seq):
            return None
        return geo

    def _apply_shard_geometry(self, job, worker_function, kwargs,
                              chipset) -> None:
        """Attach the sharded mesh view (and the chunk-seam re-shard
        probe) to one interactive solo's kwargs. Only the SD-family
        callback understands the keys; everything else runs untouched."""
        from .batching import is_interactive
        from .workflows.diffusion import diffusion_callback

        if job is None or not is_interactive(job):
            return
        if worker_function is not diffusion_callback:
            return
        geo = self._shard_geometry(chipset)
        if geo is None:
            return
        kwargs["geometry"] = {"tensor": geo[0], "seq": geo[1]}
        kwargs["reshard_probe"] = self._reshard_probe(chipset)
        logger.info(
            "interactive job %s shards over slice %s as tensor=%d seq=%d",
            job.get("id"), chipset.slice_id, geo[0], geo[1])

    def _reshard_probe(self, chipset):
        """Chunk-boundary migration policy for a sharded interactive
        pass: when the queue shifts — released work is waiting on the
        dispatch board and no slice is free — the pass migrates back to
        the slice's default data-parallel view, so its remaining chunks
        run the programs and resident weights every queued coalesced
        pass will reuse (zero geometry churn between back-to-back
        passes). An empty board keeps the latency-optimal sharded view.
        Runs on the executor thread; reads of the asyncio-side counters
        are GIL-atomic ints, same discipline as the cancel registry."""
        default = {"tensor": chipset.tensor, "seq": chipset.seq}

        def probe():
            if (self.batcher.ready_jobs > 0
                    and not self.allocator.has_free_slice()):
                return default
            return None

        return probe

    # --- preemption-tolerant denoise (ISSUE 18) ---

    async def _apply_checkpointing(self, worker_function, kwargs,
                                   offer: dict | None) -> None:
        """Arm one solo diffusion pass with the mid-pass durability seam:
        checkpoint/preview callbacks cut at the knobbed chunk cadence,
        plus — for a redelivery that arrived with a `resume` offer — the
        checkpointed state rehydrated from the hive's spool. Only the
        SD-family callback understands the keys (workflows gate them on
        `supports_checkpoint`); coalesced passes never checkpoint by
        design — a batch member's padded row is not a job's worth of
        resumable state."""
        from .workflows.diffusion import diffusion_callback

        if worker_function is not diffusion_callback:
            return
        s = self.settings
        if int(getattr(s, "denoise_chunk_steps", 0) or 0) <= 0:
            return  # fused pass: no boundaries to checkpoint at
        job_id = str(kwargs.get("id"))
        if isinstance(offer, dict) and offer.get("href"):
            state = await self._fetch_resume_state(job_id, offer)
            if state is not None:
                kwargs["resume"] = state
        loop = asyncio.get_running_loop()
        ckpt_every = int(getattr(s, "checkpoint_every_chunks", 0) or 0)
        if ckpt_every > 0:
            kwargs["checkpoint_every_chunks"] = ckpt_every
            kwargs["checkpoint_cb"] = self._checkpoint_shipper(job_id, loop)
        preview_every = int(getattr(s, "preview_every_chunks", 0) or 0)
        if preview_every > 0:
            kwargs["preview_every_chunks"] = preview_every
            kwargs["preview_cb"] = self._preview_shipper(
                job_id, loop, str(kwargs.get("content_type", "image/jpeg")))

    async def _fetch_resume_state(self, job_id: str,
                                  offer: dict) -> dict | None:
        """Fetch and unpack one resume offer's checkpoint blob. Every
        failure degrades to the full pass (counted, logged), never to a
        job error — resume is an optimization, not a dependency."""
        blob = await self.hive.fetch_artifact(str(offer["href"]))
        if blob is None:
            _RESUMES.inc(outcome="fetch_failed")
            logger.warning(
                "resume offer for %s: checkpoint fetch failed; "
                "running the full pass", job_id)
            return None
        try:
            from . import checkpoint as ckpt

            state = await asyncio.get_running_loop().run_in_executor(
                None, ckpt.unpack, blob)
        except Exception as e:
            _RESUMES.inc(outcome="unpack_failed")
            logger.warning(
                "resume offer for %s: checkpoint unpack failed (%s); "
                "running the full pass", job_id, e)
            return None
        _RESUMES.inc(outcome="resumed")
        logger.info("job %s rehydrates from checkpointed step %s",
                    job_id, state.get("step"))
        return state

    def _checkpoint_shipper(self, job_id: str, loop):
        """The checkpoint callback for one pass. Runs on the executor
        thread at chunk boundaries: packs the live state there (the
        arrays are already host-side numpy), then hands the upload to
        the event loop fire-and-forget — the denoise never waits on the
        hive, and a failed upload costs the checkpoint, not the pass."""
        max_bytes = int(getattr(
            self.settings, "checkpoint_max_bytes", 0) or 0)

        def ship(step, latents, state_leaves, signature):
            try:
                from . import checkpoint as ckpt

                blob = ckpt.pack(step, latents, state_leaves, signature)
            except Exception:
                _CHECKPOINTS.inc(outcome="error")
                logger.exception("checkpoint pack failed for %s", job_id)
                return
            if max_bytes > 0 and len(blob) > max_bytes:
                _CHECKPOINTS.inc(outcome="oversize")
                logger.warning(
                    "checkpoint for %s at step %d is %d bytes "
                    "(checkpoint_max_bytes %d); skipped",
                    job_id, step, len(blob), max_bytes)
                return
            payload = {
                "step": int(step),
                "signature": signature,
                "worker_name": self.settings.worker_name,
                "blob": base64.b64encode(blob).decode("ascii"),
            }
            coro = self._ship_partial("checkpoint", job_id, payload)
            try:
                asyncio.run_coroutine_threadsafe(coro, loop)
            except RuntimeError:  # loop gone: the worker died mid-pass
                coro.close()
                _CHECKPOINTS.inc(outcome="error")
                return
            # chaos seam (tools/chaos_smoke.py resume_after_worker_kill):
            # the worker dies HERE — mid-denoise, past a shipped
            # checkpoint — and a second worker must finish from it
            faults.hang("hang_after_checkpoint")

        return ship

    def _preview_shipper(self, job_id: str, loop, content_type: str):
        """The preview callback for one pass: VAE-decoded boundary pixels
        arrive on the executor thread, are encoded there, and ship to
        the hive's preview endpoint fire-and-forget."""
        if not content_type.startswith("image/"):
            content_type = "image/jpeg"

        def ship(step, pixels):
            try:
                from .pipelines.stable_diffusion import _to_pil
                from .post_processors.output_processor import image_to_buffer

                image = _to_pil(pixels)[0]
                payload = {
                    "step": int(step),
                    "content_type": content_type,
                    "worker_name": self.settings.worker_name,
                    "blob": base64.b64encode(
                        image_to_buffer(image, content_type).getvalue()
                    ).decode("ascii"),
                }
            except Exception:
                _PREVIEWS.inc(outcome="error")
                logger.exception("preview encode failed for %s", job_id)
                return
            coro = self._ship_partial("preview", job_id, payload)
            try:
                asyncio.run_coroutine_threadsafe(coro, loop)
            except RuntimeError:  # loop gone: the worker died mid-pass
                coro.close()
                _PREVIEWS.inc(outcome="error")

        return ship

    async def _ship_partial(self, kind: str, job_id: str,
                            payload: dict) -> None:
        """Upload one mid-pass partial; the pass never learns whether it
        landed (post_partial already absorbs refusals and transport
        errors into None)."""
        counter = _CHECKPOINTS if kind == "checkpoint" else _PREVIEWS
        try:
            ack = await self.hive.post_partial(kind, job_id, payload)
        except Exception as e:  # belt and braces: never kill the loop
            ack = None
            logger.warning("%s upload for %s raised: %s", kind, job_id, e)
        counter.inc(outcome="shipped" if ack else "error")

    @staticmethod
    def _batchable(prepared: list) -> bool:
        """A group executes as one pass only when every member formatted to
        one callback that has a batched form (`<callback>.batched`: the
        diffusion and the text-completion workflows) — anything else (a
        mid-flight fallback, a mixed group from a future scheduler) runs
        solo."""
        first = prepared[0][0]
        return (getattr(first, "batched", None) is not None
                and all(fn is first for fn, _ in prepared))

    async def get_args(self, job: dict, device_identifier: str):
        try:
            return await format_args(job, self.settings, device_identifier)
        except Exception as e:
            # input args are wrong somehow: not recoverable, don't resubmit
            # (reference swarm/worker.py:105-115)
            logger.exception("format_args failed for job %s", job.get("id"))
            result = fatal_exception_response(e, job["id"], job)
            self._finish_result(result, {})
            await self._enqueue_result(result)
        return None, None

    # --- slice watchdog ---

    def _job_deadline(self, model_name, chipset=None,
                      cap_s: float | None = None) -> float | None:
        """Execution deadline for one pass; None = watchdog off. A model
        that is not yet resident ON THIS SLICE gets the first-compile
        allowance — big programs legitimately take minutes to compile
        once, and a STOLEN group pays that on the stealing slice even
        when the model is warm elsewhere in the process. `cap_s` (the
        job's own `deadline_s`, ISSUE 10) is a hard ceiling: the
        watchdog treats the submitter's deadline as its cap, compile
        allowance included — and it arms the watchdog even when the
        worker-wide knob is off."""
        base = float(getattr(self.settings, "job_deadline_s", 0.0) or 0.0)
        deadline: float | None = None
        if base > 0:
            scale = 1.0
            try:
                from .registry import resident_models

                slice_id = getattr(chipset, "slice_id", None)
                if model_name and model_name not in resident_models(slice_id):
                    scale = max(float(getattr(
                        self.settings, "job_deadline_compile_scale", 4.0)),
                        1.0)
            except Exception:  # residency probe must never block execution
                pass
            deadline = base * scale
        if cap_s is not None and cap_s > 0:
            deadline = cap_s if deadline is None else min(deadline, cap_s)
        return deadline

    def _expire_pass(self, chipset, fut, jobs_meta: list[dict],
                     deadline: float, kind: str) -> list[dict]:
        """A pass blew its watchdog deadline: quarantine the slice, hand
        every member job the existing transient-error envelope (the hive
        may resubmit elsewhere), and let the wedged thread finish or rot
        in the background — the probe decides if the slice returns."""
        _WATCHDOG_EXPIRED.inc(len(jobs_meta), kind=kind)
        logger.error(
            "watchdog: %s pass on slice %s exceeded its %.1fs deadline "
            "(jobs %s); quarantining the slice",
            kind, chipset.slice_id, deadline,
            [m.get("id") for m in jobs_meta])
        # the orphaned executor future may still raise much later; consume
        # it so asyncio doesn't log an unretrieved exception
        fut.add_done_callback(
            lambda f: f.cancelled() or f.exception())
        self.allocator.quarantine(chipset)
        self._update_queue_gauges()
        probe = asyncio.create_task(
            self._quarantine_probe(chipset),
            name=f"quarantine_probe_{chipset.slice_id}")
        self._probe_tasks.add(probe)
        probe.add_done_callback(self._probe_tasks.discard)

        err = TimeoutError(
            f"job execution exceeded the {deadline:g}s watchdog "
            "deadline; the slice was quarantined and the job may be "
            "resubmitted")
        return [_error_envelope(err, meta.get("id"),
                                meta.get("content_type") or "image/jpeg")
                for meta in jobs_meta]

    async def _quarantine_probe(self, chipset) -> None:
        """Wait (bounded) for the wedged pass to release the slice, then
        run the tiny smoke program. Pass -> the slice returns to the
        allocator without a worker restart; fail/wedged -> it stays out
        and advertised capacity stays shrunk."""
        grace = max(float(getattr(
            self.settings, "quarantine_probe_grace_s", 30.0)), 0.0)
        deadline = time.monotonic() + grace
        while chipset.busy and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        if chipset.busy:
            _WATCHDOG_PROBES.inc(outcome="wedged")
            logger.error(
                "slice %s still wedged %.0fs after its watchdog expiry; "
                "leaving it quarantined (capacity stays shrunk)",
                chipset.slice_id, grace)
            self._update_queue_gauges()
            return
        # the default executor, not the slice pool — a wedged slice thread
        # must not be able to starve its own recovery probe
        ok = await asyncio.get_running_loop().run_in_executor(
            None, chipset.smoke_probe)
        if ok:
            self.allocator.reinstate(chipset)
            _WATCHDOG_PROBES.inc(outcome="ok")
            logger.warning(
                "slice %s passed the smoke probe; returned to service",
                chipset.slice_id)
        else:
            _WATCHDOG_PROBES.inc(outcome="failed")
            logger.error(
                "slice %s failed the smoke probe; leaving it quarantined",
                chipset.slice_id)
        self._update_queue_gauges()

    async def do_work(self, chipset, worker_function, kwargs,
                      deadline_cap_s: float | None = None) -> dict | None:
        loop = asyncio.get_running_loop()
        # captured BEFORE dispatch: the executor thread mutates kwargs
        meta = [{"id": kwargs.get("id"),
                 "content_type": kwargs.get("content_type", "image/jpeg")}]
        deadline = self._job_deadline(
            kwargs.get("model_name"), chipset, deadline_cap_s)
        fut = loop.run_in_executor(
            self._executor, self.synchronous_do_work, chipset, worker_function, kwargs
        )
        if deadline is None:
            return await fut
        try:
            return await asyncio.wait_for(asyncio.shield(fut), deadline)
        except asyncio.TimeoutError:
            return self._expire_pass(chipset, fut, meta, deadline, "solo")[0]

    async def do_batched_work(self, chipset, prepared: list,
                              deadline_cap_s: float | None = None
                              ) -> list[dict | None]:
        loop = asyncio.get_running_loop()
        meta = [{"id": kw.get("id"),
                 "content_type": kw.get("content_type", "image/jpeg")}
                for _, kw in prepared]
        deadline = self._job_deadline(prepared[0][1].get("model_name"), chipset)
        if deadline is not None:
            # budget the WORST case of this executor call: the coalesced
            # pass fails and synchronous_do_batch reruns every member
            # sequentially through the solo path — a legitimate full-group
            # fallback must not read as a hang and cost the slice
            deadline *= max(len(prepared), 1)
        if deadline_cap_s is not None and deadline_cap_s > 0:
            # the job-level deadline is an absolute promise; it caps the
            # final budget AFTER the fallback allowance, never scales
            deadline = (deadline_cap_s if deadline is None
                        else min(deadline, deadline_cap_s))
        fut = loop.run_in_executor(
            self._executor, self.synchronous_do_batch, chipset, prepared
        )
        if deadline is None:
            return await fut
        try:
            return await asyncio.wait_for(asyncio.shield(fut), deadline)
        except asyncio.TimeoutError:
            return self._expire_pass(chipset, fut, meta, deadline, "batched")

    def synchronous_do_batch(self, chipset, prepared: list) -> list[dict]:
        """One coalesced pass for a compatible group; on ANY failure, fall
        back to the single-job path per member — which reproduces the
        error with the existing fatal/transient attribution, so batching
        never changes what the hive sees beyond latency. The one typed
        exception is DeltaIneligibleError: a member whose adapter the
        runtime delta cannot express (conv/LoCon, over-rank) goes solo
        through the merged-tree path while its batchmates RE-BATCH —
        one slow adapter must not serialize the whole gang."""
        from .pipelines.lora_runtime import DeltaIneligibleError

        # the group's workflow says how it runs as one pass (_batchable)
        batched_callback = prepared[0][0].batched

        # pristine copies for the fallback: the batched path pops/injects
        # keys (seed, rng, chipset) destructively
        singles = [(fn, dict(kwargs)) for fn, kwargs in prepared]
        requests = [kwargs for _, kwargs in prepared]
        # ids stay IN the request kwargs: the batched pipeline path needs
        # them for its per-row cancel tokens (chunked denoise); the
        # callbacks read only the keys they know, so the extra key rides
        # along harmlessly
        ids = [kwargs.get("id") for kwargs in requests]
        print(
            f"Processing batch of {len(ids)} jobs {ids} "
            f"on {chipset.descriptor()}"
        )
        try:
            with trace_job(",".join(str(i) for i in ids)) as trace:
                outs = chipset.run_batched(batched_callback, requests)
            for _, pipeline_config in outs:
                # the pass was shared: so are its spans, as its timings
                pipeline_config["spans"] = list(trace.spans)
            return [
                None if pipeline_config.get("cancelled") else {
                    "id": job_id,
                    "artifacts": artifacts,
                    "nsfw": pipeline_config.get("nsfw", False),
                    "worker_version": __version__,
                    "pipeline_config": pipeline_config,
                }
                for job_id, (artifacts, pipeline_config) in zip(ids, outs)
            ]
        except JobCancelled as e:
            # every live member was cancelled: the pass aborted at a
            # chunk boundary, the slice is free, and NO envelope exists —
            # the hive tombstoned these jobs and wants nothing back
            logger.warning("coalesced pass aborted by cancellation: %s",
                           e.job_ids)
            return [None] * len(ids)
        except DeltaIneligibleError as e:
            bad = set(e.job_ids)
            eligible = [(fn, dict(kw)) for fn, kw in singles
                        if kw.get("id") not in bad]
            if not (bad & set(ids)) or len(eligible) < 2:
                # no per-member identity or nothing left worth
                # re-batching: classic whole-group solo fallback
                logger.info("coalesced pass for %s: %s", ids, e)
                return [self.synchronous_do_work(chipset, fn, dict(kw))
                        for fn, kw in singles]
            logger.info(
                "coalesced pass for %s: members %s are not delta-eligible; "
                "re-batching the %d eligible member(s)",
                ids, sorted(bad), len(eligible))
            by_id = dict(zip([kw.get("id") for _, kw in eligible],
                             self.synchronous_do_batch(chipset, eligible)))
            for fn, kw in singles:
                if kw.get("id") in bad:
                    by_id[kw.get("id")] = self.synchronous_do_work(
                        chipset, fn, dict(kw))
            return [by_id[i] for i in ids]
        except Exception as e:
            logger.exception(
                "coalesced pass for %s failed; retrying jobs individually", ids
            )
            print(f"batched pass failed ({e}); falling back to single jobs")
            return [
                self.synchronous_do_work(chipset, fn, kwargs)
                for fn, kwargs in singles
            ]

    def synchronous_do_work(self, chipset, worker_function, kwargs) -> dict:
        job_id = kwargs.pop("id")
        print(f"Processing {job_id} on {chipset.descriptor()}")

        # trace_job pins the job id on this executor thread so every log
        # line emitted during execution carries it (JSON logs), and
        # collects the pass's spans for the envelope
        trace = trace_job(job_id)
        try:
            with trace:
                artifacts, pipeline_config = chipset(worker_function, **kwargs)
        except JobCancelled:
            # aborted at a denoise chunk boundary: the hive revoked this
            # job mid-flight. No envelope — the slice frees within one
            # chunk and the hive's tombstone is the terminal truth
            logger.warning("job %s cancelled mid-denoise; pass aborted",
                           job_id)
            return None
        except (ValueError, TypeError) as e:
            # non-recoverable (e.g. incompatible adapter): fatal envelope
            return fatal_exception_response(e, job_id, kwargs)
        except Exception as e:
            logger.exception("job %s failed", job_id)
            # a failed pass spent its time too: the error envelope says
            # where
            return _error_envelope(
                e, job_id, kwargs.get("content_type", "image/jpeg"),
                trace.spans)

        pipeline_config["spans"] = trace.spans
        return {
            "id": job_id,
            "artifacts": artifacts,
            "nsfw": pipeline_config.get("nsfw", False),
            "worker_version": __version__,
            "pipeline_config": pipeline_config,
        }

    # --- uploader (durable outbox, outbox.py) ---

    async def _enqueue_result(self, *results: dict) -> None:
        """Spool the envelopes to disk, then queue them for delivery — the
        write-ahead half of the outbox contract. From this point a job
        cannot be silently lost: only a hive ACK unlinks the file. The
        writes run off-loop: a multi-MB artifact envelope on a slow disk
        must not stall timers, polls, or the drain watcher. Envelopes
        given together (a pass's) are queued together, and the uploader
        takes them together."""
        for result in results:
            # the sender's identity rides the envelope (legacy hives
            # ignore unknown keys): a lease-tracking hive needs it to
            # attribute a LATE result to the worker that actually
            # produced it, not to whoever holds the redelivered lease at
            # arrival time
            result.setdefault("worker_name", self.settings.worker_name)
        loop = asyncio.get_running_loop()
        entries = await asyncio.gather(*(
            loop.run_in_executor(None, self._spool, result)
            for result in results))
        entries[0].followers = len(entries) - 1
        for entry in entries:
            # unbounded: put never waits, so the uploader wakes to all
            await self.result_queue.put(entry)

    def _spool(self, result: dict) -> OutboxEntry:
        # span "spool": the envelope's write (histogram and profiler
        # only: the envelope it would ride in is what is being sealed)
        with Span("spool"):
            return self.outbox.spool(result)

    async def result_worker(self) -> None:
        while True:
            entries = [await self.result_queue.get()]
            # a pass's envelopes go up together, not each after the
            # other's ACK: a gang's clients see their jobs settle within
            # milliseconds (two that resubmit at once are one gang again)
            for _ in range(entries[0].followers):
                entries.append(self.result_queue.get_nowait())
            self._delivering += len(entries)
            try:
                outcomes = await asyncio.gather(
                    *map(self._deliver, entries), return_exceptions=True)
            finally:
                self._delivering -= len(entries)
                for _ in entries:
                    self.result_queue.task_done()
                self._update_queue_gauges()
            for entry, outcome in zip(entries, outcomes):
                if isinstance(outcome, FaultInjected):
                    # fault harness only: a simulated crash after upload,
                    # before ACK — the envelope stays spooled for
                    # redelivery
                    logger.error(
                        "injected crash before ack for %s", entry.job_id)
                    raise outcome
                if isinstance(outcome, Exception):
                    logger.error("result_worker error", exc_info=outcome)
                    print(f"result_worker {outcome}")

    async def _deliver(self, entry: OutboxEntry) -> None:
        """Upload one spooled envelope until the hive ACKs (capped
        exponential backoff + jitter between attempts). A permanent 4xx
        refusal parks the entry on disk instead — retried next restart,
        never dropped."""
        while True:
            err: Exception
            try:
                # span "submit": one POST of the envelope, on the event
                # loop (histogram and profiler only; a failed attempt
                # spent its time too, and hive.py counts it per endpoint)
                with Span("submit"):
                    ack = await self.hive.submit_result(entry.result)
                faults.fire("kill_before_ack")
                # disposition ACKs (ISSUE 10): the hive took the POST but
                # will never store this result — the job was cancelled,
                # expired, or retired ("gone"). PARK the envelope with
                # the reason instead of unlinking: the artifacts cost a
                # full denoise pass and stay on disk for the operator
                # (tools/outbox_inspect.py shows the reason; --requeue
                # retries them if a hive will take them later). Before
                # this, a 200 ACK always unlinked and a non-200 for a
                # gone job retried on the transient path forever.
                reason = None
                if isinstance(ack, dict):
                    if ack.get("cancelled"):
                        reason = "cancelled: hive revoked this job"
                    elif ack.get("expired"):
                        reason = "expired: job TTL lapsed at the hive"
                    elif ack.get("unknown_job"):
                        reason = "gone: hive no longer knows this job id"
                if reason is not None:
                    logger.warning(
                        "hive acknowledged but discarded result %s (%s); "
                        "parking the envelope", entry.job_id, reason)
                    await asyncio.get_running_loop().run_in_executor(
                        None, self.outbox.park, entry, reason)
                    return
                self.outbox.delivered(entry)
                return
            except FaultInjected:
                raise
            except asyncio.TimeoutError as e:
                err = e
            except HiveError as e:
                if e.permanent:
                    logger.error(
                        "hive permanently refused result %s (%s); parking "
                        "the envelope on disk", entry.job_id, e)
                    # park() rewrites the full envelope with its delivery
                    # history — off-loop, like spool(): a multi-MB
                    # artifact payload must not stall polls or timers
                    await asyncio.get_running_loop().run_in_executor(
                        None, self.outbox.park, entry, str(e))
                    return
                err = e
            except Exception as e:  # unexpected: still never drop work
                err = e
            entry.retries += 1
            self.outbox.note_retry()
            delay = outbox_mod.backoff_delay(entry.retries)
            logger.warning(
                "submit failed for %s (attempt %d: %s); retrying in %.1fs",
                entry.job_id, entry.retries, err, delay)
            await asyncio.sleep(delay)


class _HostLane:
    """Chipset stand-in for the stage lane (ISSUE 20): satisfies the
    synchronous_do_work contract — descriptor for logging, __call__
    running the callback — without touching a slice, the busy lock, or
    jax. Host stage callbacks (encode/decode/postprocess) are
    deterministic CPU work, so no seed/RNG is drawn."""

    def __init__(self, stage: str):
        self._stage = stage or "stage"

    def descriptor(self) -> str:
        return f"host:{self._stage}"

    def identifier(self) -> str:
        return "cpu"

    def __call__(self, func, **kwargs):
        model_name = kwargs.pop("model_name", "")
        kwargs.pop("seed", None)
        started = time.perf_counter()
        artifacts, pipeline_config = func("cpu", model_name, **kwargs)
        pipeline_config.setdefault("timings", {})["job_s"] = round(
            time.perf_counter() - started, 3)
        return artifacts, pipeline_config


def _error_envelope(e: Exception, job_id, content_type: str,
                    spans: list | None = None) -> dict:
    """The envelope of a job that failed, by the one attribution there
    is: ValueError/TypeError are fatal (the hive must not resubmit),
    anything else is transient — the error rendered as the artifact, the
    job still "succeeds". `spans`: where the time went before it failed
    (a transient envelope carries them; a fatal one never has)."""
    if isinstance(e, (ValueError, TypeError)):
        return fatal_exception_response(
            e, job_id, {"content_type": content_type})
    if content_type.startswith("image/"):
        artifacts, pipeline_config = exception_image(e, content_type)
    else:
        artifacts, pipeline_config = exception_message(e)
    if spans is not None:
        pipeline_config["spans"] = spans
    return {
        "id": job_id,
        "artifacts": artifacts,
        "nsfw": False,
        "worker_version": __version__,
        "pipeline_config": pipeline_config,
    }


def _span_end(span: dict) -> float:
    return span["start_wall"] + span["seconds"]


def _pass_span(result: dict) -> dict | None:
    """The `pass` span of a result's envelope (the slice held for it)."""
    spans = (result.get("pipeline_config") or {}).get("spans") or ()
    return next((s for s in spans if s["name"] == "pass"), None)


def _stamp_handoff(result: dict, ended: float) -> None:
    """Span "handoff", a job's own: from its `pass` span's end (`ended`
    where it has none) to now, which is its turn on the packaging thread
    (behind the slice's earlier passes, the executor's pick-up and its
    batchmates' encodes) or, with nothing to package, its envelope being
    finished. It ran across threads, so it is recorded."""
    held = _pass_span(result)
    start = ended if held is None else _span_end(held)
    Span("handoff", thread="deliver", spans=result.setdefault(
        "pipeline_config", {}).setdefault("spans", [])).record(
            start, max(time.time() - start, 0.0))


async def run_worker() -> None:
    await Worker().run()


def main() -> None:
    """Console entry point (`chiaswarm-tpu-worker`)."""
    try:
        asyncio.run(run_worker())
    except KeyboardInterrupt:
        print("done")


if __name__ == "__main__":
    main()
