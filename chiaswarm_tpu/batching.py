"""Cross-job micro-batching: coalesce compatible hive jobs per slice.

The round-5 worker maps one hive job to one chip slice (worker.py
slice_worker), so a batch-1 SDXL job leaves most of a slice's MXU idle
even while the queue holds more jobs for the *same resident model and
shape bucket* — the under-utilization request-batching serving systems
(SwiftDiffusion, arXiv:2407.02031) attack. This module is the batching
layer between the poll loop and the slice workers:

- `coalesce_key(job)` (now in the jax-free shared module coalesce.py,
  re-exported here, because the HIVE uses the same key to gang-schedule)
  buckets a raw hive job by everything that must be IDENTICAL for two
  jobs to share one jitted denoise+decode invocation: (model, family,
  canvas, steps, scheduler, guidance mode, workflow — plain txt2img, or
  img2img with per-request start images at a shared explicit canvas and
  strength). Jobs that carry per-job structure the batched program can't
  express — masks, ControlNet, LoRA, chained stages — key to None and
  take the existing single-job path unchanged.
- `BatchScheduler` holds compatible jobs for a short linger window
  (Settings.batch_linger_ms) so batchmates arriving in the same poll
  burst coalesce, then releases the group to the DISPATCH BOARD as ONE
  work item. Jobs that arrive PRE-BATCHED from a gang-scheduling hive
  (trace.gang on the wire, ISSUE 9) skip the linger entirely via
  `put_gang()` — the hive already did the waiting — flushing as one
  group with reason "gang". The linger is never shorter than
  LINGER_PASS_SHARE of the key's shortest pass: a batchmate that is a poll
  away is worth 1/40 of a ten-second pass. That is the LONGEST a group
  waits, not how long: a batchmate reaches the scheduler only in a /work
  reply, so once the poll loop has admitted a reply whole and said before
  which instant its timer sends no further poll (`reply_admitted`), every
  open group whose linger would end before that instant is released at
  once (`swarm_batch_releases_total{cause="no_poll_due"}`) — no reply can
  reach it before its timer would have released it anyway. A group whose
  linger outlasts the poll period keeps waiting and is joined by the next
  reply's jobs; nothing is released between two put()s of one reply. A
  group the linger formed says so in each member's trace context
  (`trace.gang`, `by: worker`), as a hive gang does, so whoever reads the
  envelopes sees the passes that ran. Groups cap at Settings.max_coalesce
  jobs and at the slice's capacity limit in images (rows_limit, wired to
  chips/requirements.fit_batch by the worker), so a coalesced batch is
  always admissible without rejection.
- The dispatch board is the placement layer (round 8): released work
  items sit on the board until an idle slice claims one via `claim()`,
  which matches groups to slices by MODEL RESIDENCY (chips/allocator.py
  residency map) — a group goes to the slice where its model is already
  warm ("affinity"), a first-load group prefers a residency-unclaimed
  slice ("cold"), and a group whose home slice is busy is STOLEN by any
  idle slice rather than lingering (the ROADMAP cross-slice-stealing
  item). Interactive groups always claim first. Outcomes are counted in
  `swarm_placement_total{outcome}`.

Batching is an optimization, not a semantic change to what the hive
gets back: every job keeps its own id, prompt, nsfw flags, and result
envelope, and a coalesced job's images depend only on its OWN seed,
never on its batchmates. One honest caveat: the batched program draws
its per-row noise differently from the legacy single-job path, so a
seed-pinned job renders a different (equally valid) image coalesced
than solo — `batched_with` in pipeline_config records which path ran.
"""

from __future__ import annotations

import asyncio
import logging
import time
import uuid
from typing import Callable

from . import telemetry
# the compatibility vocabulary moved to the jax-free shared module
# (coalesce.py) so the hive's gang scheduler and this worker-side layer
# can never disagree about what coalesces; re-exported here because five
# PRs of call sites (and tests) import them from batching
from .coalesce import (  # noqa: F401  (re-exports)
    DEFAULT_GUIDANCE,
    DEFAULT_SCHEDULER,
    DEFAULT_STEPS,
    DEFAULT_STRENGTH,
    adapter_ref,
    coalesce_key,
    is_interactive,
    job_rows,
    placement_model,
)

logger = logging.getLogger(__name__)

# why a work item left the scheduler: "solo" (unbatchable / coalescing
# off), "linger" (timer expired), "no_poll_due" (its reply is in and no
# poll is due before the timer's end, see reply_admitted()), "size" (hit
# max_coalesce), "rows" (hit the slice's image capacity), "slots" (hit the
# distinct-adapter cap, ISSUE 13), "priority" (interactive fast-path),
# "preempt" (an interactive job in a DIFFERENT group flushed this one —
# slice contention, see put()), "gang" (pre-batched by the hive's gang
# scheduler — no linger, see put_gang()), "shutdown" (flush_all)
_FLUSHES = telemetry.counter(
    "swarm_batch_flush_total",
    "Work items released by the batch scheduler, by flush reason",
    ("reason",),
)
# what ended a linger that was left to itself, of the reasons above: the
# group filled ("full": size, rows, slots), its timer ran out ("timer"), or
# no batchmate could reach it before the timer would ("no_poll_due"). A
# solo, a hive gang and a priority, preempt or shutdown flush are none of
# the three and count under their reason alone
_RELEASES = telemetry.counter(
    "swarm_batch_releases_total",
    "Lingering groups released, by cause (full | timer | no_poll_due)",
    ("cause",),
)
_RELEASE_CAUSE = {"size": "full", "rows": "full", "slots": "full",
                  "linger": "timer", "no_poll_due": "no_poll_due"}
for _cause in set(_RELEASE_CAUSE.values()):
    _RELEASES.inc(0, cause=_cause)  # all three on /metrics from the start
_GROUP_JOBS = telemetry.histogram(
    "swarm_batch_group_jobs",
    "Jobs per released work item (coalesce factor; 1 = solo dispatch)",
    buckets=(1, 2, 3, 4, 6, 8, 12, 16),
)
_GROUP_ROWS = telemetry.histogram(
    "swarm_batch_group_rows",
    "Images per released coalesced group",
    buckets=(1, 2, 4, 8, 16, 32),
)
# a group may wait this share of its key's shortest pass for batchmates (never
# less than Settings.batch_linger_ms): one more row costs a pass far less
# than the pass again, so 1/40 of it is cheap to wait; under a 2 s pass the
# fixed 50 ms linger still rules
LINGER_PASS_SHARE = 0.025
_PASS_KEYS_KEPT = 256

# what a job carries from the poll that brought it to the pass that runs
# it, beside what the hive sent (the slice worker takes both off before the
# arguments are formatted): the wall instant it arrived, where `queue_wait`
# and `linger` start, and the spans stamped on it since, for its envelope
ARRIVED, SPANS = "_telemetry_arrived", "_telemetry_spans"

# the tentpole metric: where each claimed work item landed relative to
# its model's warm state. affinity = the resident slice took it; steal =
# the resident slice was busy and an idle slice took it anyway; cold =
# the model was resident nowhere (first load / non-pipeline work)
_PLACEMENT = telemetry.counter(
    "swarm_placement_total",
    "Dispatch-board claims by placement outcome (affinity | steal | cold)",
    ("outcome",),
)

class BatchScheduler:
    """Linger-window grouping between the poll loop and the slice workers'
    dispatch board.

    put() admits raw hive jobs; released work items are LISTS of jobs —
    a singleton for unbatchable jobs (immediately), a coalesced group
    for compatible ones (after the linger window, or sooner when the
    group hits max_coalesce jobs or the slice's capacity in images, or
    when reply_admitted() finds that no poll is due before the window's
    end).
    Slice workers consume via claim() (placement-aware, residency
    routing + stealing) or the plain FIFO get(). task_done() mirrors
    asyncio.Queue so the worker's poll gating (full()) keeps bounding
    in-flight work.
    """

    def __init__(self, linger_s: float = 0.05, max_coalesce: int = 8,
                 maxsize: int = 0, ready_maxsize: int = 0,
                 rows_limit: Callable[[dict], int | None] | None = None,
                 free_slices: Callable[[], int] | None = None,
                 lora_slots: int = 8):
        self.linger_s = max(float(linger_s), 0.0)
        self.max_coalesce = int(max_coalesce)
        # most DISTINCT adapters one group may carry (ISSUE 13): the
        # batched program stacks one factor slot per adapter, so the
        # grouping layer must respect the same cap run_batched enforces
        self.lora_slots = max(int(lora_slots), 1)
        self.maxsize = int(maxsize)
        self.ready_maxsize = int(ready_maxsize)
        self.rows_limit = rows_limit
        # free-slice probe for the interactive preemption rule; None means
        # "unknown" and is treated as contended (preempt — latency first)
        self.free_slices = free_slices
        # the dispatch board: released work items awaiting a slice, oldest
        # first. Each entry: {"jobs", "model", "interactive"}
        self._board: list[dict] = []
        self._change = asyncio.Event()
        # key -> {"jobs": [...], "rows": int, "cap": int|None, "timer": handle}
        self._pending: dict[tuple, dict] = {}
        self._outstanding = 0
        self._ready_jobs = 0  # jobs released to the board, not yet claimed
        # row (image) twins of the job counters, for the capability
        # advertisement: the hive's gang budget is row-denominated, and a
        # job with num_images_per_prompt=4 occupies 4 rows of a slice's
        # coalescing appetite, not 1
        self._ready_rows = 0
        self._executing_rows = 0  # rows claimed off the board, not done
        # jobs whose pass has ended and whose envelope is not spooled yet
        # (the worker packages a pass's images after it frees the slice)
        self._undelivered = 0
        # job id -> the instant its work item was claimed, and coalesce key
        # -> [passes seen, the shortest's seconds from claim to pass_done,
        # the last one's claim instant]
        self._claimed_at: dict[str, float] = {}
        self._pass_s: dict[tuple, list] = {}
        self._closed = False  # drain mode: nothing lingers anymore

    # --- queue-compatible surface for the worker loop ---

    def full(self) -> bool:
        """Poll gating. Two bounds, so coalescing's extra headroom never
        turns into hoarding of work other swarm members could take:
        - ready_maxsize bounds jobs already RELEASED to the board (the
          round-5 work-queue bound — unbatchable singletons land here
          immediately, so mixed traffic backs polls off exactly as
          before);
        - maxsize bounds total in-flight jobs, giving only the jobs
          LINGERING in open groups the extended coalescing allowance.
        """
        if self.ready_maxsize > 0 and self._ready_jobs >= self.ready_maxsize:
            return True
        # jobs whose pass has ended want no slice any more: they are
        # outstanding until their envelope is spooled (drain, heartbeat,
        # /healthz), but must not keep the next gang at the hive
        return self.maxsize > 0 and (
            self._outstanding - self._undelivered >= self.maxsize)

    def task_done(self, job: dict | None = None) -> None:
        """One job finished, pass and delivery at once (`pass_done` +
        `job_delivered`; a no-arg call assumes one row)."""
        self.pass_done(job)
        self.job_delivered()

    def job_delivered(self) -> None:
        """A job whose pass had ended has its envelope spooled (or will
        never have one): it is no longer outstanding."""
        self._outstanding -= 1
        self._undelivered -= 1

    def pass_done(self, job: dict | None = None) -> None:
        """One job's pass ended and its slice is free: what the dispatch
        board and the advertised row depth need to know. The job stays
        outstanding until `job_delivered`. Pass the job dict so the row
        accounting can subtract its true image count."""
        self._undelivered += 1
        self._executing_rows = max(
            self._executing_rows - (job_rows(job) if job is not None else 1),
            0)
        claimed = None if job is None else self._claimed_at.pop(
            str(job.get("id")), None)
        key = coalesce_key(job) if claimed is not None else None
        if key is not None:
            seen = self._pass_s.pop(key, None) or [0, float("inf"), None]
            if seen[2] != claimed:  # a pass's jobs share their claim instant
                seen = [seen[0] + 1,
                        min(seen[1], time.monotonic() - claimed), claimed]
            self._pass_s[key] = seen  # re-inserted: the newest key last
            if len(self._pass_s) > _PASS_KEYS_KEPT:
                self._pass_s.pop(next(iter(self._pass_s)))

    def _claimed(self, entry: dict) -> None:
        self._ready_jobs -= len(entry["jobs"])
        self._ready_rows -= entry["rows"]
        self._executing_rows += entry["rows"]
        now, released = time.monotonic(), entry["released"]
        waited = time.time() - released
        for job in entry["jobs"]:
            self._claimed_at[str(job.get("id"))] = now
            # span "claim": on the board until a slice took the work item
            # (a busy slice, placement); a gang's members share it
            telemetry.Span("claim", thread="wait", spans=job.setdefault(
                SPANS, [])).record(released, waited)

    def linger_for(self, key: tuple) -> float:
        """Seconds a new group of this key waits for batchmates at most:
        the fixed linger, or LINGER_PASS_SHARE of the key's shortest pass
        if that is longer. A key's first pass carries its compile, so the
        share counts only once two passes have been seen. The wait ends
        sooner where the group fills, and where the reply that brought
        its jobs is in and the poll loop's next poll is due only after
        these seconds are over (`reply_admitted`)."""
        passes, least_s, _ = self._pass_s.get(key, (0, 0.0, None))
        if passes < 2:
            return self.linger_s
        return max(self.linger_s, LINGER_PASS_SHARE * least_s)

    @property
    def pending_jobs(self) -> int:
        """Jobs lingering in open groups (not yet released to the board)."""
        return sum(len(g["jobs"]) for g in self._pending.values())

    @property
    def ready_jobs(self) -> int:
        """Jobs released to the dispatch board but not yet claimed."""
        return self._ready_jobs

    @property
    def outstanding_jobs(self) -> int:
        """All in-flight jobs: lingering + ready + executing."""
        return self._outstanding

    @property
    def outstanding_rows(self) -> int:
        """All in-flight IMAGE ROWS: lingering + ready + executing. This
        is what the worker advertises as `queue_depth` on /work polls —
        the hive's gang budget is row-denominated, and counting jobs
        instead would let a gang reply oversubscribe a slice that is
        mid-coalesce on multi-image jobs."""
        pending_rows = sum(g["rows"] for g in self._pending.values())
        return pending_rows + self._ready_rows + self._executing_rows

    def notify(self) -> None:
        """Wake claim()/get() waiters to re-match (fired on every board
        publish, and wired by the worker to SliceAllocator slice-free
        events so a claim blocked on 'work ready, no slice free' resumes
        the moment a slice returns)."""
        ev, self._change = self._change, asyncio.Event()
        ev.set()

    async def _wait_change(self) -> None:
        # grab the CURRENT event synchronously: callers check their
        # condition and call this with no await in between, so a notify()
        # racing the check can't be lost (single-threaded event loop)
        await self._change.wait()

    async def get(self) -> list[dict]:
        """Plain FIFO pop of the oldest work item (tests/tools; the worker
        uses the placement-aware claim())."""
        while not self._board:
            await self._wait_change()
        entry = self._board.pop(0)
        self._claimed(entry)
        return entry["jobs"]

    async def claim(self, allocator) -> tuple[list[dict], object, str]:
        """Placement-aware dispatch: wait until a work item AND a free
        slice exist, then match them — returns (jobs, chipset, outcome)
        with the chipset already acquired from `allocator`.

        Match policy, in order (oldest entry first within each rule):
        1. interactive work claims first, wherever it lands;
        2. a group whose model's home slice is free goes HOME (affinity);
        3. a group with no home anywhere takes a free slice, preferring
           one that is nobody's home (cold);
        4. otherwise the oldest group's home is busy: any idle slice
           steals it rather than idling (cross-slice batch stealing).
        The check-and-acquire section is synchronous, so concurrent slice
        workers cannot double-claim an entry or a slice.
        """
        while True:
            if self._board and allocator.has_free_slice():
                match = self._match(allocator)
                if match is not None:
                    return match
            await self._wait_change()

    def _match(self, allocator):
        from .chips.allocator import resident_slice

        def take(idx: int, chipset, outcome: str):
            entry = self._board.pop(idx)
            self._claimed(entry)
            _PLACEMENT.inc(outcome=outcome)
            return entry["jobs"], chipset, outcome

        # rule 1: interactive first
        for i, entry in enumerate(self._board):
            if entry["interactive"]:
                acquired = allocator.acquire_for(entry["model"])
                if acquired is None:
                    return None
                return take(i, *acquired)
        # rule 2: any entry whose home slice is free goes home
        free_ids = allocator.free_slice_ids()
        for i, entry in enumerate(self._board):
            home = resident_slice(entry["model"])
            if home is not None and home in free_ids:
                chipset = allocator.try_acquire(home)
                if chipset is not None:
                    return take(i, chipset, "affinity")
        # rule 3: oldest homeless entry takes a fresh slice
        for i, entry in enumerate(self._board):
            if resident_slice(entry["model"]) is None:
                acquired = allocator.acquire_for(entry["model"])
                if acquired is None:
                    return None
                return take(i, *acquired)
        # rule 4: every entry's home is busy — steal for the oldest
        acquired = allocator.acquire_for(self._board[0]["model"])
        if acquired is None:
            return None
        return take(0, *acquired)

    def _release(self, jobs: list[dict]) -> None:
        rows = sum(job_rows(j) for j in jobs)
        self._ready_jobs += len(jobs)
        self._ready_rows += rows
        released = time.time()
        for job in jobs:
            # span "linger": from the job's arrival to its group's release
            # to the board, the wait for batchmates (microseconds for a
            # hive gang or a solo: nobody waited); "waiting for batchmates"
            # and "waiting for a slice" (`claim`) are different knobs
            arrived = job.setdefault(ARRIVED, released)
            telemetry.Span("linger", thread="wait", spans=job.setdefault(
                SPANS, [])).record(arrived, released - arrived)
        self._board.append({
            "jobs": jobs,
            "rows": rows,
            "released": released,
            "model": placement_model(jobs[0]),
            "interactive": any(is_interactive(j) for j in jobs),
        })
        self.notify()

    async def put(self, job: dict) -> None:
        self._outstanding += 1
        job.setdefault(ARRIVED, time.time())
        if self._closed or self.max_coalesce <= 1 or self.linger_s <= 0:
            self._release_solo(job)
            return
        key = coalesce_key(job)
        if key is None:
            if is_interactive(job):
                self._preempt_lingerers()
            self._release_solo(job)
            return

        rows = job_rows(job)
        adapter = adapter_ref(job)
        group = self._pending.get(key)
        if group is not None and group["cap"] is not None \
                and group["rows"] + rows > group["cap"]:
            # this job would push the group past what the slice fits in
            # one pass — release the full group now, start a fresh one
            self._flush(key, reason="rows")
            group = None
        if (group is not None and adapter is not None
                and adapter not in group["adapters"]
                and len(group["adapters"]) >= self.lora_slots):
            # a new DISTINCT adapter past the stacked-slot cap: release
            # the full group, start a fresh one (ISSUE 13)
            self._flush(key, reason="slots")
            group = None
        if group is None:
            cap = None
            if self.rows_limit is not None:
                try:
                    cap = self.rows_limit(job)
                except Exception:  # capacity probe is advisory, never fatal
                    logger.exception("rows_limit probe failed")
            loop = asyncio.get_running_loop()
            group = {"jobs": [], "rows": 0, "cap": cap, "adapters": set()}
            group["timer"] = loop.call_later(
                self.linger_for(key), self._flush, key)
            self._pending[key] = group
        group["jobs"].append(job)
        group["rows"] += rows
        if adapter is not None:
            group["adapters"].add(adapter)
        if is_interactive(job):
            # priority fast-path: an interactive job takes its whole group
            # with it NOW — batchmates already lingering ride along (they
            # only get faster), nobody waits on the timer
            self._flush(key, reason="priority")
            self._preempt_lingerers()
        elif len(group["jobs"]) >= self.max_coalesce:
            self._flush(key, reason="size")
        elif group["cap"] is not None and group["rows"] >= group["cap"]:
            self._flush(key, reason="rows")

    def reply_admitted(self, no_poll_before: float) -> None:
        """The poll loop has put every job of a /work reply, and its timer
        sends no further poll before `no_poll_before`, an instant on the
        event loop's clock: an open group whose linger timer fires before
        that can be joined by nobody, so it goes to the board now. One
        whose linger outlasts the poll period stays open for the next
        reply's jobs. A poll sent early on new capacity
        (worker._wait_to_poll) is not reckoned with: its reply joins the
        groups it finds still open."""
        for key, group in list(self._pending.items()):
            if group["timer"].when() < no_poll_before:
                self._flush(key, reason="no_poll_due")

    async def put_gang(self, jobs: list[dict]) -> None:
        """Admit a hive-pre-batched gang (jobs sharing one `trace.gang`
        id on the wire): flush immediately as one group with reason
        "gang" — the hive already did the waiting, so a linger window
        here would only add latency. Degrades gracefully: members whose
        key disagrees (or is None — the hive and worker should agree,
        but the worker's view is authoritative for its own slice) fall
        back to the normal put() path, and a gang larger than one
        slice's capacity splits into admissible chunks."""
        if len(jobs) <= 1 or self._closed or self.max_coalesce <= 1:
            for job in jobs:
                await self.put(job)
            return
        solos: list[dict] = []
        by_key: dict[tuple, list[dict]] = {}
        for job in jobs:
            key = coalesce_key(job)
            if key is None:
                solos.append(job)
            else:
                by_key.setdefault(key, []).append(job)
        for members in by_key.values():
            cap = None
            if self.rows_limit is not None:
                try:
                    cap = self.rows_limit(members[0])
                except Exception:  # capacity probe is advisory, never fatal
                    logger.exception("rows_limit probe failed")
            chunk: list[dict] = []
            rows = 0
            adapters: set[str] = set()
            for job in members:
                r = job_rows(job)
                a = adapter_ref(job)
                if chunk and (len(chunk) >= self.max_coalesce
                              or (cap is not None and rows + r > cap)
                              or (a is not None and a not in adapters
                                  and len(adapters) >= self.lora_slots)):
                    self._release_gang(chunk, rows)
                    chunk, rows, adapters = [], 0, set()
                chunk.append(job)
                rows += r
                if a is not None:
                    adapters.add(a)
            if chunk:
                self._release_gang(chunk, rows)
        for job in solos:
            await self.put(job)
        if any(is_interactive(j) for j in jobs):
            # same latency-first rule as put(): an interactive gang on a
            # contended worker must not queue behind linger-timer luck
            self._preempt_lingerers()

    def _release_gang(self, jobs: list[dict], rows: int) -> None:
        self._outstanding += len(jobs)
        _FLUSHES.inc(reason="gang")
        _GROUP_JOBS.observe(len(jobs))
        _GROUP_ROWS.observe(rows)
        for job in jobs:
            if isinstance(job.get("trace"), dict):
                job["trace"]["coalesced_with"] = len(jobs) - 1
        if len(jobs) > 1:
            logger.info("hive gang of %d jobs (%d images) for %s",
                        len(jobs), rows, jobs[0].get("model_name"))
        self._release(jobs)

    def _preempt_lingerers(self) -> None:
        """Interactive preemption ACROSS groups (ROADMAP): when an
        interactive job dispatches while slices are contended (at most one
        free), any group still lingering would contend for that slice the
        moment its timer fires — and linger-timer luck must not decide who
        goes first. Flushing them now (reason "preempt") puts every
        contender on the dispatch board, where claim() serves the
        interactive group first, then the preempted groups in age order.
        With multiple free slices nothing blocks, so lingering continues.
        (Callers flush the interactive job's own group before this runs,
        so _pending holds only the OTHER groups.)
        """
        if not self._pending:
            return
        contended = True
        if self.free_slices is not None:
            try:
                contended = int(self.free_slices()) <= 1
            except Exception:  # probe is advisory; stay latency-first
                contended = True
        if not contended:
            return
        for other in list(self._pending):
            self._flush(other, reason="preempt")

    def _release_solo(self, job: dict) -> None:
        _FLUSHES.inc(reason="solo")
        _GROUP_JOBS.observe(1)
        self._release([job])

    def _flush(self, key: tuple, reason: str = "linger") -> None:
        group = self._pending.pop(key, None)
        if group is None:  # timer fired after a size-triggered flush
            return
        group["timer"].cancel()
        _FLUSHES.inc(reason=reason)
        if reason in _RELEASE_CAUSE:
            _RELEASES.inc(cause=_RELEASE_CAUSE[reason])
        _GROUP_JOBS.observe(len(group["jobs"]))
        _GROUP_ROWS.observe(group["rows"])
        gang_id = uuid.uuid4().hex[:12]
        for index, job in enumerate(group["jobs"]):
            if isinstance(job.get("trace"), dict):
                job["trace"]["coalesced_with"] = len(group["jobs"]) - 1
                if len(group["jobs"]) > 1:
                    # the pass these jobs ride in, as the hive stamps a
                    # gang it formed: the envelopes echo it
                    job["trace"]["gang"] = {
                        "id": gang_id, "size": len(group["jobs"]),
                        "index": index, "by": "worker"}
        if len(group["jobs"]) > 1:
            logger.info(
                "coalesced %d jobs (%d images) for %s [%s]",
                len(group["jobs"]), group["rows"], key[0], reason,
            )
        self._release(group["jobs"])

    def cancel(self, job_id: str) -> bool:
        """Drop a job the hive cancelled while it was still HELD here —
        lingering in an open group or released to the dispatch board but
        not yet claimed by a slice. Returns True when found (the caller
        produces no envelope for it: the hive tombstoned the job, the
        worker simply never runs it). A job already claimed/executing is
        NOT here — that is the cancel registry's half (cancel.py), probed
        by the chunked denoise at chunk boundaries.

        Accounting mirrors the claim/task_done path: the job leaves
        outstanding/ready/row counters so poll gating and the advertised
        queue_depth stay truthful, and an emptied group or board entry
        disappears entirely (its linger timer cancelled)."""
        job_id = str(job_id)

        def matches(job: dict) -> bool:
            return str(job.get("id")) == job_id

        for key, group in list(self._pending.items()):
            for job in group["jobs"]:
                if not matches(job):
                    continue
                group["jobs"].remove(job)
                group["rows"] -= job_rows(job)
                # recompute the distinct-adapter slot accounting (an
                # adapter may be shared by surviving members): a stale
                # set would flush future same-key groups on reason
                # "slots" for adapters no surviving job carries
                group["adapters"] = {
                    a for a in map(adapter_ref, group["jobs"])
                    if a is not None}
                self._outstanding -= 1
                if not group["jobs"]:
                    group["timer"].cancel()
                    del self._pending[key]
                logger.info("cancelled lingering job %s before dispatch",
                            job_id)
                return True
        for entry in list(self._board):
            for job in entry["jobs"]:
                if not matches(job):
                    continue
                rows = job_rows(job)
                entry["jobs"].remove(job)
                entry["rows"] -= rows
                self._ready_jobs -= 1
                self._ready_rows -= rows
                self._outstanding -= 1
                if not entry["jobs"]:
                    self._board.remove(entry)
                logger.info("cancelled board job %s before a slice "
                            "claimed it", job_id)
                return True
        return False

    def flush_all(self) -> None:
        """Release every lingering group immediately (shutdown/tests)."""
        for key in list(self._pending):
            self._flush(key, reason="shutdown")

    def close(self) -> None:
        """Drain mode (worker stop(drain=True)): release every lingering
        group now and dispatch any straggler put() immediately — no job
        may sit in a linger window while the process is trying to exit."""
        self._closed = True
        self.flush_all()
