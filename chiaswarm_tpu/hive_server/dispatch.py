"""Residency-aware dispatch: place jobs where the weights are warm.

chips/allocator.py routes work items to the chip slice whose HBM already
holds the model (affinity / cold / steal). This module is the same
policy one level up — across WORKERS instead of slices — using only what
each worker volunteers in its /work query: `resident_models` (the
registry's warm set), `chips`/`hbm_gb`, live load, and the
`unconverted_families` honesty key. SwiftDiffusion (arXiv 2407.02031)
and LegoDiffusion (arXiv 2604.08123) both put the next serving win
exactly here: a request placed on a cold worker pays the full weight
load + compile; placed on the warm one it pays neither.

Outcomes (counted in `swarm_hive_dispatch_total{outcome}`):

- affinity  the polling worker already holds the job's model;
- adapter_affinity  the polling worker holds the job's model AND
            advertises the job's adapter operands resident
            (`resident_adapters`, ISSUE 16) — the zero-upload placement;
            a model-warm poller WITHOUT the adapter defers (counted as
            `hold`) while an adapter-warm peer is live and the job is
            inside `affinity_hold_s`: operands prefer, never starve;
- cold      no live worker holds it — whoever polls first loads it;
- steal     a warm worker exists but the job has waited past
            `affinity_hold_s`, so the cold poller takes it rather than
            letting latency pile up behind a busy home;
- hold      the job was SKIPPED this poll (a warm worker is live and the
            hold window hasn't lapsed) — deferred, not dispatched;
- gang      the job rode along as a gang MEMBER behind a seed job with
            the same coalesce key (ISSUE 9): same-key queued batchmates
            leave in ONE /work reply, pre-batched, so the worker's
            linger window is no longer the only coalescing opportunity;
- straggler_hold  an INTERACTIVE job was withheld from a poller the
            fleet stats flag as a straggler (fleet.py) while a healthy
            capable worker is live — bounded by the same hold window as
            affinity, so stragglers degrade latency-sensitive placement,
            never availability.
- shard_hold  an INTERACTIVE job was withheld from a poller that cannot
            run it as one sharded multi-chip program (`shard_capable`,
            ISSUE 12) while a shard-capable worker is live — same hold
            window bound: geometry prefers, never starves.
- flap_hold a FRESH seed (never dispatched) was withheld from a poller
            whose leases have expired `hive_flap_threshold` consecutive
            times (ISSUE 18) while a healthy capable worker is live —
            same hold window bound, and one settled result clears the
            streak: flap detection prefers, never starves.

Gang scheduling: when the picked job is coalesce-compatible
(coalesce.py — the exact key the worker's BatchScheduler groups by) and
the worker advertised a per-slice row appetite (`gang_rows`, its
max_coalesce), the dispatcher pulls queued same-class same-key
batchmates up to min(advertised rows, hive_gang_max, per-poll job cap).
A gang is a dispatch-time grouping, not a new lifecycle: each member is
leased and journaled individually, redelivery may degrade it to
singles, and the only wire evidence is `trace.gang = {id, size, index}`
stamped into each member's trace context. The seed keeps its placement
outcome (so affinity still prefers the worker whose slice holds the
model — the whole gang follows the seed's placement), members count as
`gang`, and `swarm_hive_gang_size` histograms the grouping.
"""

from __future__ import annotations

import dataclasses
import math
import uuid

from .. import telemetry
from ..coalesce import (CHIP_STAGES, adapter_ref, canonical_adapter_ref,
                        job_rows, placement_model, stage_of)
from .clock import CLOCK
from .fleet import parse_stats
from .queue import JobRecord, PriorityJobQueue

_DISPATCH = telemetry.counter(
    "swarm_hive_dispatch_total",
    "Hive /work dispatch decisions by placement outcome "
    "(affinity | adapter_affinity | cold | steal | hold | gang | "
    "straggler_hold | shard_hold | flap_hold)",
    ("outcome",),
)
_GANG_SIZE = telemetry.histogram(
    "swarm_hive_gang_size",
    "Jobs per gang-scheduled /work group (observed once per gang; "
    "solo dispatches are not observed)",
    buckets=(2, 3, 4, 6, 8, 12, 16),
)
_WORKERS_LIVE = telemetry.gauge(
    "swarm_hive_workers_live",
    "Distinct workers seen polling within the liveness window")


def _split_csv(value: str | None) -> frozenset[str]:
    return frozenset(
        part.strip() for part in (value or "").split(",") if part.strip())


def _to_int(value, default: int = 0) -> int:
    try:
        return int(float(value))
    except (TypeError, ValueError):
        return default


def _parse_family_rows(raw) -> dict:
    """"family:rows,family:rows" -> {family: rows >= 1}; anything that
    does not parse is left out (the family then has the default
    appetite)."""
    out = {}
    for part in str(raw or "").split(","):
        family, _, rows = part.partition(":")
        if family.strip() and _to_int(rows) >= 1:
            out[family.strip()] = _to_int(rows)
    return out


@dataclasses.dataclass
class WorkerInfo:
    """One worker's latest self-advertisement, parsed from /work query
    params (everything arrives stringified — hive.py ask_for_work)."""

    name: str
    version: str = ""
    resident: frozenset[str] = frozenset()
    unconverted: frozenset[str] = frozenset()
    chips: int = 0
    hbm_gb: int = 0
    slices: int = 1
    busy_slices: int = 0
    queue_depth: int = 0
    # per-slice coalescing appetite in image rows (the worker's
    # max_coalesce — a JOB cap, so multi-image jobs make this a
    # conservative under-estimate of the slice's true row capacity:
    # gangs under-fill rather than oversubscribe); 1 = no appetite
    gang_rows: int = 1
    # whether the poll advertised gang_rows at all: a gang-aware worker
    # also reports queue_depth in ROWS incl. executing (ISSUE 9); a
    # legacy poller keeps the pre-gang budget contract
    gang_aware: bool = False
    # families whose rows are not canvases (a text job's rows are its
    # sequences): rows ONE pass of the family admits on a slice of this
    # worker, from its own admission (`family_gang_rows`, "family:rows"
    # csv); a family that is not here has the `gang_rows` appetite
    family_rows: dict = dataclasses.field(default_factory=dict)
    # per-stage EWMA stats blob from the `stats` poll param (fleet.py):
    # {stage: (ewma_seconds, samples)}; empty for legacy pollers
    stats: dict = dataclasses.field(default_factory=dict)
    # slice-geometry advertisement (ISSUE 12): chips one job slice spans,
    # and whether the worker runs interactive jobs as ONE sharded program
    # over them (shard_interactive on a multi-chip slice). The dispatcher
    # prefers a shard-capable worker for interactive seeds.
    chips_per_slice: int = 0
    shard_capable: bool = False
    # adapter-operand residency (ISSUE 16): canonical adapter refs whose
    # stacked device operands are warm on this worker (lora_operands.py)
    # — the dispatcher routes a repeat adapter gang back to them so the
    # steady state re-uploads nothing
    resident_adapters: frozenset[str] = frozenset()
    # preemption tolerance (ISSUE 18): the worker runs a chunked,
    # checkpoint-armed denoise and can rehydrate a checkpoint blob —
    # only these pollers get `resume` offers on redelivered jobs
    resume_capable: bool = False
    # stage-typed placement (ISSUE 20): the stage names this poller will
    # serve (`stages` csv param — a jax-free host advertises only the
    # CPU set). `stage_aware` records whether the param was present at
    # all: a legacy poller never sees stage-jobs, in either direction.
    stages: frozenset[str] = frozenset()
    stage_aware: bool = False
    last_seen: float = 0.0

    @property
    def free_slices(self) -> int:
        return max(self.slices - self.busy_slices, 0)

    def rows_per_pass(self, coalesce: tuple | None) -> int:
        """The per-slice row appetite for a job of this coalesce key (its
        second element is the family, coalesce.coalesce_key)."""
        if coalesce is not None and len(coalesce) > 1:
            return self.family_rows.get(coalesce[1], self.gang_rows)
        return self.gang_rows

    def can_run(self, model: str | None) -> bool:
        """Capability gate from the honesty key: never hand a worker a
        model family it advertised as unconverted (it can only fail)."""
        if not model:
            return True
        lowered = model.lower()
        return not any(k and k in lowered for k in self.unconverted)

    def snapshot(self) -> dict:
        return {
            "name": self.name,
            "version": self.version,
            "chips": self.chips,
            "hbm_gb": self.hbm_gb,
            "slices": self.slices,
            "busy_slices": self.busy_slices,
            "queue_depth": self.queue_depth,
            "gang_rows": self.gang_rows,
            "family_gang_rows": dict(self.family_rows),
            "chips_per_slice": self.chips_per_slice,
            "shard_capable": self.shard_capable,
            "resume_capable": self.resume_capable,
            "resident_models": sorted(self.resident),
            "resident_adapters": sorted(self.resident_adapters),
            "stages": sorted(self.stages),
        }


class WorkerDirectory:
    """Who is alive and what is warm where. Entries refresh on every
    /work poll and age out after `ttl_s` — a dead worker's stale
    residency claim must not hold jobs hostage (see live_holders)."""

    def __init__(self, ttl_s: float, fleet=None):
        self.ttl_s = max(float(ttl_s), 0.0)
        # FleetStats (fleet.py): fed the per-stage EWMA blobs workers
        # piggyback on their polls, pruned in lockstep with liveness so
        # a departed worker's stats can't skew the straggler medians
        self.fleet = fleet
        self._workers: dict[str, WorkerInfo] = {}

    def observe(self, query: dict) -> WorkerInfo:
        name = str(query.get("worker_name") or "anonymous")
        info = WorkerInfo(
            name=name,
            version=str(query.get("worker_version", "")),
            resident=_split_csv(query.get("resident_models")),
            # keywords are substring-matched against model.lower() in
            # can_run — lowercase them here or a capitalized keyword
            # fails open and the job dispatches to a worker that can
            # only fail it
            unconverted=_split_csv(
                (query.get("unconverted_families") or "").lower()),
            chips=_to_int(query.get("chips")),
            hbm_gb=_to_int(query.get("hbm_gb")),
            slices=max(_to_int(query.get("slices"), 1), 1),
            busy_slices=_to_int(query.get("busy_slices")),
            queue_depth=_to_int(query.get("queue_depth")),
            gang_rows=max(_to_int(query.get("gang_rows"), 1), 1),
            gang_aware="gang_rows" in query,
            family_rows=_parse_family_rows(query.get("family_gang_rows")),
            stats=parse_stats(query.get("stats")),
            chips_per_slice=_to_int(query.get("chips_per_slice")),
            shard_capable=_to_int(query.get("shard_capable")) > 0,
            resident_adapters=_split_csv(query.get("resident_adapters")),
            resume_capable=_to_int(query.get("resume_capable")) > 0,
            stages=_split_csv(query.get("stages")),
            stage_aware="stages" in query,
            last_seen=CLOCK.mono(),
        )
        self._workers[name] = info
        # drop aged-out entries here rather than letting the dict grow
        # with every worker name ever seen (ephemeral/autoscaled fleets
        # register a fresh name per restart) — live() then scans only
        # names that could actually matter
        cutoff = CLOCK.mono() - self.ttl_s
        for stale in [n for n, w in self._workers.items()
                      if w.last_seen < cutoff]:
            del self._workers[stale]
            if self.fleet is not None:
                self.fleet.forget(stale)
        if self.fleet is not None:
            self.fleet.note(name, info.stats)
            self.fleet.refresh_metrics(self.live_names())
        _WORKERS_LIVE.set(len(self.live()))
        return info

    def live_names(self) -> list[str]:
        return [w.name for w in self.live()]

    def live(self) -> list[WorkerInfo]:
        cutoff = CLOCK.mono() - self.ttl_s
        return [w for w in self._workers.values() if w.last_seen >= cutoff]

    def live_holders(self, model: str | None,
                     exclude: str | None = None) -> list[WorkerInfo]:
        """Live workers (other than `exclude`) advertising `model` warm."""
        if not model:
            return []
        return [
            w for w in self.live()
            if w.name != exclude and model in w.resident
        ]

    def snapshot(self) -> list[dict]:
        return [w.snapshot() for w in sorted(
            self.live(), key=lambda w: w.name)]


class Dispatcher:
    """The placement decision for one /work poll."""

    def __init__(self, directory: WorkerDirectory, affinity_hold_s: float,
                 max_jobs_per_poll: int, gang_max: int = 8,
                 lora_slots: int = 8, flap_threshold: int = 0,
                 flapping_fn=None):
        self.directory = directory
        self.affinity_hold_s = max(float(affinity_hold_s), 0.0)
        self.max_jobs_per_poll = max(int(max_jobs_per_poll), 1)
        # flap detection (ISSUE 18): `flapping_fn` returns the worker
        # names whose leases have expired `flap_threshold` consecutive
        # times (LeaseTable.flapping) — derived live state, queried once
        # per select() call
        self.flap_threshold = max(int(flap_threshold), 0)
        self.flapping_fn = flapping_fn
        # most jobs one GANG may hold (Settings.hive_gang_max); <= 1
        # disables gang scheduling hive-side entirely
        self.gang_max = max(int(gang_max), 1)
        # most DISTINCT adapters one gang may carry (ISSUE 13,
        # Settings.lora_slots_max): the worker's stacked-factor program
        # has that many slots, so a gang past the cap would only fall
        # apart into solo fallbacks at the slice
        self.lora_slots = max(int(lora_slots), 1)

    def _budget(self, worker: WorkerInfo,
                per_slice: int | None = None) -> tuple[int, int]:
        """(work items, rows) to hand this poll, for jobs whose per-slice
        appetite is `per_slice` rows (the worker's `gang_rows` unless the
        job's family advertised its own: `WorkerInfo.rows_per_pass`).
        `queue_depth` is rows of whatever kind the worker holds: exact
        where a worker serves one kind, and where kinds mix it errs
        towards handing out less (256 sequences in flight leave an image
        job nothing; 4 images in flight leave a text gang 252).

        Gang-aware workers (they sent `gang_rows`): work items are
        slice-grained — each solo job or gang lands on ONE slice, so at
        most `free_slices` of them leave per poll — and rows are the
        worker's total advertised appetite (slices x gang_rows) minus
        `queue_depth`, which for these workers counts lingering + ready
        + EXECUTING rows (ISSUE 9), so a slice mid-coalesce is already
        accounted and a gang reply can't oversubscribe it.

        Legacy pollers (no `gang_rows`) keep the EXACT pre-gang
        contract: `free_slices - queue_depth` jobs, one row each —
        their depth excludes executing work (busy_slices covers it), and
        mixing it into the rows formula would hand a job to a worker
        whose free slice is already spoken for by a queued one. Either
        way a worker advertising no net capacity gets NOTHING: its poll
        is a heartbeat, and handing it work anyway would bury it while
        an idle worker's next poll could have taken the work
        immediately."""
        if not worker.gang_aware:
            free = max(worker.free_slices - worker.queue_depth, 0)
            return free, free
        per_slice = max(per_slice or worker.gang_rows, 1)
        free_rows = max(worker.slices * per_slice - worker.queue_depth, 0)
        items = min(worker.free_slices, math.ceil(free_rows / per_slice))
        return max(items, 0), free_rows

    def unplaceable(self, record: JobRecord) -> bool:
        """True when every LIVE worker has declared itself incapable of
        the job's model family. Such a job is skipped by select() on
        every poll, so it never leases — and therefore never reaches the
        redelivery/failed machinery — while still counting against
        admission depth. The reaper parks it (see HiveServer._reap_loop)
        rather than letting it clog the queue forever. An empty
        directory is NOT unplaceable: with nobody polling, the job
        simply waits for a worker to arrive."""
        live = self.directory.live()
        if not live:
            return False
        model = placement_model(record.job)
        return all(not w.can_run(model) for w in live)

    def select(self, worker: WorkerInfo, queue: PriorityJobQueue
               ) -> list[tuple[JobRecord, str, dict | None]]:
        """Pick (record, outcome, gang) triples for this worker, class
        order first, residency second. Jobs a warm OTHER worker should
        take are held back ("hold") until `affinity_hold_s` lapses; jobs
        this worker cannot run at all (unconverted family) are skipped
        silently for it.

        When a picked SEED job is coalesce-compatible and the worker
        advertised gang capacity, its queued same-class same-key
        batchmates leave in the same reply as one gang — never split
        across the per-poll budget (the stamped gang size is exactly
        what this reply carries) and never pulled across priority
        classes (the peers index is per-class). `gang` is
        {id, size, index} for gang members, None for solo dispatches."""
        handed: list[tuple[JobRecord, str, dict | None]] = []
        # what this reply may still hand, by per-slice appetite: the
        # budget at the reply's start less what it has handed so far
        budgets: dict[int, tuple[int, int]] = {}
        spent_items = spent_rows = 0
        now = CLOCK.mono()
        taken: set[str] = set()
        # straggler + shard-capability view for this poll: ONE live
        # snapshot (directory.live() filters the whole map per call, so
        # per-record rebuilds would make select() O(jobs x workers))
        fleet = self.directory.fleet
        live = self.directory.live()
        live_names = [w.name for w in live]
        poller_is_straggler = (
            fleet is not None and fleet.is_outlier(worker.name, live_names))
        flapping: set[str] = set()
        if self.flap_threshold > 0 and self.flapping_fn is not None:
            flapping = set(self.flapping_fn() or ())
        for record in queue.iter_queued():
            appetite = worker.rows_per_pass(record.coalesce)
            if appetite not in budgets:
                budgets[appetite] = self._budget(worker, appetite)
            items = budgets[appetite][0] - spent_items
            free_rows = budgets[appetite][1] - spent_rows
            if (items <= 0 or free_rows <= 0
                    or len(handed) >= self.max_jobs_per_poll):
                break
            if record.job_id in taken:
                continue  # already left as a gang member this reply
            # placement_model maps tiny-flagged jobs to the stand-in
            # name the worker's registry (and therefore its advertised
            # resident_models) actually knows them by
            model = placement_model(record.job)
            if not worker.can_run(model):
                continue
            stage = stage_of(record.job)
            if stage is not None and (
                    not worker.stage_aware or stage not in worker.stages
                    or (stage in CHIP_STAGES and worker.chips <= 0)):
                # stage-typed placement (ISSUE 20): a stage-job only
                # leaves with a poller that advertised its stage —
                # legacy pollers (no `stages` param) never see graph
                # work — and chip-path stages (denoise/upscale/video)
                # additionally require a chip host, whatever it claims
                continue
            cpu_stage = stage is not None and stage not in CHIP_STAGES
            if (not cpu_stage and poller_is_straggler
                    and record.job_class == "interactive"
                    and now - record.submitted_at < self.affinity_hold_s
                    and any(w.name != worker.name and w.can_run(model)
                            and not fleet.is_outlier(w.name, live_names)
                            for w in live)):
                # observability feeding placement: a latency-sensitive
                # seed is withheld from a fleet straggler while a
                # healthy capable worker is live — but only inside the
                # placement-hold window, so a fleet of stragglers (or a
                # healthy worker that stopped polling) degrades to the
                # slow dispatch, never to starvation
                _DISPATCH.inc(outcome="straggler_hold")
                continue
            if (worker.name in flapping
                    and record.attempts == 0
                    and now - record.submitted_at < self.affinity_hold_s
                    and any(w.name != worker.name and w.can_run(model)
                            and w.name not in flapping
                            for w in live)):
                # flap detection (ISSUE 18): a worker losing lease after
                # lease is probably dying repeatedly (OOM loop, flaky
                # host) — withhold FRESH seeds from it while a healthy
                # capable worker is live, inside the same hold window as
                # every other preference. Redeliveries are exempt (they
                # already waited a full deadline), and a settled result
                # resets the streak: flapping degrades placement, never
                # availability.
                _DISPATCH.inc(outcome="flap_hold")
                continue
            if (not cpu_stage
                    and record.job_class == "interactive"
                    and not worker.shard_capable
                    and now - record.submitted_at < self.affinity_hold_s
                    and any(w.name != worker.name and w.shard_capable
                            and w.can_run(model)
                            and (fleet is None or not fleet.is_outlier(
                                w.name, live_names))
                            for w in live)):
                # slice-geometry preference (ISSUE 12): an interactive
                # seed waits (inside the same hold window as affinity)
                # for a worker that will fan the single image over every
                # chip of its slice — the sharded pass is the latency
                # win the class exists for. Bounded exactly like
                # affinity/straggler holds: once the window lapses, or
                # when no shard-capable worker is live, any poller takes
                # it — geometry prefers, never starves. A straggler-
                # flagged shard-capable worker does NOT count as a
                # target: straggler_hold withholds the seed from it, so
                # counting it here would make the two rules defer to
                # each other and park the seed for the whole window.
                _DISPATCH.inc(outcome="shard_hold")
                continue
            if cpu_stage:
                # host-path stages (encode/decode/postprocess) have no
                # warm-weight economics: no affinity hold applies, the
                # first capable poller drains them immediately — which
                # is exactly what lets a jax-free encode host keep the
                # chip fleet fed without ever touching a chip itself
                outcome = "cold"
            elif model and model in worker.resident:
                aref = canonical_adapter_ref(record.job)
                if aref is not None and aref in worker.resident_adapters:
                    # model AND stacked adapter operands warm here: the
                    # zero-upload placement (ISSUE 16). Gang riders
                    # follow the seed as ever, so the whole repeat gang
                    # lands where its operand cache entry lives.
                    outcome = "adapter_affinity"
                elif (aref is not None
                        and now - record.submitted_at < self.affinity_hold_s
                        and any(aref in w.resident_adapters
                                for w in self.directory.live_holders(
                                    model, exclude=worker.name))):
                    # model warm here but the adapter's operands are warm
                    # on ANOTHER model-warm worker: defer inside the same
                    # hold window affinity uses. Operand residency
                    # PREFERS, never starves — once the window lapses (or
                    # the operand-warm peer goes dark) this poller takes
                    # the job as plain affinity.
                    _DISPATCH.inc(outcome="hold")
                    continue
                else:
                    outcome = "affinity"
            else:
                holders = self.directory.live_holders(model, exclude=worker.name)
                if not holders:
                    outcome = "cold"
                elif now - record.submitted_at >= self.affinity_hold_s:
                    outcome = "steal"
                else:
                    _DISPATCH.inc(outcome="hold")
                    # first hold only: the job's trace shows WHEN the
                    # affinity window started costing it latency without
                    # one event per skipped poll. Advisory until the next
                    # journaled transition carries the timeline forward.
                    # Held seeds hold their whole gang implicitly: the
                    # peers stay queued for the warm worker's next poll —
                    # affinity places the GANG, not just the seed.
                    if not any(e.get("event") == "hold"
                               for e in record.timeline):
                        # the queue's clock, not the module CLOCK: every
                        # other timeline stamp uses the injected clock,
                        # and mixing timebases would scramble the sorted
                        # trace under a test-injected wall clock
                        record.timeline.append({
                            "event": "hold", "wall": queue.clock.wall(),
                            "worker": worker.name,
                            "warm_on": sorted(h.name for h in holders)})
                    continue
            members = [record]
            # a legacy poller's budget is in JOBS (its depth never knew
            # rows); only gang-aware workers get row-denominated math —
            # a 4-image job must not eat 4 of a legacy worker's job slots
            rows = job_rows(record.job) if worker.gang_aware else 1
            if (record.coalesce is not None and self.gang_max > 1
                    and appetite > 1):
                # one gang = one slice pass: its rows must fit the
                # per-slice appetite AND the poll's remaining row budget
                cap_jobs = min(self.gang_max,
                               self.max_jobs_per_poll - len(handed))
                cap_rows = min(appetite, free_rows)
                # adapter-aware gangs (ISSUE 13): mixed-adapter members
                # share one pass as stacked per-row deltas, capped at
                # lora_slots DISTINCT adapters (the worker program's
                # factor-slot dimension)
                adapters = {a for a in (adapter_ref(record.job),)
                            if a is not None}
                for peer in queue.queued_peers(record):
                    if len(members) >= cap_jobs:
                        break
                    if peer.job_id in taken:
                        # already left with an EARLIER gang this reply;
                        # it stays queue-live until app.py takes it
                        # after select() returns, so the index alone
                        # cannot know
                        continue
                    peer_rows = job_rows(peer.job)
                    if rows + peer_rows > cap_rows:
                        # stop rather than skip ahead: pulling a later
                        # smaller peer over this one would reorder the
                        # class FIFO
                        break
                    peer_adapter = adapter_ref(peer.job)
                    if (peer_adapter is not None
                            and peer_adapter not in adapters
                            and len(adapters) >= self.lora_slots):
                        # same stop-don't-skip rule as rows: a later
                        # same-adapter peer must not overtake this one
                        break
                    members.append(peer)
                    rows += peer_rows
                    if peer_adapter is not None:
                        adapters.add(peer_adapter)
            spent_items += 1
            spent_rows += rows
            taken.update(m.job_id for m in members)
            if len(members) > 1:
                gang_id = uuid.uuid4().hex[:12]
                _GANG_SIZE.observe(len(members))
                for i, member in enumerate(members):
                    # the seed keeps its placement outcome; riders are
                    # the gang win the counter exists to measure
                    member_outcome = outcome if i == 0 else "gang"
                    _DISPATCH.inc(outcome=member_outcome)
                    handed.append((member, member_outcome, {
                        "id": gang_id, "size": len(members), "index": i}))
            else:
                _DISPATCH.inc(outcome=outcome)
                handed.append((record, outcome, None))
        return handed
