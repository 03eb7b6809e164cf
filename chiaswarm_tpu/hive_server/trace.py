"""Per-job trace assembly: one ordered, gap-attributed timeline.

The raw material is collected elsewhere — the hive stamps wall-clock
lifecycle events into ``JobRecord.timeline`` at every mutation site
(admit/shed in queue.py, dispatch in ``take()``, lease grants in
leases.py, redeliver/park in the reaper path, settle in app.py), the
journal persists the timeline with every WAL event so it survives crash
recovery, compaction, and standby promotion, and the worker's
``trace_job`` stage spans ride back inside the result envelope's
``pipeline_config.spans`` (an older worker's durations in
``pipeline_config.timings``; the wire trace context echoed under
``pipeline_config.trace``). This module is the read side: it merges
those sources into the one answer nobody could give before —
"where did job X spend its 40 seconds?" — served at
``GET /api/jobs/{id}/trace`` and asserted gap-free by the bench's
trace_e2e row.

The timeline contract:

- events are ordered by their wall stamps (they are appended in order;
  sorting is stable, so two events sharing an instant — dispatch and its
  lease — keep their append order);
- every inter-event gap is attributed (hive_queue, executing,
  lease_lost, resubmit_backoff, ...) so the sum of gaps IS the job's
  hive wall clock, with nothing hidden;
- an executing gap is broken down further with the worker's own stage
  spans, each clipped to the gap: a worker that stamps its life between
  passes sends ``tick_wait`` and ``poll`` (they begin before the
  dispatch they brought: what lies before it is the hive's queue, the
  gap before), ``queue_wait`` (``linger``, ``claim`` and
  ``package_wait`` inside it are its detail), ``format_args``, the
  children of ``pass``, ``handoff`` and ``artifact_encode``; whatever
  the spans do not cover (the envelope's spool write, its wait for the
  uploader, the POST, the hive's own bookkeeping, any hole between the
  spans) is reported honestly as ``unattributed_s`` rather than
  silently absorbed, and where the ``settle`` event carries
  ``received_wall`` (the instant the result's POST reached its handler)
  split at it into ``unattributed_wire_s`` (before: the worker's and
  the wire's) and ``unattributed_hive_s`` (after: parse, spool, settle);
- ``worker.stages`` is the same list unclipped, every span on the one
  wall clock.
"""

from __future__ import annotations

from typing import Any

# gap attribution between consecutive timeline events, keyed on
# (from_event, to_event); pairs not listed fall back to "other"
_GAP_LABELS = {
    ("shed", "shed"): "resubmit_backoff",
    ("shed", "admit"): "resubmit_backoff",
    ("admit", "dispatch"): "hive_queue",
    ("admit", "hold"): "hive_queue",
    ("hold", "dispatch"): "affinity_hold",
    ("redeliver", "hold"): "hive_queue",
    ("redeliver", "dispatch"): "hive_queue",
    ("dispatch", "lease"): "hive_grant",
    ("lease", "settle"): "executing",
    ("lease", "redeliver"): "lease_lost",
    ("lease", "lease"): "lease_regrant",
    ("lease", "park"): "lease_lost",
    ("dispatch", "settle"): "executing",
    ("admit", "park"): "unplaceable_wait",
    # cancellation & deadlines (ISSUE 10): a cancel caught the job
    # waiting (hive_queue) or executing; an expire is TTL'd queue time
    ("admit", "cancel"): "hive_queue",
    ("hold", "cancel"): "hive_queue",
    ("redeliver", "cancel"): "hive_queue",
    ("dispatch", "cancel"): "executing",
    ("lease", "cancel"): "executing",
    ("cancel", "settle"): "cancel_vs_result_race",
    ("admit", "expire"): "ttl_expired",
    ("hold", "expire"): "ttl_expired",
    ("redeliver", "expire"): "ttl_expired",
    # mid-pass durability (ISSUE 18): checkpoint/preview events land
    # DURING execution, so the spans around them are still executing
    # time; a lease lost after a checkpoint is the resume-saved window
    ("lease", "checkpoint"): "executing",
    ("dispatch", "checkpoint"): "executing",
    ("checkpoint", "checkpoint"): "executing",
    ("checkpoint", "preview"): "executing",
    ("checkpoint", "settle"): "executing",
    ("checkpoint", "redeliver"): "lease_lost",
    ("checkpoint", "cancel"): "executing",
    ("checkpoint", "park"): "lease_lost",
    ("lease", "preview"): "executing",
    ("dispatch", "preview"): "executing",
    ("preview", "preview"): "executing",
    ("preview", "checkpoint"): "executing",
    ("preview", "settle"): "executing",
    ("preview", "redeliver"): "lease_lost",
    ("preview", "cancel"): "executing",
    ("preview", "park"): "lease_lost",
    # a redelivered dispatch carrying a resume offer stamps it between
    # the lease grant and the (shorter) execution window
    ("lease", "resume_offer"): "hive_grant",
    ("resume_offer", "checkpoint"): "executing",
    ("resume_offer", "preview"): "executing",
    ("resume_offer", "settle"): "executing",
    ("resume_offer", "redeliver"): "lease_lost",
    ("resume_offer", "cancel"): "executing",
    ("resume_offer", "park"): "lease_lost",
}

# a span's end is its wall-clock start plus a perf_counter duration: a
# child may appear to overhang its parent's end by this much and still
# be inside it (starts are stamps of one clock and need no slack)
_SPAN_SLACK_S = 0.0001


def _envelope_config(result: dict | None) -> dict:
    if isinstance(result, dict) and isinstance(
            result.get("pipeline_config"), dict):
        return result["pipeline_config"]
    return {}


def _envelope_spans(cfg: dict) -> list[dict]:
    """The well-formed entries of ``pipeline_config.spans`` as
    {stage, thread, start_wall, seconds}, in start order."""
    spans = []
    for span in cfg.get("spans") or ():
        try:
            spans.append({
                "stage": str(span["name"]),
                "thread": str(span.get("thread", "")),
                "start_wall": float(span["start_wall"]),
                "seconds": max(float(span["seconds"]), 0.0),
            })
        except (TypeError, KeyError, ValueError, AttributeError):
            continue
    return sorted(spans, key=lambda span: span["start_wall"])


def _inside(child: dict, parent: dict) -> bool:
    return (child is not parent
            and child["thread"] == parent["thread"]
            and child["start_wall"] >= parent["start_wall"]
            and child["start_wall"] + child["seconds"]
            <= parent["start_wall"] + parent["seconds"] + _SPAN_SLACK_S
            # of two spans with one interval, the earlier entry is inside
            and (child["seconds"] < parent["seconds"]
                 or child["start_wall"] > parent["start_wall"]))


def _union_seconds(spans: list[dict]) -> float:
    total, reach = 0.0, float("-inf")
    for span in sorted(spans, key=lambda span: span["start_wall"]):
        start, end = span["start_wall"], span["start_wall"] + span["seconds"]
        total += max(end - max(start, reach), 0.0)
        reach = max(reach, end)
    return total


def worker_stages(result: dict | None) -> list[dict]:
    """The worker's stages from a settled envelope.

    From ``pipeline_config.spans`` (wall-stamped, thread-aware) when the
    worker sent them: the top level of what the job waited for, in start
    order — what the worker stamps between passes (``tick_wait``,
    ``poll``, ``queue_wait``, ``format_args``, ``handoff``,
    ``artifact_encode``) and the children of ``pass`` (the slice held;
    it is the parent, never a stage), each with its ``start_wall``; a
    span inside another on the same thread (``safety`` inside ``decode``,
    ``linger`` inside ``queue_wait``) is that stage's detail and would
    count its time twice.

    From ``pipeline_config.timings``'s ``*_s`` entries for a worker that
    sends no spans (insertion order is stage order — JSON preserves it),
    ``job_s`` left out: it is the whole pass, not a stage of it."""
    cfg = _envelope_config(result)
    spans = [span for span in _envelope_spans(cfg) if span["stage"] != "pass"]
    if spans:
        return [span for span in spans
                if not any(_inside(span, other) for other in spans)]
    timings = cfg.get("timings")
    if not isinstance(timings, dict):
        return []
    stages = []
    # every other *_s timing is a stage — queue_wait_s included: the
    # worker-side handoff wait is a real slice of the execution window
    for key, value in timings.items():
        if not isinstance(key, str) or not key.endswith("_s") \
                or key == "job_s":
            continue
        try:
            stages.append({"stage": key[:-2], "seconds": float(value)})
        except (TypeError, ValueError):
            continue
    return stages


def clipped_stages(stages: list[dict], lo: float, hi: float) -> list[dict]:
    """The part of each wall-stamped stage that lies inside [lo, hi] (the
    gap the stages carve), stages outside it left out; stages without
    stamps (an old worker's `timings`) as they are."""
    carved = []
    for stage in stages:
        if "start_wall" not in stage:
            return stages
        start = max(stage["start_wall"], lo)
        end = min(stage["start_wall"] + stage["seconds"], hi)
        if end > start or (end == start and not stage["seconds"]):
            carved.append({**stage, "start_wall": start,
                           "seconds": end - start})
    return carved


def worker_total_seconds(stages: list[dict]) -> float:
    """Seconds the stages cover: the union of their intervals where they
    carry wall stamps (two threads may overlap), else their sum."""
    if stages and all("start_wall" in stage for stage in stages):
        return _union_seconds(stages)
    return sum(stage["seconds"] for stage in stages)


def wire_trace_context(record, gang: dict | None = None) -> dict:
    """The trace context a /work reply carries into the worker: enough
    for the worker to stamp its half of the trace into the envelope and
    for the hive to attribute the returning spans to the right dispatch
    attempt. Field set is pinned by the protocol-conformance suite.

    `gang` ({id, size, index}) rides along when this dispatch left as
    part of a gang-scheduled group — the worker's poll loop uses the id
    to feed the members into its BatchScheduler as one pre-formed group
    (flush reason "gang", no linger). Solo dispatches carry NO gang key
    at all, so a legacy worker sees nothing new."""
    dispatched_wall = None
    for entry in reversed(record.timeline):
        if entry.get("event") == "dispatch":
            dispatched_wall = entry.get("wall")
            break
    context = {
        "id": record.job_id,
        "attempt": record.attempts,
        "dispatched_wall": dispatched_wall,
        "queue_wait_s": record.queue_wait_s,
    }
    if gang is not None:
        context["gang"] = {
            "id": str(gang.get("id")),
            "size": int(gang.get("size", 0)),
            "index": int(gang.get("index", 0)),
        }
    stage = record.job.get("stage") if isinstance(record.job, dict) else None
    if isinstance(stage, dict) and stage.get("workflow"):
        # stage-jobs (ISSUE 20) carry their graph coordinates so the
        # worker's envelope echo — and anything tailing the wire — can
        # attribute spans to the parent workflow; monolithic dispatches
        # carry NO stage key, keeping the legacy wire shape untouched
        context["stage"] = {
            "workflow_id": str(stage.get("workflow")),
            "stage": str(stage.get("name", "")),
            "index": int(stage.get("index", 0)),
        }
    return context


def envelope_trace(result: dict | None) -> dict:
    """The worker-echoed trace context from a settled envelope."""
    trace = _envelope_config(result).get("trace")
    return trace if isinstance(trace, dict) else {}


def build_trace(record, now_wall: float) -> dict[str, Any]:
    """Assemble the ordered, gap-attributed trace payload for one job."""
    raw = [dict(e) for e in record.timeline if isinstance(e, dict)]
    events = sorted(raw, key=lambda e: float(e.get("wall", 0.0)))
    # append order IS the causal order; a sort that actually changed it
    # means the stored timeline was scrambled (replay bug, clock skew) —
    # rendered sorted for display, but flagged so trace_missing (and the
    # chaos no-reordering assertion) can see the repair instead of being
    # silently satisfied by it
    events_resorted = events != raw
    t0 = float(events[0]["wall"]) if events else now_wall
    for event in events:
        event["t_s"] = round(float(event.get("wall", t0)) - t0, 3)

    stages = worker_stages(record.result)
    worker_total = round(worker_total_seconds(stages), 3)
    echoed = envelope_trace(record.result)

    gaps: list[dict] = []
    for prev, nxt in zip(events, events[1:]):
        seconds = round(float(nxt["wall"]) - float(prev["wall"]), 3)
        gap = {
            "from": prev.get("event"),
            "to": nxt.get("event"),
            "seconds": seconds,
            "attribution": _GAP_LABELS.get(
                (prev.get("event"), nxt.get("event")), "other"),
        }
        if gap["attribution"] == "executing" and stages:
            # the worker's own spans carve the execution window up, as
            # far as they lie in it; the remainder (spool, upload, the
            # hive's bookkeeping, and any hole between the spans) is
            # reported rather than absorbed
            carved = clipped_stages(
                stages, float(prev["wall"]), float(nxt["wall"]))
            carved_total = round(worker_total_seconds(carved), 3)
            unattributed = round(max(seconds - carved_total, 0.0), 3)
            gap["worker_stages"] = carved
            gap["worker_total_s"] = carved_total
            gap["unattributed_s"] = unattributed
            received = nxt.get("received_wall")
            if isinstance(received, (int, float)):
                hive_s = round(min(max(
                    float(nxt["wall"]) - received, 0.0), unattributed), 3)
                gap["unattributed_hive_s"] = hive_s
                gap["unattributed_wire_s"] = round(unattributed - hive_s, 3)
        gaps.append(gap)

    terminal = events[-1].get("event") if events else None
    open_ended = terminal not in ("settle", "park", "cancel", "expire")
    total_s = round(
        (now_wall if open_ended else float(events[-1]["wall"])) - t0, 3)

    payload: dict[str, Any] = {
        "id": record.job_id,
        "class": record.job_class,
        "status": record.state,
        "attempts": record.attempts,
        "placement": record.placement,
        "queue_wait_s": record.queue_wait_s,
        "events": events,
        "events_resorted": events_resorted,
        "gaps": gaps,
        "total_s": max(total_s, 0.0),
        "open": open_ended,
        "worker": {
            "stages": stages,
            "total_s": worker_total,
            "trace": echoed,
        },
    }
    return payload


def build_shed_trace(job_id: str, shed_events: list[dict]) -> dict[str, Any]:
    """Trace payload for an id that was shed but never admitted: the
    refusals ARE its timeline, and the spans between them are the
    submitter's backoff — reported with the same gap arithmetic an
    admitted record gets, not flattened to zero."""
    events = sorted((dict(e) for e in shed_events if isinstance(e, dict)),
                    key=lambda e: float(e.get("wall", 0.0)))
    t0 = float(events[0]["wall"]) if events else 0.0
    for event in events:
        event["t_s"] = round(float(event.get("wall", t0)) - t0, 3)
    gaps = [{
        "from": "shed", "to": "shed",
        "seconds": round(float(nxt["wall"]) - float(prev["wall"]), 3),
        "attribution": "resubmit_backoff",
    } for prev, nxt in zip(events, events[1:])]
    total_s = round(float(events[-1]["wall"]) - t0, 3) if events else 0.0
    return {
        "id": job_id, "status": "shed",
        "events": events, "gaps": gaps,
        "total_s": max(total_s, 0.0), "open": True,
    }


def trace_missing(payload: dict) -> list[str]:
    """What a COMPLETE (settled, gap-free) trace is missing, empty when
    nothing — the bench trace_e2e row and the durability tests assert
    on this instead of re-deriving completeness ad hoc.

    Complete means: admit, at least one dispatch with a placement
    outcome, and a settle are all present; events are monotonically
    ordered; the admit->dispatch queue wait is attributed; the worker's
    stage spans came back through the envelope."""
    missing: list[str] = []
    events = payload.get("events") or []
    kinds = [e.get("event") for e in events]
    if "admit" not in kinds:
        missing.append("no admit event")
    if "dispatch" not in kinds:
        missing.append("no dispatch event")
    elif not any(e.get("event") == "dispatch" and e.get("outcome")
                 for e in events):
        missing.append("dispatch event lacks a placement outcome")
    if "settle" not in kinds:
        missing.append("no settle event")
    if payload.get("events_resorted"):
        # build_trace sorts for display, so the served walls are always
        # monotone; the flag is the only witness that the STORED order
        # disagreed with the wall stamps
        missing.append("stored events were not monotonically ordered "
                       "(resorted by wall for display)")
    if payload.get("queue_wait_s") is None:
        missing.append("no queue wait recorded")
    gaps = payload.get("gaps") or []
    if not any(g.get("attribution") == "hive_queue" for g in gaps):
        missing.append("no attributed hive_queue gap")
    if not (payload.get("worker") or {}).get("stages"):
        missing.append("no worker stage spans in the envelope")
    return missing
