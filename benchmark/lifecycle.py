"""Arithmetic on the spans of a job's life between passes, which the
lifecycle readers share.

A worker that stamps them (`chiaswarm_tpu/worker.py`, `batching.py`) sends
in every envelope, beside the pass's own spans (`spans.py`): `tick_wait`
and `poll` (the poll that brought the job; a gang's members carry the same
two), `queue_wait` tiled by `linger`, `claim` and `package_wait`,
`format_args` up to the `pass` span's start, and after the pass the job's
own `handoff` and `artifact_encode`; its hive writes on the `settle` event
of the job's timeline `received_wall`, the instant the result's POST
reached its handler. All on `time.time()`. A program that stamps none of
them (an older worker, an older hive) gives empty lists here, and every
reader `None`.
"""

from __future__ import annotations

from . import measure, spans

# the worker's own account of a stretch with the slice free
BETWEEN_PASSES = ("tick_wait", "poll", "queue_wait", "format_args",
                  "handoff", "artifact_encode")


def own(job: dict, name: str) -> list[dict]:
    """The spans called `name` in the job's own envelope, by start."""
    return spans.named(spans.of_pass([job]), name)


def by_pass(jobs: list[dict]) -> list[tuple[list[dict], list[dict]]]:
    """(members, distinct spans) of every pass the jobs rode in; passes
    without spans are left out."""
    grouped: dict[str, list[dict]] = {}
    for job in jobs:
        grouped.setdefault(measure.pass_id(job), []).append(job)
    passes = ((members, spans.of_pass(members))
              for members in grouped.values())
    return [(members, found) for members, found in passes if found]


def of_pass(found: list[dict], name: str) -> dict | None:
    """The pass's span called `name`: where its jobs carry several (a
    group the linger joined: each job's own wait), the longest."""
    return max(spans.named(found, name), default=None,
               key=lambda span: span["seconds"])


def per_pass(record: dict, name: str) -> list[float]:
    """Seconds of the span called `name`, a pass settled inside the
    window."""
    found = (of_pass(found, name)
             for _, found in by_pass(measure.settled_in_window(record)))
    return [span["seconds"] for span in found if span is not None]


def per_job(record: dict, name: str) -> list[float]:
    """Seconds of each window job's own span called `name`."""
    return [span["seconds"] for job in measure.window_jobs(record)
            for span in own(job, name)[-1:]]


def handed_over(job: dict) -> float | None:
    """When the worker's last span of the job ended: its packaging
    (`artifact_encode`), else its `handoff`."""
    last = own(job, "artifact_encode") or own(job, "handoff")
    return spans.end(last[-1]) if last else None


def received(job: dict) -> float | None:
    """The `received_wall` of the job's last `settle` event."""
    found = None
    for entry in (job.get("trace") or {}).get("events", []):
        if entry.get("event") == "settle":
            found = entry.get("received_wall")
    return found
