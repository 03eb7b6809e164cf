"""Arithmetic the metric readers share: medians, a job's wall stamps from
its hive timeline, passes, and the whole-pass rate."""

from __future__ import annotations

import statistics


def median(values) -> float | None:
    values = [v for v in values if v is not None]
    return statistics.median(values) if values else None


def stamp(job: dict, event: str) -> float | None:
    """The wall stamp of the job's last `event` (admit, dispatch, lease,
    settle) on the hive's own timeline."""
    found = None
    for entry in (job.get("trace") or {}).get("events", []):
        if entry.get("event") == event:
            found = entry.get("wall")
    return found


def envelope(job: dict) -> dict:
    return ((job.get("status") or {}).get("result") or {}).get(
        "pipeline_config") or {}


def timing(job: dict, name: str) -> float | None:
    return (envelope(job).get("timings") or {}).get(name)


def images(job: dict) -> int:
    size = envelope(job).get("batch_rows")
    return int(size[1]) if size else 1


def pass_id(job: dict) -> str:
    gang = (envelope(job).get("trace") or {}).get("gang") or {}
    return str(gang.get("id") or job["id"])


def done(job: dict) -> bool:
    return (job.get("status") or {}).get("status") == "done"


def settled_in_window(record: dict) -> list[dict]:
    """Jobs settled `done` at the hive inside the window, whenever they
    were submitted."""
    lo, hi = record["window"]["open_wall"], record["window"]["close_wall"]
    return [job for job in record["jobs"] if done(job)
            and stamp(job, "settle") is not None
            and lo <= stamp(job, "settle") <= hi]


def window_jobs(record: dict) -> list[dict]:
    """Jobs submitted inside the window, awaited, and done."""
    return [job for job in record["jobs"]
            if job.get("in_window") and not job["withdrawn"] and done(job)]


def passes(jobs: list[dict]) -> list[dict]:
    """Jobs grouped into the passes they rode in, each with the instant
    its last job settled and its images, in order of that instant."""
    grouped: dict[str, dict] = {}
    for job in jobs:
        entry = grouped.setdefault(
            pass_id(job), {"end_wall": 0.0, "images": 0, "jobs": 0})
        entry["end_wall"] = max(entry["end_wall"], stamp(job, "settle"))
        entry["images"] += images(job)
        entry["jobs"] += 1
    return sorted(grouped.values(), key=lambda p: p["end_wall"])


def whole_pass_rate(pass_list: list[dict]) -> float | None:
    """Images per second over whole passes: the images of every pass after
    the first, over the time from the first pass's end to the last's.
    Settles come a gang at a time, so counting to the window's edges would
    swing by a gang."""
    if len(pass_list) < 2:
        return None
    span = pass_list[-1]["end_wall"] - pass_list[0]["end_wall"]
    if span <= 0:
        return None
    return sum(p["images"] for p in pass_list[1:]) / span


def per_pass(jobs: list[dict], value) -> list:
    """`value(job, rows)` once per pass: one job stands for its pass (a
    pass's envelopes carry the same copied timings), `rows` are the
    pass's images."""
    first, rows = {}, {}
    for job in jobs:
        key = pass_id(job)
        first.setdefault(key, job)
        rows[key] = rows.get(key, 0) + images(job)
    return [value(job, rows[key]) for key, job in first.items()]
