"""Arithmetic on the program's own spans, which the span readers share.

A worker that stamps them (`chiaswarm_tpu/telemetry.py`: `Span`,
`trace_job`) sends in every envelope of a pass `pipeline_config.spans`: one
`{name, thread, start_wall, seconds}` per stage of the pass, `start_wall`
from `time.time()`, the same clock as the hive's stamps and the tracer's
`bench_sync` mark. `pass` is the slice held; a child lies inside its parent
on the same thread. An envelope of a gang carries the whole pass's spans, so
spans are told apart by (name, thread, start). A program without spans
gives empty lists here, and every reader `None`.
"""

from __future__ import annotations

from . import measure

# a span's end is its wall-clock start plus a perf_counter duration: a
# child may overhang its parent's end by this much and still be inside
# (starts are stamps of one clock and need no slack)
SLACK_S = 0.0001


def of_pass(members: list[dict]) -> list[dict]:
    """The distinct spans of one pass, from all its envelopes, by start."""
    seen = {}
    for job in members:
        for span in measure.envelope(job).get("spans") or ():
            seen.setdefault(
                (span["name"], span["thread"], span["start_wall"]), span)
    return sorted(seen.values(), key=lambda span: span["start_wall"])


def by_pass(jobs: list[dict]) -> list[list[dict]]:
    """`of_pass` for every pass the jobs rode in; passes without spans
    are left out."""
    grouped: dict[str, list[dict]] = {}
    for job in jobs:
        grouped.setdefault(measure.pass_id(job), []).append(job)
    return [spans for spans in map(of_pass, grouped.values()) if spans]


def named(spans: list[dict], name: str) -> list[dict]:
    return [span for span in spans if span["name"] == name]


def end(span: dict) -> float:
    return span["start_wall"] + span["seconds"]


def interval(span: dict) -> tuple[float, float]:
    return span["start_wall"], end(span)


def inside(child: dict, parent: dict) -> bool:
    return (child is not parent and child["thread"] == parent["thread"]
            and child["start_wall"] >= parent["start_wall"]
            and end(child) <= end(parent) + SLACK_S)


def children(spans: list[dict], parent: dict) -> list[dict]:
    """Every span inside `parent` on its thread, at any depth."""
    return [span for span in spans if inside(span, parent)]


def overlap(lo: float, hi: float, merged: list[tuple[float, float]]) -> float:
    """Seconds of [lo, hi] under a sorted list of disjoint intervals."""
    return sum(max(min(hi, b) - max(lo, a), 0.0) for a, b in merged)
