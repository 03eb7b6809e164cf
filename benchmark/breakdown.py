"""The `breakdown` of a traced run: the device operations that took most
time, under the names the trace gives, and the idle gaps by what the host
was doing, in the program's own words: each gap is put on the wall clock
and takes the name of the span the program stamped over its middle
(`pipeline_config.spans` of the envelopes, `spans.py`). No stage of any
pipeline is named here; a program that stamps other stages gets other
labels, and one that stamps none gets `unknown`."""

from __future__ import annotations

import re

from . import measure, spans

TOP = 10
# where the worker stamps what it does for a pass after letting its slice
# go (the packaging of the pass's artifacts): work, not a wait
HOST_THREAD = "host"


def clock(trace: dict):
    """trace nanoseconds -> wall seconds, from the `bench_sync` annotation
    the tracer wrote at a known wall time."""
    for name, start_ns, _ in trace.get("annotations", []):
        m = re.match(r"bench_sync wall=([\d.]+)", name)
        if m:
            wall0 = float(m.group(1))
            return lambda ns: wall0 + (ns - start_ns) / 1e9
    return None


def covers(span: dict, wall: float) -> bool:
    return span["start_wall"] <= wall <= spans.end(span)


def innermost(found: list[dict], wall: float) -> dict | None:
    """The shortest of `found` over `wall`: of nested spans the inner."""
    over = [span for span in found if covers(span, wall)]
    return min(over, key=lambda span: span["seconds"]) if over else None


def passes_of(jobs: list[dict]) -> list[dict]:
    """What labels a gap, for every pass that stamped spans: each `pass`
    span (the slice held) with its children on its thread, the spans of
    the host thread, and the stretch from the slice let go to the pass's
    last settle at the hive."""
    grouped: dict[str, list[dict]] = {}
    for job in jobs:
        grouped.setdefault(measure.pass_id(job), []).append(job)
    out = []
    for members in grouped.values():
        found = spans.of_pass(members)
        if not found:
            continue
        held = spans.named(found, "pass")
        settles = [s for s in (measure.stamp(m, "settle") for m in members)
                   if s is not None]
        out.append({
            "held": [(parent, spans.children(found, parent))
                     for parent in held],
            "host": [s for s in found if s["thread"] == HOST_THREAD],
            "settling": ((max(map(spans.end, held)), max(settles))
                         if held and settles else None)})
    return out


def label_gap(passes: list[dict], wall: float) -> str:
    """The name of what the host was doing at `wall`: under a `pass`, its
    innermost child there; with the slice free, the host thread's span, or
    the settling of a pass, or neither."""
    if not passes:
        return "unknown"
    for entry in passes:
        for parent, children in entry["held"]:
            if covers(parent, wall):
                child = innermost(children, wall)
                return child["name"] if child else "pass (no child span)"
    for entry in passes:
        span = innermost(entry["host"], wall)
        if span:
            return span["name"]
    for entry in passes:
        if entry["settling"] and \
                entry["settling"][0] <= wall <= entry["settling"][1]:
            return "upload + settle"
    return "between passes (poll + hive)"


def build(record: dict) -> dict | None:
    trace = record.get("trace")
    if not trace:
        return None
    ops = sorted(trace["op_seconds"].items(), key=lambda kv: -kv[1])[:TOP]
    to_wall = clock(trace)
    passes = passes_of(record["jobs"])
    gaps: dict[str, list[float]] = {}
    for lo, hi in trace["gaps_ns"]:
        label = ("unknown" if to_wall is None
                 else label_gap(passes, to_wall((lo + hi) / 2)))
        gaps.setdefault(label, []).append((hi - lo) / 1e9)
    idle = sorted(((f"{label} x{len(found)}", sum(found))
                   for label, found in gaps.items()),
                  key=lambda kv: -kv[1])[:TOP]
    return {"device_ops": [[name, seconds] for name, seconds in ops],
            "idle_gaps": [[label, seconds] for label, seconds in idle]}
