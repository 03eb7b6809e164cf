"""The `breakdown` of a traced run: the device operations that took most
time, under the names the trace gives, and the idle gaps by what the host
was doing — as far as the benchmark can see from outside the program: the
hive timeline's wall stamps and the envelope's stage durations. Finer
labels need the program's spans in the profiler's trace (PERF.md, Open
questions)."""

from __future__ import annotations

import re

from . import measure

TOP = 10


def clock(trace: dict):
    """trace nanoseconds -> wall seconds, from the `bench_sync` annotation
    the tracer wrote at a known wall time."""
    for name, start_ns, _ in trace.get("annotations", []):
        m = re.match(r"bench_sync wall=([\d.]+)", name)
        if m:
            wall0 = float(m.group(1))
            return lambda ns: wall0 + (ns - start_ns) / 1e9
    return None


def host_phases(record: dict) -> list[tuple[float, float, str]]:
    """(from, to, label) in wall seconds for every pass, rebuilt from the
    envelope: the worker's receipt stamp, then the stage durations laid end
    to end, then the upload until the pass's last settle."""
    jobs = [job for job in record["jobs"] if measure.done(job)]
    by_pass: dict[str, list[dict]] = {}
    for job in jobs:
        by_pass.setdefault(measure.pass_id(job), []).append(job)
    phases = []
    for members in by_pass.values():
        job = members[0]
        received = (measure.envelope(job).get("trace") or {}).get(
            "received_wall")
        if received is None:
            continue
        t = received + (measure.timing(job, "queue_wait_s") or 0.0)
        end = t + (measure.timing(job, "job_s") or 0.0)
        for stage, label in (("text_encode_s", "text encode"),
                             ("trace_s", "program lookup"),
                             ("denoise_decode_s", "inside denoise + decode")):
            seconds = measure.timing(job, stage) or 0.0
            phases.append((t, t + seconds, label))
            t += seconds
        phases.append((t, end, "artifact encode"))
        settled = max(measure.stamp(m, "settle") or end for m in members)
        phases.append((end, settled, "upload + settle"))
    return phases


def label_gap(phases, lo: float, hi: float) -> str:
    middle = (lo + hi) / 2
    for start, end, label in phases:
        if start <= middle <= end:
            return label
    return "between passes (poll + hive)" if phases else "unknown"


def build(record: dict) -> dict | None:
    trace = record.get("trace")
    if not trace:
        return None
    ops = sorted(trace["op_seconds"].items(), key=lambda kv: -kv[1])[:TOP]
    to_wall = clock(trace)
    phases = host_phases(record)
    gaps: dict[str, list[float]] = {}
    for lo, hi in trace["gaps_ns"]:
        label = ("unknown" if to_wall is None
                 else label_gap(phases, to_wall(lo), to_wall(hi)))
        gaps.setdefault(label, []).append((hi - lo) / 1e9)
    idle = sorted(((f"{label} x{len(spans)}", sum(spans))
                   for label, spans in gaps.items()),
                  key=lambda kv: -kv[1])[:TOP]
    return {"device_ops": [[name, seconds] for name, seconds in ops],
            "idle_gaps": [[label, seconds] for label, seconds in idle]}
