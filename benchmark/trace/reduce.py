"""From a profiler trace (`.xplane.pb`) to numbers.

Read with `jax.profiler.ProfileData` and nothing else. What a v5e trace
holds (looked at by hand first, `record_small.py`): a plane per chip named
`/device:TPU:<n>` whose line `XLA Ops` has one event per executed HLO
instruction, named by the instruction's full text
(`%flash_attention.1 = bf16[2,10,4096,64]{...} custom-call(...)`); a Pallas
kernel's instruction carries the kernel's `name`. Host threads are lines of
the plane `/host:CPU`; the benchmark's own `TraceAnnotation`s land there.
All starts are nanoseconds on one clock.

- busy: the union of the `XLA Ops` intervals inside the stretch, averaged
  over the device planes; idle share is 1 - busy / stretch.
- an op's time: the summed durations of its events, grouped by instruction
  name without its numeric suffix. `while`, `call` and `conditional` hold
  other ops and are left out of the sums (not of the union).
- idle gaps: the complement of the union on the first device plane.
"""

from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
CONTAINERS = (" while(", " call(", " conditional(")
_SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")
_SUFFIX = re.compile(r"(\.\d+)+$")


def op_name(text: str) -> str:
    """`%flash_attention.1 = ...` -> `flash_attention`."""
    return _SUFFIX.sub("", text.split(" = ", 1)[0].lstrip("%"))


def shapes_in(text: str) -> list[tuple[int, ...]]:
    """Every array shape in an instruction's text, in order: its result
    first, then its operands."""
    return [tuple(int(n) for n in dims.split(",") if n)
            for dims in _SHAPE.findall(text)]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def load(path: str | Path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(path))


def device_events(data) -> dict[str, list[tuple[str, float, float]]]:
    """{plane name: [(instruction text, start_ns, duration_ns)]} for every
    device plane's `XLA Ops` line."""
    out = {}
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                out[plane.name] = [
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events]
    return out


def host_annotations(data, prefix: str) -> list[tuple[str, float, float]]:
    """The benchmark's own annotations: host events whose name starts with
    `prefix`, as (name, start_ns, duration_ns)."""
    found = []
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            found.extend((e.name, float(e.start_ns), float(e.duration_ns))
                         for e in line.events if e.name.startswith(prefix))
    return sorted(found, key=lambda event: event[1])


def reduce_trace(path: str | Path, stretch_marks: tuple[str, str] | None = None,
                 kernels: tuple[str, ...] = (), sync_prefix: str = "bench_"):
    """The reduced trace: a dict of plain numbers and short lists.

    `stretch_marks` names two host annotations (by prefix) whose starts
    bound the stretch; None, or marks that are not there, takes the first
    device event's start to the last one's end. `kernels` names the ops
    whose every call is kept with its shapes (for a roofline share).
    """
    data = load(path)
    planes = device_events(data)
    if not planes:
        return None
    annotations = host_annotations(data, sync_prefix)
    stretch = None
    if stretch_marks is not None:
        marks = [next((start for name, start, _ in annotations
                       if name.startswith(mark)), None)
                 for mark in stretch_marks]
        if None not in marks:
            stretch = (marks[0], marks[1])
    if stretch is None:
        starts = [s for events in planes.values() for _, s, _ in events]
        ends = [s + d for events in planes.values() for _, s, d in events]
        stretch = (min(starts), max(ends))
    lo, hi = stretch
    busy, ops, calls, gaps = [], {}, {k: [] for k in kernels}, []
    for index, (_, events) in enumerate(sorted(planes.items())):
        inside = [(text, s, d) for text, s, d in events
                  if d > 0 and s + d > lo and s < hi]
        merged = clip(union([(s, s + d) for _, s, d in inside]), lo, hi)
        busy.append(sum(b - a for a, b in merged))
        for text, s, d in inside:
            if any(token in text for token in CONTAINERS):
                continue
            name = op_name(text)
            seconds = (min(s + d, hi) - max(s, lo)) / 1e9
            ops[name] = ops.get(name, 0.0) + seconds / len(planes)
            if name in calls:
                calls[name].append({"seconds": seconds,
                                    "shapes": shapes_in(text)})
        if index == 0:
            edges = [lo] + [t for ab in merged for t in ab] + [hi]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    busy_s = sum(busy) / len(busy) / 1e9
    window_s = (hi - lo) / 1e9
    return {
        "chips": len(planes),
        "stretch_ns": [lo, hi],
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "op_seconds": ops,
        "kernel_calls": calls,
        "gaps_ns": sorted(gaps, key=lambda g: g[0] - g[1]),
        "annotations": annotations,
    }
