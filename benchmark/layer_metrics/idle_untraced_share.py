"""The share of the device's idle time that the program's spans cannot
name, in %: idle seconds inside a `pass` span (the slice held) and under
none of its children, over all idle seconds of the traced stretch. The
reduced trace's idle gaps are put on the wall clock with the tracer's
`bench_sync` mark (`breakdown.clock`) and intersected with the envelopes'
spans. Idle time between passes is in the denominator only: the slice was
free, and `slice_free_s_per_pass` counts it. It is the measure of the
spans' own coverage: what is left is host code on the slice thread that no
span wraps."""

from benchmark import breakdown, spans
from benchmark.trace.reduce import union


def read(record):
    trace = record.get("trace")
    if not trace or not trace.get("gaps_ns"):
        return None
    to_wall = breakdown.clock(trace)
    passes = spans.by_pass(record["jobs"])
    if to_wall is None or not passes:
        return None
    held, named = [], []
    for found in passes:
        for parent in spans.named(found, "pass"):
            held.append(spans.interval(parent))
            named.extend(spans.interval(child)
                         for child in spans.children(found, parent))
    if not held:
        return None
    held, named = union(held), union(named)
    idle = untraced = 0.0
    for lo_ns, hi_ns in trace["gaps_ns"]:
        lo, hi = to_wall(lo_ns), to_wall(hi_ns)
        idle += hi - lo
        untraced += spans.overlap(lo, hi, held) - spans.overlap(lo, hi, named)
    return 100.0 * untraced / idle if idle > 0 else None
