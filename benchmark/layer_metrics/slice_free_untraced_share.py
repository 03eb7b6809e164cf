"""The share of the device's idle time with the slice free that the
program's spans cannot name, in %: idle seconds of the traced stretch that
lie under no `pass` span **and** under none of `tick_wait`, `poll`,
`queue_wait`, `format_args`, `handoff`, `artifact_encode` of any pass, nor
between a job's last worker span and its `settle` stamp at the hive (the
delivery), over all idle seconds under no `pass` span. The sibling of
`idle_untraced_share` for the other half of the idle time; the gaps are
put on the wall clock the same way (`breakdown.clock`). What is left is the
client's (a think time, a resubmission on its way) or code between passes
that no span wraps. A program that stamps no `poll` span reads nothing."""

from benchmark import breakdown, lifecycle, measure, spans
from benchmark.trace.reduce import union


def read(record):
    trace = record.get("trace")
    if not trace or not trace.get("gaps_ns"):
        return None
    to_wall = breakdown.clock(trace)
    passes = lifecycle.by_pass(record["jobs"])
    if to_wall is None or not any(
            spans.named(found, "poll") for _, found in passes):
        return None
    held, named = [], []
    for members, found in passes:
        held.extend(map(spans.interval, spans.named(found, "pass")))
        named.extend(spans.interval(span) for span in found
                     if span["name"] in lifecycle.BETWEEN_PASSES)
        for job in members:
            sent, settled = (lifecycle.handed_over(job),
                             measure.stamp(job, "settle"))
            if sent is not None and settled is not None and settled > sent:
                named.append((sent, settled))
    # what a `pass` covers is the other reader's: the named stretches
    # count where no pass does
    held = union(held)
    named = union(held + named)
    free = untraced = 0.0
    for lo_ns, hi_ns in trace["gaps_ns"]:
        lo, hi = to_wall(lo_ns), to_wall(hi_ns)
        outside = (hi - lo) - spans.overlap(lo, hi, held)
        free += outside
        untraced += (hi - lo) - spans.overlap(lo, hi, named)
    return 100.0 * untraced / free if free > 0 else None
