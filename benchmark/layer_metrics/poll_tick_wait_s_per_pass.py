"""Seconds a pass's work waited at the hive because the worker, able to
take it, was asleep between two polls: the `tick_wait` span of the poll
that completed the pass (the later of the poll before's end and the
instant a slice came free -> this poll's request; where a linger joined
jobs of two polls, the later poll's), begun no earlier than the latest
hive `admit` of the jobs that poll brought (a worker asleep while the hive
had nothing for it lost nothing); median over the passes settled inside
the window. What a poll at the instant a slice frees, or a job arrives,
would save."""

from benchmark import lifecycle, measure, spans


def read(record):
    waits = []
    for members, found in lifecycle.by_pass(
            measure.settled_in_window(record)):
        ticks = spans.named(found, "tick_wait")
        if not ticks:
            continue
        tick = ticks[-1]  # by start: the last poll's
        admits = [measure.stamp(job, "admit") for job in members
                  if tick in lifecycle.own(job, "tick_wait")]
        start = max([tick["start_wall"], *(a for a in admits if a)])
        waits.append(max(spans.end(tick) - start, 0.0))
    return measure.median(waits)
