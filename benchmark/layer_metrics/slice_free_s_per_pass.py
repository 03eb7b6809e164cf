"""Seconds the slice sat free after a pass before the next one took it
(result hand-off, spool, the next poll, the batcher, argument formatting):
the program's `swarm_slice_free_seconds_total` across the window, over the
passes that ended in it (`swarm_slice_execute_seconds_count`, every kind).
Counters of the program alone: it is what an operator reads with no
profiler, and it measures the poll cadence's share of a cycle directly."""

NAME = "swarm_slice_free_seconds_total"
PASSES = "swarm_slice_execute_seconds_count"


def moved(record, name):
    """A counter's movement across the window, every label summed."""
    return (sum(record["scrape_close"].get(name, {}).values())
            - sum(record["scrape_open"].get(name, {}).values()))


def read(record):
    if NAME not in record["scrape_close"]:
        return None  # a program without the counter
    passes = moved(record, PASSES)
    return moved(record, NAME) / passes if passes > 0 else None
