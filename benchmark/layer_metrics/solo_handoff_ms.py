"""From a job's `pass` span ending to its packaging beginning on the host
thread (the event loop learning the pass is over, the slice's earlier
passes, the executor's pick-up, its batchmates' encodes): the job's own
`handoff` span, median over the window's jobs, in ms."""

from benchmark import lifecycle, measure


def read(record):
    found = measure.median(lifecycle.per_job(record, "handoff"))
    return None if found is None else 1e3 * found
