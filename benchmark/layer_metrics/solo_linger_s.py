"""The `linger` span of a pass (a job's wait for batchmates that, for a
lone job, never come), median over the passes settled inside the
window."""

from benchmark import lifecycle, measure


def read(record):
    return measure.median(lifecycle.per_pass(record, "linger"))
