"""From a slice picking a pass's jobs up to its `pass` span opening: the
jobs' arguments formatted (for a text job, its rows of ids), the
executor's pick-up, the busy lock and the jobs' keys; the `format_args`
span, median over the passes settled inside the window, in ms."""

from benchmark import lifecycle, measure


def read(record):
    found = measure.median(lifecycle.per_pass(record, "format_args"))
    return None if found is None else 1e3 * found
