"""The envelope's `prefill_s` (span `prefill`: the jitted prefill program
over every row of the pass, execution only, ended by a blocking wait);
median over the passes that settled inside the window."""

from benchmark import measure


def read(record):
    return measure.median(measure.per_pass(
        measure.settled_in_window(record),
        lambda job, rows: measure.timing(job, "prefill_s")))
