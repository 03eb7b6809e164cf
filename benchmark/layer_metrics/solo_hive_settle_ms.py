"""The hive's own part of a settle: the result's POST reaching its handler
(`received_wall`) -> the `settle` stamp (the body read and parsed, the
artifacts spooled, the record settled); median over the window's jobs, in
ms."""

from benchmark import lifecycle, measure


def read(record):
    waits = []
    for job in measure.window_jobs(record):
        got, settled = lifecycle.received(job), measure.stamp(job, "settle")
        if got is not None and settled is not None:
            waits.append(1e3 * (settled - got))
    return measure.median(waits)
