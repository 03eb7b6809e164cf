"""From the worker's last span of a job ending (`artifact_encode`, else
`handoff`) to its result's POST reaching the hive's handler (the `settle`
event's `received_wall`): the envelope finished, its spool write, its wait
for the uploader and the POST's way through the one event loop; median
over the window's jobs."""

from benchmark import lifecycle, measure


def read(record):
    waits = []
    for job in measure.window_jobs(record):
        sent, got = lifecycle.handed_over(job), lifecycle.received(job)
        if sent is not None and got is not None:
            waits.append(got - sent)
    return measure.median(waits)
