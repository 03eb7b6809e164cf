"""From the slice letting a job go to the hive calling it settled: the end
of the job's `pass` span to the hive's `settle` stamp (result hand-off,
the envelope's spool write, the POST, the hive's bookkeeping), one host
clock; median over the window's jobs."""

from benchmark import measure, spans


def read(record):
    waits = []
    for job in measure.window_jobs(record):
        held = spans.named(spans.of_pass([job]), "pass")
        settled = measure.stamp(job, "settle")
        if held and settled is not None:
            waits.append(settled - spans.end(held[-1]))
    return measure.median(waits)
