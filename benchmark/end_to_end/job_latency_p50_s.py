"""Median, over the jobs submitted inside the window, of the client's
submit stamp (taken before the POST) to the hive's own `settle` wall stamp.
Both are `time.time()` of one host. With ~20 jobs the median is the highest
percentile the sample supports."""

from benchmark import measure


def read(record):
    return measure.median(
        measure.stamp(job, "settle") - job["submit_wall"]
        for job in measure.window_jobs(record)
        if measure.stamp(job, "settle") is not None)
