"""Closed loop: from a client's job settling (the hive's wall stamp) to
its next POST being accepted, less the think time the traffic asked for
before that POST, median, in ms. Generator, hive and worker share a
process; this says how long the generator kept the system waiting beyond
what the traffic says a client waits.
"""

from benchmark import measure


def read(record):
    by_id = {job["id"]: job for job in record["jobs"]}
    gaps = []
    for job in record["jobs"]:
        before = by_id.get(job.get("previous"))
        if before is None or not job.get("in_window"):
            continue
        settled = measure.stamp(before, "settle")
        if settled is not None and "accepted_wall" in job:
            gaps.append((job["accepted_wall"] - settled
                         - job.get("think_s", 0.0)) * 1e3)
    return measure.median(gaps)
