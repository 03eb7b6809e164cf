"""Sequences a pass, over the passes that settled inside the window: jobs
grouped by the gang the hive dispatched them in (its trace context, echoed
in the envelope), each job's rows from the envelope's `sequences`."""

from benchmark import measure


def read(record):
    passes: dict[str, int] = {}
    for job in measure.settled_in_window(record):
        rows = measure.envelope(job).get("sequences")
        if rows is None:
            return None  # a program whose envelopes count no sequences
        key = measure.pass_id(job)
        passes[key] = passes.get(key, 0) + int(rows)
    return sum(passes.values()) / len(passes) if passes else None
