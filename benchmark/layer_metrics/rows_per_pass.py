"""Images per pass, over the passes that settled inside the window: jobs
grouped by the gang the hive dispatched them in (its trace context, echoed
in the envelope), each job's rows from the envelope's `batch_rows`."""

from benchmark import measure


def read(record):
    passes = measure.passes(measure.settled_in_window(record))
    if not passes:
        return None
    return sum(p["images"] for p in passes) / len(passes)
