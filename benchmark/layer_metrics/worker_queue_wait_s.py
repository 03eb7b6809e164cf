"""The envelope's `timings.queue_wait_s` (the worker receiving the job to
a slice starting on it: linger, board, a busy slice), median."""

from benchmark import measure


def read(record):
    return measure.median(measure.timing(job, "queue_wait_s")
                          for job in measure.window_jobs(record))
