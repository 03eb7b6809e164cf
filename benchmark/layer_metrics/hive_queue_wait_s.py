"""The hive's own `queue_wait_s` (submit to first dispatch) from
`GET /api/jobs/<id>`, median over the window's jobs."""

from benchmark import measure


def read(record):
    return measure.median(job["status"].get("queue_wait_s")
                          for job in measure.window_jobs(record))
