"""Seconds spent reading executables back from the persistent compile
cache over set-up: `swarm_job_stage_seconds_sum{stage="xla_cache_read"}` at
the window's opening (0 on a start that hit nothing)."""

from benchmark import setup_split


def read(record):
    return setup_split.stage_s(record["scrape_open"], "xla_cache_read")
