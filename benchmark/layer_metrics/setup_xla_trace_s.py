"""Seconds jax spent tracing Python into jaxprs over set-up (process start
to the window's opening), each function's self time:
`swarm_job_stage_seconds_sum{stage="xla_trace"}` at the opening."""

from benchmark import setup_split


def read(record):
    return setup_split.stage_s(record["scrape_open"], "xla_trace")
