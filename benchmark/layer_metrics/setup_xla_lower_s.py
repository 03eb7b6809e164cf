"""Seconds jax spent lowering jaxprs to MLIR modules over set-up:
`swarm_job_stage_seconds_sum{stage="xla_lower"}` at the window's opening."""

from benchmark import setup_split


def read(record):
    return setup_split.stage_s(record["scrape_open"], "xla_lower")
