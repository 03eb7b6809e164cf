"""Seconds the program's registry spent building pipelines before the
window opened (shapes, weights, placement; the resident models included):
`swarm_job_stage_seconds_sum{stage="registry_build"}` at the window's
opening, the program's own span around the factory on a miss."""


def read(record):
    sums = record["scrape_open"].get("swarm_job_stage_seconds_sum", {})
    return sums.get("registry_build")
