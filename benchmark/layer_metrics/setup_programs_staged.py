"""Programs handed to the backend (compiled or read back) over set-up:
`swarm_job_stage_seconds_count{stage="xla_compile"}` at the window's
opening."""


def read(record):
    counts = record["scrape_open"].get("swarm_job_stage_seconds_count", {})
    return counts.get("xla_compile")
