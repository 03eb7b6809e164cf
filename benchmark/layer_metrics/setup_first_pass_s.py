"""The worker's first pass, start to end: gauge
`swarm_startup_seconds{mark="first_pass_end"}` less
`{mark="first_pass_start"}` at the window's opening."""


def read(record):
    marks = record["scrape_open"].get("swarm_startup_seconds", {})
    if "first_pass_start" not in marks or "first_pass_end" not in marks:
        return None
    return marks["first_pass_end"] - marks["first_pass_start"]
