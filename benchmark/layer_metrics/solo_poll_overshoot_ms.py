"""How long the poll loop's sleep lasted beyond what it asked for, a poll:
the program's `swarm_poll_overshoot_seconds_total` across the window over
the polls in it (`swarm_job_stage_seconds_count{stage="poll"}`), in ms. One
event loop carries the poll, the uploads and (here) the hive's handlers:
this is its lag where it delays a poll."""

from benchmark.harness import counter

NAME = "swarm_poll_overshoot_seconds_total"
POLLS = "swarm_job_stage_seconds_count"


def read(record):
    opened, closed = record["scrape_open"], record["scrape_close"]
    if NAME not in closed:
        return None  # a program without the counter
    polls = counter(closed, POLLS, "poll") - counter(opened, POLLS, "poll")
    if polls <= 0:
        return None
    return 1e3 * (counter(closed, NAME) - counter(opened, NAME)) / polls
