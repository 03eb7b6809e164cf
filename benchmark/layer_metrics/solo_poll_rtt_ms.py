"""The round trip of the polls that brought the window's jobs, request
sent -> reply parsed, with the hive's dispatch and gang-forming inside:
the distinct `poll` spans of the window's envelopes, median, in ms."""

from benchmark import lifecycle, measure


def read(record):
    polls = {(span["start_wall"], span["seconds"])
             for job in measure.window_jobs(record)
             for span in lifecycle.own(job, "poll")}
    found = measure.median(seconds for _, seconds in polls)
    return None if found is None else 1e3 * found
