"""Images of jobs settled `done` at the hive, per second, over whole
passes inside the window (`measure.whole_pass_rate`)."""

from benchmark import measure


def read(record):
    return measure.whole_pass_rate(
        measure.passes(measure.settled_in_window(record)))
