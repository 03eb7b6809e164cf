"""Seconds a pass's group stayed open for batchmates before the batcher
released it to the board: the pass's `linger` span (the longest of its
jobs': the first to arrive waited for the rest), **mean** over the passes
settled inside the window: where a linger is a quarter of a second one
cycle in four to eight and nothing otherwise, a median reads nothing."""

from benchmark import lifecycle


def read(record):
    lingers = lifecycle.per_pass(record, "linger")
    return sum(lingers) / len(lingers) if lingers else None
