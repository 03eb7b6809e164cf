"""The worker's start and the three warm-up passes: wall clock from the
scrape before the worker exists to the window's opening."""

from benchmark import setup_split


def read(record):
    return setup_split.worker_wall_s(record)
