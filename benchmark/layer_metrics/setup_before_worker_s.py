"""Set-up before the worker exists: imports, weights, resident models and
the kernel checks. `setup_s` less the wall clock from the scrape before
the worker to the window's opening."""

from benchmark import setup_split


def read(record):
    worker = setup_split.worker_wall_s(record)
    if worker is None or record.get("setup_s") is None:
        return None
    return record["setup_s"] - worker
