"""What the serving programs' first calls cost in trace + lower + compile
(or read-back), without the kernel checks': the three staging stages' sums
at the window's opening less the same at the scrape before the worker
exists."""

from benchmark import setup_split


def read(record):
    opened = setup_split.staged_s(record["scrape_open"])
    if opened is None:
        return None
    return opened - (setup_split.staged_s(record["scrape_before_worker"])
                     or 0.0)
