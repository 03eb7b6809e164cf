"""Process start to the window's opening: imports, the swarm's start,
on-device weights, compile or cache read, the kernel checks, the warm-up
passes."""


def read(record):
    return record["setup_s"]
