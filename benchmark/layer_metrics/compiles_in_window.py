"""`swarm_xla_compiles_total` across the window. Anything but 0 makes the
run incorrect."""


def read(record):
    return record["window_compiles"]
