"""`swarm_xla_compile_seconds_total` over set-up (process start to the
window's opening): seconds in backend compiles and persistent-cache reads.
"""

from benchmark.harness import counter


def read(record):
    return counter(record["scrape_open"], "swarm_xla_compile_seconds_total")
