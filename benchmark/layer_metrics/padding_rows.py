"""Padding rows the batched program ran inside the window:
`swarm_batch_pass_rows_total{kind="padding"}` across it."""

from benchmark.harness import counter


def read(record):
    name = "swarm_batch_pass_rows_total"
    return (counter(record["scrape_close"], name, "padding")
            - counter(record["scrape_open"], name, "padding"))
