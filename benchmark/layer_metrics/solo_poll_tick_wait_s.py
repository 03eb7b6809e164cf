"""`poll_tick_wait_s_per_pass` under the name that moves the latency
metric."""

from benchmark.harness import load_reader

read = load_reader("layer_metrics", "poll_tick_wait_s_per_pass")
