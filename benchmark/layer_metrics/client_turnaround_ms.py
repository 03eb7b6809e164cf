"""`generator_late_ms` under the name that moves the latency metric."""

from benchmark.harness import load_reader

read = load_reader("layer_metrics", "generator_late_ms")
