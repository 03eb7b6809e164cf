"""`slice_free_untraced_share` under the name that moves the latency
metric."""

from benchmark.harness import load_reader

read = load_reader("layer_metrics", "slice_free_untraced_share")
