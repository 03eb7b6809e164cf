"""`artifact_encode_s_per_pass` under the name that moves the latency
metric."""

from benchmark.harness import load_reader

read = load_reader("layer_metrics", "artifact_encode_s_per_pass")
