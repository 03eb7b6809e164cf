"""`denoise_decode_s_per_image` under the name that moves the latency
metric (one row a pass there, so it is the pass's device span)."""

from benchmark.harness import load_reader

read = load_reader("layer_metrics", "denoise_decode_s_per_image")
