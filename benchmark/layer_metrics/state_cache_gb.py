"""GB of one pass's cache that are recurrent state and convolution tail
(rows x the linear-attention layers, whatever the positions): the
program's gauge `swarm_pass_state_bytes{model}`, set when the pass's
programs are placed, at the window's close. Beside `pass_cache_gb`, the
whole. A program without the gauge (the parent of PR 42): nothing is
read."""


def read(record):
    model = record["spec"]["config"]["job"]["model_name"]
    gauge = record["scrape_close"].get("swarm_pass_state_bytes", {})
    return gauge[model] / 1e9 if model in gauge else None
