"""GB of latent cache one pass holds: the program's gauge
`swarm_pass_cache_bytes{model}` (rows x positions x cache width x layers,
set when the pass's programs are placed), at the window's close."""


def read(record):
    model = record["spec"]["config"]["job"]["model_name"]
    gauge = record["scrape_close"].get("swarm_pass_cache_bytes", {})
    return gauge[model] / 1e9 if model in gauge else None
