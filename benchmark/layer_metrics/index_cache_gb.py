"""GB of one pass's cache that are index keys (rows x positions x the
index key's width x layers: what the lightning indexer scores a query
against): the program's gauge `swarm_pass_index_cache_bytes{model}`, set
when the pass's programs are placed, at the window's close. Beside
`pass_cache_gb`, the whole."""


def read(record):
    model = record["spec"]["config"]["job"]["model_name"]
    gauge = record["scrape_close"].get("swarm_pass_index_cache_bytes", {})
    return gauge[model] / 1e9 if model in gauge else None
