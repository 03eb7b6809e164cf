"""GB of the cell's model on the chip that holds most of it, at the
window's opening: the program's gauge `swarm_resident_param_bytes{model}`,
set when a pipeline's parameter tree is placed (the largest chip's bytes
over that tree's shards). A model whose partition rules fell through to
replicated reads its whole size here. A program without the gauge gives
nothing to read: the metric is then left out."""


def read(record):
    model = record["spec"]["config"]["job"]["model_name"]
    gauge = record["scrape_open"].get("swarm_resident_param_bytes", {})
    return gauge[model] / 1e9 if model in gauge else None
