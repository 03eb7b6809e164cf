"""How uneven the held experts' load is: the fullest held expert's pairs
of a call over the mean of the held experts', both summed over the
window's expert-layer calls (`swarm_expert_pairs_max_total` x held /
`swarm_expert_pairs_total`). 1 is even; the slowest expert of a call sets
an expert-parallel layer's time."""

from benchmark.layer_metrics.held_expert_pair_share import moved


def read(record):
    top = moved(record, "swarm_expert_pairs_max_total")
    pairs = moved(record, "swarm_expert_pairs_total")
    held = record["spec"]["config"].get("n_routed_experts")
    return top * held / pairs if top is not None and pairs and held else None
