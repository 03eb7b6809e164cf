"""Of the (token, expert) pairs the router made in the window, the share
that fell on an expert held here, in %: `swarm_expert_pairs_total` over
`swarm_routed_tokens_total`. With 12 of 384 experts held and even routing
it is 3.125."""

PAIRS = "swarm_expert_pairs_total"
ROUTED = "swarm_routed_tokens_total"


def moved(record, name):
    """A counter's movement across the window, every label summed; None
    for a program without it."""
    if name not in record["scrape_close"]:
        return None
    return (sum(record["scrape_close"][name].values())
            - sum(record["scrape_open"].get(name, {}).values()))


def read(record):
    pairs, routed = moved(record, PAIRS), moved(record, ROUTED)
    return 100.0 * pairs / routed if pairs is not None and routed else None
