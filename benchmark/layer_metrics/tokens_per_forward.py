"""Ids a block decode handed back a row a forward, across the window:
`swarm_generated_tokens_total` over `swarm_block_forward_rows_total` (both
kinds: a commit yields nothing and costs a forward). A decode that feeds a
row one token a step would read 1; a block of 4 positions that takes 2
denoise forwards and a commit reads 4 / 3, less what a pass's first block
(up to 3 given ids) and last block (not committed) change."""

TOKENS = "swarm_generated_tokens_total"
FORWARD_ROWS = "swarm_block_forward_rows_total"


def moved(record, name, kind=None):
    """A counter's movement across the window, summed over its labels (of
    them those whose last label is `kind`, where one is given); None for a
    program without it."""
    if name not in record["scrape_close"]:
        return None

    def total(scraped):
        return sum(value for labels, value in scraped.get(name, {}).items()
                   if kind is None or labels.split(",")[-1] == kind)

    return total(record["scrape_close"]) - total(record["scrape_open"])


def read(record):
    tokens, rows = moved(record, TOKENS), moved(record, FORWARD_ROWS)
    return tokens / rows if tokens is not None and rows else None
