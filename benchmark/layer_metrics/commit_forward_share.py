"""Of the forwards a block decode made in the window, the share that were
commits, in %: `swarm_block_forward_rows_total{kind="commit"}` over both
kinds. A commit writes a finished block's keys and values and yields no
id: what a commit fused into the next block's first denoise forward would
remove."""

from benchmark.layer_metrics.tokens_per_forward import FORWARD_ROWS, moved


def read(record):
    commit = moved(record, FORWARD_ROWS, "commit")
    both = moved(record, FORWARD_ROWS)
    return 100.0 * commit / both if commit is not None and both else None
