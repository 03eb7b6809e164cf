"""Of the cached positions the window's real queries could see, the share
attention read, in %: `swarm_sparse_selected_positions_total` over
`swarm_sparse_visible_positions_total`, both phases, every layer, summed
on the device. A query at position `t` sees `t + 1` and reads `min(t + 1,
index_topk)`: for prompts of 28.7 k to 32.8 k ids and 2048 selected it is
~13. A program without the counters reads nothing."""

from benchmark.layer_metrics.held_expert_pair_share import moved

VISIBLE = "swarm_sparse_visible_positions_total"
SELECTED = "swarm_sparse_selected_positions_total"


def read(record):
    visible, selected = moved(record, VISIBLE), moved(record, SELECTED)
    return (100.0 * selected / visible
            if selected is not None and visible else None)
