"""Of the positions a block decode's denoise forwards computed for real
rows and either fixed in that forward or found fixed already, the share
found fixed, in %: `swarm_block_slots_total{kind="idle"}` over `idle` +
`unmasked`. A position fixed by an earlier forward of its block (or given
by the prompt's tail) is computed again by every later one: at 2 denoise
forwards a block of 4, two of the second forward's four."""

from benchmark.layer_metrics.tokens_per_forward import moved

SLOTS = "swarm_block_slots_total"


def read(record):
    idle, unmasked = (moved(record, SLOTS, kind)
                      for kind in ("idle", "unmasked"))
    if idle is None or not idle + unmasked:
        return None
    return 100.0 * idle / (idle + unmasked)
