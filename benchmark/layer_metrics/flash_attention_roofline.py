"""The flash kernel's share of its roofline, in %: the least time the chip
could take for every traced call (`costs/flash_attention.py` at the call's
true lengths, `costs/peaks.py`), summed, over the kernel's summed device
time. Which bound holds, and how many calls matched a listed shape, go
into the record's `notes`."""

from benchmark.costs import flash_attention as cost
from benchmark.costs.peaks import least_seconds


def read(record):
    trace = record.get("trace")
    calls = (trace or {}).get("kernel_calls", {}).get("flash_attention")
    if not calls:
        return None
    listed = record["spec"]["config"]["attention_shapes"]
    kind = record["device"]["kind"]
    least = spent = 0.0
    bounds = {"compute": 0, "memory": 0}
    unmatched = 0
    for call in calls:
        out, q, k = call["shapes"][0], call["shapes"][1], call["shapes"][2]
        batch, heads, sq_pad, dim = q
        sq, skv, matched = cost.true_lengths(heads, sq_pad, k[2], dim, listed)
        unmatched += not matched
        seconds, bound = least_seconds(
            *cost.needed(batch, heads, sq, skv, dim), kind)
        least += seconds
        bounds[bound] += 1
        spent += call["seconds"]
    record.setdefault("notes", {})["flash_attention_roofline"] = {
        "calls": len(calls), "bound_by": bounds, "unmatched": unmatched}
    return 100.0 * least / spent if spent else None
