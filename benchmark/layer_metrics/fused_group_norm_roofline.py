"""The fused GroupNorm kernel's share of its roofline, in %: the least
time for every traced call (`costs/group_norm.py`: the tile read once and
written once; memory bound) over the kernel's summed device time."""

from benchmark.costs import group_norm as cost
from benchmark.costs.peaks import least_seconds


def read(record):
    trace = record.get("trace")
    calls = (trace or {}).get("kernel_calls", {}).get("fused_group_norm")
    if not calls:
        return None
    kind = record["device"]["kind"]
    least = spent = 0.0
    for call in calls:
        batch, rows, channels = call["shapes"][0]
        least += least_seconds(*cost.needed(batch, rows, channels), kind)[0]
        spent += call["seconds"]
    record.setdefault("notes", {})["fused_group_norm_roofline"] = {
        "calls": len(calls), "kernel_s": spent}
    return 100.0 * least / spent if spent else None
