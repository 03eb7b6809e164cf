"""The state-space step kernel's share of its roofline, in %: the least
time the chip could take for every traced call (`costs/ssd_step.py`: the
state read once and written once and the vectors beside it, from the
call's own operand shapes; `costs/peaks.py`), summed, over the kernel's
summed device time. Which bound holds goes into the record's `notes`. A
program without the kernel has no such call: nothing is read."""

from benchmark.costs import ssd_step as cost
from benchmark.costs.peaks import least_seconds


def read(record):
    trace = record.get("trace")
    calls = (trace or {}).get("kernel_calls", {}).get("ssd_step")
    if not calls:
        return None
    kind = record["device"]["kind"]
    least = spent = 0.0
    bounds = {"compute": 0, "memory": 0}
    for call in calls:
        what = cost.call_of(call["shapes"])
        if what is None:
            return None  # a call that does not show its state
        seconds, bound = least_seconds(*cost.needed(*what), kind)
        least += seconds
        bounds[bound] += 1
        spent += call["seconds"]
    record.setdefault("notes", {})["ssd_step_roofline"] = {
        "calls": len(calls), "bound_by": bounds}
    return 100.0 * least / spent if spent else None
