"""The grouped expert matmul's share of its roofline, in %: the least time
the chip could take for the pairs really routed and the experts that really
had pairs (`costs/expert_matmul.py`, `costs/peaks.py`), over the kernel's
summed device time in the traced stretch.

What ran inside the stretch is read from the program's own tally, a pass's
two programs apart (`pipeline_config.routing.prefill` / `.decode`): each is
taken in the proportion of its span (`prefill`, `decode`) that lies inside
the stretch, which is exact for a program wholly inside or outside and
spreads a decode scan's pairs evenly over its steps otherwise. The sums go
into one bound (the larger of summed operations over the peak rate and
summed bytes over the peak bandwidth: no more than the calls' own bounds
summed), so the share errs low. Which bound holds goes into the record's
`notes`."""

from benchmark import breakdown, measure, spans
from benchmark.costs import expert_matmul as cost
from benchmark.costs.peaks import least_seconds


def read(record):
    trace = record.get("trace")
    calls = (trace or {}).get("kernel_calls", {}).get("expert_matmul")
    to_wall = breakdown.clock(trace) if calls else None
    if not calls or to_wall is None:
        return None
    lo, hi = (to_wall(ns) for ns in trace["stretch_ns"])
    config = record["spec"]["config"]
    hidden, width = config["hidden_size"], config["moe_intermediate_size"]
    pairs = active = 0.0
    seen = set()
    for job in record["jobs"]:
        routing = measure.envelope(job).get("routing") or {}
        for span in spans.of_pass([job]):
            key = (span["name"], span["start_wall"])
            if span["name"] not in routing or key in seen \
                    or span["seconds"] <= 0:
                continue
            seen.add(key)
            inside = spans.overlap(*spans.interval(span), [(lo, hi)])
            share = min(inside / span["seconds"], 1.0)
            pairs += share * routing[span["name"]]["pairs"]
            active += share * routing[span["name"]]["active"]
    spent = sum(call["seconds"] for call in calls)
    if not spent or not pairs:
        return None
    least, bound = least_seconds(
        *cost.needed(pairs, active, hidden, width), record["device"]["kind"])
    record.setdefault("notes", {})["expert_matmul_roofline"] = {
        "calls": len(calls), "pairs": pairs, "active_experts": active,
        "bound_by": bound}
    return 100.0 * least / spent
