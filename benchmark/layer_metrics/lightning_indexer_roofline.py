"""The lightning indexer kernel's share of its roofline, in %: the least
time the chip could take for every traced call
(`costs/lightning_indexer.py`: the visible pairs of the call's span,
`costs/peaks.py`), summed, over the kernel's summed device time. What a
call is (queries, keys, heads) is read from the metadata the kernel puts
into its instruction; its rows and its heads' width from its operands.
One compiled kernel serves every span of a row, the span's first position
a number it is handed as it runs, which no instruction's text holds: `keys`
is the row's whole bucket, and which span a call was is told by its place
among the calls (`span_of`: a row's spans in turn, every layer of a span
before the next span; the traced stretch begins with a pass, and a share
over whole passes does not depend on where it begins). Which bound holds
goes into the record's `notes`. A decode step's one
query a row is plain XLA and has no call here. A program without the
kernel has no such call: nothing is read."""

from benchmark.costs import lightning_indexer as cost
from benchmark.costs.peaks import least_seconds


def read(record):
    trace = record.get("trace")
    calls = (trace or {}).get("kernel_calls", {}).get("lightning_indexer")
    if not calls:
        return None
    kind = record["device"]["kind"]
    layers = record["spec"]["config"]["num_hidden_layers"]
    least = spent = 0.0
    bounds = {"compute": 0, "memory": 0}
    for number, call in enumerate(calls):
        what = cost.call_of(call["shapes"])
        if what is None:
            return None  # a kernel that does not say what it was asked
        queries, keys, heads = what
        # the result, then (behind the scalar it is handed) the queries
        scores, q = [shape for shape in call["shapes"] if len(shape) == 3][:2]
        seen = (cost.span_of(number, layers, queries, keys) + 1) * queries
        seconds, bound = least_seconds(*cost.needed(
            scores[0], queries, seen, heads, q[-1] // heads), kind)
        least += seconds
        bounds[bound] += 1
        spent += call["seconds"]
    record.setdefault("notes", {})["lightning_indexer_roofline"] = {
        "calls": len(calls), "bound_by": bounds}
    return 100.0 * least / spent if spent else None
