"""The sparse latent attention kernel's share of its roofline, in %: the
least time the chip could take for every traced prefill call
(`costs/sparse_latent_attention.py`: the pairs the selection lets through,
`index_topk` a query at most, `costs/peaks.py`), summed, over the kernel's
summed device time. What a call is (queries, keys, heads) is read from the
metadata the kernel puts into its instruction; its rows and its heads'
width from its operands; which span of its row a call was, from its place
among the calls (`costs/lightning_indexer.py` `span_of`, as that kernel's
reader). The program's kernel computes every visible pair
under the selection's mask, so this reads low by design: it is the room a
kernel that reads the selected keys alone would have. Which bound holds
goes into the record's `notes`. A decode step's gathered attention is
plain XLA and has no call here. A program without the kernel has no such
call: nothing is read."""

from benchmark.costs import sparse_latent_attention as cost
from benchmark.costs.lightning_indexer import span_of
from benchmark.costs.peaks import least_seconds


def read(record):
    trace = record.get("trace")
    calls = (trace or {}).get("kernel_calls", {}).get(
        "sparse_latent_attention")
    if not calls:
        return None
    topk = record["spec"]["config"]["index_topk"]
    layers = record["spec"]["config"]["num_hidden_layers"]
    kind = record["device"]["kind"]
    least = spent = 0.0
    bounds = {"compute": 0, "memory": 0}
    for number, call in enumerate(calls):
        what = cost.call_of(call["shapes"])
        if what is None:
            return None  # a kernel that does not say what it was asked
        queries, keys, heads = what
        out = call["shapes"][0]  # [rows, queries, heads x head_dim]
        seen = (span_of(number, layers, queries, keys) + 1) * queries
        seconds, bound = least_seconds(*cost.needed(
            out[0], heads, queries, seen, topk, out[-1] // heads), kind)
        least += seconds
        bounds[bound] += 1
        spent += call["seconds"]
    record.setdefault("notes", {})["sparse_latent_attention_roofline"] = {
        "calls": len(calls), "bound_by": bounds}
    return 100.0 * least / spent if spent else None
