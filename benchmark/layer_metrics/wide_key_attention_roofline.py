"""The wide-key attention kernel's share of its roofline, in %: the least
time the chip could take for every traced call
(`costs/wide_key_attention.py`: the visible pairs of the call's own mask at
the head's published 192 + 128, `costs/peaks.py`), summed, over the
kernel's summed device time. What a call is (queries, keys, window, sink,
heads and their widths) is read from the metadata the kernel puts into its
instruction; its rows from its output. A full layer's call is handed the
row's whole cache and walks it up to its span's end, which is data: which
span it was follows from its place among the full layers' calls
(`span_of`; the configuration's `deployment_share.layers_run` says how
many layers of each kind a span goes through). A window layer's call is its
span behind the 128 keys before it, which at a row's first span hold no
position and are masked out. Which bound held and the calls, seconds and
share by kind go into the record's `notes`. A program without the kernel
has no such call: nothing is read."""

from benchmark.costs import wide_key_attention as cost
from benchmark.costs.peaks import least_seconds


def read(record):
    trace = record.get("trace")
    calls = (trace or {}).get("kernel_calls", {}).get("wide_key_attention")
    if not calls:
        return None
    config = record["spec"]["config"]
    # the layers as run (the top-level list is the published 48, whole)
    pattern = config["deployment_share"]["layers_run"]["hybrid_layer_pattern"]
    layers = {True: sum(1 for kind in pattern if kind),
              False: sum(1 for kind in pattern if not kind)}
    kind = record["device"]["kind"]
    bounds = {"compute": 0, "memory": 0}
    by_kind = {name: {"calls": 0, "seconds": 0.0, "least_s": 0.0}
               for name in ("full", "window")}
    seen = {True: 0, False: 0}
    for call in calls:
        what = cost.call_of(call["shapes"])
        if what is None:
            return None  # a kernel that does not say what it was asked
        windowed = bool(what["window"])
        queries, keys = what["queries"], what["keys"]
        if windowed:
            # the span and the tail before it: `keys - queries` columns
            first = cost.span_of(seen[True], layers[True], queries,
                                 config["denoiser"]["prompt_slots"]) == 0
            keys = queries if first else keys
        else:
            span = cost.span_of(seen[False], layers[False], queries, keys)
            keys = min((span + 1) * queries, keys)
        seen[windowed] += 1
        seconds, bound = least_seconds(*cost.needed(
            call["shapes"][0][0], what["heads"], what["key_heads"], queries,
            keys, what["window"], what["key_dim"], what["value_dim"],
            bool(what["sink"])), kind)
        bounds[bound] += 1
        mine = by_kind["window" if windowed else "full"]
        mine["calls"] += 1
        mine["seconds"] += call["seconds"]
        mine["least_s"] += seconds
    least = sum(mine["least_s"] for mine in by_kind.values())
    spent = sum(mine["seconds"] for mine in by_kind.values())
    record.setdefault("notes", {})["wide_key_attention_roofline"] = {
        "calls": len(calls), "bound_by": bounds,
        "by_kind": {name: {**mine, "share_pct": (
            100.0 * mine["least_s"] / mine["seconds"]
            if mine["seconds"] else None)} for name, mine in by_kind.items()}}
    return 100.0 * least / spent if spent else None
