"""Summed device time of the collective instructions (`all-reduce*`,
`all-gather*`, `reduce-scatter*`, `all-to-all*`, `collective-permute*`, the
`-start` and `-done` halves of an asynchronous one both) over device busy
time, in %; both are means over the chips' planes (`trace/reduce.py`). A
`-done` event lasts while its chip waits for the others, so the share is
the time a chip spends in or waiting on collectives, not the wire's. Which
names were found, with their seconds, goes into the record's `notes`. A
one-chip program has none: the metric is then left out."""

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def read(record):
    trace = record.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    found = {name: seconds for name, seconds in trace["op_seconds"].items()
             if name.startswith(COLLECTIVES)}
    if not found:
        return None
    record.setdefault("notes", {})["collective_device_share"] = {
        "seconds_by_name": {name: round(seconds, 6)
                            for name, seconds in sorted(found.items())}}
    return 100.0 * sum(found.values()) / trace["busy_s"]
