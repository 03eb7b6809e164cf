"""Summed device time of the flash kernel's events (instruction name
`flash_attention`, the Pallas call's `name`) over device busy time, in %.
"""


def read(record):
    trace = record.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    seconds = trace["op_seconds"].get("flash_attention")
    return None if seconds is None else 100.0 * seconds / trace["busy_s"]
