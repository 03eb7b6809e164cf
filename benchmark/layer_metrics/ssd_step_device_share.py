"""Summed device time of the state-space step kernel's events (instruction
name `ssd_step`, the Pallas call's `name`) over device busy time, in %. A
program without the kernel (the parent of PR 46) has no such event: nothing
is read."""


def read(record):
    trace = record.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    seconds = trace["op_seconds"].get("ssd_step")
    return None if seconds is None else 100.0 * seconds / trace["busy_s"]
