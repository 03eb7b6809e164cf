"""Summed device time of the gated delta step kernel's events (instruction
name `gated_delta_step`, the Pallas call's `name`) over device busy time,
in %."""


def read(record):
    trace = record.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    seconds = trace["op_seconds"].get("gated_delta_step")
    return None if seconds is None else 100.0 * seconds / trace["busy_s"]
