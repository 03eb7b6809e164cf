"""Summed device time of the grouped expert matmul's events (instruction
name `expert_matmul`, the Pallas call's `name`) over device busy time, in %.
"""


def read(record):
    trace = record.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    seconds = trace["op_seconds"].get("expert_matmul")
    return None if seconds is None else 100.0 * seconds / trace["busy_s"]
