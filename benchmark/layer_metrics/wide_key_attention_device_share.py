"""Summed device time of the wide-key attention kernel's events
(instruction name `wide_key_attention`, the Pallas call's `name`: MiMo-V2's
prefill attention, full layers and window layers alike) over device busy
time, in %. A program without the kernel has no such event: nothing is
read."""


def read(record):
    trace = record.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    seconds = trace["op_seconds"].get("wide_key_attention")
    return None if seconds is None else 100.0 * seconds / trace["busy_s"]
