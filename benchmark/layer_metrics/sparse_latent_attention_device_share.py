"""Summed device time of the sparse latent attention kernel's events (instruction
name `sparse_latent_attention`, the Pallas call's `name`: a prefill span's; what
a decode step does under the same name is plain XLA, whose instructions
carry other names) over device busy time, in %."""


def read(record):
    trace = record.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    seconds = trace["op_seconds"].get("sparse_latent_attention")
    return None if seconds is None else 100.0 * seconds / trace["busy_s"]
