"""1 - (union of device-op intervals / stretch), in %, over the traced
whole cycles of steady state (`trace/reduce.py`)."""


def read(record):
    trace = record.get("trace")
    if not trace or trace.get("idle_share") is None:
        return None
    return 100.0 * trace["idle_share"]
