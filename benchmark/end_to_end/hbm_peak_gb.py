"""`peak_bytes_in_use` of the fullest chip, read at the window's end and
before the reference evaluation. It is the process's peak: weight
placement, warm-up and the kernel checks are in it."""


def read(record):
    peak = record["memory"]["peak_bytes"]
    return peak / 1e9 if peak else None
