"""Milliseconds a decode step: the envelope's `decode_s` (span `decode`:
the jitted scan of the pass's decode steps, execution only) over its
`decode_steps`; median over the passes that settled inside the window."""

from benchmark import measure


def read(record):
    def per_step(job, rows):
        seconds = measure.timing(job, "decode_s")
        steps = measure.envelope(job).get("decode_steps")
        return None if seconds is None or not steps else (
            1000.0 * seconds / steps)

    return measure.median(measure.per_pass(
        measure.settled_in_window(record), per_step))
