"""Seconds of a pass the slice thread spent packaging images with the
device idle: the program's `artifact_encode` spans (grid, PNG/JPEG encode,
base64, hash; one per job of the pass), summed over the pass's distinct
spans; median over the passes settled inside the window."""

from benchmark import measure, spans


def read(record):
    totals = []
    for found in spans.by_pass(measure.settled_in_window(record)):
        encodes = spans.named(found, "artifact_encode")
        if encodes:
            totals.append(sum(span["seconds"] for span in encodes))
    return measure.median(totals)
