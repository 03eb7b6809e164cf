"""The envelope's `denoise_decode_s` (the fused denoise + VAE-decode
program, execution only, ended by a blocking read) over the pass's real
rows; median over the passes that settled inside the window."""

from benchmark import measure


def read(record):
    def per_image(job, rows):
        seconds = measure.timing(job, "denoise_decode_s")
        return None if seconds is None else seconds / rows

    return measure.median(measure.per_pass(
        measure.settled_in_window(record), per_image))
