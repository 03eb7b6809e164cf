"""What holds the slice besides the device program: the envelope's `job_s`
(the slice's wall clock for the pass) less `denoise_decode_s` — text
encode, artifact encode, packaging; median over passes."""

from benchmark import measure


def read(record):
    def host(job, rows):
        job_s = measure.timing(job, "job_s")
        device = measure.timing(job, "denoise_decode_s")
        return None if job_s is None or device is None else job_s - device

    return measure.median(measure.per_pass(
        measure.settled_in_window(record), host))
