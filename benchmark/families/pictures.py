"""What the families whose jobs return a picture share: how one primary
artifact is judged (`correct` 1) and what differs from one text-to-image
job to the next. No family of its own: `sd.py` and `flux.py` call it with
their configuration's canvas (README, "A family")."""

from __future__ import annotations

import hashlib
import io


def check(blob: bytes, ref: dict, height: int, width: int) -> str | None:
    """`correct` 1 for one job's primary artifact: hashes to its name,
    decodes to the canvas, is not constant."""
    import numpy as np
    from PIL import Image

    if hashlib.sha256(blob).hexdigest() != ref.get("sha256"):
        return "artifact does not hash to its name"
    pixels = np.asarray(Image.open(io.BytesIO(blob)).convert("RGB"))
    if pixels.shape != (height, width, 3):
        return f"image decodes to {pixels.shape}, not {height}x{width}"
    if pixels.min() == pixels.max():
        return f"image is constant ({pixels.min()})"
    return None


def check_artifact(blob: bytes, ref: dict, config: dict) -> str | None:
    """`check` at the canvas every job of `config` asks for."""
    job = config["job"]
    return check(blob, ref, int(job["height"]), int(job["width"]))


def job_fields(rng, traffic: dict, count: int, probe: bool) -> dict:
    """A distinct prompt a job: subject x style (two draws of `rng`, in
    that order) x the running number; the probe's is the traffic file's
    own and draws nothing."""
    if probe:
        return {"prompt": traffic["probe"]["prompt"]}
    prompts = traffic["prompts"]
    return {"prompt": (f"{rng.choice(prompts['subjects'])}, "
                       f"{rng.choice(prompts['styles'])}, take {count}")}
