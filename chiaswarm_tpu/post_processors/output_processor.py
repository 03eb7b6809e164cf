"""Artifact packaging: images/text -> hive result envelopes.

Wire-format parity with reference swarm/post_processors/output_processor.py:
every artifact is {blob: b64, content_type, thumbnail: b64 100px jpeg,
sha256_hash}; 2-9 images are composited into a grid (1x2 / 2x2 / 2x3 / 3x3,
:91-108); exceptions become *image* artifacts with the message rendered onto
them (:158-171) so failures surface to end users through the normal result
path; ValueError/TypeError mark the envelope fatal so the hive won't
resubmit (:140-155).
"""

from __future__ import annotations

import base64
import hashlib
import io
import itertools
import json

from PIL import Image, ImageDraw

from .. import __version__

GRID_LAYOUTS = ((1, (1, 1)), (2, (1, 2)), (4, (2, 2)), (6, (2, 3)), (9, (3, 3)))
THUMBNAIL_SIZE = (100, 100)


class OutputProcessor:
    """Collects pipeline outputs and renders the hive `artifacts` dict."""

    def __init__(self, output_list, main_content_type: str):
        self.outputs: list[Image.Image] = []
        self.other_outputs: dict[str, list[Image.Image]] = {}
        self.output_list = output_list
        self.main_content_type = main_content_type

    def add_outputs(self, images) -> None:
        self.outputs.extend(images)

    def add_other_outputs(self, name: str, images) -> None:
        self.other_outputs[name] = list(images)

    def get_results(self) -> dict:
        results = {}
        if "primary" in self.output_list:
            results["primary"] = self._package(self.outputs)
        for key, images in self.other_outputs.items():
            results[key] = self._package(images)
        return results

    def _package(self, images: list[Image.Image]) -> dict:
        composite = post_process(images)
        buffer = image_to_buffer(composite, self.main_content_type)
        # PNG is lossless: the composite is what decoding `buffer` gives,
        # so the thumbnail is made from it and the bytes are the same
        thumb = composite if self.main_content_type == "image/png" else buffer
        return make_result(buffer, thumb, self.main_content_type)


def post_process(image_list: list[Image.Image]) -> Image.Image:
    """Composite 1-9 images into the reference's grid layouts."""
    n = len(image_list)
    for cap, (rows, cols) in GRID_LAYOUTS:
        if n <= cap:
            if rows == cols == 1:
                return image_list[0]
            return image_grid(image_list, rows, cols)
    raise ValueError(
        f"Too many images ({n}) for post-processing. Maximum supported images: 9"
    )


def image_grid(image_list: list[Image.Image], rows: int, cols: int) -> Image.Image:
    w, h = image_list[0].size
    grid = Image.new("RGB", size=(cols * w, rows * h))
    for img, (r, c) in zip(image_list, itertools.product(range(rows), range(cols))):
        grid.paste(img, box=(c * w, r * h))
    return grid


def image_to_buffer(
    image: Image.Image, content_type: str, quality="web_high"
) -> io.BytesIO:
    if not content_type.startswith("image"):
        raise ValueError(f"Unsupported content type: {content_type}")

    buffer = io.BytesIO()
    if content_type == "image/png":
        image.save(buffer, format="PNG")
    elif content_type == "image/jpeg":
        image.save(
            buffer, format="JPEG", quality=quality, optimize=True, progressive=True
        )
    else:
        raise ValueError(f"Invalid image format: {content_type}")
    buffer.seek(0)
    return buffer


def make_thumbnail(source) -> io.BytesIO:
    """A 100 px JPEG of `source`: an image, or the bytes or buffer of an
    encoded one."""
    if not isinstance(source, Image.Image):
        if not isinstance(source, io.BytesIO):
            source = io.BytesIO(source)
        source = Image.open(source)
    image = source.convert("RGB")  # a copy: thumbnail() works in place
    image.thumbnail(THUMBNAIL_SIZE, Image.Resampling.LANCZOS)
    return image_to_buffer(image, "image/jpeg", "web_low")


def image_from_text(text: str, size=(512, 512), color=0) -> Image.Image:
    image = Image.new(mode="RGB", size=size, color=color)
    ImageDraw.Draw(image).multiline_text((5, 5), text)
    return image


def make_result(buffer: io.BytesIO, thumb, content_type: str) -> dict:
    if thumb is None:
        thumb = image_to_buffer(
            image_from_text(content_type, THUMBNAIL_SIZE, 1), "image/jpeg", "web_low"
        )
    else:
        thumb = make_thumbnail(thumb)

    payload = buffer.getvalue()
    return {
        "blob": base64.b64encode(payload).decode("UTF-8"),
        "content_type": content_type,
        "thumbnail": base64.b64encode(thumb.getvalue()).decode("UTF-8"),
        "sha256_hash": hashlib.sha256(payload).hexdigest(),
    }


def make_text_result(string: str) -> dict:
    # NB wire parity: sha256_hash covers the raw caption string, NOT the JSON
    # blob (reference output_processor.py:70) — hives verify against this.
    blob = json.dumps({"caption": string}).encode("utf-8")
    thumb = image_to_buffer(
        image_from_text("text/plain", THUMBNAIL_SIZE, 1), "image/jpeg", "web_low"
    )
    return {
        "blob": base64.b64encode(blob).decode("UTF-8"),
        "content_type": "application/json",
        "thumbnail": base64.b64encode(thumb.getvalue()).decode("UTF-8"),
        "sha256_hash": hashlib.sha256(string.encode()).hexdigest(),
    }


def make_token_result(token_ids) -> dict:
    """A text completion's artifact: `{"token_ids": [[...], ...]}`, a row
    a sequence, as JSON; `sha256_hash` is over the blob."""
    blob = json.dumps({"token_ids": [[int(i) for i in row]
                                     for row in token_ids]},
                      separators=(",", ":")).encode("utf-8")
    thumb = image_to_buffer(
        image_from_text("application/json", THUMBNAIL_SIZE, 1),
        "image/jpeg", "web_low")
    return {
        "blob": base64.b64encode(blob).decode("UTF-8"),
        "content_type": "application/json",
        "thumbnail": base64.b64encode(thumb.getvalue()).decode("UTF-8"),
        "sha256_hash": hashlib.sha256(blob).hexdigest(),
    }


def exception_image(e: Exception, content_type: str):
    message = e.args[0] if e.args else "error generating image"
    buffer = image_to_buffer(image_from_text(str(message)), content_type)
    return (
        {"primary": make_result(buffer, buffer, content_type)},
        {"error": message},
    )


def exception_message(e: Exception):
    message = e.args[0] if e.args else "error generating image"
    return {"primary": make_text_result(str(e))}, {"error": message}


def fatal_exception_response(e: Exception, job_id, job: dict) -> dict:
    """Result envelope for unrecoverable jobs: hive must NOT resubmit."""
    content_type = job.get("content_type", "image/jpeg")
    if content_type.startswith("image/"):
        artifacts, pipeline_config = exception_image(e, content_type)
    else:
        artifacts, pipeline_config = exception_message(e)

    return {
        "id": job_id,
        "artifacts": artifacts,
        "nsfw": pipeline_config.get("nsfw", False),
        "worker_version": __version__,
        "fatal_error": True,
        "pipeline_config": pipeline_config,
    }


def is_nsfw(pipeline_config: dict) -> bool:
    """NSFW flag from a pipeline result dict (vs reference's pipe attribute)."""
    flag = pipeline_config.get("nsfw_content_detected")
    if isinstance(flag, bool):
        return flag
    if isinstance(flag, (list, tuple)):
        return any(flag)
    return False
