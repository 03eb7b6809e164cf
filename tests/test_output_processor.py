"""Artifact format: blob/thumbnail/sha256 envelopes, grids, error paths.

Parity targets: reference swarm/post_processors/output_processor.py.
"""

import base64
import hashlib
import io
import json

import pytest
from PIL import Image

from chiaswarm_tpu.post_processors.output_processor import (
    OutputProcessor,
    exception_image,
    exception_message,
    fatal_exception_response,
    image_grid,
    image_to_buffer,
    is_nsfw,
    make_text_result,
    make_thumbnail,
    post_process,
)


def _img(w=64, h=64, color=(255, 0, 0)):
    return Image.new("RGB", (w, h), color)


def _decode_image(result):
    return Image.open(io.BytesIO(base64.b64decode(result["blob"])))


def test_single_image_result_envelope():
    proc = OutputProcessor(["primary"], "image/jpeg")
    proc.add_outputs([_img()])
    results = proc.get_results()

    primary = results["primary"]
    assert set(primary) == {"blob", "content_type", "thumbnail", "sha256_hash"}
    assert primary["content_type"] == "image/jpeg"

    payload = base64.b64decode(primary["blob"])
    assert primary["sha256_hash"] == hashlib.sha256(payload).hexdigest()

    thumb = Image.open(io.BytesIO(base64.b64decode(primary["thumbnail"])))
    assert max(thumb.size) <= 100


@pytest.mark.parametrize("mode,n", [("RGB", 1), ("RGB", 3), ("RGBA", 1),
                                    ("L", 1)])
def test_png_thumbnail_is_the_one_decoding_the_png_gives(mode, n):
    """A PNG artifact's thumbnail is made from the composite, without
    decoding the PNG just written: the bytes are those of the decode."""
    import numpy as np

    rng = np.random.default_rng(7)
    channels = {"RGB": 3, "RGBA": 4, "L": 1}[mode]
    images = [Image.fromarray(np.squeeze(rng.integers(
        0, 256, (96, 160, channels), dtype=np.uint8)), mode)
        for _ in range(n)]
    kept = [image.copy() for image in images]
    proc = OutputProcessor(["primary"], "image/png")
    proc.add_outputs(images)
    primary = proc.get_results()["primary"]
    png = base64.b64decode(primary["blob"])
    assert base64.b64decode(primary["thumbnail"]) == make_thumbnail(
        png).getvalue()
    # made on a copy: the caller's images are as they were
    assert all(a.size == b.size and a.tobytes() == b.tobytes()
               for a, b in zip(images, kept))


@pytest.mark.parametrize(
    "n,expected_size",
    [(1, (64, 64)), (2, (128, 64)), (3, (128, 128)), (5, (192, 128)), (9, (192, 192))],
)
def test_grid_layouts(n, expected_size):
    composite = post_process([_img() for _ in range(n)])
    assert composite.size == expected_size


def test_more_than_nine_images_rejected():
    with pytest.raises(ValueError, match="Too many images"):
        post_process([_img() for _ in range(10)])


def test_grid_pastes_in_row_major_order():
    grid = image_grid([_img(color=(255, 0, 0)), _img(color=(0, 255, 0))], 1, 2)
    assert grid.getpixel((0, 0)) == (255, 0, 0)
    assert grid.getpixel((64, 0)) == (0, 255, 0)


def test_png_and_jpeg_encoding():
    png = image_to_buffer(_img(), "image/png").getvalue()
    assert png.startswith(b"\x89PNG")
    jpg = image_to_buffer(_img(), "image/jpeg").getvalue()
    assert jpg.startswith(b"\xff\xd8")
    with pytest.raises(ValueError):
        image_to_buffer(_img(), "image/webp")


def test_text_result_is_json_caption():
    r = make_text_result("a red square")
    assert r["content_type"] == "application/json"
    blob = json.loads(base64.b64decode(r["blob"]))
    assert blob == {"caption": "a red square"}
    assert r["sha256_hash"] == hashlib.sha256(b"a red square").hexdigest()


def test_exception_image_renders_message():
    artifacts, config = exception_image(Exception("boom"), "image/jpeg")
    assert config["error"] == "boom"
    img = _decode_image(artifacts["primary"])
    assert img.size == (512, 512)


def test_exception_message_path():
    artifacts, config = exception_message(Exception("bad text"))
    assert config["error"] == "bad text"
    assert artifacts["primary"]["content_type"] == "application/json"


def test_fatal_response_envelope():
    envelope = fatal_exception_response(ValueError("bad args"), "job-1", {})
    assert envelope["fatal_error"] is True
    assert envelope["id"] == "job-1"
    assert envelope["pipeline_config"]["error"] == "bad args"
    assert "worker_version" in envelope


def test_is_nsfw_variants():
    assert is_nsfw({"nsfw_content_detected": True})
    assert is_nsfw({"nsfw_content_detected": [False, True]})
    assert not is_nsfw({"nsfw_content_detected": [False]})
    assert not is_nsfw({"nsfw_content_detected": None})
    assert not is_nsfw({})
