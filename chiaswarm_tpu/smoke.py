"""Per-family canned-job smoke harness — the reference's manual hardware
test bench (`swarm/test.py:8-311` holds 18 canned job dicts run through
`format_args` + `do_work` without a hive) rebuilt for this worker.

One command, no hive, real serving path:

    chiaswarm-tpu-smoke --list
    chiaswarm-tpu-smoke --tiny                  # every family, tiny models
    chiaswarm-tpu-smoke sdxl bark --out /tmp/a  # two families, save artifacts

Each canned job goes through the exact worker code path (`format_args` ->
slice `ChipSet(worker_function, **kwargs)`), so what passes here serves.
`--tiny` swaps every model for its tiny random-weight stand-in
(`parameters.test_tiny_model`, the same hook the hermetic tests use) and
shrinks canvases/steps/frames so the sweep runs on CPU or one small chip
without downloads. Without `--tiny`, jobs use the real model names and
need converted weights under the model root (weights.py policy).

Input images/videos come from an in-process asset server, not the public
URLs the reference's jobs embed — the harness must work with zero egress.

Exit code: number of failed jobs (0 = all selected families served).
"""

from __future__ import annotations

import argparse
import asyncio
import base64
import io
import sys
import time

from .job_arguments import format_args
from .settings import load_settings

_EXT = {"image/jpeg": "jpg", "image/png": "png", "video/mp4": "mp4",
        "video/webm": "webm", "image/gif": "gif", "audio/mpeg": "mp3",
        "text/plain": "txt", "application/json": "json"}


def _asset_image(size: int = 256) -> bytes:
    """A deterministic gradient-with-shapes PNG (content-ful enough for
    img2img/annotators to produce nontrivial conditioning)."""
    import numpy as np
    from PIL import Image, ImageDraw

    y, x = np.mgrid[0:size, 0:size]
    arr = np.stack(
        [x * 255 // size, y * 255 // size, (x + y) * 255 // (2 * size)],
        axis=-1,
    ).astype("uint8")
    img = Image.fromarray(arr)
    d = ImageDraw.Draw(img)
    d.rectangle([size // 4, size // 4, size // 2, size // 2], fill=(200, 40, 40))
    d.ellipse([size // 2, size // 3, 7 * size // 8, 3 * size // 4],
              fill=(40, 200, 90))
    buf = io.BytesIO()
    img.save(buf, format="PNG")
    return buf.getvalue()


def _asset_video(size: int = 64, frames: int = 8) -> tuple[bytes, str]:
    """A tiny moving-square clip via the repo's own exporter (cv2 mp4,
    GIF fallback)."""
    import numpy as np
    from PIL import Image

    from .toolbox.video_helpers import export_frames

    imgs = []
    for i in range(frames):
        arr = np.zeros((size, size, 3), "uint8")
        pos = (i * size // frames) % max(size - 16, 1)
        arr[pos:pos + 16, pos:pos + 16] = (255, 128, 0)
        imgs.append(Image.fromarray(arr))
    buf, ctype = export_frames(imgs, "video/mp4", fps=4)
    return buf, ctype


class AssetServer:
    """Serves the generated inputs over localhost HTTP so jobs exercise
    the REAL external_resources fetch path (caps, content-type checks)."""

    def __init__(self):
        self.port: int | None = None
        self._runner = None

    @property
    def base(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    async def start(self) -> "AssetServer":
        from aiohttp import web

        png = _asset_image()
        video, video_ctype = _asset_video()
        # aiohttp drains a BytesIO payload on the first request; serve the
        # raw bytes so re-fetches don't get an empty 200
        if hasattr(video, "getvalue"):
            video = video.getvalue()

        async def image(_):
            return web.Response(body=png, content_type="image/png")

        async def clip(_):
            return web.Response(body=video, content_type=video_ctype)

        app = web.Application()
        app.router.add_get("/image.png", image)
        app.router.add_get("/clip.mp4", clip)
        self._runner = web.AppRunner(app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, "127.0.0.1", 0)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]
        return self

    async def stop(self) -> None:
        if self._runner:
            await self._runner.cleanup()


def canned_jobs(assets: AssetServer) -> dict[str, dict]:
    """Family -> canned job. Mirrors the reference bench's coverage
    (/root/reference/swarm/test.py) plus the families it lacked a row for
    (SVD, AudioLDM2, captioning, upscale, stitch)."""
    img = f"{assets.base}/image.png"
    clip = f"{assets.base}/clip.mp4"
    neg = "blurry, low quality, deformed"
    return {
        "echo": {
            "workflow": "echo", "model_name": "none", "prompt": "smoke",
        },
        "txt2img": {
            "workflow": "txt2img",
            "model_name": "stabilityai/stable-diffusion-2-1",
            "prompt": "a watercolor fox in a forest", "negative_prompt": neg,
            "num_inference_steps": 10,
        },
        "sdxl": {
            "workflow": "txt2img",
            "model_name": "stabilityai/stable-diffusion-xl-base-1.0",
            "prompt": "a photograph of an astronaut riding a horse",
            "negative_prompt": neg, "num_inference_steps": 10,
        },
        "img2img": {
            "workflow": "img2img",
            "model_name": "stabilityai/stable-diffusion-2-1",
            "prompt": "a fantasy landscape, cinematic lighting",
            "start_image_uri": img, "strength": 0.6,
            "num_inference_steps": 10,
        },
        "inpaint": {
            "workflow": "img2img",
            "model_name": "stabilityai/stable-diffusion-2-inpainting",
            "prompt": "a red balloon", "start_image_uri": img,
            "mask_image_uri": img, "num_inference_steps": 10,
        },
        "controlnet": {
            "workflow": "img2img",
            "model_name": "runwayml/stable-diffusion-v1-5",
            "prompt": "a glass building", "start_image_uri": img,
            "num_inference_steps": 10,
            "parameters": {"controlnet": {
                "controlnet_model_name": "lllyasviel/sd-controlnet-canny",
                "preprocess": True, "type": "canny",
                "control_image_uri": img,
            }},
        },
        "qr": {
            # needs the optional `qrcode` package (external_resources.py);
            # auto-skipped when it isn't importable
            "workflow": "img2img",
            "model_name": "SG161222/Realistic_Vision_V5.1_noVAE",
            "prompt": "a badger", "strength": 0.95,
            "num_inference_steps": 10, "start_image_uri": "",
            "parameters": {
                "scheduler_type": "EulerAncestralDiscreteScheduler",
                "controlnet": {
                    "type": "qrcode",
                    "controlnet_model_name":
                        "monster-labs/control_v1p_sd15_qrcode_monster",
                    "preprocess": True,
                    "controlnet_conditioning_scale": 0.88,
                    "qr_code_contents": "https://example.org/smoke",
                },
            },
        },
        "upscale": {
            "workflow": "txt2img",
            "model_name": "stabilityai/stable-diffusion-2-1",
            "prompt": "a lighthouse at dusk", "num_inference_steps": 10,
            "upscale": True,
        },
        "deepfloyd": {
            "workflow": "txt2img", "model_name": "DeepFloyd/IF-I-M-v1.0",
            "prompt": "a frog holding a sign that says smoke",
            "num_inference_steps": 10,
        },
        "kandinsky22": {
            "workflow": "txt2img",
            "model_name": "kandinsky-community/kandinsky-2-2-decoder",
            "prompt": "a fantasy landscape, cinematic lighting",
            "negative_prompt": "low quality", "num_inference_steps": 10,
            "parameters": {"pipeline_type": "AutoPipelineForText2Image",
                           "prior_guidance_scale": 1.0},
        },
        "kandinsky3": {
            "workflow": "txt2img",
            "model_name": "kandinsky-community/kandinsky-3",
            "prompt": "a fantasy landscape, cinematic lighting",
            "num_inference_steps": 10,
            "parameters": {"pipeline_type": "AutoPipelineForText2Image"},
        },
        "cascade": {
            "workflow": "txt2img",
            "model_name": "stabilityai/stable-cascade",
            "prompt": "an armchair shaped like an avocado",
            "num_inference_steps": 10,
        },
        "flux": {
            "workflow": "txt2img",
            "model_name": "black-forest-labs/FLUX.1-schnell",
            "prompt": "a cartoon marmot DJ", "guidance_scale": 0,
            "num_inference_steps": 4,
            "parameters": {"pipeline_type": "FluxPipeline",
                           "max_sequence_length": 256},
        },
        "txt2vid": {
            "workflow": "txt2vid", "model_name": "emilianJR/epiCRealism",
            "prompt": "a dancing marmot", "num_inference_steps": 6,
            "guidance_scale": 2.0, "num_frames": 8,
            "content_type": "image/gif",
            "parameters": {
                "pipeline_type": "AnimateDiffPipeline",
                "scheduler_type": "LCMScheduler",
                "motion_adapter": {"model_name": "wangfuyun/AnimateLCM"},
                "scheduler_args": {"beta_schedule": "linear"},
            },
        },
        "zeroscope": {
            "workflow": "txt2vid",
            "model_name": "cerspense/zeroscope_v2_576w",
            "prompt": "waves crashing on a beach", "num_frames": 8,
            "num_inference_steps": 10, "content_type": "video/webm",
        },
        "img2vid": {
            "workflow": "img2vid",
            "model_name": "ali-vilab/i2vgen-xl",
            "prompt": "the scene comes alive", "start_image_uri": img,
            "num_inference_steps": 10, "num_frames": 8,
            "content_type": "video/mp4",
        },
        "svd": {
            "workflow": "img2vid",
            "model_name": "stabilityai/stable-video-diffusion-img2vid",
            "start_image_uri": img, "num_inference_steps": 10,
            "num_frames": 8, "content_type": "video/mp4",
            "parameters": {
                "pipeline_type": "StableVideoDiffusionPipeline"},
        },
        "vid2vid": {
            "workflow": "vid2vid",
            "model_name": "timbrooks/instruct-pix2pix",
            "prompt": "make it sunny", "video_uri": clip,
            "num_inference_steps": 8,
        },
        "audioldm": {
            "workflow": "txt2audio", "model_name": "cvssp/audioldm-s-full-v2",
            "prompt": "techno music with a strong upbeat tempo",
            "num_inference_steps": 10,
            "parameters": {"audio_length_in_s": 2.5},
        },
        "audioldm2": {
            "workflow": "txt2audio", "model_name": "cvssp/audioldm2",
            "prompt": "water drops echoing in a cave",
            "num_inference_steps": 10,
            "parameters": {"audio_length_in_s": 2.5},
        },
        "bark": {
            "workflow": "txt2audio", "model_name": "suno/bark",
            "prompt": "Hello, my name is smoke test.",
        },
        "img2txt": {
            "workflow": "img2txt", "model_name":
                "Salesforce/blip-image-captioning-large",
            "start_image_uri": img,
        },
        "stitch": {
            "workflow": "stitch", "model_name": "none",
            "jobs": [{"resultUri": img}, {"resultUri": img}],
        },
    }


# geometry shrink applied in --tiny mode, per family (the tiny models are
# built for 64px canvases; video/audio also cut frames/steps)
_TINY_OVERRIDES: dict[str, dict] = {
    "txt2img": {"height": 64, "width": 64, "num_inference_steps": 2},
    "sdxl": {"height": 64, "width": 64, "num_inference_steps": 2},
    "img2img": {"height": 64, "width": 64, "num_inference_steps": 2},
    "inpaint": {"height": 64, "width": 64, "num_inference_steps": 2},
    "controlnet": {"height": 64, "width": 64, "num_inference_steps": 2},
    "qr": {"height": 64, "width": 64, "num_inference_steps": 2},
    "upscale": {"height": 64, "width": 64, "num_inference_steps": 2},
    "deepfloyd": {"height": 64, "width": 64, "num_inference_steps": 2},
    "kandinsky22": {"height": 64, "width": 64, "num_inference_steps": 2},
    "kandinsky3": {"height": 64, "width": 64, "num_inference_steps": 2},
    "cascade": {"height": 64, "width": 64, "num_inference_steps": 2},
    "flux": {"height": 64, "width": 64, "num_inference_steps": 2},
    "txt2vid": {"height": 64, "width": 64, "num_inference_steps": 2,
                "num_frames": 4},
    "zeroscope": {"height": 64, "width": 64, "num_inference_steps": 2,
                  "num_frames": 4},
    "img2vid": {"height": 64, "width": 64, "num_inference_steps": 2,
                "num_frames": 4},
    "svd": {"height": 64, "width": 64, "num_inference_steps": 2,
            "num_frames": 4},
    # vid2vid's tiny hook reads the top-level key, not parameters
    # (pipelines/video.py run_vid2vid)
    "vid2vid": {"num_inference_steps": 2, "test_tiny_model": True},
    "audioldm": {"num_inference_steps": 2},
    "audioldm2": {"num_inference_steps": 2},
    "bark": {},
    "img2txt": {},
}


def _apply_tiny(name: str, job: dict) -> dict:
    job = dict(job)
    job.update(_TINY_OVERRIDES.get(name, {}))
    params = dict(job.get("parameters") or {})
    params["test_tiny_model"] = True
    if name in ("audioldm", "audioldm2"):
        params["audio_length_in_s"] = 1.0
    if "controlnet" in params:
        # the tiny hook swaps only the main model; the controlnet
        # sub-model needs its own tiny stand-in
        cn = dict(params["controlnet"])
        cn["controlnet_model_name"] = "test/tiny-controlnet"
        params["controlnet"] = cn
    job["parameters"] = params
    return job


def _save_artifacts(out_dir, family: str, artifacts: dict) -> list[str]:
    import pathlib

    saved = []
    root = pathlib.Path(out_dir)
    root.mkdir(parents=True, exist_ok=True)
    for key, art in (artifacts or {}).items():
        blob = art.get("blob")
        if not blob:
            continue
        ext = _EXT.get(art.get("content_type", ""), "bin")
        path = root / f"{family}.{key}.{ext}"
        path.write_bytes(base64.b64decode(blob))
        saved.append(str(path))
    return saved


async def run_family(name: str, job: dict, chipset, settings,
                     out_dir: str | None) -> tuple[bool, float]:
    from .workflows.diffusion import packaged

    job = dict(job, id=f"smoke-{name}")
    t0 = time.perf_counter()
    try:
        func, kwargs = await format_args(job, settings, chipset.identifier())
        kwargs.pop("id", None)
        loop = asyncio.get_running_loop()
        artifacts, config = await loop.run_in_executor(
            None, lambda: chipset(func, **kwargs)
        )
        artifacts = packaged(artifacts)
    except Exception as e:
        print(f"  {name}: FAILED {type(e).__name__}: {e} "
              f"({time.perf_counter() - t0:.1f}s)")
        return False, time.perf_counter() - t0
    elapsed = time.perf_counter() - t0
    if "error" in config:
        print(f"  {name}: FAILED (job error) {config['error']} "
              f"({elapsed:.1f}s)")
        return False, elapsed
    timings = config.get("timings", {})
    detail = " ".join(f"{k}={v}" for k, v in sorted(timings.items()))
    print(f"  {name}: ok in {elapsed:.1f}s  {detail}")
    if out_dir:
        for p in _save_artifacts(out_dir, name, artifacts):
            print(f"    -> {p}")
    return True, elapsed


async def amain(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="chiaswarm-tpu-smoke", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("families", nargs="*",
                        help="families to run (default: all)")
    parser.add_argument("--list", action="store_true",
                        help="list families and exit")
    parser.add_argument("--tiny", action="store_true",
                        help="tiny hermetic models (no weights needed)")
    parser.add_argument("--out", default=None,
                        help="directory to save result artifacts into")
    args = parser.parse_args(argv)

    if args.list:
        # listing needs only the names — no asset server, no jax
        fake = AssetServer()
        fake.port = 0
        for name in canned_jobs(fake):
            print(name)
        return 0

    assets = await AssetServer().start()
    try:
        jobs = canned_jobs(assets)
        selected = args.families or list(jobs)
        unknown = [f for f in selected if f not in jobs]
        if unknown:
            parser.error(f"unknown families: {unknown} "
                         f"(see --list)")

        try:
            import qrcode  # noqa: F401
        except ImportError:
            if "qr" in selected and not args.families:
                print("skipping qr (optional 'qrcode' package not installed)")
                selected = [f for f in selected if f != "qr"]

        from .chips.allocator import SliceAllocator

        settings = load_settings()
        allocator = SliceAllocator(
            chips_per_job=settings.chips_per_job,
            tensor_parallelism=settings.tensor_parallelism,
            sequence_parallelism=settings.sequence_parallelism,
        )
        chipset = await allocator.acquire()
        print(f"smoke: {len(selected)} famil{'y' if len(selected) == 1 else 'ies'} "
              f"on {chipset.descriptor()}" + (" [tiny]" if args.tiny else ""))
        failed = 0
        try:
            for name in selected:
                job = _apply_tiny(name, jobs[name]) if args.tiny else jobs[name]
                ok, _ = await run_family(name, job, chipset, settings, args.out)
                failed += 0 if ok else 1
        finally:
            allocator.release(chipset)
        print(f"smoke: {len(selected) - failed}/{len(selected)} ok")
        return failed
    finally:
        await assets.stop()


def main() -> None:
    sys.exit(asyncio.run(amain()))


if __name__ == "__main__":
    main()
