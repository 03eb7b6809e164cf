"""The harness, the checks and the compile check know no network and no
kind of artifact: they call through the module that `config["family"]`
names. Shown on `stub_family.py`, which is no diffusion UNet and whose jobs
return text."""

import asyncio
import hashlib
import importlib.util
import io
import json
import os
import sys
import time
import types
from pathlib import Path

import pytest

from benchmark import checks, harness

STUB = Path(__file__).with_name("stub_family.py")
CONFIG = {"family": "stub", "kernel_dtype": "float32",
          "job": {"model_name": "test/stub", "rows": 4}}


@pytest.fixture
def stub(monkeypatch):
    """`stub_family.py`, importable as `benchmark.families.stub`."""
    spec = importlib.util.spec_from_file_location(
        "benchmark.families.stub", STUB)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    monkeypatch.setitem(sys.modules, "benchmark.families.stub", module)
    return module


def closed_window() -> harness.Window:
    window = harness.Window(0.0)
    window.open()
    return window


def test_the_three_calls_go_through_the_configurations_family(
        stub, monkeypatch):
    from chiaswarm_tpu import registry

    pipe = stub.StubPipeline(seed=3)
    asked = []

    def get_pipeline(model_name, pipeline_type, chipset=None):
        asked.append((model_name, pipeline_type, chipset))
        return pipe

    monkeypatch.setattr(registry, "get_pipeline", get_pipeline)
    family = harness.load_family(CONFIG)
    assert family is stub
    record = {"spec": {"config": CONFIG}, "seed": 3}
    worker = types.SimpleNamespace(
        allocator=types.SimpleNamespace(slices=["the slice"]))
    got_pipe, inputs, want, _ = asyncio.run(
        harness.at_window_close(closed_window(), record, worker, family))
    assert got_pipe is pipe
    # no type in the job's `parameters`: the family's own
    assert asked == [("test/stub", stub.PIPELINE_TYPE, "the slice")]
    assert inputs["x"].shape == (4, stub.WIDTH)
    assert "peak_bytes" in record["memory"] and record["scrape_close"]
    failures, reading = checks.denoiser(family, pipe, inputs, want)
    assert failures == [] and set(reading) == {
        "rel_l2", "max_abs", "ref_rms", "limit"}
    assert stub.CALLS == ["denoiser_inputs", "denoiser_reference",
                          "denoiser_serve"]


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("weight_bits, passes", [(None, True), (8, False)])
def test_the_comparison_holds_the_family_to_its_own_tolerance(
        stub, weight_bits, passes, seed):
    pipe = stub.StubPipeline(seed, weight_bits)
    inputs = stub.denoiser_inputs(pipe, CONFIG, seed)
    want = stub.denoiser_reference(pipe, inputs)
    failures, reading = checks.denoiser(stub, pipe, inputs, want)
    assert (failures == []) is passes, reading
    if passes:
        assert reading["rel_l2"] < stub.DENOISER_REL_L2_TOL / 3
    else:
        assert reading["rel_l2"] > 3 * stub.DENOISER_REL_L2_TOL
        assert str(stub.DENOISER_REL_L2_TOL) in failures[0]


@pytest.mark.parametrize("name", harness.FAMILY_CONTRACT)
def test_a_family_without_a_name_fails_before_the_swarm_starts(
        stub, monkeypatch, name):
    from chiaswarm_tpu.hive_server import harness as swarm_harness

    def no_swarm(*args, **kwargs):
        raise AssertionError("the swarm was started")

    monkeypatch.setattr(swarm_harness, "LocalSwarm", no_swarm)
    monkeypatch.delattr(stub, name)
    spec = {"config": CONFIG, "traffic": {}, "cell": {"chips": 1}}
    with pytest.raises(harness.RunFailure, match=name):
        asyncio.run(harness.run_cell(spec, 1, 1.0, False, time.monotonic()))
    assert "register" not in stub.CALLS


def png(height: int, width: int) -> bytes:
    import numpy as np
    from PIL import Image

    pixels = np.random.default_rng(1).integers(
        0, 255, (height, width, 3), np.uint8)
    out = io.BytesIO()
    Image.fromarray(pixels).save(out, format="PNG")
    return out.getvalue()


def caption(text: str) -> bytes:
    return json.dumps({"caption": text}).encode()


class Hive:
    """The client's two calls `check_jobs` makes, over artifacts in hand:
    job `n` is done and its primary artifact is `blobs[n]`, named by its
    own sha256 unless `misnamed`."""

    def __init__(self, blobs: list[bytes], misnamed: int | None = None):
        self.blobs = {f"/a/{n}": blob for n, blob in enumerate(blobs)}
        self.misnamed = misnamed

    def _status(self, n: int) -> dict:
        digest = hashlib.sha256(
            b"other" if n == self.misnamed else self.blobs[f"/a/{n}"])
        return {"status": "done", "attempts": 1, "result": {
            "pipeline_config": {}, "artifacts": {"primary": {
                "href": f"/a/{n}", "sha256": digest.hexdigest()}}}}

    async def status(self, job_id: str) -> dict:
        return self._status(int(job_id))

    async def artifact(self, href: str) -> bytes:
        return self.blobs[href]

    def record(self, config: dict, window_jobs: int) -> dict:
        jobs = [{"id": str(n), "submit_wall": 10.0 + n, "withdrawn": False,
                 "status": self._status(n)} for n in range(window_jobs)]
        return {"spec": {"config": config}, "jobs": jobs, "failures": [],
                "window": {"open_wall": 10.0, "close_wall": 60.0}}


SD_CONFIG = {"family": "sd", "job": {"height": 64, "width": 64}}


@pytest.mark.parametrize("config, blobs, misnamed, words", [
    (CONFIG, [caption("a"), caption("b"), caption("p"), caption("p")],
     None, None),
    (SD_CONFIG, [png(64, 64)] * 4, None, None),
    (CONFIG, [caption("a"), caption("b"), caption("p"), caption("p")],
     1, "job 1: text artifact does not hash to its name"),
    (CONFIG, [caption("a"), b"not json", caption("p"), caption("p")],
     None, "job 1: text artifact is no JSON object with a caption"),
    (CONFIG, [caption("a"), caption(" "), caption("p"), caption("p")],
     None, "job 1: caption is ' ', not a line of text"),
    (SD_CONFIG, [png(64, 64), png(32, 64), png(64, 64), png(64, 64)],
     None, "job 1: image decodes to (32, 64, 3), not 64x64"),
    (SD_CONFIG, [png(64, 64), png(64, 64), png(64, 32), png(64, 32)],
     None, "probe 2: image decodes to (64, 32, 3), not 64x64"),
    (SD_CONFIG, [png(64, 64), png(64, 64), png(64, 64), png(64, 64)],
     2, "probe 2: artifact does not hash to its name"),
    (CONFIG, [caption("a"), caption("b"), caption("p"), caption("q")],
     None, "the probe (one job, one seed) gave 2 artifacts"),
], ids=["text", "picture", "text-misnamed", "text-no-json", "text-empty",
        "picture-other-canvas", "probe-other-canvas", "probe-misnamed",
        "probe-two-answers"])
def test_every_artifact_is_judged_in_the_familys_own_words(
        stub, config, blobs, misnamed, words):
    """Two window jobs and the probe's two rides: the run is correct when
    the family takes all four and the probe's are the same bytes, and
    fails with what the family said of the one it refused."""
    family = harness.load_family(config)
    hive = Hive(blobs, misnamed)
    record = hive.record(config, window_jobs=2)
    asyncio.run(harness.check_jobs(hive, record, family, ["2", "3"]))
    assert (record["attempted"], len(record["probe_sha256"])) == (2, 2)
    if words is None:
        assert record["failures"] == [] and record["failed"] == 0
    else:
        assert record["failures"][0].startswith(words), record["failures"]
        assert record["failed"] == int(words.startswith("job"))


@pytest.mark.parametrize("seed", range(300, 312))
def test_the_stub_s_own_kernel_is_held_to_its_own_tolerance(stub, seed):
    """Sound float32 reads under a third of the tolerance, the bfloat16
    control over three times it, and fails in the family's words."""
    import jax.numpy as jnp

    config = {"stub_matmul_shapes": [[64, 32, 16]] * (seed - 299)}
    failures, readings = stub.kernel_checks(config, jnp.float32)
    assert failures == [] and len(readings) == seed - 299
    assert readings[-1]["max_abs"] < stub.STUB_MATMUL_TOL / 3
    failures, readings = stub.kernel_checks(config, jnp.bfloat16)
    assert readings[-1]["max_abs"] > 3 * stub.STUB_MATMUL_TOL
    assert failures[-1].startswith("stub matmul 64x32x16: max abs error")


def test_the_benchmarks_families_return_the_shared_kernel_checks():
    from benchmark.families import flux, sd

    assert sd.kernel_checks is checks.kernels is flux.kernel_checks
    assert (sd.PIPELINE_TYPE, flux.PIPELINE_TYPE) == (
        "DiffusionPipeline", "FluxPipeline")


def test_a_family_that_is_not_there_is_a_run_failure():
    with pytest.raises(harness.RunFailure, match="no benchmark/families"):
        harness.load_family({"family": "no_such_family"})


def test_every_configurations_family_has_the_contract():
    import json

    bench = json.loads((harness.REPO / "BENCHMARK.json").read_text())
    for entry in bench["configs"]:
        config = json.loads((harness.REPO / entry["file"]).read_text())
        assert harness.load_family(config).__name__.endswith(config["family"])


@pytest.mark.parametrize("config, readings", [
    ({}, 0),
    ({"attention_shapes": [[64, 77, 2, 16]]}, 1),
    ({"group_norm_shapes": [[8, 8, 64]]}, 1)])
def test_kernels_takes_a_configuration_without_either_list(config, readings):
    import jax.numpy as jnp

    failures, read = checks.kernels(config, jnp.float32, True)
    assert failures == [] and len(read) == readings


def test_the_compile_check_compiles_what_the_family_hands_it(
        stub, monkeypatch):
    import jax

    from benchmark import compile_check

    spec = {"config": CONFIG, "traffic": {}, "cell": {"chips": 1}}
    monkeypatch.setattr(harness, "load_cell", lambda name: spec)
    monkeypatch.setenv("SDAAS_ROOT", os.environ["SDAAS_ROOT"])  # check sets it
    line = compile_check.check("any", jax.devices())
    assert stub.CALLS == ["compile_operands"]
    assert line["rows"] == stub.ROWS and "1 chip" in line["compiled_for"]
    assert line["argument_gb"] > 0
