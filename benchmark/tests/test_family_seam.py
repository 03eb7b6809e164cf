"""The harness, the checks and the compile check know no network: they call
through the module that `config["family"]` names. Shown on `stub_family.py`,
which is no diffusion UNet."""

import asyncio
import importlib.util
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
    assert asked == [("test/stub", "DiffusionPipeline", "the slice")]
    assert inputs["x"].shape == (4, stub.WIDTH)
    assert "peak_bytes" in record["memory"] and record["scrape_close"]
    failures, reading = checks.denoiser(family, pipe, inputs, want)
    assert failures == [] and set(reading) == {"rel_l2", "max_abs", "ref_rms"}
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
