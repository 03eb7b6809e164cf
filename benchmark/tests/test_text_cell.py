"""A cell whose jobs return text, rehearsed whole on the CPU: `run.py`'s own
`main` (swarm, warm-up, window, checks, readers, the last line) over a spec
that exists only here, through a workflow the program already has
(`img2txt` on `test/tiny-blip`) and `stub_family.py`. The input picture is
served from a loopback socket of the test's own process; nothing is fetched
from outside. One run, in a process of its own (it sets the deployment's
environment and holds a swarm)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
STUB = Path(__file__).with_name("stub_family.py")
ROOT = ".benchmark_run/text-cell"

SPEC = {
    "cell": {"name": "stub-questions", "config": "stub-blip",
             "traffic": "questions", "chips": 1,
             "why": "closed loop, 2 clients, one 64^2 picture and a "
                    "5-token question a job, a 7-token answer as JSON"},
    "config": {
        "family": "stub", "kernel_dtype": "float32",
        "job": {"workflow": "img2txt", "model_name": "test/tiny-blip",
                "rows": 4, "parameters": {}},
        # the entry that says its own type: a second model of the family
        "resident_models": [{"model_name": "test/tiny-blip-vqa",
                             "pipeline_type": "BlipForQuestionAnswering"}],
        "stub_matmul_shapes": [[64, 32, 16]],
        "expected_kernel_paths": [],
        "deployment": {
            "env": {"SDAAS_ROOT": ROOT, "SDAAS_TOKEN": "benchmark",
                    "CHIASWARM_MODEL_ROOT_DIR": f"{ROOT}/models",
                    "CHIASWARM_LORA_ROOT_DIR": f"{ROOT}/lora",
                    "CHIASWARM_HIVE_PORT": "0",
                    "CHIASWARM_METRICS_PORT": "0",
                    "CHIASWARM_HIVE_LEASE_DEADLINE_S": "1800",
                    "CHIASWARM_SAFETY_CHECKER_MODEL": "",
                    "CHIASWARM_POLL_SECONDS": "0.1"},
            "paths": ["SDAAS_ROOT", "CHIASWARM_MODEL_ROOT_DIR",
                      "CHIASWARM_LORA_ROOT_DIR"]}},
    "traffic": {
        "generator": "closed_loop", "clients": 2, "think_s": 0,
        "status_poll_s": 0.02,
        "job": {},  # the script adds `start_image_uri`, once it has a port
        # as many words a question as the probe's: one prefix length, one
        # decode program, nothing compiled in the window
        "vocabulary": ["what", "who", "where", "is", "stands", "here",
                       "there", "red", "old", "near"],
        "words": 3,
        "probe": {"prompt": "what is here 0?", "seed": 1234},
        "trace_cycles": 1, "trace_max_s": 3},
    "end_to_end": [
        {"name": "job_latency_p50_s", "unit": "s"},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [],
}

# what the child runs: the spec from stdin, the picture over loopback, the
# stub installed as a family, and beside the family's verdict on every
# artifact the parent commit's (`checks.artifact`, which opened every
# primary artifact as a picture of the configuration's canvas)
SCRIPT = r"""
import functools, http.server, importlib.util, json, sys, threading
from pathlib import Path

repo, stub_path = sys.argv[1], sys.argv[2]
sys.path.insert(0, repo)
spec = json.load(sys.stdin)

served = Path(repo) / spec["config"]["deployment"]["env"]["SDAAS_ROOT"]
served = served.parent / "text-cell-input"
served.mkdir(parents=True, exist_ok=True)
import numpy as np
from PIL import Image
pixels = np.random.default_rng(5).integers(0, 255, (64, 64, 3), np.uint8)
Image.fromarray(pixels).save(served / "input.png")
handler = functools.partial(http.server.SimpleHTTPRequestHandler,
                            directory=str(served))
server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), handler)
threading.Thread(target=server.serve_forever, daemon=True).start()
spec["traffic"]["job"]["start_image_uri"] = (
    f"http://127.0.0.1:{server.server_address[1]}/input.png")

module_spec = importlib.util.spec_from_file_location(
    "benchmark.families.stub", stub_path)
stub = importlib.util.module_from_spec(module_spec)
module_spec.loader.exec_module(stub)
sys.modules["benchmark.families.stub"] = stub

from benchmark import harness, run
from benchmark.families import pictures

harness.load_cell = lambda name: spec
parents = []
family_check = stub.check_artifact

def both(blob, ref, config):
    try:  # the parent's check, at the canvas the tiny model takes
        parents.append(pictures.check(blob, ref, 64, 64))
    except Exception as error:
        parents.append(f"raised {type(error).__name__}")
    return family_check(blob, ref, config)

stub.check_artifact = both
try:
    code = run.main(["--workload", spec["cell"]["name"], "--seed",
                     "2718281829", "--seconds", "4", "--trace", "0"],
                    platform="cpu", rehearsal=True)
finally:
    server.shutdown()
print(json.dumps({"exit": code, "parent_verdicts": parents,
                  "calls": stub.CALLS}))
"""


@pytest.fixture(scope="module")
def lines():
    done = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(REPO), str(STUB)],
        input=json.dumps(SPEC), capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-3000:]
    return [json.loads(line) for line in done.stdout.splitlines()
            if line.startswith("{")]


def test_a_cell_of_text_jobs_reaches_the_last_line_correct(lines):
    result, ours = lines[-2], lines[-1]
    assert ours["exit"] == 0
    assert result["correct"] is True, lines
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert set(result["metrics"]) == {"job_latency_p50_s", "setup_s"}
    # every number compared, beside its limit, last in the line
    assert list(result)[-1] == "compared"
    assert set(result["compared"]) == {
        "stub_matmul_64x32x16_max_abs", "denoiser_rel_l2", "jobs_failed",
        "probe_distinct_sha256", "kernel_paths_missing", "window_compiles"}
    for number, limit in result["compared"].values():
        assert number <= limit
    summary = next(line for line in lines if line.get("phase") == "summary")
    assert summary["failures"] == []
    assert len(set(summary["probe_sha256"])) == 1


def test_every_name_of_the_contract_was_called_but_the_compile_check(lines):
    called = set(lines[-1]["calls"])
    assert called == {"register", "kernel_checks", "denoiser_inputs",
                      "denoiser_reference", "denoiser_serve"}
    kernels = next(line for line in lines if line.get("phase") == "kernels")
    assert [r["stub_matmul"] for r in kernels["readings"]] == [[64, 32, 16]]


def test_the_parents_check_takes_none_of_the_same_artifacts(lines):
    """Every window job's and both probe rides' artifact went through the
    family's check; the parent's refuses each (it does not even give a
    verdict: PIL raises on JSON, and the run would end with no line)."""
    result, verdicts = lines[-2], lines[-1]["parent_verdicts"]
    assert len(verdicts) == result["attempted"] + 2
    assert all(verdict is not None for verdict in verdicts), verdicts
