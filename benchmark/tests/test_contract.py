"""`BENCHMARK.json` against the parts of its contract that can be checked
here, and the harness against its own rule: driven by data."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[0-9A-Za-z_][0-9A-Za-z_.\-]{0,63}$")
UNIT = re.compile(r"^[0-9A-Za-z_/%.\-]{1,16}$")


def metrics():
    return BENCH["end_to_end"] + BENCH["per_layer"]


def test_keys_names_and_units():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [m["name"] for m in metrics()]
    assert len(names) == len(set(names))
    for entry in metrics() + BENCH["configs"] + BENCH["workloads"]:
        assert NAME.match(entry["name"]), entry["name"]
    for metric in metrics():
        assert UNIT.match(metric["unit"]) and metric["better"] in (
            "lower", "higher")
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
    for metric in BENCH["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_every_entry_has_its_file():
    root = REPO / BENCH["paths"][0]
    for config in BENCH["configs"]:
        body = json.loads((REPO / config["file"]).read_text())
        assert body["source"] == config["source"]
        assert body["reduced"] == config["reduced"]
        assert (root / "families" / f"{body['family']}.py").is_file()
    for cell in BENCH["workloads"]:
        traffic = json.loads(
            (root / "traffic" / f"{cell['traffic']}.json").read_text())
        assert (root / "generators" / f"{traffic['generator']}.py").is_file()
        assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    for metric in BENCH["end_to_end"]:
        assert (root / "end_to_end" / f"{metric['name']}.py").is_file()
    for metric in BENCH["per_layer"]:
        assert (root / "layer_metrics" / f"{metric['name']}.py").is_file()


def test_every_cell_reports_enough():
    from benchmark import harness

    for cell in BENCH["workloads"]:
        spec = harness.load_cell(cell["name"])
        names = {m["name"] for m in spec["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert spec["per_layer"], cell["name"]
        for metric in spec["per_layer"]:
            assert metric["moves"] in names


def code_of(path: Path) -> str:
    """A file's code without its docstrings and comments."""
    code = re.sub(r'""".*?"""', "", path.read_text(), flags=re.S)
    return "\n".join(line.split("#")[0] for line in code.splitlines())


# the files that may know no cell, model, metric or network
DATA_DRIVEN = ("harness.py", "run.py", "rehearse.py", "measure.py",
               "breakdown.py", "checks.py", "compile_check.py",
               "generators/closed_loop.py", "trace/reduce.py")
# one network's own names, and one kind of artifact's (a canvas, the library
# that opens a picture, a pipeline's stage keys, a wire name of the
# registry): they belong in a module under `families/`
NETWORK_WORDS = ("unet", "is_xl", "unet2d", "SDPipeline", "_denoise_program",
                 "height", "width", "PIL", "denoise_decode_s",
                 "text_encode_s", "DiffusionPipeline")
# the same as whole words, case kept, docstrings and comments included:
# what the harness proper may not even mention
PICTURE_WORDS = ("height", "width", "PIL", "Image", "denoise_decode_s",
                 "text_encode_s", "DiffusionPipeline")
HARNESS_PROPER = ("harness.py", "checks.py", "breakdown.py", "measure.py",
                  "run.py")


def test_harness_names_no_cell_model_or_metric():
    words = {c["name"] for c in BENCH["configs"]}
    words |= {w["name"] for w in BENCH["workloads"]}
    words |= {w["traffic"] for w in BENCH["workloads"]}
    words |= {m["name"] for m in metrics()} - {"setup_s"}
    for config in BENCH["configs"]:
        words.add(json.loads(
            (REPO / config["file"]).read_text())["job"]["model_name"])
    root = REPO / BENCH["paths"][0]
    for path in DATA_DRIVEN:
        code = code_of(root / path)
        for word in words:
            assert not re.search(
                rf"(?<![\w.\-]){re.escape(word)}(?![\w.\-])", code), (
                path, word)


@pytest.mark.parametrize("path", DATA_DRIVEN)
def test_harness_knows_no_network(path):
    """Attribute or name, any case: `pipe.unet`, `UNET_TOL`, `unet2d`."""
    code = code_of(REPO / BENCH["paths"][0] / path)
    for word in NETWORK_WORDS:
        found = re.search(rf"(?i)(?<![0-9a-z]){re.escape(word)}(?![0-9a-z])",
                          code)
        assert not found, (path, word)


@pytest.mark.parametrize("path", HARNESS_PROPER)
def test_the_harness_proper_mentions_no_picture(path):
    text = (REPO / BENCH["paths"][0] / path).read_text()
    found = re.findall(rf"\b(?:{'|'.join(PICTURE_WORDS)})\b", text)
    assert not found, (path, found)


@pytest.mark.parametrize("chips", [4, 1])
def test_rehearsal_gives_a_cell_one_host_device_a_chip(chips):
    """`rehearse.host_devices` before jax is imported, in a process of its
    own; no pipeline is built."""
    script = (
        "import os, sys\n"
        f"sys.path.insert(0, {str(REPO)!r})\n"
        "from benchmark import rehearse\n"
        "assert 'jax' not in sys.modules\n"
        "rehearse.host_devices({'cell': {'chips': int(sys.argv[1])}})\n"
        "import jax\n"
        "print(jax.devices()[0].platform, len(jax.devices()))\n")
    out = subprocess.run(
        [sys.executable, "-c", script, str(chips)], check=True,
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    assert out.stdout.split() == ["cpu", str(chips)]
