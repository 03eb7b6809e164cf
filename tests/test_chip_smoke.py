"""chip_smoke.py rehearsed on the CPU at a tiny size: the same control flow
the chip run takes (hive and worker child processes, jobs through POST
/api/jobs, the worker's own reports), with the job size, the expected
platform and the kernels that platform traces swapped in-process — the
script has no rehearsal switch of its own. A chip call costs budget; a
wrong path, argument or shutdown is found here first.
"""

import ast
import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


@pytest.fixture()
def rehearsal(monkeypatch):
    """Tiny model, fast polls, and a record of every child started."""
    monkeypatch.setattr(chip_smoke, "MODEL", "test/tiny-sd")
    monkeypatch.setattr(chip_smoke, "SIZE", 64)
    monkeypatch.setattr(chip_smoke, "STEPS", 2)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", "")  # conftest's eight are not inherited
    monkeypatch.setenv("CHIASWARM_POLL_SECONDS", "0.5")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    started = []
    real_popen = subprocess.Popen

    def recording_popen(*args, **kwargs):
        proc = real_popen(*args, **kwargs)
        started.append(proc)
        return proc

    monkeypatch.setattr(chip_smoke.subprocess, "Popen", recording_popen)
    yield started
    shutil.rmtree(REPO / ".chip_smoke", ignore_errors=True)


def _lines(capsys) -> list[dict]:
    return [json.loads(line)
            for line in capsys.readouterr().out.splitlines() if line]


def test_parent_never_imports_jax():
    """The chip belongs to the worker child: no jax import anywhere in the
    script, not even a lazy one."""
    tree = ast.parse((REPO / "chip_smoke.py").read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported.update(alias.name.split(".")[0] for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert "jax" not in imported and "flax" not in imported


def test_one_chip_flow_at_tiny_size(rehearsal, monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "EXPECT_PLATFORM", "cpu")
    monkeypatch.setattr(chip_smoke, "REQUIRED_KERNELS",
                        ("attention,reference", "group_norm,reference"))
    assert chip_smoke.main([]) == 0
    lines = _lines(capsys)
    assert lines[-1] == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    jobs = [ln for ln in lines if ln.get("phase") == "job"]
    assert [j["name"] for j in jobs] == [
        "echo", "txt2img-1", "txt2img-2", "txt2img-3"]
    # same (prompt, seed) twice, a different one between; the first job
    # compiles, the third compiles nothing
    assert jobs[1]["sha256"] == jobs[3]["sha256"] != jobs[2]["sha256"]
    assert jobs[1]["compiles"] > 0 and jobs[3]["compiles"] == 0
    assert jobs[1]["worker_timings_s"]["load_s"] > 0
    runtime = next(ln for ln in lines if ln.get("phase") == "worker")
    assert runtime["runtime"]["jax"] and runtime["runtime"]["jaxlib"]
    cache = [ln for ln in lines
             if ln.get("phase", "").startswith("compile_cache")]
    assert [c["placed_by"] for c in cache] == ["default", "default"]
    # both children were started, and both are gone
    assert len(rehearsal) == 2
    assert all(proc.poll() is not None for proc in rehearsal)


def test_refuses_a_worker_that_is_not_on_the_tpu(rehearsal, capsys):
    """The unpatched script on a machine without a chip: non-zero exit,
    last line "ok": false, before any model is built, children stopped."""
    assert chip_smoke.EXPECT_PLATFORM == "tpu"
    assert chip_smoke.main([]) == 1
    lines = _lines(capsys)
    assert lines[-1]["ok"] is False
    assert "platform 'cpu'" in lines[-1]["error"]
    assert not [ln for ln in lines if ln.get("phase") == "job"]
    assert len(rehearsal) == 2
    assert all(proc.poll() is not None for proc in rehearsal)


def test_four_chip_flow_on_virtual_devices(rehearsal, monkeypatch, capsys):
    """--chips 4: a tensor=4 worker, then a one-chip worker, one after the
    other on the same four (virtual) devices, and the image comparison."""
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    monkeypatch.setattr(chip_smoke, "EXPECT_PLATFORM", "cpu")
    def four_rows(devices):  # CPU devices report no bytes in use
        assert [d["device"] for d in devices] == [
            f"cpu:{i}" for i in range(4)]

    monkeypatch.setattr(chip_smoke, "check_all_hold_params", four_rows)
    assert chip_smoke.main(["--chips", "4"]) == 0
    lines = _lines(capsys)
    assert lines[-1]["device"]["count"] == 4 and lines[-1]["ok"] is True
    jobs = {ln["name"]: ln for ln in lines if ln.get("phase") == "job"}
    assert jobs["smoke-tensor4"]["geometry"] == {
        "data": 1, "tensor": 4, "seq": 1}
    assert jobs["smoke-1chip"]["geometry"] == {
        "data": 1, "tensor": 1, "seq": 1}
    compare = next(ln for ln in lines if ln.get("phase") == "compare")
    assert compare["within_cpu_f32_bound_of_2"] is True  # float32 here
    # hive + two workers, started one after the other, all gone
    assert len(rehearsal) == 3
    assert all(proc.poll() is not None for proc in rehearsal)


def test_fails_where_the_repo_is_not(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo: non-zero exit and no "ok": true."""
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
