"""The persistent compile cache across a process restart, as counts.

Two child processes share one empty ``JAX_COMPILATION_CACHE_DIR``. Each
builds the tiny SD pipeline and runs one job of the same shapes: the first
is a cold start, the second a restarted worker. What is compared is the
directory's entries and jax's own cache events (``swarm_xla_cache_total``,
compile_cache.py); no seconds.
"""

import json
import os
import subprocess
import sys

import pytest


def _child(program: str) -> None:
    import jax

    from chiaswarm_tpu import telemetry
    from chiaswarm_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()
    # the worker's floor is a spam guard; every program of the tiny
    # pipeline compiles in under it, and all of them are to persist
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from chiaswarm_tpu.chips.device import ChipSet
    from chiaswarm_tpu.pipelines.stable_diffusion import SDPipeline

    pipe = SDPipeline("test/tiny-sd", chipset=ChipSet(jax.devices()),
                      allow_random_init=True)
    shared = dict(height=64, width=64, num_inference_steps=4,
                  scheduler_type="EulerDiscreteScheduler")
    if program == "solo":
        pipe.run(prompt="restart", rng=jax.random.key(0), **shared)
    else:
        pipe.run_batched(
            [{"prompt": f"row {i}", "rng": jax.random.key(i)}
             for i in range(4)], **shared)
    lookups = telemetry.REGISTRY.get("swarm_xla_cache_total")
    print(json.dumps({"hit": lookups.value(event="hit"),
                      "miss": lookups.value(event="miss")}))


def _start(program: str, cache_dir) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(cache_dir)}
    env.pop("XLA_FLAGS", None)  # one CPU device: the smallest compile
    code = f"from tests.test_compile_cache import _child; _child({program!r})"
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("program", ["solo", "batched"])
def test_restarted_process_reads_its_programs_back(tmp_path, program):
    cache_dir = tmp_path / "xla-cache"
    cache_dir.mkdir()
    assert os.listdir(cache_dir) == []

    cold = _start(program, cache_dir)
    written = sorted(os.listdir(cache_dir))
    # every program it compiled is one entry; its few hits are programs it
    # had written itself a moment before (two jit sites, one HLO)
    assert cold["miss"] == len(written) > 0, cold
    assert cold["hit"] < cold["miss"], cold

    restarted = _start(program, cache_dir)
    # same shapes, same directory: every look-up is a read, nothing is
    # compiled, so nothing new is written
    assert restarted["miss"] == 0, restarted
    assert restarted["hit"] == cold["hit"] + cold["miss"], (cold, restarted)
    assert sorted(os.listdir(cache_dir)) == written
