#!/usr/bin/env python3
"""Compile-only rehearsal: every cell's denoise program at its real widths
and row count, for a described `v5e:2x2` chip that is not attached.

    JAX_PLATFORMS=cpu python3 benchmark/compile_check.py [--workload <cell>]

Nothing runs, so this says nothing of results or times; it says what the
chip's compiler would refuse (a kernel, a row count, a program that does
not fit) and what `memory_analysis()` counts for the one program — not
what else the process keeps on the device. One line per cell, and never a
device metric. Takes one to two minutes a cell in the sandbox.

Code that asks jax for its backend sees the CPU here, so the kernel
dispatch's `trace_platform` is patched to say `tpu` for the duration
(on-chip-measurement guide, section 2.3); the pipeline is built without
weights and handed shapes.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path
from unittest import mock

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))


def program_and_shapes(spec: dict, device):
    """The cell's denoise program (as `run_batched` / `run` would key it)
    and its arguments as shapes on `device`."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark.families.sd import init_shapes, make_pipeline_class
    from chiaswarm_tpu.pipelines.stable_diffusion import (
        SchedulerConfig,
        dataclass_items,
    )
    from chiaswarm_tpu.settings import load_settings

    config, traffic = spec["config"], spec["traffic"]
    job = {**config["job"], **traffic["job"]}
    one = SingleDeviceSharding(device)

    class Shapes(make_pipeline_class()):
        def _load_params(self):
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, self.dtype,
                                               sharding=one),
                init_shapes(self))

    pipe = Shapes(job["model_name"], dtype=jnp.dtype(config["kernel_dtype"]))
    rows = min(int(traffic["clients"]),
               int(load_settings().hive_max_jobs_per_poll))
    lh = int(job["height"]) // pipe.latent_factor
    lw = int(job["width"]) // pipe.latent_factor
    scheduler = config["job"].get("parameters", {}).get(
        "scheduler_type", "DPMSolverMultistepScheduler")
    sched_cfg = SchedulerConfig(prediction_type=pipe.prediction_type,
                                use_karras_sigmas=False)
    sched_key = (scheduler, tuple(sorted(dataclass_items(sched_cfg))))
    # a gang of one takes the worker's solo path (`run`), larger ones the
    # batched one (`run_batched`): their programs are keyed differently
    mode = "batched" if rows > 1 else "txt2img"
    key = (mode, lh, lw, rows, int(job["num_inference_steps"]), sched_key,
           0, None)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    rng = jax.eval_shape(lambda: jax.random.key(0))
    rngs = shape((rows,) + rng.shape, rng.dtype) if rows > 1 \
        else shape(rng.shape, rng.dtype)
    cross = pipe.unet.config.cross_attention_dim
    added = None
    if pipe.is_xl:
        pooled = (pipe.unet.config.addition_embed_dim
                  - 6 * pipe.unet.config.addition_time_embed_dim)
        added = {"text_embeds": shape((2 * rows, pooled), pipe.dtype),
                 "time_ids": shape((2 * rows, 6), jnp.float32)}
    scalar = shape((), jnp.float32)
    args = (pipe.params, rngs, shape((2 * rows, 77, cross), pipe.dtype),
            added, scalar, scalar, shape((1, 1, 1, 4), jnp.float32),
            shape((1, 1, 1, 1), jnp.float32), rngs, {},
            shape((1, 1, 1, 3), jnp.float32), scalar, {})
    return pipe._denoise_program(key), args, rows


def check(name: str, device) -> dict:
    from benchmark import harness
    spec = harness.load_cell(name)
    os.environ["SDAAS_ROOT"] = str(REPO / ".benchmark_run" / "compile_check")
    before = harness.scrape().get("swarm_kernel_traces_total", {})
    started = time.monotonic()
    program, args, rows = program_and_shapes(spec, device)
    compiled = program.lower(*args).compile()
    memory = compiled.memory_analysis()
    after = harness.scrape().get("swarm_kernel_traces_total", {})
    return {
        "cell": name, "rows": rows, "compiled_for": "v5e:2x2, one chip",
        "compile_here_s": round(time.monotonic() - started, 1),
        "argument_gb": memory.argument_size_in_bytes / 1e9,
        "temp_gb": memory.temp_size_in_bytes / 1e9,
        "output_gb": memory.output_size_in_bytes / 1e9,
        "code_gb": memory.generated_code_size_in_bytes / 1e9,
        "program_total_gb": (memory.argument_size_in_bytes
                             + memory.temp_size_in_bytes
                             + memory.output_size_in_bytes
                             + memory.generated_code_size_in_bytes) / 1e9,
        "kernel_sites": {labels: after[labels] - before.get(labels, 0.0)
                         for labels in after},
        "note": "a compile for a described chip, not a chip run",
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", action="append")
    args = parser.parse_args(argv)
    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = args.workload or [w["name"] for w in json.loads(
        (REPO / "BENCHMARK.json").read_text())["workloads"]]
    with mock.patch("chiaswarm_tpu.ops.platform.trace_platform",
                    return_value="tpu"), \
            mock.patch("chiaswarm_tpu.ops.attention.trace_platform",
                       return_value="tpu"), \
            mock.patch("chiaswarm_tpu.ops.group_norm.trace_platform",
                       return_value="tpu"):
        for name in names:
            print(json.dumps(check(name, topo.devices[0])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
