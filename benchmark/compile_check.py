#!/usr/bin/env python3
"""Compile-only rehearsal: every cell's denoise program at its real widths
and row count, for the cell's chips of a described `v5e:2x2` that is not
attached.

    JAX_PLATFORMS=cpu python3 benchmark/compile_check.py [--workload <cell>]

Nothing runs, so this says nothing of results or times; it says what the
chip's compiler would refuse (a kernel, a row count, a program that does
not fit) and what `memory_analysis()` counts for the one program — not
what else the process keeps on the device. One line per cell, and never a
device metric. Takes one to two minutes a cell in the sandbox.

Code that asks jax for its backend sees the CPU here, so the kernel
dispatch's `trace_platform` is patched to say `tpu` for the duration
(on-chip-measurement guide, section 2.3). The program and its operands as
shapes come from the configuration's family (`compile_operands`), which
builds its pipeline without weights.
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


def check(name: str, described) -> dict:
    """Compile the cell's program for the first `chips` of the `described`
    devices."""
    from benchmark import harness

    spec = harness.load_cell(name)
    os.environ["SDAAS_ROOT"] = str(REPO / ".benchmark_run" / "compile_check")
    family = harness.load_family(spec["config"])
    devices = list(described[:spec["cell"]["chips"]])
    before = harness.scrape().get("swarm_kernel_traces_total", {})
    started = time.monotonic()
    program, args, rows = family.compile_operands(spec, devices)
    compiled = program.lower(*args).compile()
    memory = compiled.memory_analysis()
    after = harness.scrape().get("swarm_kernel_traces_total", {})
    return {
        "cell": name, "rows": rows,
        "compiled_for": f"v5e:2x2, {len(devices)} chip(s)",
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
            print(json.dumps(check(name, topo.devices)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
