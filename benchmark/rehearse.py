#!/usr/bin/env python3
"""CPU rehearsal of a cell: the command's whole control flow at a tiny size.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py --workload <cell> --seed 1 --seconds 8 --trace 1

Runs `run.py`'s own `main` with each file's `rehearsal` block applied
(`test/tiny-*` models at 64^2, two steps, tiny kernel shapes, the fused
GroupNorm kernel in interpret mode) and the expected platform set to `cpu`.
It walks every phase of the cell — swarm, warm-up, window, trace capture,
checks, readers — and prints counts only: every time, rate and share reads
"not measured", and the last line names the CPU, so it can never be taken
for a chip run. It is an entry of its own, never a fallback of the command.
The host platform is asked for as many devices as the cell has chips, so a
four-chip cell builds its slice and mesh over four CPU devices.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark import harness, run  # noqa: E402


def host_devices(spec: dict) -> None:
    """One host device a chip of the cell; must run before jax is
    imported."""
    flags = [flag for flag in os.environ.get("XLA_FLAGS", "").split()
             if not flag.startswith("--xla_force_host_platform_device_count")]
    flags.append("--xla_force_host_platform_device_count="
                 f"{int(spec['cell']['chips'])}")
    os.environ["XLA_FLAGS"] = " ".join(flags)


def main(argv=None) -> int:
    try:
        host_devices(harness.load_cell(run.parse(argv).workload))
    except harness.RunFailure as failure:
        print(f"benchmark: {failure}", file=sys.stderr)
        return 3
    return run.main(argv, platform="cpu", rehearsal=True)


if __name__ == "__main__":
    sys.exit(main())
