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
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark import run  # noqa: E402

if __name__ == "__main__":
    sys.exit(run.main(platform="cpu", rehearsal=True))
