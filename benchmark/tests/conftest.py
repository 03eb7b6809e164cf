"""Run by hand, not by tier-1: `python -m pytest benchmark/tests -q -p no:cacheprovider`
(under a minute on the CPU)."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
os.environ.setdefault("SDAAS_ROOT", str(REPO / ".benchmark_run" / "tests"))
