"""Starting and finding a profiler trace: the two things `run.py` and
`record_small.py` share."""

from __future__ import annotations

from pathlib import Path


def profile_options():
    """Device events and the benchmark's own `TraceAnnotation`s; no Python
    tracer (it slows the host the run is measuring and bloats the file)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    return options


def xplane_files(log_dir: Path) -> list[Path]:
    return sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
