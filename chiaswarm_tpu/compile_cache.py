"""Persistent XLA compilation cache, placed from outside the program.

A cold slice pays the full XLA trace+compile on its first pass of every
shape bucket (minutes for an SDXL denoise program). The compiled
executables are deterministic per (HLO, backend), so JAX's persistent
compilation cache carries the compile half across process restarts: a
restarted worker then pays only trace + cache deserialization.

The directory is part of the cache's key, so it must not move:

- where ``JAX_COMPILATION_CACHE_DIR`` is set, jax itself reads it and this
  module sets no directory in code — whoever starts the process (the
  Dockerfile, an operator, a test harness handing one directory to two
  children) places the cache;
- where it is not set, the cache is ``.jax_cache`` beside the package
  (the root of a checkout, git-ignored): one fixed path, independent of
  ``SDAAS_ROOT``, pid, time and temp names.

A directory that cannot be written is an error, not a silently cold cache.

Consumers: worker.startup() and the benchmark's harness. A process that
wants every program cached (tests/test_compile_cache.py; the benchmark's
families around their warm-up) lowers jax's own
``jax_persistent_cache_min_compile_time_secs`` after enabling the cache.
"""

from __future__ import annotations

import os
from pathlib import Path

from . import telemetry

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parent.parent / ".jax_cache"

# jax's own cache events: a "hit" is an executable read back from the
# directory instead of compiled, a "miss" one compiled and written to it
_LOOKUPS = telemetry.counter(
    "swarm_xla_cache_total",
    "Persistent XLA compilation cache lookups by outcome (hit = read "
    "from the cache directory, miss = compiled and written there)",
    ("event",),
)
_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
# every program jax hands the backend — each jit site's and each eager
# op's first use of a shape, whichever path it took there (the program
# ledger sees only its own sites). "No compile inside the window" is this
# counter standing still.
_COMPILES = telemetry.counter(
    "swarm_xla_compiles_total",
    "Programs handed to the backend compiler or read back from the "
    "persistent cache (every jit site and eager op)")
_COMPILE_SECONDS = telemetry.counter(
    "swarm_xla_compile_seconds_total",
    "Seconds spent in backend compiles and persistent-cache reads")
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
# programs that compile faster than this are not written to the cache: a
# spam guard (a worker compiles thousands of trivial eager sub-programs),
# not a correctness knob
_MIN_COMPILE_TIME_S = 1.0
_listening = False


def _count_event(event: str, **_) -> None:
    outcome = _EVENTS.get(event)
    if outcome:
        _LOOKUPS.inc(event=outcome)


def _count_duration(event: str, duration: float, **_) -> None:
    if event == _BACKEND_COMPILE:
        _COMPILES.inc()
        _COMPILE_SECONDS.inc(duration)


def cache_dir() -> Path:
    """Where the cache lives. Pure path logic — no writes, no jax."""
    return Path(os.environ.get(ENV_VAR) or DEFAULT_DIR)


def writable_cache_dir() -> Path:
    """`cache_dir()`, created and proven writable. Raises OSError when the
    directory cannot be created or written. No jax."""
    path = cache_dir()
    path.mkdir(parents=True, exist_ok=True)
    probe = path / ".write_probe"
    probe.write_text("ok")
    probe.unlink()
    return path


def enable_compile_cache() -> Path:
    """Turn jax's persistent compilation cache on at `cache_dir()` and
    return that path. Raises OSError when the directory cannot be created
    or written."""
    path = writable_cache_dir()

    import jax

    global _listening
    if not _listening:
        jax.monitoring.register_event_listener(_count_event)
        jax.monitoring.register_event_duration_secs_listener(_count_duration)
        _listening = True
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      _MIN_COMPILE_TIME_S)
    return path
