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

The same call makes jax's own staging events the program's spans. jax times
every stage of every jit site's and eager op's first use of a shape itself
(``jax/_src/dispatch.py`` ``log_elapsed_time``) and hands the times to
whoever listens; nothing is rerouted, a site that is never listened to
runs the same code. Each event becomes one ``telemetry.Span`` record:

==================  ======================================================
``xla_trace``       ``jaxpr_trace_duration``, SELF time: a jitted function
                    traced inside another's trace reports inside its
                    caller's event, so the stage's sum tiles the tracing
``xla_lower``       ``jaxpr_to_mlir_module_duration``
``xla_compile``     ``backend_compile_duration``: the backend's compile OR
                    the read-back from the cache directory (what
                    ``swarm_xla_compile_seconds_total`` sums)
``xla_cache_read``  ``cache_retrieval_time_sec``, on a hit only; it lies
                    inside the ``xla_compile`` that follows it
==================  ======================================================

and one line of the table by function (``staging()``, served as
``staging`` by ``programs.snapshot``): which program costs the start, and
which one was staged again later.
"""

from __future__ import annotations

import collections
import functools
import logging
import os
import threading
import time
from pathlib import Path

from . import telemetry

logger = logging.getLogger(__name__)

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
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
# jax's staging events that carry a function's name, a start and an end
# -> the stage each is stamped as, and its column of the table by function
_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": ("xla_trace", "trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("xla_lower", "lower_s"),
    _BACKEND_COMPILE: ("xla_compile", "compile_s"),
}
# a first pass stages hundreds of eager one-liners: an event shorter than
# this reaches the stage histogram and no envelope
ENVELOPE_MIN_S = 0.010
# rows of the table by function; what comes after goes under "other"
MAX_FUNCTIONS = 256
OTHER = "other"
# trace events a thread keeps until the event they lie inside arrives; a
# trace at top level has none to wait for and leaves by the left
_PENDING_TRACES = 4096
# how jax names a function's module in its lower and compile events
_WRAPPERS = ("jit(", "pmap(")
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
    elif event == _CACHE_READ:
        _stamp_cache_read(duration)


# --- jax's staging events as the program's spans ---------------------------

_TABLE_LOCK = threading.Lock()
_TABLE: dict[str, dict] = {}
# per thread: `traces`, the (start, seconds) of trace events already
# reported and not yet claimed by the event they lie inside; `cache_read`,
# the seconds of a read-back whose `xla_compile` has not ended yet
_THREAD = threading.local()


def _quiet(listener):
    """A listener that raises would fail the compile it reports on. The
    spans corroborate; they never break a pass (as `programs._capture`)."""

    @functools.wraps(listener)
    def guarded(*args, **kwargs):
        try:
            listener(*args, **kwargs)
        except Exception:
            logger.debug("staging listener failed", exc_info=True)

    return guarded


def _self_seconds(start: float, seconds: float) -> float:
    """A trace event's seconds less those of the traces inside it. Inner
    events end first, on the same thread: what this thread has reported
    that began at or after `start` lies inside this one."""
    done = _THREAD.__dict__.setdefault(
        "traces", collections.deque(maxlen=_PENDING_TRACES))
    inner = 0.0
    while done and done[-1][0] >= start:
        inner += done.pop()[1]
    done.append((start, seconds))
    return max(seconds - inner, 0.0)


def _note(name: str, column: str, start: float, seconds: float,
          cache_read: float = 0.0) -> None:
    """One event into its function's row of the table."""
    if name.endswith(")") and name.startswith(_WRAPPERS):
        name = name[name.index("(") + 1:-1]
    with _TABLE_LOCK:
        row = _TABLE.get(name)
        if row is None:
            if len(_TABLE) >= MAX_FUNCTIONS:
                name = OTHER
            row = _TABLE.setdefault(name, {
                "events": 0, "trace_s": 0.0, "lower_s": 0.0,
                "compile_s": 0.0, "cache_read_s": 0.0,
                "first_wall": start})
        row["events"] += 1
        row[column] += seconds
        row["cache_read_s"] += cache_read
        row["last_wall"] = start + seconds


def _stamp(stage: str, start: float, seconds: float) -> None:
    # a short event is stamped into a list nobody reads: the histogram
    # only. A long one joins the pass's envelope where a `JobTrace` is
    # open on this thread, as a child of the span it fell in
    telemetry.Span(
        stage, spans=[] if seconds < ENVELOPE_MIN_S else None,
    ).record(start, seconds)


@_quiet
def _stage_span(event: str, start_time: float, end_time: float,
                fun_name: str = "", **_) -> None:
    staged = _STAGES.get(event)
    if staged is None:
        return
    stage, column = staged
    seconds = end_time - start_time
    cache_read = 0.0
    if stage == "xla_trace":
        seconds = _self_seconds(start_time, seconds)
    elif stage == "xla_compile":
        cache_read = _THREAD.__dict__.pop("cache_read", 0.0)
    _stamp(stage, start_time, seconds)
    _note(str(fun_name), column, start_time, seconds, cache_read)


@_quiet
def _stamp_cache_read(seconds: float) -> None:
    # jax reports the read-back as a duration alone, the moment it ends
    _THREAD.cache_read = seconds
    _stamp("xla_cache_read", time.time() - seconds, seconds)


def staging() -> list[dict]:
    """The table by function, dearest first: per name (`jit(...)` /
    `pmap(...)` stripped) its events, self `trace_s`, `lower_s`,
    `compile_s` (of it `cache_read_s` read back and not compiled) and
    the wall stamps of its first event's start and its last one's end.
    Each column adds up to its stage's `swarm_job_stage_seconds` sum."""
    with _TABLE_LOCK:
        rows = [{"function": name, **row} for name, row in _TABLE.items()]
    rows.sort(key=lambda r: -(r["trace_s"] + r["lower_s"] + r["compile_s"]))
    for row in rows:
        for key in ("trace_s", "lower_s", "compile_s", "cache_read_s"):
            row[key] = round(row[key], 6)
    return rows


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
        jax.monitoring.register_event_time_span_listener(_stage_span)
        _listening = True
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      _MIN_COMPILE_TIME_S)
    return path
