"""Process-wide telemetry: metrics registry, per-job trace spans, local HTTP.

The reference swarm emits nothing but a flat rotating log file
(swarm/log_setup.py); there is no way to see where a job's wall clock goes
or how well the batching layer packs rows. Diffusion-serving work
(SwiftDiffusion arXiv:2407.02031, SD-Acc arXiv:2507.01309) is driven by
exactly the per-stage latency breakdown this module provides. Design:

- a tiny, stdlib-only metrics registry (`Counter`, `Gauge`, `Histogram`
  with fixed buckets) rendering the Prometheus text exposition format —
  deliberately NOT a prometheus_client dependency: the worker image must
  not grow a runtime dep for what is ~200 lines of dict arithmetic;
- a `Span` / `trace_job` context-manager API that stamps per-stage wall
  time into the process-wide `swarm_job_stage_seconds{stage=...}`
  histogram, the per-job `timings` dict AND the pass's wall-stamped,
  thread-aware `spans` list, both of which ride the result envelope
  (`pipeline_config`), and shows each span in a profiler capture as
  `swarm/<stage>` — so the hive, the local scrape and the device trace
  see the same numbers from the same measurement;
- an aiohttp app (`GET /metrics`, `GET /healthz`) the worker starts next
  to its jax.profiler server. `Settings.metrics_port` / the
  `CHIASWARM_METRICS_PORT` env knob picks the port; 0 disables the server
  (instrumentation itself is dict ops and stays on).

Everything is thread-safe: spans fire from slice executor threads while
the asyncio loop scrapes.
"""

from __future__ import annotations

import bisect
import contextvars
import os
import sys
import threading
import time

# per-job stage timings land here; label value = stage name
STAGE_METRIC = "swarm_job_stage_seconds"
_STAGE_HELP = "Per-job wall-clock seconds by lifecycle stage"

# generic latency buckets: 5 ms poll hops up to 10-minute SDXL compiles
DEFAULT_BUCKETS = (
    0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0,
)

# the job id of the currently-executing job, for log correlation
# (log_setup.JsonFormatter reads it); set by trace_job / worker threads
current_job_id: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "chiaswarm_job_id", default=None
)


def _fmt_value(v: float) -> str:
    if v == float("inf"):
        return "+Inf"
    if v == float("-inf"):
        return "-Inf"
    f = float(v)
    if f.is_integer() and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def _escape_label(v) -> str:
    return (
        str(v)
        .replace("\\", "\\\\")
        .replace("\n", "\\n")
        .replace('"', '\\"')
    )


def _escape_help(v: str) -> str:
    return v.replace("\\", "\\\\").replace("\n", "\\n")


def _sample_line(name: str, labelnames, labelvalues, value: float,
                 extra: tuple[str, str] | None = None) -> str:
    """One exposition line; labels render in DECLARED order (stable), with
    an optional trailing (name, value) pair — histograms put `le` last."""
    pairs = list(zip(labelnames, labelvalues))
    if extra is not None:
        pairs.append(extra)
    if pairs:
        lbl = ",".join(f'{n}="{_escape_label(v)}"' for n, v in pairs)
        return f"{name}{{{lbl}}} {_fmt_value(value)}"
    return f"{name} {_fmt_value(value)}"


class _Metric:
    kind = "untyped"

    def __init__(self, name: str, help: str = "", labelnames=()):
        self.name = name
        self.help = help
        self.labelnames = tuple(labelnames)
        self._lock = threading.Lock()
        self._values: dict[tuple, object] = {}

    def _key(self, labels: dict) -> tuple:
        if set(labels) != set(self.labelnames):
            raise ValueError(
                f"{self.name} takes labels {self.labelnames}, got "
                f"{tuple(labels)}"
            )
        return tuple(str(labels[n]) for n in self.labelnames)

    def remove(self, **labels) -> None:
        """Drop one label combination's series. Bounded-cardinality
        surfaces (per-tenant usage gauges folded to top-K, per-worker
        outlier flags pruned with the directory) retire label values
        here instead of exposing stale series forever."""
        with self._lock:
            self._values.pop(self._key(labels), None)

    def samples(self) -> list[str]:
        raise NotImplementedError

    def render(self) -> str:
        lines = []
        if self.help:
            lines.append(f"# HELP {self.name} {_escape_help(self.help)}")
        lines.append(f"# TYPE {self.name} {self.kind}")
        lines.extend(self.samples())
        return "\n".join(lines)


class Counter(_Metric):
    kind = "counter"

    def inc(self, amount: float = 1.0, **labels) -> None:
        if amount < 0:
            raise ValueError("counters only go up")
        key = self._key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def value(self, **labels) -> float:
        with self._lock:
            return float(self._values.get(self._key(labels), 0.0))

    def total(self) -> float:
        """Sum across every label combination (heartbeat snapshots)."""
        with self._lock:
            return float(sum(self._values.values()))

    def samples(self) -> list[str]:
        with self._lock:
            items = sorted(self._values.items())
        return [
            _sample_line(self.name, self.labelnames, key, v)
            for key, v in items
        ]


class Gauge(_Metric):
    kind = "gauge"

    def set(self, value: float, **labels) -> None:
        key = self._key(labels)
        with self._lock:
            self._values[key] = float(value)

    def inc(self, amount: float = 1.0, **labels) -> None:
        key = self._key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def dec(self, amount: float = 1.0, **labels) -> None:
        self.inc(-amount, **labels)

    def value(self, **labels) -> float:
        with self._lock:
            return float(self._values.get(self._key(labels), 0.0))

    def samples(self) -> list[str]:
        with self._lock:
            items = sorted(self._values.items())
        return [
            _sample_line(self.name, self.labelnames, key, v)
            for key, v in items
        ]


class Histogram(_Metric):
    kind = "histogram"

    def __init__(self, name: str, help: str = "", labelnames=(),
                 buckets=DEFAULT_BUCKETS):
        super().__init__(name, help, labelnames)
        bounds = sorted(float(b) for b in buckets)
        if not bounds:
            raise ValueError("histogram needs at least one bucket bound")
        self.buckets = tuple(bounds)

    def observe(self, value: float, **labels) -> None:
        key = self._key(labels)
        v = float(value)
        with self._lock:
            state = self._values.get(key)
            if state is None:
                # per-bound counts + overflow slot, running sum, count
                state = [[0] * (len(self.buckets) + 1), 0.0, 0]
                self._values[key] = state
            state[0][bisect.bisect_left(self.buckets, v)] += 1
            state[1] += v
            state[2] += 1

    def count(self, **labels) -> int:
        with self._lock:
            state = self._values.get(self._key(labels))
            return int(state[2]) if state else 0

    def sum(self, **labels) -> float:
        with self._lock:
            state = self._values.get(self._key(labels))
            return float(state[1]) if state else 0.0

    def label_values(self, labelname: str) -> list[str]:
        """Distinct observed values of one label (e.g. every stage seen)."""
        idx = self.labelnames.index(labelname)
        with self._lock:
            return sorted({key[idx] for key in self._values})

    def samples(self) -> list[str]:
        with self._lock:
            items = sorted(
                (key, [list(s[0]), s[1], s[2]])
                for key, s in self._values.items()
            )
        lines = []
        for key, (counts, total, n) in items:
            cumulative = 0
            for bound, c in zip(self.buckets, counts):
                cumulative += c
                lines.append(_sample_line(
                    f"{self.name}_bucket", self.labelnames, key, cumulative,
                    extra=("le", _fmt_value(bound)),
                ))
            lines.append(_sample_line(
                f"{self.name}_bucket", self.labelnames, key, n,
                extra=("le", "+Inf"),
            ))
            lines.append(_sample_line(
                f"{self.name}_sum", self.labelnames, key, total))
            lines.append(_sample_line(
                f"{self.name}_count", self.labelnames, key, n))
        return lines


class Registry:
    """Get-or-create metric container; one module-level instance serves the
    whole process (slice executor threads + asyncio loop)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: dict[str, _Metric] = {}

    def _get_or_create(self, cls, name, help, labelnames, **kw) -> _Metric:
        with self._lock:
            existing = self._metrics.get(name)
            if existing is not None:
                if not isinstance(existing, cls) or existing.labelnames != tuple(
                    labelnames
                ):
                    raise ValueError(
                        f"metric {name} already registered with a different "
                        "type or label set"
                    )
                return existing
            metric = cls(name, help, labelnames, **kw)
            self._metrics[name] = metric
            return metric

    def counter(self, name, help="", labelnames=()) -> Counter:
        return self._get_or_create(Counter, name, help, labelnames)

    def gauge(self, name, help="", labelnames=()) -> Gauge:
        return self._get_or_create(Gauge, name, help, labelnames)

    def histogram(self, name, help="", labelnames=(),
                  buckets=DEFAULT_BUCKETS) -> Histogram:
        return self._get_or_create(
            Histogram, name, help, labelnames, buckets=buckets)

    def get(self, name) -> _Metric | None:
        with self._lock:
            return self._metrics.get(name)

    def render(self) -> str:
        with self._lock:
            metrics = [self._metrics[n] for n in sorted(self._metrics)]
        return "\n".join(m.render() for m in metrics) + "\n"


REGISTRY = Registry()


def counter(name, help="", labelnames=()) -> Counter:
    return REGISTRY.counter(name, help, labelnames)


def gauge(name, help="", labelnames=()) -> Gauge:
    return REGISTRY.gauge(name, help, labelnames)


def histogram(name, help="", labelnames=(), buckets=DEFAULT_BUCKETS) -> Histogram:
    return REGISTRY.histogram(name, help, labelnames, buckets=buckets)


# --- startup marks ---------------------------------------------------------

_IMPORTED_WALL = time.time()


def _process_start_wall() -> float:
    """The wall-clock instant the OS started this process: its start in
    clock ticks since boot (`/proc/self/stat`, field 22) against the
    seconds since boot (`/proc/uptime`). Where that cannot be read, or
    reads as later than now, the import of this module."""
    try:
        with open("/proc/self/stat") as stat:
            # the command's name may hold spaces: count from its ")"
            ticks = float(stat.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as uptime:
            age = float(uptime.read().split()[0]) - ticks / os.sysconf(
                "SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return _IMPORTED_WALL
    return _IMPORTED_WALL - age if age >= 0 else _IMPORTED_WALL


PROCESS_START_WALL = _process_start_wall()
_STARTUP = gauge(
    "swarm_startup_seconds",
    "Seconds from the process's start, as the OS has it, to each mark of "
    "a worker's start (worker_started, first_poll, first_pass_start, "
    "first_pass_end); a mark is set once",
    ("mark",),
)
_marked: set[str] = set()
_mark_lock = threading.Lock()


def mark_startup(mark: str) -> None:
    """Stamp `swarm_startup_seconds{mark}` with the seconds since the
    process's start, the first time `mark` is reached and never again."""
    if mark in _marked:
        return
    with _mark_lock:
        if mark not in _marked:
            _marked.add(mark)
            _STARTUP.set(time.time() - PROCESS_START_WALL, mark=mark)


# --- spans -----------------------------------------------------------------

# the name a span carries in the profiler's trace: "swarm/<stage>"
ANNOTATION_PREFIX = "swarm/"

# the collector of the pass the current thread is working on (or any
# thread running under a copy of its context); set by trace_job
_current_trace: contextvars.ContextVar["JobTrace | None"] = (
    contextvars.ContextVar("chiaswarm_job_trace", default=None))

_TraceAnnotation = None  # jax.profiler.TraceAnnotation, once found


def _annotation(stage: str):
    """A `jax.profiler.TraceAnnotation` for the stage, so the span shows
    on its thread's line of a profiler capture beside the device's ops.
    jax is never imported here: a process that has not loaded it (the
    hive) gets no annotation; one that has resolves the class once.
    With no profiler session an annotation costs well under a
    microsecond (PERF.md)."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is None:
            return None
        _TraceAnnotation = profiler.TraceAnnotation
    return _TraceAnnotation(ANNOTATION_PREFIX + stage)


class Span:
    """Times one stage — the one way a stage is stamped. As a context
    manager it takes the wall clock and `perf_counter` on entry and shows
    in the profiler's trace as `swarm/<stage>`; `record()` (which exit
    calls, and which a stage measured elsewhere calls directly) puts the
    seconds in the stage histogram, in `timings[key or f"{stage}_s"]`
    when a timings dict is given (rounded the way the envelope's timings
    are), and `{name, thread, start_wall, seconds}` (unrounded) in the
    current `JobTrace`'s spans — or in `spans`, for a stage stamped
    after the pass's trace has closed. Records on exception too: a
    failed denoise still spent the time.

    `thread` is "slice" on the thread that opened the JobTrace (the
    executor thread holding the chip), else the thread's name, unless
    the caller names it."""

    def __init__(self, stage: str, timings: dict | None = None, *,
                 key: str | None = None, registry: Registry | None = None,
                 thread: str | None = None, spans: list | None = None):
        self.stage = stage
        self.timings = timings
        self.key = key or f"{stage}_s"
        self.registry = registry
        self.thread = thread
        self.spans = spans
        self.start_wall: float | None = None
        self.elapsed: float | None = None

    def __enter__(self) -> "Span":
        self._annotation = _annotation(self.stage)
        if self._annotation is not None:
            self._annotation.__enter__()
        self.start_wall = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        seconds = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        self.record(self.start_wall, seconds)

    def record(self, start_wall: float, seconds: float) -> None:
        self.start_wall, self.elapsed = start_wall, seconds
        (self.registry or REGISTRY).histogram(
            STAGE_METRIC, _STAGE_HELP, ("stage",)
        ).observe(seconds, stage=self.stage)
        if self.timings is not None:
            self.timings[self.key] = round(seconds, 3)
        spans, thread = self.spans, self.thread
        if spans is None:
            trace = _current_trace.get()
            if trace is None:
                return
            spans, thread = trace.spans, thread or trace.thread_label()
        spans.append({
            "name": self.stage,
            "thread": thread or threading.current_thread().name,
            "start_wall": start_wall,
            "seconds": seconds,
        })


class JobTrace:
    """The collector of one pass: a context manager that pins
    `current_job_id` for log correlation and gathers every `Span` that
    ends while it is current — on the thread that opened it, or on one
    running under a copy of its context — into `spans`, which the worker
    copies into the envelopes of the pass (`pipeline_config.spans`)."""

    def __init__(self, job_id: str | None = None):
        self.job_id = job_id
        self.spans: list[dict] = []
        self._thread: int | None = None
        self._tokens = None

    def __enter__(self) -> "JobTrace":
        self._thread = threading.get_ident()
        self._tokens = (
            current_job_id.set(str(self.job_id))
            if self.job_id is not None else None,
            _current_trace.set(self),
        )
        return self

    def __exit__(self, *exc) -> None:
        id_token, trace_token = self._tokens
        _current_trace.reset(trace_token)
        if id_token is not None:
            current_job_id.reset(id_token)
        self._tokens = None

    def thread_label(self) -> str:
        if threading.get_ident() == self._thread:
            return "slice"
        return threading.current_thread().name


def trace_job(job_id: str | None = None) -> JobTrace:
    return JobTrace(job_id)


# --- HTTP exposition -------------------------------------------------------


# profiler captures may not stack and a runaway duration would pin the
# trace machinery for the whole window — bound one capture hard
PROFILE_MAX_SECONDS = 120.0


def build_metrics_app(registry: Registry | None = None, health=None,
                      profile=None, token: str = "", programs=None,
                      memory=None):
    """aiohttp app with GET /metrics (Prometheus text) and GET /healthz
    (JSON from the caller's `health()` snapshot; a payload carrying
    `status` != "ok" answers 503 so probes can act on it). aiohttp is
    imported lazily — the registry itself must stay dependency-free.

    `profile` (optional) is an async callable `(seconds) -> dict` wired
    to POST /debug/profile?seconds=N — the worker passes its on-demand
    jax.profiler capture (writes a perfetto trace under
    $SDAAS_ROOT/profiles/). The callable raising PermissionError maps to
    403 (the Settings.profiler_capture gate), RuntimeError to 409 (a
    capture already running); no callable, no route. Unlike the
    read-only GETs, /debug/profile MUTATES (pins an executor thread,
    writes prompt-exposing traces to disk), so when `token` is set it
    requires the same bearer auth the hive APIs use — a worker whose
    metrics_host is widened off loopback must not expose an anonymous
    write endpoint (empty token = dev mode, matching the hive).

    `programs` / `memory` (optional, ISSUE 17) are sync callables
    returning JSON-ready dicts, wired to GET /debug/programs (the
    compiled-program ledger, programs.snapshot) and GET /debug/memory
    (the fleet byte census, memory_census.census). Read-only like
    /metrics; no callable, no route."""
    from aiohttp import web

    reg = registry or REGISTRY

    async def metrics(_request):
        return web.Response(
            text=reg.render(),
            headers={"Content-Type": "text/plain; version=0.0.4; charset=utf-8"},
        )

    async def healthz(_request):
        payload = {"status": "ok"}
        if health is not None:
            try:
                payload.update(health() or {})
            except Exception as e:  # a broken probe must still answer
                return web.json_response(
                    {"status": "error", "error": f"{type(e).__name__}: {e}"},
                    status=503,
                )
        status = 200 if payload.get("status") == "ok" else 503
        return web.json_response(payload, status=status)

    async def debug_profile(request):
        if token and request.headers.get(
                "Authorization", "") != f"Bearer {token}":
            return web.json_response(
                {"message": "unauthorized"}, status=401)
        try:
            seconds = float(request.query.get("seconds", "2"))
        except ValueError:
            return web.json_response(
                {"message": "seconds must be a number"}, status=400)
        if not 0 < seconds <= PROFILE_MAX_SECONDS:
            return web.json_response(
                {"message": f"seconds must be in (0, "
                            f"{PROFILE_MAX_SECONDS:g}]"}, status=400)
        try:
            detail = await profile(seconds)
        except PermissionError as e:
            return web.json_response({"message": str(e)}, status=403)
        except RuntimeError as e:
            return web.json_response({"message": str(e)}, status=409)
        except Exception as e:  # profiling must never kill the app
            return web.json_response(
                {"message": f"{type(e).__name__}: {e}"}, status=500)
        return web.json_response({"status": "ok", **(detail or {})})

    def debug_snapshot(provider):
        async def handler(_request):
            try:
                payload = provider() or {}
            except Exception as e:  # a broken ledger must not kill the app
                return web.json_response(
                    {"message": f"{type(e).__name__}: {e}"}, status=500)
            return web.json_response(payload)
        return handler

    app = web.Application()
    app.router.add_get("/metrics", metrics)
    app.router.add_get("/healthz", healthz)
    if profile is not None:
        app.router.add_post("/debug/profile", debug_profile)
    if programs is not None:
        app.router.add_get("/debug/programs", debug_snapshot(programs))
    if memory is not None:
        app.router.add_get("/debug/memory", debug_snapshot(memory))
    return app


async def start_metrics_server(port: int, registry: Registry | None = None,
                               health=None, host: str = "127.0.0.1",
                               profile=None, token: str = "",
                               programs=None, memory=None):
    """Bind the telemetry app; returns the AppRunner (caller cleans up) or
    None when port is falsy (CHIASWARM_METRICS_PORT=0 opt-out)."""
    if not port:
        return None
    from aiohttp import web

    runner = web.AppRunner(
        build_metrics_app(registry, health, profile, token,
                          programs=programs, memory=memory))
    await runner.setup()
    await web.TCPSite(runner, host, int(port)).start()
    return runner
