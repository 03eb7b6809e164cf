"""Compiled-program ledger: every jit site, accounted.

ISSUE 17. The pipeline compiles programs at two kinds of site — the
constructor's aux programs (text encode, TE-delta encode, VAE encode,
latent 2x) and the per-bucket denoise variants flowing through
``SDPipeline._program`` (fused, prep, chunk, decode; geometry and
adapter-signature suffixed) — and until now the only visibility into
that population was a hit/miss counter. This module wraps each jitted
program in a thin instrumented callable that:

- times the FIRST call (trace + XLA compile + execute — the compile
  cost an operator actually pays at that site), and adds the lower +
  compile part of it to ``compile_seconds()``, the calling thread's
  running total, which lets a pipeline take compile time out of the
  span its first call happened to land in;
- captures XLA's own ``cost_analysis()`` (flops, bytes accessed) from
  the lowered module and ``memory_analysis()`` (argument / output /
  temp / generated-code bytes) from the compiled executable, both
  best-effort — an analysis API missing on some backend records an
  error string, never breaks serving;
- cross-checks the analytic FLOP denominator (models/flops.py) against
  XLA's count when the call site supplies its analytic figure, feeding
  ``swarm_flops_divergence_ratio{model}`` via costs.note_divergence;
- tracks the eviction lifecycle: ``_trim_program_caches`` calls
  ``clear_cache()`` on LRU-evicted programs, which marks the entry
  evicted here (the ledger keeps a bounded tail of evicted entries so
  /debug/programs shows churn, not just survivors).

Served at worker ``GET /debug/programs`` via ``snapshot()``.

Import-time jax-free: the ledger wraps callables it is handed and only
ever touches jax objects the pipeline already created.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict

from . import compile_cache, costs, telemetry

# entries kept, live + evicted (popitem LRU below): big enough that a
# program_cache_max=64 pipeline's full churn history fits, small enough
# that a pathological retrace storm cannot grow the ledger unboundedly
MAX_ENTRIES = 512

_LIVE = telemetry.gauge(
    "swarm_programs_live",
    "Compiled XLA programs currently registered live in the program "
    "ledger (constructor aux programs + denoise variants), per model",
    ("model",),
)


class ProgramEntry:
    """One jit site's ledger row (mutable; snapshot() serialises it)."""

    __slots__ = ("model", "kind", "key", "state", "calls", "compile_s",
                 "analytic_flops", "xla", "memory", "divergence", "error",
                 "registered_at")

    def __init__(self, model: str, kind: str, key):
        self.model = model
        self.kind = kind
        self.key = repr(key) if key is not None else ""
        self.state = "registered"  # -> live (first call) -> evicted
        self.calls = 0
        # first call: trace + compile + execute. What parts it is the
        # table by function (`staging` in snapshot()): the site's row has
        # its `trace_s`, `lower_s` and `compile_s` (of it `cache_read_s`)
        self.compile_s = None
        self.analytic_flops = None
        self.xla = None  # {"flops", "bytes_accessed"} from cost_analysis
        self.memory = None  # byte breakdown from memory_analysis
        self.divergence = None  # xla_flops / analytic_flops
        self.error = None
        self.registered_at = time.time()

    def as_dict(self) -> dict:
        return {
            "model": self.model,
            "kind": self.kind,
            "key": self.key,
            "state": self.state,
            "calls": self.calls,
            "compile_s": (None if self.compile_s is None
                          else round(self.compile_s, 3)),
            "analytic_flops": self.analytic_flops,
            "xla": self.xla,
            "memory": self.memory,
            "divergence": (None if self.divergence is None
                           else round(self.divergence, 4)),
            "error": self.error,
        }


_LOCK = threading.Lock()
_LEDGER: OrderedDict[int, ProgramEntry] = OrderedDict()
_next_id = 0
_COMPILING = threading.local()


def compile_seconds() -> float:
    """Seconds the calling thread has spent lowering + compiling
    instrumented programs so far. jit compiles lazily, on a program's
    first call, so a caller that timed a span takes the difference around
    it to tell compile from execution."""
    return getattr(_COMPILING, "seconds", 0.0)


def _flops_of(analysis) -> float | None:
    """The 'flops' figure from a cost_analysis() result, which jax
    returns as a dict (Lowered) or a 1-element list of dicts
    (Compiled) depending on version and stage."""
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else None
    if isinstance(analysis, dict):
        v = analysis.get("flops")
        if isinstance(v, (int, float)) and v >= 0:
            return float(v)
    return None


def _capture(entry: ProgramEntry, fn, args, kwargs):
    """Best-effort XLA analysis of the program, using the exact
    arguments its first call traced with. Returns the AOT-compiled
    executable when one was produced — the wrapper executes through it,
    so the analysed compile IS the serving compile (the jit path would
    not share it and the site would pay XLA twice). Everything is
    guarded: the ledger corroborates, it must never fail a pass."""
    try:
        lowered = fn.lower(*args, **kwargs)
    except Exception as e:  # non-loweable wrapper, backend quirk, ...
        entry.error = f"lower: {type(e).__name__}: {e}"
        return None
    try:
        analysis = lowered.cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0] if analysis else {}
        flops = _flops_of(analysis)
        entry.xla = {
            "flops": flops,
            "bytes_accessed": analysis.get("bytes accessed")
            if isinstance(analysis, dict) else None,
        }
    except Exception as e:
        entry.error = f"cost_analysis: {type(e).__name__}: {e}"
    compiled = None
    try:
        compiled = lowered.compile()
        stats = compiled.memory_analysis()
        if stats is not None:
            arg_b = int(getattr(stats, "argument_size_in_bytes", 0) or 0)
            out_b = int(getattr(stats, "output_size_in_bytes", 0) or 0)
            tmp_b = int(getattr(stats, "temp_size_in_bytes", 0) or 0)
            code_b = int(getattr(
                stats, "generated_code_size_in_bytes", 0) or 0)
            entry.memory = {
                "argument_bytes": arg_b,
                "output_bytes": out_b,
                "temp_bytes": tmp_b,
                "generated_code_bytes": code_b,
                # what the executable pins at once: arguments + outputs
                # + scratch (an upper bound; XLA may alias)
                "peak_bytes": arg_b + out_b + tmp_b,
            }
    except Exception as e:
        entry.error = f"memory_analysis: {type(e).__name__}: {e}"
    xla_flops = entry.xla.get("flops") if entry.xla else None
    if entry.analytic_flops and xla_flops:
        entry.divergence = costs.note_divergence(
            entry.model, entry.analytic_flops, xla_flops)
    return compiled


class InstrumentedProgram:
    """Thin callable wrapper around one jitted program. The first call
    lowers, analyses and AOT-compiles, then executes through that same
    executable — one XLA compile total, exactly like the bare jit path
    (the jit cache and the AOT path do NOT share executables, so
    analyse-then-call-jit would compile everything twice). Argument
    signatures the AOT executable rejects (the jit path is laxer) fall
    back to the jitted callable, permanently for that site. Exposes
    ``clear_cache`` so the pipeline's LRU eviction (and its executable
    freeing) passes straight through — marking the ledger entry evicted
    and dropping the held executable on the way."""

    __slots__ = ("_fn", "_entry", "_compiled")

    def __init__(self, fn, entry: ProgramEntry):
        self._fn = fn
        self._entry = entry
        self._compiled = None

    def __call__(self, *args, **kwargs):
        entry = self._entry
        if entry.calls == 0:
            t0 = time.perf_counter()
            compiled = _capture(entry, self._fn, args, kwargs)
            _COMPILING.seconds = (
                compile_seconds() + time.perf_counter() - t0)
            out = _SENTINEL = object()
            if compiled is not None:
                try:
                    out = compiled(*args, **kwargs)
                    self._compiled = compiled
                except (TypeError, ValueError):
                    pass  # AOT signature stricter than jit: use jit path
            if out is _SENTINEL:
                out = self._fn(*args, **kwargs)
            entry.compile_s = time.perf_counter() - t0
            entry.calls += 1
            entry.state = "live"
            return out
        entry.calls += 1
        compiled = self._compiled
        if compiled is not None:
            try:
                return compiled(*args, **kwargs)
            except (TypeError, ValueError):
                self._compiled = None  # arg drift: hand back to jit cache
        return self._fn(*args, **kwargs)

    def clear_cache(self) -> None:
        entry = self._entry
        self._compiled = None
        if entry.state != "evicted":
            entry.state = "evicted"
            _refresh_live()
        clear = getattr(self._fn, "clear_cache", None)
        if callable(clear):
            clear()

    def lower(self, *args, **kwargs):
        return self._fn.lower(*args, **kwargs)

    def __getattr__(self, name):
        # a drop-in must expose whatever else the jitted callable does
        # (trace inspection, test recorders, future jax surface)
        return getattr(self._fn, name)


def _refresh_live() -> None:
    """Re-export the per-model live gauge (caller need not hold _LOCK —
    a slightly stale count beats a deadlock)."""
    counts: dict[str, int] = {}
    with _LOCK:
        for entry in _LEDGER.values():
            if entry.state != "evicted":
                counts[entry.model] = counts.get(entry.model, 0) + 1
        models = {e.model for e in _LEDGER.values()}
    for model in models:
        _LIVE.set(counts.get(model, 0), model=model)


def instrument(fn, *, model: str, kind: str, key=None,
               analytic_flops: float | None = None):
    """Register one jit site and return its instrumented wrapper (a
    drop-in for the jitted callable). ``analytic_flops`` — supplied by
    call sites that know their program's analytic UNet FLOP count —
    arms the divergence cross-check."""
    global _next_id
    entry = ProgramEntry(model, kind, key)
    if analytic_flops and analytic_flops > 0:
        entry.analytic_flops = float(analytic_flops)
    with _LOCK:
        _LEDGER[_next_id] = entry
        _next_id += 1
        while len(_LEDGER) > MAX_ENTRIES:
            _LEDGER.popitem(last=False)
    _refresh_live()
    return InstrumentedProgram(fn, entry)


def snapshot() -> dict:
    """The GET /debug/programs payload: every ledger entry (live ones
    first, registration order within each state) plus roll-up counts,
    the per-model worst divergence and `staging`, jax's own account of
    every function it staged, ledger site or not
    (`compile_cache.staging`)."""
    with _LOCK:
        entries = [e.as_dict() for e in _LEDGER.values()]
    entries.sort(key=lambda e: (e["state"] == "evicted",))
    live = sum(1 for e in entries if e["state"] != "evicted")
    divergence: dict[str, float] = {}
    for e in entries:
        d = e.get("divergence")
        if d is None:
            continue
        model = e["model"]
        prior = divergence.get(model)
        if prior is None or abs(d - 1.0) > abs(prior - 1.0):
            divergence[model] = d
    return {
        "programs": entries,
        "live": live,
        "evicted": len(entries) - live,
        "divergence": divergence,
        "staging": compile_cache.staging(),
    }


def resident_code_bytes() -> dict:
    """Memory-census provider: generated-code bytes of live programs
    (XLA's own figure where the backend reports one — 0 on CPU) plus
    the live-entry count, so /debug/memory totals the program LRUs next
    to the data caches."""
    with _LOCK:
        live = [e for e in _LEDGER.values() if e.state != "evicted"]
    code = sum((e.memory or {}).get("generated_code_bytes", 0) or 0
               for e in live)
    return {"bytes": int(code), "entries": len(live)}


def reset() -> None:
    """Drop every ledger entry (tests)."""
    with _LOCK:
        _LEDGER.clear()
    _refresh_live()
