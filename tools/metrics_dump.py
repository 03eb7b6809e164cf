#!/usr/bin/env python
"""Fetch a worker's /metrics and print the per-stage latency table.

Three modes (combinable):

  python tools/metrics_dump.py --url http://127.0.0.1:8061
      Scrape a LIVE worker's telemetry endpoint (Settings.metrics_port /
      CHIASWARM_METRICS_PORT) and print its stage breakdown + health.

  python tools/metrics_dump.py --hive http://127.0.0.1:9511
      Scrape a LIVE hive coordinator and print its dispatch-outcome,
      shed/admission, and lease/result tables plus per-class
      queue-wait / dispatch-to-settle quantiles — the hive half of the
      same picture, renderable next to the worker stage table.

  python tools/metrics_dump.py
      No worker required: run one hermetic tiny-model txt2img smoke job
      IN PROCESS through the real serving path (format_args -> ChipSet ->
      jitted denoise+decode), then print the stage table from the
      process-local registry. Uses the ambient JAX backend (set
      JAX_PLATFORMS=cpu to keep it off an attached chip).

The tables are computed from the histogram/counter series (count / mean /
approx p50 / p90 from the cumulative buckets), so what it prints is
exactly what a Prometheus scrape would see.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import re
import sys
import urllib.request

STAGE_METRIC = "swarm_job_stage_seconds"

_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>.*)\})?\s+(?P<value>[^\s]+)$"
)
_LABEL_RE = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


_ESCAPES = {'"': '"', "n": "\n", "\\": "\\"}


def _unescape(v: str) -> str:
    # single pass: ordered str.replace would corrupt values where a
    # doubled backslash precedes an 'n' (e.g. 'C:\\new')
    return re.sub(r"\\(.)", lambda m: _ESCAPES.get(m.group(1), m.group(0)),
                  v)


def parse_metrics(text: str) -> list[tuple[str, dict, float]]:
    """Prometheus text -> [(metric_name, labels, value)]."""
    samples = []
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if not m:
            continue
        labels = {
            k: _unescape(v) for k, v in _LABEL_RE.findall(m.group("labels") or "")
        }
        raw = m.group("value")
        value = float("inf") if raw == "+Inf" else float(raw)
        samples.append((m.group("name"), labels, value))
    return samples


def _quantile_from_buckets(buckets: list[tuple[float, float]], count: float,
                           q: float) -> float | None:
    """Approximate quantile from cumulative (le, count) pairs — the bucket
    upper bound where the cumulative count first crosses q*count (what
    Prometheus' histogram_quantile reports, minus interpolation)."""
    if count <= 0:
        return None
    target = q * count
    for le, cum in sorted(buckets, key=lambda b: b[0]):
        if cum >= target:
            return le
    return None


def stage_rows(samples: list[tuple[str, dict, float]]) -> list[dict]:
    """Aggregate the stage histogram series into per-stage table rows."""
    by_stage: dict[str, dict] = {}
    for name, labels, value in samples:
        if not name.startswith(STAGE_METRIC):
            continue
        stage = labels.get("stage", "?")
        s = by_stage.setdefault(stage, {"buckets": [], "sum": 0.0, "count": 0.0})
        if name == f"{STAGE_METRIC}_bucket":
            le = labels.get("le", "+Inf")
            s["buckets"].append(
                (float("inf") if le == "+Inf" else float(le), value))
        elif name == f"{STAGE_METRIC}_sum":
            s["sum"] = value
        elif name == f"{STAGE_METRIC}_count":
            s["count"] = value
    rows = []
    for stage, s in sorted(by_stage.items()):
        n = s["count"]
        rows.append({
            "stage": stage,
            "count": int(n),
            "mean_s": (s["sum"] / n) if n else None,
            "p50_le_s": _quantile_from_buckets(s["buckets"], n, 0.5),
            "p90_le_s": _quantile_from_buckets(s["buckets"], n, 0.9),
            "total_s": s["sum"],
        })
    return rows


def _fmt_seconds(v) -> str:
    if v is None:
        return "-"
    if v == float("inf"):
        return "+Inf"
    return f"{v:.3f}"


def render_table(rows: list[dict]) -> str:
    if not rows:
        return "(no job stages recorded yet — has a job run?)"

    fmt = _fmt_seconds
    header = f"{'stage':<14} {'count':>6} {'mean_s':>9} " \
             f"{'p50<=s':>9} {'p90<=s':>9} {'total_s':>9}"
    lines = [header, "-" * len(header)]
    for r in rows:
        lines.append(
            f"{r['stage']:<14} {r['count']:>6} {fmt(r['mean_s']):>9} "
            f"{fmt(r['p50_le_s']):>9} {fmt(r['p90_le_s']):>9} "
            f"{fmt(r['total_s']):>9}"
        )
    return "\n".join(lines)


def fetch(url: str, path: str) -> str:
    with urllib.request.urlopen(f"{url.rstrip('/')}{path}", timeout=10) as r:
        return r.read().decode("utf-8")


# --- hive-side tables (--hive) ---------------------------------------------

HIVE_CLASSES = ("interactive", "default", "batch")


def _label_counts(samples, name: str, label: str) -> dict[str, float]:
    return {labels[label]: value for metric, labels, value in samples
            if metric == name and label in labels}


def _gauge_value(samples, name: str) -> float | None:
    for metric, _labels, value in samples:
        if metric == name:
            return value
    return None


def _class_quantiles(samples, name: str) -> list[dict]:
    """Per-class p50/p95 rows from a {class}-labeled hive histogram."""
    rows = []
    for cls in HIVE_CLASSES:
        buckets, count = [], 0.0
        for metric, labels, value in samples:
            if labels.get("class") != cls:
                continue
            if metric == f"{name}_bucket":
                le = labels.get("le", "+Inf")
                buckets.append(
                    (float("inf") if le == "+Inf" else float(le), value))
            elif metric == f"{name}_count":
                count = value
        if count:
            rows.append({
                "class": cls, "count": int(count),
                "p50_le_s": _quantile_from_buckets(buckets, count, 0.5),
                "p95_le_s": _quantile_from_buckets(buckets, count, 0.95),
            })
    return rows


def _gang_summary(samples) -> dict:
    """Gang-size histogram -> {gangs, jobs, p50, p95} (ISSUE 9)."""
    buckets, count, total = [], 0.0, 0.0
    for metric, labels, value in samples:
        if metric == "swarm_hive_gang_size_bucket":
            le = labels.get("le", "+Inf")
            buckets.append(
                (float("inf") if le == "+Inf" else float(le), value))
        elif metric == "swarm_hive_gang_size_count":
            count = value
        elif metric == "swarm_hive_gang_size_sum":
            total = value
    return {
        "gangs": int(count),
        "jobs": int(total),
        "size_p50": _quantile_from_buckets(buckets, count, 0.5),
        "size_p95": _quantile_from_buckets(buckets, count, 0.95),
    }


def _tenant_summary(samples) -> dict:
    """Per-tenant usage gauges -> {tenant: {chip_seconds, rows,
    petaflops}}, sorted by chip-seconds (the hive already folded
    past-top-K tenants into "other", so cardinality here is bounded by
    construction)."""
    chip = _label_counts(
        samples, "swarm_hive_tenant_chip_seconds_total", "tenant")
    rows = _label_counts(samples, "swarm_hive_tenant_rows_total", "tenant")
    flops = _label_counts(samples, "swarm_hive_tenant_flops_total", "tenant")
    return {
        tenant: {"chip_seconds": chip[tenant],
                 "rows": int(rows.get(tenant, 0)),
                 # cost plane (ISSUE 17): "petaflops served" next to the
                 # chip-seconds it was served in
                 "petaflops": round(flops.get(tenant, 0.0) / 1e15, 6)}
        for tenant in sorted(chip, key=lambda t: (-chip[t], t))
    }


def _slo_summary(samples) -> dict:
    """SLO gauges -> per-class fast/slow burn + worst compliance."""
    compliance = _label_counts(
        samples, "swarm_hive_slo_compliance", "class")
    burns: dict[str, dict[str, float]] = {}
    for metric, labels, value in samples:
        if metric != "swarm_hive_slo_burn_rate":
            continue
        cls, window = labels.get("class"), labels.get("window")
        if cls and window:
            burns.setdefault(cls, {})[window] = value
    return {
        cls: {
            "fast_burn": burns.get(cls, {}).get("fast", 0.0),
            "slow_burn": burns.get(cls, {}).get("slow", 0.0),
            "compliance": compliance.get(cls),
        }
        for cls in sorted(set(burns) | set(compliance))
    }


def dag_summary(samples) -> dict | None:
    """Stage-graph serving summary (ISSUE 20): workflow population by
    aggregate state, the ready depth (stage-jobs admitted but not yet
    settled), per-stage lifecycle outcomes, and per-stage queue-wait
    quantiles (admit -> first dispatch). None when the hive never
    tracked a workflow — classic single-stage fleets render nothing."""
    stages: dict[str, dict[str, int]] = {}
    for metric, labels, value in samples:
        if metric == "swarm_hive_dag_stages_total" \
                and "stage" in labels and "outcome" in labels:
            stages.setdefault(labels["stage"], {})[labels["outcome"]] = \
                int(value)
    workflows = {k: int(v) for k, v in sorted(_label_counts(
        samples, "swarm_hive_dag_workflows", "state").items())}
    ready = _gauge_value(samples, "swarm_hive_dag_ready_depth")
    if not stages and not any(workflows.values()) and ready is None:
        return None
    waits = []
    for stage in sorted(stages):
        buckets, count = [], 0.0
        for metric, labels, value in samples:
            if labels.get("stage") != stage:
                continue
            if metric == "swarm_hive_dag_stage_queue_wait_seconds_bucket":
                le = labels.get("le", "+Inf")
                buckets.append(
                    (float("inf") if le == "+Inf" else float(le), value))
            elif metric == "swarm_hive_dag_stage_queue_wait_seconds_count":
                count = value
        if count:
            waits.append({
                "stage": stage, "count": int(count),
                "p50_le_s": _quantile_from_buckets(buckets, count, 0.5),
                "p95_le_s": _quantile_from_buckets(buckets, count, 0.95),
            })
    return {
        "workflows": workflows,
        "ready_depth": int(ready or 0),
        "stages": {s: dict(sorted(o.items()))
                   for s, o in sorted(stages.items())},
        "stage_queue_wait": waits,
    }


def hive_summary(samples) -> dict:
    """Exposition samples -> the hive-side dispatch/shed/lease view."""
    return {
        # stage-graph serving (ISSUE 20)
        "dag": dag_summary(samples),
        # fleet observability plane (ISSUE 11)
        "tenants": _tenant_summary(samples),
        "slo": _slo_summary(samples),
        "usage_fallback": next(
            (int(v) for m, _, v in samples
             if m == "swarm_hive_usage_fallback_total"), 0),
        "outliers": sorted(
            labels["worker"] for m, labels, v in samples
            if m == "swarm_hive_worker_outlier" and v >= 1
            and "worker" in labels),
        "dispatch": {k: int(v) for k, v in sorted(_label_counts(
            samples, "swarm_hive_dispatch_total", "outcome").items())},
        "gang": _gang_summary(samples),
        "submitted": {k: int(v) for k, v in sorted(_label_counts(
            samples, "swarm_hive_jobs_submitted_total", "class").items())},
        "shed": {k: int(v) for k, v in sorted(_label_counts(
            samples, "swarm_hive_shed_total", "class").items())},
        "queue_depth": {k: int(v) for k, v in sorted(_label_counts(
            samples, "swarm_hive_queue_depth", "class").items())},
        "results": {k: int(v) for k, v in sorted(_label_counts(
            samples, "swarm_hive_results_total", "status").items())},
        "leases_active": next(
            (int(v) for m, _, v in samples
             if m == "swarm_hive_leases_active"), 0),
        "leases_expired": next(
            (int(v) for m, _, v in samples
             if m == "swarm_hive_leases_expired_total"), 0),
        "jobs_failed": next(
            (int(v) for m, _, v in samples
             if m == "swarm_hive_jobs_failed_total"), 0),
        # cancellation & deadlines (ISSUE 10)
        "cancelled": {k: int(v) for k, v in sorted(_label_counts(
            samples, "swarm_hive_cancelled_total", "stage").items())},
        "expired": next(
            (int(v) for m, _, v in samples
             if m == "swarm_hive_expired_total"), 0),
        "cancel_revocations_pending": next(
            (int(v) for m, _, v in samples
             if m == "swarm_hive_cancel_revocations_pending"), 0),
        "queue_wait": _class_quantiles(
            samples, "swarm_hive_queue_wait_seconds"),
        "dispatch_to_settle": _class_quantiles(
            samples, "swarm_hive_dispatch_to_settle_seconds"),
        # preemption tolerance (ISSUE 18): mid-pass checkpoint blobs,
        # progressive-preview artifacts, resume offers on redelivery
        "partials": {
            "checkpoints": {k: int(v) for k, v in sorted(_label_counts(
                samples, "swarm_hive_checkpoints_total",
                "outcome").items())},
            "previews": {k: int(v) for k, v in sorted(_label_counts(
                samples, "swarm_hive_previews_total", "outcome").items())},
            "resume_offers": next(
                (int(v) for m, _, v in samples
                 if m == "swarm_hive_resume_offers_total"), 0),
        },
    }


def render_hive_tables(summary: dict) -> str:
    fmt = _fmt_seconds
    lines = ["hive dispatch outcomes"]
    if summary["dispatch"]:
        for outcome, n in summary["dispatch"].items():
            lines.append(f"  {outcome:<10} {n:>8}")
    else:
        lines.append("  (no dispatches yet)")

    gang = summary.get("gang") or {}
    if gang.get("gangs"):
        # gang rate = jobs that left pre-batched over all DELIVERED jobs
        # ("hold" is a deferral, not a delivery); sizes are job COUNTS,
        # not seconds — integer buckets, +Inf = past the largest bucket
        def fmt_size(v):
            if v is None:
                return "-"
            return ">16" if v == float("inf") else str(int(v))

        delivered = sum(n for o, n in summary["dispatch"].items()
                        if o != "hold") or 1
        lines.append(
            f"hive gangs    count={gang['gangs']} jobs={gang['jobs']} "
            f"rate={min(gang['jobs'] / delivered, 1.0):.2f} "
            f"size p50<={fmt_size(gang['size_p50'])} "
            f"p95<={fmt_size(gang['size_p95'])}")

    lines.append("hive admission by class "
                 "(queued now / admitted / shed 429)")
    classes = sorted(set(summary["submitted"]) | set(summary["shed"])
                     | set(summary["queue_depth"]))
    for cls in classes or ["-"]:
        lines.append(
            f"  {cls:<12} {summary['queue_depth'].get(cls, 0):>6} "
            f"{summary['submitted'].get(cls, 0):>9} "
            f"{summary['shed'].get(cls, 0):>6}")

    lines.append(
        f"hive leases   active={summary['leases_active']} "
        f"expired={summary['leases_expired']} "
        f"failed={summary['jobs_failed']}")
    if (summary.get("cancelled") or summary.get("expired")
            or summary.get("cancel_revocations_pending")):
        cancelled = summary.get("cancelled") or {}
        lines.append(
            "hive cancels  "
            + " ".join(f"{s}={n}" for s, n in cancelled.items())
            + (" " if cancelled else "")
            + f"expired={summary.get('expired', 0)} "
            f"pending_revocations="
            f"{summary.get('cancel_revocations_pending', 0)}")
    if summary["results"]:
        lines.append("hive results  " + " ".join(
            f"{s}={n}" for s, n in summary["results"].items()))
    partials = summary.get("partials") or {}
    if (partials.get("checkpoints") or partials.get("previews")
            or partials.get("resume_offers")):
        bits = []
        if partials.get("checkpoints"):
            bits.append("checkpoints " + " ".join(
                f"{o}={n}" for o, n in partials["checkpoints"].items()))
        if partials.get("previews"):
            bits.append("previews " + " ".join(
                f"{o}={n}" for o, n in partials["previews"].items()))
        bits.append(f"resume_offers={partials.get('resume_offers', 0)}")
        lines.append("hive partials " + "  ".join(bits))

    # stage-graph serving (ISSUE 20): workflow population, ready depth,
    # and per-stage outcomes + queue-wait quantiles — absent entirely on
    # fleets that never submitted a workflow
    dag = summary.get("dag")
    if dag:
        wf = dag["workflows"]
        lines.append(
            "hive dag      "
            + " ".join(f"{s}={wf.get(s, 0)}"
                       for s in ("running", "done", "failed", "cancelled"))
            + f" ready_depth={dag['ready_depth']}")
        if dag["stages"]:
            lines.append("hive dag stages (lifecycle outcomes)")
            for stage, outcomes in dag["stages"].items():
                lines.append(
                    f"  {stage:<12} "
                    + " ".join(f"{o}={n}" for o, n in outcomes.items()))
        if dag["stage_queue_wait"]:
            lines.append("hive dag stage wait (admit -> first dispatch)")
            for r in dag["stage_queue_wait"]:
                lines.append(
                    f"  {r['stage']:<12} n={r['count']:<6} "
                    f"p50<={fmt(r['p50_le_s'])} p95<={fmt(r['p95_le_s'])}")

    for key, title in (("queue_wait", "hive queue wait"),
                       ("dispatch_to_settle", "hive dispatch->settle")):
        rows = summary[key]
        if not rows:
            continue
        lines.append(f"{title} (per class)")
        for r in rows:
            lines.append(
                f"  {r['class']:<12} n={r['count']:<6} "
                f"p50<={fmt(r['p50_le_s'])} p95<={fmt(r['p95_le_s'])}")

    # fleet observability plane (ISSUE 11): who consumed the chips, is
    # each class inside its objective, who is dragging the fleet
    tenants = summary.get("tenants") or {}
    if tenants:
        lines.append("hive tenants  (chip_s / rows / Pflops; past-top-K "
                     "folded into 'other')")
        for tenant, t in tenants.items():
            lines.append(
                f"  {tenant:<16} {t['chip_seconds']:>10.3f} "
                f"{t['rows']:>6} {t.get('petaflops', 0.0):>10.6f}")
        if summary.get("usage_fallback"):
            lines.append(
                f"  (usage fallback settles: {summary['usage_fallback']})")
    slo = summary.get("slo") or {}
    if slo:
        lines.append("hive slo      (burn rate: 1.0 = budget spent "
                     "exactly; fast window pages)")
        for cls, view in slo.items():
            comp = view.get("compliance")
            lines.append(
                f"  {cls:<12} fast={view['fast_burn']:.2f} "
                f"slow={view['slow_burn']:.2f} "
                f"compliance={'-' if comp is None else f'{comp:.2f}'}")
    if summary.get("outliers"):
        lines.append("hive outliers " + " ".join(summary["outliers"]))
    return "\n".join(lines)


def embed_cache_line(samples) -> str | None:
    """Worker-side prompt-embedding cache summary (ISSUE 9), rendered
    under the stage table; None when no lookup ever happened (cache
    disabled, or no encode ran)."""
    events = _label_counts(samples, "swarm_embed_cache_total", "event")
    hits, misses = events.get("hit", 0.0), events.get("miss", 0.0)
    total = hits + misses
    if total <= 0:
        return None
    return (f"embed cache    hit={int(hits)} miss={int(misses)} "
            f"hit_rate={hits / total:.2f}")


def lora_summary(samples) -> dict | None:
    """Adapter-serving summary (ISSUE 13): image rows by execution mode
    (delta = runtime per-row low-rank deltas on the resident base tree,
    merged = full merged-tree fallback, none = adapter-free), plus the
    factor cache's hit rate and residency. None when no SD pass ever
    ran AND no adapter was ever resolved."""
    rows = _label_counts(samples, "swarm_lora_rows_total", "mode")
    events = _label_counts(samples, "swarm_lora_cache_total", "event")
    hits, misses = events.get("hit", 0.0), events.get("miss", 0.0)
    lookups = hits + misses
    operand = _label_counts(
        samples, "swarm_lora_operand_cache_total", "event")
    ohits, omisses = operand.get("hit", 0.0), operand.get("miss", 0.0)
    olookups = ohits + omisses
    if not rows and lookups <= 0 and olookups <= 0:
        return None
    adapter_rows = rows.get("delta", 0.0) + rows.get("merged", 0.0)
    summary = {
        "rows": {k: int(v) for k, v in sorted(rows.items())},
        "adapter_rows": int(adapter_rows),
        "delta_rate": (round(rows.get("delta", 0.0) / adapter_rows, 4)
                       if adapter_rows else None),
        "cache": {
            "hits": int(hits),
            "misses": int(misses),
            "hit_rate": round(hits / lookups, 4) if lookups else None,
            "bytes": int(_gauge_value(
                samples, "swarm_lora_cache_bytes") or 0),
            "entries": int(_gauge_value(
                samples, "swarm_lora_cache_entries") or 0),
        },
    }
    if olookups > 0:
        # stacked-operand residency (ISSUE 16): steady-state repeat
        # gangs should drive hit_rate -> 1.0 with the working set's
        # device footprint held in `bytes`; absent entirely on fleets
        # that never consulted the operand cache
        summary["operand_cache"] = {
            "hits": int(ohits),
            "misses": int(omisses),
            "hit_rate": round(ohits / olookups, 4),
            "bytes": int(_gauge_value(
                samples, "swarm_lora_operand_cache_bytes") or 0),
            "entries": int(_gauge_value(
                samples, "swarm_lora_operand_cache_entries") or 0),
        }
    return summary


def lora_line(samples) -> str | None:
    """Human-readable twin of lora_summary."""
    summary = lora_summary(samples)
    if summary is None:
        return None
    rows = summary["rows"]
    cache = summary["cache"]
    parts = [f"adapters       rows "
             + " ".join(f"{k}={v}" for k, v in rows.items())]
    if cache["hits"] or cache["misses"]:
        parts.append(
            f"cache hit_rate={cache['hit_rate']:.2f} "
            f"entries={cache['entries']} "
            f"bytes={cache['bytes']}")
    operand = summary.get("operand_cache")
    if operand is not None:
        parts.append(
            f"operands hit_rate={operand['hit_rate']:.2f} "
            f"entries={operand['entries']} "
            f"resident_bytes={operand['bytes']}")
    return " ".join(parts)


def geometry_summary(samples) -> dict | None:
    """Per-geometry pass counts (swarm_sharded_passes_total, ISSUE 12):
    how many denoise passes ran replicated (data-parallel coalescing
    view) vs sharded (tensorN/seqN interactive view). None when no pass
    ever ran."""
    passes = _label_counts(samples, "swarm_sharded_passes_total", "geometry")
    if not passes:
        return None
    total = sum(passes.values())
    sharded = sum(v for k, v in passes.items() if k != "replicated")
    return {
        "passes": {k: int(v) for k, v in sorted(passes.items())},
        "total": int(total),
        "sharded": int(sharded),
        "sharded_rate": round(sharded / total, 4) if total else 0.0,
    }


def geometry_line(samples) -> str | None:
    """Human-readable twin of geometry_summary."""
    summary = geometry_summary(samples)
    if summary is None:
        return None
    counts = " ".join(
        f"{k}={v}" for k, v in summary["passes"].items())
    return (f"slice geometry {counts} "
            f"sharded_rate={summary['sharded_rate']:.2f}")


def cost_summary(samples) -> dict | None:
    """Serving-path cost plane (ISSUE 17): analytic UNet FLOPs served
    per model, latest MFU per model/geometry (absent on accelerators
    with no peak-FLOPs table entry — CPU always), the analytic-vs-XLA
    divergence ratio, and live compiled programs per model. None when
    no denoise pass ever stamped a cost."""
    flops = _label_counts(samples, "swarm_pass_flops_total", "model")
    if not flops:
        return None
    mfu = {
        f"{labels['model']}/{labels['geometry']}": round(v, 4)
        for m, labels, v in samples
        if m == "swarm_pass_mfu" and "model" in labels
        and "geometry" in labels
    }
    return {
        "pass_flops": {k: int(v) for k, v in sorted(flops.items())},
        "mfu": dict(sorted(mfu.items())),
        "divergence": {
            k: round(v, 4) for k, v in sorted(_label_counts(
                samples, "swarm_flops_divergence_ratio", "model").items())},
        "programs_live": {k: int(v) for k, v in sorted(_label_counts(
            samples, "swarm_programs_live", "model").items())},
    }


def cost_line(samples) -> str | None:
    """Human-readable twin of cost_summary."""
    summary = cost_summary(samples)
    if summary is None:
        return None
    tflops = " ".join(
        f"{model}={flops / 1e12:.3f}"
        for model, flops in summary["pass_flops"].items())
    parts = [f"cost           tflops {tflops}"]
    if summary["mfu"]:
        parts.append("mfu " + " ".join(
            f"{k}={v:.3f}" for k, v in summary["mfu"].items()))
    if summary["divergence"]:
        parts.append("xla_divergence " + " ".join(
            f"{k}={v:.2f}" for k, v in summary["divergence"].items()))
    live = sum(summary["programs_live"].values())
    if live:
        parts.append(f"programs_live={live}")
    return " ".join(parts)


def resume_summary(samples) -> dict | None:
    """Preemption-tolerance summary (ISSUE 18): mid-pass checkpoints
    shipped at chunk boundaries, preview frames decoded, and redelivered
    passes that resumed from a checkpoint instead of recomputing. None
    when the feature never engaged (checkpoint_every_chunks = 0, or no
    chunked pass ever ran)."""
    ckpts = _label_counts(samples, "swarm_checkpoints_total", "outcome")
    previews = _label_counts(samples, "swarm_previews_total", "outcome")
    resumes = _label_counts(samples, "swarm_resume_total", "outcome")
    if not ckpts and not previews and not resumes:
        return None
    return {
        "checkpoints": {k: int(v) for k, v in sorted(ckpts.items())},
        "previews": {k: int(v) for k, v in sorted(previews.items())},
        "resumes": {k: int(v) for k, v in sorted(resumes.items())},
    }


def resume_line(samples) -> str | None:
    """Human-readable twin of resume_summary."""
    summary = resume_summary(samples)
    if summary is None:
        return None
    parts = []
    for key in ("checkpoints", "previews", "resumes"):
        if summary[key]:
            parts.append(f"{key} " + " ".join(
                f"{o}={n}" for o, n in summary[key].items()))
    return "resume         " + "  ".join(parts)


def polls_summary(samples) -> dict | None:
    """What ended the wait before each hive poll (ISSUE 39): `capacity`
    (the worker became able to take work: a slice came free), `timer`
    (the cadence or a back-off ran out) or `heartbeat` (the timer's
    `cancel_only` poll of a busy worker). None before the first poll."""
    polls = _label_counts(samples, "swarm_polls_total", "cause")
    if not polls:
        return None
    return {k: int(v) for k, v in sorted(polls.items())}


def polls_line(samples) -> str | None:
    """Human-readable twin of polls_summary."""
    summary = polls_summary(samples)
    if summary is None:
        return None
    return "polls          " + " ".join(
        f"{cause}={n}" for cause, n in summary.items())


async def _run_smoke_job() -> None:
    """One tiny-model txt2img job through the REAL worker path (the same
    code a hive job takes minus the HTTP hop), populating the stage spans."""
    from chiaswarm_tpu.chips.allocator import SliceAllocator
    from chiaswarm_tpu.job_arguments import format_args
    from chiaswarm_tpu.settings import load_settings

    job = {
        "id": "metrics-dump-smoke",
        "workflow": "txt2img",
        "model_name": "stabilityai/stable-diffusion-2-1",
        "prompt": "a red cube on a table",
        "height": 64,
        "width": 64,
        "num_inference_steps": 2,
        "parameters": {"test_tiny_model": True},
    }
    settings = load_settings()
    allocator = SliceAllocator(chips_per_job=0)
    chipset = await allocator.acquire()
    try:
        func, kwargs = await format_args(job, settings, chipset.identifier())
        kwargs.pop("id", None)
        chipset(func, **kwargs)
    finally:
        allocator.release(chipset)


def run_inprocess() -> str:
    """Run the smoke job and return the process-local registry rendering."""
    from chiaswarm_tpu.telemetry import REGISTRY

    asyncio.run(_run_smoke_job())
    return REGISTRY.render()


def _jsonable(value):
    """JSON-safe twin of a summary structure: bucket bounds and
    quantiles can be float('inf'), which json.dumps would emit as the
    non-standard `Infinity` literal — render them as the exposition
    format's own "+Inf" spelling instead."""
    if isinstance(value, float) and value == float("inf"):
        return "+Inf"
    if isinstance(value, dict):
        return {k: _jsonable(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [_jsonable(v) for v in value]
    return value


def embed_cache_summary(samples) -> dict | None:
    """The machine-readable twin of embed_cache_line."""
    events = _label_counts(samples, "swarm_embed_cache_total", "event")
    hits, misses = events.get("hit", 0.0), events.get("miss", 0.0)
    total = hits + misses
    if total <= 0:
        return None
    return {"hits": int(hits), "misses": int(misses),
            "hit_rate": round(hits / total, 4)}


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="metrics_dump", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument(
        "--url", default=None,
        help="live worker telemetry base URL (e.g. http://127.0.0.1:8061); "
             "omit to run one in-process smoke job instead")
    parser.add_argument(
        "--hive", default=None,
        help="live hive base URL (e.g. http://127.0.0.1:9511): also print "
             "the hive-side dispatch/shed/lease tables")
    parser.add_argument(
        "--raw", action="store_true",
        help="also dump the raw /metrics exposition text")
    parser.add_argument(
        "--json", action="store_true",
        help="emit ONE machine-readable JSON object — the twin of every "
             "table this run would render — instead of the tables, so CI "
             "and bench tooling consume structured data, not screen text")
    args = parser.parse_args(argv)
    payload: dict = {}

    if args.hive:
        hive_text = fetch(args.hive, "/metrics")
        if args.raw and not args.json:
            print(hive_text)
        summary = hive_summary(parse_metrics(hive_text))
        payload["hive"] = summary
        if not args.json:
            print(render_hive_tables(summary))
            print()
        if not args.url:
            # hive-only mode: no worker scrape, no in-process smoke job
            if args.json:
                print(json.dumps(_jsonable(payload)))
            return 0

    health = None
    if args.url:
        text = fetch(args.url, "/metrics")
        try:
            health = json.loads(fetch(args.url, "/healthz"))
            if not args.json:
                print(f"healthz: {json.dumps(health, indent=1)}")
        except Exception as e:  # the table is still worth printing
            if not args.json:
                print(f"healthz unavailable: {e}")
    else:
        if not args.json:
            print("no --url given: running one in-process tiny smoke job "
                  "(this compiles a tiny pipeline; ~a minute on CPU)")
        text = run_inprocess()

    if args.raw and not args.json:
        print(text)
    samples = parse_metrics(text)
    rows = stage_rows(samples)
    payload["worker"] = {
        "stages": rows,
        "embed_cache": embed_cache_summary(samples),
        "lora": lora_summary(samples),
        "geometry": geometry_summary(samples),
        "cost": cost_summary(samples),
        "resume": resume_summary(samples),
        "polls": polls_summary(samples),
        "healthz": health,
    }
    if args.json:
        print(json.dumps(_jsonable(payload)))
    else:
        print(render_table(rows))
        embed = embed_cache_line(samples)
        if embed:
            print(embed)
        adapters = lora_line(samples)
        if adapters:
            print(adapters)
        geometry = geometry_line(samples)
        if geometry:
            print(geometry)
        cost = cost_line(samples)
        if cost:
            print(cost)
        resume = resume_line(samples)
        if resume:
            print(resume)
        polls = polls_line(samples)
        if polls:
            print(polls)
    return 0 if rows else 1


if __name__ == "__main__":
    sys.exit(main())
