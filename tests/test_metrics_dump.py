"""tools/metrics_dump.py contract tests: the exposition parser and stage
table on synthetic input, and the REAL in-process smoke-job mode — so the
operator tool can't rot between TPU windows."""

import importlib.util
import pathlib
import sys

_TOOL = pathlib.Path(__file__).resolve().parent.parent / "tools" / "metrics_dump.py"


def _load_tool():
    spec = importlib.util.spec_from_file_location("metrics_dump", _TOOL)
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault("metrics_dump", mod)
    spec.loader.exec_module(mod)
    return mod


SYNTHETIC = """\
# HELP swarm_job_stage_seconds Per-job wall-clock seconds by lifecycle stage
# TYPE swarm_job_stage_seconds histogram
swarm_job_stage_seconds_bucket{stage="denoise",le="1"} 1
swarm_job_stage_seconds_bucket{stage="denoise",le="5"} 3
swarm_job_stage_seconds_bucket{stage="denoise",le="+Inf"} 4
swarm_job_stage_seconds_sum{stage="denoise"} 14.5
swarm_job_stage_seconds_count{stage="denoise"} 4
swarm_job_stage_seconds_bucket{stage="submit",le="1"} 2
swarm_job_stage_seconds_bucket{stage="submit",le="+Inf"} 2
swarm_job_stage_seconds_sum{stage="submit"} 0.2
swarm_job_stage_seconds_count{stage="submit"} 2
# TYPE swarm_jobs_completed_total counter
swarm_jobs_completed_total{outcome="ok"} 4
"""


def test_parse_and_stage_table_from_synthetic_text():
    tool = _load_tool()
    samples = tool.parse_metrics(SYNTHETIC)
    assert ("swarm_jobs_completed_total", {"outcome": "ok"}, 4.0) in samples

    rows = tool.stage_rows(samples)
    by_stage = {r["stage"]: r for r in rows}
    assert set(by_stage) == {"denoise", "submit"}
    d = by_stage["denoise"]
    assert d["count"] == 4
    assert d["mean_s"] == 14.5 / 4
    assert d["p50_le_s"] == 5.0  # cumulative 3/4 crossed at le=5
    assert d["p90_le_s"] == float("inf")
    assert by_stage["submit"]["p50_le_s"] == 1.0

    table = tool.render_table(rows)
    assert "denoise" in table and "submit" in table
    assert "+Inf" in table

    # empty input degrades to a message, not a crash
    assert "no job stages" in tool.render_table(tool.stage_rows([]))


def test_inprocess_smoke_job_prints_stage_table(sdaas_root, capsys):
    """The tool's no-hive mode runs one tiny txt2img job through the real
    serving path and prints a table covering the pipeline stages."""
    tool = _load_tool()
    rc = tool.main([])
    out = capsys.readouterr().out
    assert rc == 0
    for stage in ("compile", "denoise", "decode", "text_encode"):
        assert stage in out, out
    # the smoke encode went through the embedding cache (default-on)
    assert "embed cache" in out and "hit_rate=" in out, out


def test_embed_cache_line_from_synthetic_text():
    tool = _load_tool()
    samples = tool.parse_metrics(
        'swarm_embed_cache_total{event="hit"} 6\n'
        'swarm_embed_cache_total{event="miss"} 2\n')
    assert tool.embed_cache_line(samples) == \
        "embed cache    hit=6 miss=2 hit_rate=0.75"
    assert tool.embed_cache_line([]) is None


def test_lora_line_from_synthetic_text():
    """ISSUE 13: the adapter-serving line (rows by execution mode +
    factor-cache hit rate/residency) and its machine-readable twin."""
    tool = _load_tool()
    samples = tool.parse_metrics(
        'swarm_lora_rows_total{mode="delta"} 6\n'
        'swarm_lora_rows_total{mode="merged"} 2\n'
        'swarm_lora_rows_total{mode="none"} 8\n'
        'swarm_lora_cache_total{event="hit"} 3\n'
        'swarm_lora_cache_total{event="miss"} 1\n'
        'swarm_lora_cache_bytes 2048\n'
        'swarm_lora_cache_entries 2\n')
    assert tool.lora_line(samples) == (
        "adapters       rows delta=6 merged=2 none=8 "
        "cache hit_rate=0.75 entries=2 bytes=2048")
    summary = tool.lora_summary(samples)
    assert summary == {
        "rows": {"delta": 6, "merged": 2, "none": 8},
        "adapter_rows": 8,
        "delta_rate": 0.75,
        "cache": {"hits": 3, "misses": 1, "hit_rate": 0.75,
                  "bytes": 2048, "entries": 2},
    }
    # adapter-free fleets render nothing rather than a zero line
    assert tool.lora_line([]) is None
    assert tool.lora_summary([]) is None


def test_lora_operand_residency_line_from_synthetic_text():
    """ISSUE 16: once the device-resident operand cache sees lookups,
    the adapters line and summary grow a stacked-operand section (hit
    rate + resident footprint); fleets that never consulted it keep the
    ISSUE 13 shape (pinned above) with no operand_cache key at all."""
    tool = _load_tool()
    samples = tool.parse_metrics(
        'swarm_lora_rows_total{mode="delta"} 6\n'
        'swarm_lora_cache_total{event="hit"} 3\n'
        'swarm_lora_cache_total{event="miss"} 1\n'
        'swarm_lora_cache_bytes 2048\n'
        'swarm_lora_cache_entries 2\n'
        'swarm_lora_operand_cache_total{event="hit"} 9\n'
        'swarm_lora_operand_cache_total{event="miss"} 1\n'
        'swarm_lora_operand_cache_bytes 4096\n'
        'swarm_lora_operand_cache_entries 3\n')
    assert tool.lora_line(samples) == (
        "adapters       rows delta=6 "
        "cache hit_rate=0.75 entries=2 bytes=2048 "
        "operands hit_rate=0.90 entries=3 resident_bytes=4096")
    summary = tool.lora_summary(samples)
    assert summary["operand_cache"] == {
        "hits": 9, "misses": 1, "hit_rate": 0.9,
        "bytes": 4096, "entries": 3}


def test_geometry_line_from_synthetic_text():
    """ISSUE 12: the per-geometry pass distribution renders under the
    stage table (and its machine-readable twin carries the sharded
    rate)."""
    tool = _load_tool()
    samples = tool.parse_metrics(
        'swarm_sharded_passes_total{geometry="replicated"} 6\n'
        'swarm_sharded_passes_total{geometry="tensor2"} 2\n')
    assert tool.geometry_line(samples) == \
        "slice geometry replicated=6 tensor2=2 sharded_rate=0.25"
    summary = tool.geometry_summary(samples)
    assert summary == {"passes": {"replicated": 6, "tensor2": 2},
                       "total": 8, "sharded": 2, "sharded_rate": 0.25}
    assert tool.geometry_line([]) is None
    assert tool.geometry_summary([]) is None


def test_cost_line_from_synthetic_text():
    """ISSUE 17: the serving-path cost line — analytic TFLOPs served per
    model, MFU per model/geometry, XLA divergence, live program count —
    and its machine-readable twin; MFU and divergence sections vanish on
    fleets (CPU) that never produced them."""
    tool = _load_tool()
    samples = tool.parse_metrics(
        'swarm_pass_flops_total{model="sdxl"} 4.2e+12\n'
        'swarm_pass_mfu{model="sdxl",geometry="replicated"} 0.4321\n'
        'swarm_pass_mfu{model="sdxl",geometry="tensor2"} 0.3111\n'
        'swarm_flops_divergence_ratio{model="sdxl"} 1.02\n'
        'swarm_programs_live{model="sdxl"} 5\n')
    assert tool.cost_line(samples) == (
        "cost           tflops sdxl=4.200 "
        "mfu sdxl/replicated=0.432 sdxl/tensor2=0.311 "
        "xla_divergence sdxl=1.02 programs_live=5")
    summary = tool.cost_summary(samples)
    assert summary == {
        "pass_flops": {"sdxl": 4_200_000_000_000},
        "mfu": {"sdxl/replicated": 0.4321, "sdxl/tensor2": 0.3111},
        "divergence": {"sdxl": 1.02},
        "programs_live": {"sdxl": 5},
    }
    # a CPU fleet has flops but no MFU/divergence — partial line, no "-"
    cpu = tool.parse_metrics(
        'swarm_pass_flops_total{model="sd21"} 1e+09\n')
    assert tool.cost_line(cpu) == "cost           tflops sd21=0.001"
    assert tool.cost_summary(cpu)["mfu"] == {}
    # a fleet that never stamped a pass renders nothing at all
    assert tool.cost_line([]) is None
    assert tool.cost_summary([]) is None


def test_resume_line_from_synthetic_text():
    """ISSUE 18: the worker-side preemption line — checkpoints shipped
    at chunk boundaries (plus skips/failures), preview frames decoded,
    and redelivered passes resumed from a checkpoint — with its
    machine-readable twin; fleets that never engaged the feature render
    nothing at all."""
    tool = _load_tool()
    samples = tool.parse_metrics(
        'swarm_checkpoints_total{outcome="shipped"} 5\n'
        'swarm_checkpoints_total{outcome="oversize"} 1\n'
        'swarm_previews_total{outcome="shipped"} 3\n'
        'swarm_resume_total{outcome="resumed"} 2\n'
        'swarm_resume_total{outcome="fetch_failed"} 1\n')
    assert tool.resume_line(samples) == (
        "resume         checkpoints oversize=1 shipped=5  "
        "previews shipped=3  resumes fetch_failed=1 resumed=2")
    assert tool.resume_summary(samples) == {
        "checkpoints": {"oversize": 1, "shipped": 5},
        "previews": {"shipped": 3},
        "resumes": {"fetch_failed": 1, "resumed": 2},
    }
    assert tool.resume_line([]) is None
    assert tool.resume_summary([]) is None


def test_polls_line_from_synthetic_text():
    """ISSUE 39: what ended the wait before each hive poll, under the
    stage table and in the machine-readable twin; a worker that has not
    polled yet renders nothing."""
    tool = _load_tool()
    samples = tool.parse_metrics(
        'swarm_polls_total{cause="timer"} 7\n'
        'swarm_polls_total{cause="capacity"} 23\n'
        'swarm_polls_total{cause="heartbeat"} 41\n')
    assert tool.polls_line(samples) == (
        "polls          capacity=23 heartbeat=41 timer=7")
    assert tool.polls_summary(samples) == {
        "capacity": 23, "heartbeat": 41, "timer": 7}
    assert tool.polls_line([]) is None
    assert tool.polls_summary([]) is None


HIVE_SYNTHETIC = """\
# TYPE swarm_hive_dispatch_total counter
swarm_hive_dispatch_total{outcome="affinity"} 6
swarm_hive_dispatch_total{outcome="cold"} 2
swarm_hive_dispatch_total{outcome="hold"} 1
swarm_hive_dispatch_total{outcome="gang"} 4
# TYPE swarm_hive_gang_size histogram
swarm_hive_gang_size_bucket{le="2"} 1
swarm_hive_gang_size_bucket{le="4"} 2
swarm_hive_gang_size_bucket{le="+Inf"} 2
swarm_hive_gang_size_sum 6
swarm_hive_gang_size_count 2
# TYPE swarm_hive_jobs_submitted_total counter
swarm_hive_jobs_submitted_total{class="default"} 7
swarm_hive_jobs_submitted_total{class="batch"} 3
# TYPE swarm_hive_shed_total counter
swarm_hive_shed_total{class="batch"} 2
# TYPE swarm_hive_cancelled_total counter
swarm_hive_cancelled_total{stage="queued"} 3
swarm_hive_cancelled_total{stage="leased"} 2
# TYPE swarm_hive_expired_total counter
swarm_hive_expired_total 4
# TYPE swarm_hive_cancel_revocations_pending gauge
swarm_hive_cancel_revocations_pending 2
# TYPE swarm_hive_queue_depth gauge
swarm_hive_queue_depth{class="default"} 1
swarm_hive_queue_depth{class="batch"} 0
swarm_hive_queue_depth{class="interactive"} 0
# TYPE swarm_hive_leases_active gauge
swarm_hive_leases_active 2
# TYPE swarm_hive_leases_expired_total counter
swarm_hive_leases_expired_total 1
# TYPE swarm_hive_results_total counter
swarm_hive_results_total{status="ok"} 5
swarm_hive_results_total{status="duplicate"} 1
# TYPE swarm_hive_queue_wait_seconds histogram
swarm_hive_queue_wait_seconds_bucket{class="default",le="0.1"} 3
swarm_hive_queue_wait_seconds_bucket{class="default",le="1"} 6
swarm_hive_queue_wait_seconds_bucket{class="default",le="+Inf"} 6
swarm_hive_queue_wait_seconds_sum{class="default"} 2.0
swarm_hive_queue_wait_seconds_count{class="default"} 6
# TYPE swarm_hive_dispatch_to_settle_seconds histogram
swarm_hive_dispatch_to_settle_seconds_bucket{class="default",le="5"} 5
swarm_hive_dispatch_to_settle_seconds_bucket{class="default",le="+Inf"} 5
swarm_hive_dispatch_to_settle_seconds_sum{class="default"} 9.0
swarm_hive_dispatch_to_settle_seconds_count{class="default"} 5
# TYPE swarm_hive_tenant_chip_seconds_total gauge
swarm_hive_tenant_chip_seconds_total{tenant="acme"} 42.5
swarm_hive_tenant_chip_seconds_total{tenant="other"} 1.5
# TYPE swarm_hive_tenant_rows_total gauge
swarm_hive_tenant_rows_total{tenant="acme"} 19
swarm_hive_tenant_rows_total{tenant="other"} 1
# TYPE swarm_hive_tenant_flops_total gauge
swarm_hive_tenant_flops_total{tenant="acme"} 2e+15
# TYPE swarm_hive_usage_fallback_total counter
swarm_hive_usage_fallback_total 2
# TYPE swarm_hive_slo_burn_rate gauge
swarm_hive_slo_burn_rate{class="interactive",window="fast"} 2.4
swarm_hive_slo_burn_rate{class="interactive",window="slow"} 0.3
# TYPE swarm_hive_slo_compliance gauge
swarm_hive_slo_compliance{class="interactive"} 0.88
# TYPE swarm_hive_worker_outlier gauge
swarm_hive_worker_outlier{worker="w-slow"} 1
swarm_hive_worker_outlier{worker="w-fast"} 0
# TYPE swarm_hive_checkpoints_total counter
swarm_hive_checkpoints_total{outcome="stored"} 4
swarm_hive_checkpoints_total{outcome="superseded"} 3
# TYPE swarm_hive_previews_total counter
swarm_hive_previews_total{outcome="stored"} 2
# TYPE swarm_hive_resume_offers_total counter
swarm_hive_resume_offers_total 1
# TYPE swarm_hive_dag_stages_total counter
swarm_hive_dag_stages_total{stage="denoise",outcome="admitted"} 4
swarm_hive_dag_stages_total{stage="denoise",outcome="done"} 3
swarm_hive_dag_stages_total{stage="encode",outcome="done"} 4
swarm_hive_dag_stages_total{stage="decode",outcome="cancelled"} 1
# TYPE swarm_hive_dag_ready_depth gauge
swarm_hive_dag_ready_depth 2
# TYPE swarm_hive_dag_workflows gauge
swarm_hive_dag_workflows{state="running"} 1
swarm_hive_dag_workflows{state="done"} 3
swarm_hive_dag_workflows{state="cancelled"} 1
# TYPE swarm_hive_dag_stage_queue_wait_seconds histogram
swarm_hive_dag_stage_queue_wait_seconds_bucket{stage="denoise",le="0.1"} 1
swarm_hive_dag_stage_queue_wait_seconds_bucket{stage="denoise",le="1"} 3
swarm_hive_dag_stage_queue_wait_seconds_bucket{stage="denoise",le="+Inf"} 3
swarm_hive_dag_stage_queue_wait_seconds_sum{stage="denoise"} 1.2
swarm_hive_dag_stage_queue_wait_seconds_count{stage="denoise"} 3
"""


def test_hive_tables_from_synthetic_text():
    """--hive satellite (ISSUE 8): the hive-side dispatch/shed/lease
    tables render from exposition text alone — the same shape a live
    scrape produces."""
    tool = _load_tool()
    summary = tool.hive_summary(tool.parse_metrics(HIVE_SYNTHETIC))
    assert summary["dispatch"] == {"affinity": 6, "cold": 2, "gang": 4,
                                   "hold": 1}
    # gang-scheduled dispatch (ISSUE 9): 2 gangs totalling 6 jobs
    assert summary["gang"] == {"gangs": 2, "jobs": 6,
                               "size_p50": 2.0, "size_p95": 4.0}
    assert summary["submitted"] == {"batch": 3, "default": 7}
    assert summary["shed"] == {"batch": 2}
    assert summary["leases_active"] == 2
    assert summary["leases_expired"] == 1
    assert summary["results"] == {"duplicate": 1, "ok": 5}
    # cancellation & deadlines (ISSUE 10)
    assert summary["cancelled"] == {"leased": 2, "queued": 3}
    assert summary["expired"] == 4
    assert summary["cancel_revocations_pending"] == 2
    [qw] = summary["queue_wait"]
    assert qw["class"] == "default" and qw["count"] == 6
    assert qw["p50_le_s"] == 0.1  # cumulative 3/6 crosses at le=0.1
    [d2s] = summary["dispatch_to_settle"]
    assert d2s["p50_le_s"] == 5.0

    # fleet observability plane (ISSUE 11): per-tenant usage, SLO burn,
    # fallback settles, straggler flags
    assert summary["tenants"] == {
        "acme": {"chip_seconds": 42.5, "rows": 19, "petaflops": 2.0},
        "other": {"chip_seconds": 1.5, "rows": 1, "petaflops": 0.0}}
    assert list(summary["tenants"]) == ["acme", "other"]  # cost-sorted
    assert summary["usage_fallback"] == 2
    assert summary["slo"] == {"interactive": {
        "fast_burn": 2.4, "slow_burn": 0.3, "compliance": 0.88}}
    assert summary["outliers"] == ["w-slow"]
    # preemption tolerance (ISSUE 18)
    assert summary["partials"] == {
        "checkpoints": {"stored": 4, "superseded": 3},
        "previews": {"stored": 2},
        "resume_offers": 1,
    }
    # stage-graph serving (ISSUE 20): workflow population, ready depth,
    # per-stage outcomes, and per-stage queue-wait quantiles
    assert summary["dag"] == {
        "workflows": {"cancelled": 1, "done": 3, "running": 1},
        "ready_depth": 2,
        "stages": {
            "decode": {"cancelled": 1},
            "denoise": {"admitted": 4, "done": 3},
            "encode": {"done": 4},
        },
        "stage_queue_wait": [{
            "stage": "denoise", "count": 3,
            "p50_le_s": 1.0, "p95_le_s": 1.0,
        }],
    }

    table = tool.render_hive_tables(summary)
    assert "affinity" in table and "6" in table
    # 6 gang jobs over 12 delivered (hold excluded) -> rate 0.50;
    # sizes render as integer job counts, not seconds
    assert "hive gangs    count=2 jobs=6 rate=0.50" in table
    assert "size p50<=2 p95<=4" in table
    assert "hive admission by class" in table
    assert "batch" in table and "shed" not in summary["dispatch"]
    assert ("hive cancels  leased=2 queued=3 expired=4 "
            "pending_revocations=2") in table
    assert "hive queue wait" in table
    assert "hive dispatch->settle" in table
    assert "p50<=0.100" in table
    assert "hive tenants" in table and "acme" in table
    assert "usage fallback settles: 2" in table
    assert "hive slo" in table
    assert "fast=2.40 slow=0.30 compliance=0.88" in table
    assert "hive outliers w-slow" in table
    assert ("hive partials checkpoints stored=4 superseded=3  "
            "previews stored=2  resume_offers=1") in table
    assert ("hive dag      running=1 done=3 failed=0 cancelled=1 "
            "ready_depth=2") in table
    assert "hive dag stages (lifecycle outcomes)" in table
    assert "denoise      admitted=4 done=3" in table
    assert "hive dag stage wait (admit -> first dispatch)" in table
    # a fleet that never submitted a workflow renders no dag block
    assert tool.dag_summary([]) is None
    assert "hive dag" not in tool.render_hive_tables(
        tool.hive_summary([]))


def test_json_mode_emits_machine_readable_twin(monkeypatch, capsys):
    """--json (ISSUE 11 satellite): one JSON object carrying the twin of
    every table — hive summary (tenants/slo included) and the worker
    stage rows — with inf bucket bounds spelled "+Inf" so the output is
    strict JSON that CI tooling can parse without screen-scraping."""
    import json

    tool = _load_tool()

    def fake_fetch(url, path):
        if path == "/metrics":
            return HIVE_SYNTHETIC if "9511" in url else SYNTHETIC
        return json.dumps({"status": "ok"})

    monkeypatch.setattr(tool, "fetch", fake_fetch)
    rc = tool.main(["--hive", "http://h:9511", "--url", "http://w:8061",
                    "--json"])
    out = capsys.readouterr().out.strip()
    assert rc == 0
    payload = json.loads(out)  # strict JSON — a single object
    assert payload["hive"]["tenants"]["acme"]["chip_seconds"] == 42.5
    assert payload["hive"]["slo"]["interactive"]["fast_burn"] == 2.4
    assert payload["hive"]["dispatch"]["affinity"] == 6
    assert payload["hive"]["partials"]["resume_offers"] == 1
    assert payload["hive"]["dag"]["ready_depth"] == 2
    assert payload["hive"]["dag"]["stages"]["denoise"]["done"] == 3
    # the synthetic worker never checkpointed: the twin is null, not {}
    assert payload["worker"]["resume"] is None
    assert payload["worker"]["polls"] is None
    stages = {r["stage"]: r for r in payload["worker"]["stages"]}
    assert stages["denoise"]["count"] == 4
    assert stages["denoise"]["p90_le_s"] == "+Inf"  # inf spelled safely
    assert payload["worker"]["healthz"] == {"status": "ok"}

    # hive-only --json still emits the hive twin and exits 0
    rc = tool.main(["--hive", "http://h:9511", "--json"])
    out = capsys.readouterr().out.strip()
    assert rc == 0
    payload = json.loads(out)
    assert "hive" in payload and "worker" not in payload
