"""Settings layering: file defaults, JSON values, env overrides.

Parity targets: reference swarm/settings.py:19-43.
"""

import json
import os

import pytest

from chiaswarm_tpu.settings import (
    Settings,
    get_settings_full_path,
    load_settings,
    save_settings,
)


def test_defaults_when_no_file(sdaas_root):
    s = load_settings()
    assert s.sdaas_uri == "http://localhost:9511"
    assert s.worker_name == "worker"
    assert s.log_level == "WARN"
    assert s.lora_root_dir == "~/lora"


def test_file_values_loaded(sdaas_root):
    save_settings(Settings(sdaas_token="tok", worker_name="tpu-worker"))
    s = load_settings()
    assert s.sdaas_token == "tok"
    assert s.worker_name == "tpu-worker"


def test_env_overrides_file(sdaas_root, monkeypatch):
    save_settings(Settings(sdaas_token="file-tok", worker_name="file-name"))
    monkeypatch.setenv("SDAAS_TOKEN", "env-tok")
    monkeypatch.setenv("SDAAS_WORKERNAME", "env-name")
    monkeypatch.setenv("SDAAS_URI", "https://hive.example")
    s = load_settings()
    assert s.sdaas_token == "env-tok"
    assert s.worker_name == "env-name"
    assert s.sdaas_uri == "https://hive.example"


def test_invalid_json_falls_back_to_defaults(sdaas_root):
    get_settings_full_path().write_text("{not json")
    s = load_settings()
    assert s.worker_name == "worker"


def test_unknown_keys_ignored(sdaas_root):
    get_settings_full_path().write_text(json.dumps({"bogus": 1, "sdaas_token": "t"}))
    s = load_settings()
    assert s.sdaas_token == "t"


def test_hive_durability_env_overrides(sdaas_root, monkeypatch):
    monkeypatch.setenv("CHIASWARM_HIVE_WAL_DIR", "custom_wal")
    monkeypatch.setenv("CHIASWARM_HIVE_WAL_FSYNC", "true")
    monkeypatch.setenv("CHIASWARM_HIVE_WAL_COMPACT_EVERY", "64")
    monkeypatch.setenv("CHIASWARM_HIVE_SHED_WATERMARKS", "batch:0.25")
    monkeypatch.setenv("CHIASWARM_HIVE_SPOOL_MAX_BYTES", "1048576")
    monkeypatch.setenv("CHIASWARM_HIVE_SPOOL_MAX_AGE_S", "3600")
    s = load_settings()
    assert s.hive_wal_dir == "custom_wal"
    assert s.hive_wal_fsync is True
    assert s.hive_wal_compact_every == 64
    assert s.hive_shed_watermarks == "batch:0.25"
    assert s.hive_spool_max_bytes == 1048576
    assert s.hive_spool_max_age_s == 3600.0
    # the WAL defaults ON — durability is not opt-in
    monkeypatch.undo()
    assert load_settings().hive_wal_dir == "hive_wal"
    assert load_settings().hive_wal_fsync is False


def test_cancellation_knobs(sdaas_root, monkeypatch):
    """ISSUE 10: the chunked-denoise and admission-TTL knobs layer like
    every other setting — defaults OFF (single-pass denoise, no TTL),
    env overrides win."""
    s = load_settings()
    assert s.denoise_chunk_steps == 0  # single fused pass at zero cost
    assert s.hive_job_ttl_s == 0.0  # queued jobs never expire by default
    monkeypatch.setenv("CHIASWARM_DENOISE_CHUNK_STEPS", "4")
    monkeypatch.setenv("CHIASWARM_HIVE_JOB_TTL_S", "7.5")
    s = load_settings()
    assert s.denoise_chunk_steps == 4
    assert s.hive_job_ttl_s == 7.5
    monkeypatch.undo()
    assert load_settings().denoise_chunk_steps == 0


def test_lora_serving_knobs(sdaas_root, monkeypatch):
    """ISSUE 13: runtime-delta adapter serving layers like every other
    setting — delta ON by default (the multi-tenant path is the serving
    path), env overrides win."""
    s = load_settings()
    assert s.lora_runtime_delta is True
    assert s.lora_cache_mb == 256
    assert s.lora_operand_cache_mb == 512
    assert s.lora_slots_max == 8
    assert s.lora_rank_max == 128
    monkeypatch.setenv("CHIASWARM_LORA_RUNTIME_DELTA", "0")
    monkeypatch.setenv("CHIASWARM_LORA_CACHE_MB", "64")
    monkeypatch.setenv("CHIASWARM_LORA_OPERAND_CACHE_MB", "128")
    monkeypatch.setenv("CHIASWARM_LORA_SLOTS_MAX", "4")
    monkeypatch.setenv("CHIASWARM_LORA_RANK_MAX", "32")
    s = load_settings()
    assert s.lora_runtime_delta is False
    assert s.lora_cache_mb == 64
    assert s.lora_operand_cache_mb == 128
    assert s.lora_slots_max == 4
    assert s.lora_rank_max == 32
    monkeypatch.undo()
    assert load_settings().lora_runtime_delta is True


def test_shard_geometry_knobs(sdaas_root, monkeypatch):
    """ISSUE 12: the class-aware sharding knobs layer like every other
    setting — interactive sharding OFF by default (the sharded view
    compiles its own program set), tensor auto / seq off, CHIASWARM_SHARD_*
    env overrides win."""
    s = load_settings()
    assert s.shard_interactive is False
    assert s.shard_tensor == 0  # 0 = auto degree
    assert s.shard_seq == 1
    monkeypatch.setenv("CHIASWARM_SHARD_INTERACTIVE", "1")
    monkeypatch.setenv("CHIASWARM_SHARD_TENSOR", "4")
    monkeypatch.setenv("CHIASWARM_SHARD_SEQ", "2")
    s = load_settings()
    assert s.shard_interactive is True
    assert s.shard_tensor == 4
    assert s.shard_seq == 2
    monkeypatch.setenv("CHIASWARM_SHARD_INTERACTIVE", "false")
    assert load_settings().shard_interactive is False
    monkeypatch.undo()
    assert load_settings().shard_interactive is False


def test_fleet_observability_knobs(sdaas_root, monkeypatch):
    """ISSUE 11: the accounting/SLO/straggler knobs layer like every
    other setting — SLO engine off by default, sane window/top-K/EWMA
    defaults, env overrides win."""
    s = load_settings()
    assert s.hive_slo == ""  # engine disabled until declared
    assert s.hive_slo_fast_window_s == 60.0
    assert s.hive_slo_slow_window_s == 600.0
    assert s.hive_tenant_topk == 10
    assert s.hive_stats_ewma_alpha == 0.2
    assert s.hive_straggler_factor == 2.5
    monkeypatch.setenv("CHIASWARM_HIVE_SLO",
                       "interactive:queue_wait_p95<2.0")
    monkeypatch.setenv("CHIASWARM_HIVE_SLO_FAST_WINDOW_S", "30")
    monkeypatch.setenv("CHIASWARM_HIVE_SLO_SLOW_WINDOW_S", "300")
    monkeypatch.setenv("CHIASWARM_HIVE_TENANT_TOPK", "3")
    monkeypatch.setenv("CHIASWARM_HIVE_STATS_EWMA_ALPHA", "0.5")
    monkeypatch.setenv("CHIASWARM_HIVE_STRAGGLER_FACTOR", "4.0")
    s = load_settings()
    assert s.hive_slo == "interactive:queue_wait_p95<2.0"
    assert s.hive_slo_fast_window_s == 30.0
    assert s.hive_slo_slow_window_s == 300.0
    assert s.hive_tenant_topk == 3
    assert s.hive_stats_ewma_alpha == 0.5
    assert s.hive_straggler_factor == 4.0
    monkeypatch.undo()
    assert load_settings().hive_slo == ""


def test_tpu_fields_roundtrip(sdaas_root):
    save_settings(Settings(chips_per_job=4, dtype="float32"))
    s = load_settings()
    assert s.chips_per_job == 4
    assert s.dtype == "float32"


@pytest.fixture()
def jax_cache_config():
    """Snapshot and restore jax's cache directory option: enable_* may set
    it, and a later test must not keep writing where this one pointed."""
    import jax

    was = jax.config.jax_compilation_cache_dir
    yield jax.config
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_places_it_and_code_sets_nothing(
        tmp_path, monkeypatch, jax_cache_config):
    """JAX_COMPILATION_CACHE_DIR set: the cache is there, placed from
    outside — jax reads the variable itself (in a fresh process) and the
    program calls jax.config.update("jax_compilation_cache_dir", ...)
    nowhere."""
    import subprocess
    import sys

    from chiaswarm_tpu import compile_cache

    outside = tmp_path / "placed-from-outside"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(outside))
    assert compile_cache.cache_dir() == outside

    updates = []
    real_update = jax_cache_config.update
    monkeypatch.setattr(
        jax_cache_config, "update",
        lambda name, value: (updates.append(name), real_update(name, value)))
    assert compile_cache.enable_compile_cache() == outside
    assert outside.is_dir()
    assert "jax_compilation_cache_dir" not in updates

    # what a process started with the variable sees: jax reports the
    # environment's directory without any code having set it
    code = ("from chiaswarm_tpu.compile_cache import enable_compile_cache;"
            "import jax; enable_compile_cache();"
            "print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=str(compile_cache.DEFAULT_DIR.parent),
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-500:]
    assert out.stdout.strip().splitlines()[-1] == str(outside)


@pytest.mark.parametrize("root", ["sdaas-a", "somewhere/else/sdaas-b"])
def test_compile_cache_default_is_one_fixed_path_in_the_checkout(
        tmp_path, monkeypatch, jax_cache_config, root):
    """Variable unset: one fixed path inside the checkout whatever
    SDAAS_ROOT is — the directory is part of the cache's key, so a cache
    that moves with every drive's root never hits."""
    import pathlib

    import chiaswarm_tpu
    from chiaswarm_tpu import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("SDAAS_ROOT", str(tmp_path / root))
    checkout = pathlib.Path(chiaswarm_tpu.__file__).resolve().parent.parent
    assert compile_cache.cache_dir() == checkout / ".jax_cache"
    assert compile_cache.enable_compile_cache() == checkout / ".jax_cache"
    assert jax_cache_config.jax_compilation_cache_dir == str(
        checkout / ".jax_cache")
    assert not hasattr(load_settings(), "compile_cache_dir")


def test_compile_cache_unwritable_directory_is_an_error(
        tmp_path, monkeypatch, jax_cache_config):
    """No silent cold cache: a directory that cannot be created or written
    raises (the worker stops at start-up, chip_smoke.py fails)."""
    from chiaswarm_tpu.compile_cache import enable_compile_cache

    blocker = tmp_path / "blocked"
    blocker.write_text("a file where the cache dir should go")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(blocker))
    with pytest.raises(OSError):
        enable_compile_cache()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(blocker / "under"))
    with pytest.raises(OSError):
        enable_compile_cache()


def test_observability_knobs(sdaas_root, monkeypatch):
    s = load_settings()
    assert s.metrics_port == 8061  # default: local /metrics + /healthz on
    assert s.metrics_host == "127.0.0.1"  # loopback unless opted in
    assert s.log_format == "plain"
    monkeypatch.setenv("CHIASWARM_METRICS_PORT", "0")
    monkeypatch.setenv("CHIASWARM_METRICS_HOST", "0.0.0.0")
    monkeypatch.setenv("CHIASWARM_LOG_FORMAT", "json")
    s = load_settings()
    assert s.metrics_port == 0  # opt-out disables the HTTP server
    assert s.metrics_host == "0.0.0.0"
    assert s.log_format == "json"


def test_tracing_and_profiler_knobs(sdaas_root, monkeypatch):
    s = load_settings()
    assert s.profiler_capture is False  # arming a profile is opt-in
    assert s.hive_replication_lag_degraded_s == 30.0
    monkeypatch.setenv("CHIASWARM_PROFILER_CAPTURE", "1")
    monkeypatch.setenv("CHIASWARM_HIVE_REPLICATION_LAG_DEGRADED_S", "5.5")
    s = load_settings()
    assert s.profiler_capture is True
    assert s.hive_replication_lag_degraded_s == 5.5
    monkeypatch.setenv("CHIASWARM_PROFILER_CAPTURE", "false")
    assert load_settings().profiler_capture is False


# --- ISSUE 15 (swarmlint SW004): the knob catalog is a contract ------------

# Every Settings field, literally. Adding a field without extending this
# tuple — and the README "Configuration reference" row, and the
# _ENV_OVERRIDES entry — fails this test AND `python -m chiaswarm_tpu.lint`.
EXPECTED_FIELDS = (
    "log_level", "log_filename", "sdaas_token", "sdaas_uri", "worker_name",
    "lora_root_dir", "chips_per_job", "tensor_parallelism",
    "sequence_parallelism", "ring_min_seq",
    "model_root_dir", "dtype", "depth_model", "pose_model",
    "safety_checker_model", "profiler_port", "profiler_capture",
    "flux_streaming", "flux_stream_int8", "batch_linger_ms", "max_coalesce",
    "embed_cache_mb", "lora_runtime_delta", "lora_cache_mb",
    "lora_operand_cache_mb", "lora_slots_max", "lora_rank_max",
    "program_cache_max",
    "denoise_chunk_steps", "checkpoint_every_chunks", "checkpoint_max_bytes",
    "preview_every_chunks",
    "shard_interactive", "shard_tensor", "shard_seq",
    "metrics_port", "metrics_host", "log_format", "job_deadline_s",
    "job_deadline_compile_scale", "quarantine_probe_grace_s",
    "drain_deadline_s", "outbox_dir", "outbox_max_entries",
    "fault_injection", "hive_host", "hive_port", "hive_lease_deadline_s",
    "hive_max_redeliveries", "hive_queue_depth_limit",
    "hive_affinity_hold_s", "hive_worker_ttl_s", "hive_max_jobs_per_poll",
    "hive_gang_max", "hive_spool_dir", "hive_job_history_limit",
    "hive_job_ttl_s", "hive_wal_dir", "hive_wal_fsync",
    "hive_wal_compact_every", "hive_shed_watermarks",
    "hive_spool_max_bytes", "hive_spool_max_age_s", "hive_slo",
    "hive_slo_fast_window_s", "hive_slo_slow_window_s", "hive_tenant_topk",
    "hive_stats_ewma_alpha", "hive_straggler_factor", "hive_flap_threshold",
    "sdaas_uris",
    "hive_standby_of", "hive_replication_poll_s", "hive_failover_grace_s",
    "hive_replication_lag_degraded_s", "hive_failover_errors",
    "memory_headroom_degraded",
    "stage_roles", "stage_workers", "hive_dag_history",
)


def test_settings_field_catalog_is_exhaustive():
    """The literal tuple above IS the drift tripwire: a new field lands
    here in the same PR that documents and env-wires it."""
    assert tuple(Settings.field_names()) == EXPECTED_FIELDS


def test_every_field_has_exactly_one_env_override():
    from chiaswarm_tpu.settings import _ENV_OVERRIDES

    mapped = list(_ENV_OVERRIDES.values())
    # no field double-mapped (last-env-wins would be load-order dependent)
    assert sorted(mapped) == sorted(set(mapped))
    assert set(mapped) == set(Settings.field_names())


def test_every_env_override_roundtrips(sdaas_root, monkeypatch):
    """Each env key actually lands on its field with the field's type —
    the whole _ENV_OVERRIDES table, not a sampled subset."""
    from chiaswarm_tpu.settings import _ENV_OVERRIDES

    defaults = Settings()
    for env, attr in sorted(_ENV_OVERRIDES.items()):
        default = getattr(defaults, attr)
        if isinstance(default, bool):  # before int: bool is an int
            value, expect = ("0" if default else "1"), (not default)
        elif isinstance(default, int):
            value, expect = "1234", 1234
        elif isinstance(default, float):
            value, expect = "17.5", 17.5
        else:
            value, expect = f"env-{attr}", f"env-{attr}"
        monkeypatch.setenv(env, value)
        assert getattr(load_settings(), attr) == expect, (env, attr)
        monkeypatch.delenv(env)
        assert getattr(load_settings(), attr) == default, (env, attr)


def test_preemption_knobs(sdaas_root, monkeypatch):
    """ISSUE 18: the checkpoint/preview/flap knobs layer like every
    other setting — checkpoints and previews OFF by default (the classic
    path stays byte-identical), an 8 MiB blob ceiling, flap detection at
    3 consecutive expiries, env overrides win."""
    s = load_settings()
    assert s.checkpoint_every_chunks == 0
    assert s.checkpoint_max_bytes == 8 * 1024 * 1024
    assert s.preview_every_chunks == 0
    assert s.hive_flap_threshold == 3
    monkeypatch.setenv("CHIASWARM_CHECKPOINT_EVERY_CHUNKS", "2")
    monkeypatch.setenv("CHIASWARM_CHECKPOINT_MAX_BYTES", "1048576")
    monkeypatch.setenv("CHIASWARM_PREVIEW_EVERY_CHUNKS", "4")
    monkeypatch.setenv("CHIASWARM_HIVE_FLAP_THRESHOLD", "0")
    s = load_settings()
    assert s.checkpoint_every_chunks == 2
    assert s.checkpoint_max_bytes == 1048576
    assert s.preview_every_chunks == 4
    assert s.hive_flap_threshold == 0  # 0 disables flap holds entirely
    monkeypatch.undo()
    assert load_settings().checkpoint_every_chunks == 0


def test_stage_graph_knobs(sdaas_root, monkeypatch):
    """ISSUE 20: stage-typed placement layers like every other setting —
    `auto` advertisement derives stages from hardware, two host-path
    lane slots so decode overlaps the next denoise, a bounded workflow
    history, env overrides win."""
    s = load_settings()
    assert s.stage_roles == "auto"
    assert s.stage_workers == 2
    assert s.hive_dag_history == 256
    monkeypatch.setenv("CHIASWARM_STAGE_ROLES", "encode,decode")
    monkeypatch.setenv("CHIASWARM_STAGE_WORKERS", "0")
    monkeypatch.setenv("CHIASWARM_HIVE_DAG_HISTORY", "16")
    s = load_settings()
    assert s.stage_roles == "encode,decode"
    assert s.stage_workers == 0  # 0 disables the host-path side lane
    assert s.hive_dag_history == 16
    monkeypatch.undo()
    assert load_settings().stage_roles == "auto"


def test_program_cache_knob(sdaas_root, monkeypatch):
    """ISSUE 15 (SW007 headline): the compiled-variant cache bound
    layers like every other setting — bounded by default, env wins."""
    assert load_settings().program_cache_max == 64
    monkeypatch.setenv("CHIASWARM_PROGRAM_CACHE_MAX", "2")
    assert load_settings().program_cache_max == 2
    monkeypatch.undo()
    assert load_settings().program_cache_max == 64
