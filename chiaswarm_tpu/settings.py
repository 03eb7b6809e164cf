"""Layered configuration: JSON settings file + environment overrides.

Behavior parity with reference swarm/settings.py:7-76 — same file location
($SDAAS_ROOT or ~/.sdaas/settings.json), same field names, same env override
keys (SDAAS_TOKEN / SDAAS_URI / SDAAS_WORKERNAME) — plus TPU-specific fields
the reference has no analog for (mesh topology, batching, the embedded hive).
"""

from __future__ import annotations

import dataclasses
import json
import os
from pathlib import Path


@dataclasses.dataclass
class Settings:
    log_level: str = "WARN"
    log_filename: str = "log/generator.log"
    sdaas_token: str = ""
    sdaas_uri: str = "http://localhost:9511"
    worker_name: str = "worker"
    lora_root_dir: str = "~/lora"
    # --- TPU-native additions (no reference analog) ---
    # chips per job slice; 0 = use every local chip as one slice
    chips_per_job: int = 0
    # tensor-parallel degree within each slice (Megatron-style sharding of
    # attention/MLP kernels over the mesh's `tensor` axis); must divide the
    # slice's chip count
    tensor_parallelism: int = 1
    # sequence-parallel degree within each slice (ring attention over the
    # mesh's `seq` axis for long self-attention); tensor * seq must divide
    # the slice's chip count
    sequence_parallelism: int = 1
    # self-attention sequence length at which the ring route engages when a
    # seq axis is active (4096 tokens = a 1024^2 SDXL canvas's largest
    # attention level); configurable so tests and small-canvas deployments
    # exercise the exact production routing instead of monkey-patching
    ring_min_seq: int = 2048
    # model weight root (converted Flax checkpoints / HF safetensors)
    model_root_dir: str = "~/.sdaas/models"
    # dtype policy for pipeline params: "bfloat16" | "float32"
    dtype: str = "bfloat16"
    # aux depth model serving the `depth` preprocessor + Kandinsky hint
    depth_model: str = "Intel/dpt-large"
    # aux pose model for the openpose preprocessor
    pose_model: str = "lllyasviel/ControlNet-openpose"
    # NSFW safety checker feeding the envelope flag ("" disables)
    safety_checker_model: str = "CompVis/stable-diffusion-safety-checker"
    # jax.profiler trace server port (0 = disabled)
    profiler_port: int = 0
    # arm the on-demand profiler capture hook on the worker metrics app
    # (POST /debug/profile?seconds=N writes a perfetto trace under
    # $SDAAS_ROOT/profiles/); off by default — profiling is an operator
    # action, not an always-on surface
    profiler_capture: bool = False
    # serve Flux on single-chip slices by paging transformer blocks from
    # host RAM (the TPU analog of the reference's sequential CPU offload);
    # False restores the round-4 behavior of refusing with flux_min_chips
    flux_streaming: bool = True
    # store the paged transformer blocks as per-channel int8 (halves the
    # per-step PCIe traffic — the streamed mode's bottleneck — at a small
    # bounded accuracy cost; dequantization happens on-chip)
    flux_stream_int8: bool = False
    # cross-job micro-batching (batching.py): how long a compatible txt2img
    # job waits for batchmates before its group dispatches to a slice. 0
    # disables the linger (every job dispatches alone, round-5 behavior)
    batch_linger_ms: float = 50.0
    # most jobs one coalesced group may hold; <= 1 disables coalescing
    max_coalesce: int = 8
    # text-encoder embedding cache (pipelines/, embed_cache.py): LRU
    # byte cap in MiB for encoded (model, prompt-text) rows, so gang
    # members and repeat prompts skip text_encode entirely; 0 disables
    embed_cache_mb: int = 64
    # --- multi-tenant add-on serving (ISSUE 13, pipelines/lora_runtime) ---
    # apply LoRA adapters as RUNTIME per-row low-rank deltas inside the
    # jitted program (one resident base UNet, adapters as stacked
    # factors) instead of merging each adapter into a full param-tree
    # copy. Off restores the merged-tree path everywhere (and makes
    # adapter jobs uncoalesceable again)
    lora_runtime_delta: bool = True
    # byte cap (MiB) for the process-wide raw adapter-factor LRU
    # (lora_cache.py); 0 disables caching (adapters reload per pass)
    lora_cache_mb: int = 256
    # byte cap (MiB) for the DEVICE-resident stacked-operand LRU
    # (lora_operands.py, ISSUE 16): already-assembled, already-uploaded
    # A/B stacks keyed by (model, adapter set, sig, dtype, geometry), so
    # a repeat gang of the same adapters uploads nothing. Coherent with
    # the factor LRU (factor eviction drops derived stacks); 0 disables
    # (every pass re-assembles + re-uploads, the PR 13 behavior)
    lora_operand_cache_mb: int = 512
    # most DISTINCT adapters one coalesced group/gang may carry. Shared
    # vocabulary: the hive's gang dispatcher, the worker's batch
    # scheduler, and run_batched all cap on it. The compiled slot
    # dimension is pow2(cap + 1) — one implicit zero slot for
    # adapter-free rows, padded to a power of two — so a FULL gang at
    # the default 8 compiles a 16-slot stack; set 7 to stay at 8 slots
    lora_slots_max: int = 8
    # adapters with rank beyond this serve via the merged-tree fallback
    # (their padded factor stacks would rival the activations they ride)
    lora_rank_max: int = 128
    # most compiled denoise-program variants (and assembled runners) one
    # pipeline keeps resident (pipelines/stable_diffusion.py). The
    # runtime-delta adapter path compiles one variant per (slot-bucket,
    # rank-bucket, targeted-module-path-set) and the path-set fan-out is
    # census-dependent, so a fleet-realistic worker bounds the cache:
    # past the cap the LRU entry is evicted WITH its compiled executable
    # (counted in swarm_program_cache_evicted_total). 0 = unbounded
    # (the pre-ISSUE-15 behavior)
    program_cache_max: int = 64
    # chunked denoise (pipelines/stable_diffusion.py): run the compiled
    # denoise loop in chunks of this many steps, probing the cancel
    # registry (cancel.py) at every chunk boundary so a cancelled job
    # frees its slice within one chunk instead of one full pass. 0 (the
    # default) keeps the single-pass compiled denoise at zero cost;
    # chunked and single-pass outputs are bitwise identical (pinned)
    denoise_chunk_steps: int = 0
    # --- preemption-tolerant denoise (ISSUE 18, checkpoint.py) ---
    # ship a durable mid-pass checkpoint (latents + scheduler state +
    # step index) to the hive every N chunk boundaries of a chunked
    # denoise, so a redelivered job resumes at step K instead of
    # recomputing the whole pass. Requires denoise_chunk_steps > 0.
    # 0 (the default) disables: the classic path stays byte-identical
    checkpoint_every_chunks: int = 0
    # largest checkpoint blob the worker will ship (bytes); a bigger
    # pack is skipped (counted), never truncated — losing a checkpoint
    # only costs recompute on redelivery
    checkpoint_max_bytes: int = 8388608
    # VAE-decode the intermediate latents every N chunk boundaries into
    # a progressive-preview artifact (spooled hive-side, surfaced as the
    # `partial` disposition on GET /api/jobs/{id}); 0 disables
    preview_every_chunks: int = 0
    # --- priority-aware multi-chip sharding (ISSUE 12) ---
    # run INTERACTIVE solo jobs as ONE sharded program over every chip of
    # their slice (attention heads + MLP inner dims on the mesh's tensor
    # axis, the CFG pair on data, optional ring-attention seq axis) so a
    # single job's latency scales with the slice instead of being bounded
    # by one chip. Batch/coalesced traffic keeps the data-parallel view
    # either way — the job class picks the geometry. Off by default: the
    # sharded view compiles its own program set per bucket, so an
    # operator turns it on per-fleet once the compile budget is warm
    shard_interactive: bool = False
    # tensor-parallel degree for sharded interactive passes; 0 = auto
    # (the largest power-of-two that still leaves a data axis >= the CFG
    # pair). Must divide the slice's chip count (with shard_seq)
    shard_tensor: int = 0
    # ring-attention sequence-parallel degree for sharded interactive
    # passes (long-canvas latents); 1 = off
    shard_seq: int = 1
    # --- observability (telemetry.py) ---
    # local /metrics + /healthz HTTP port; 0 disables the server (the
    # in-process instrumentation stays on either way — it is dict ops)
    metrics_port: int = 8061
    # bind address for the metrics server; loopback by default so worker
    # internals are not exposed off-host unless the operator opts in
    # (set 0.0.0.0 for a Prometheus scrape from another machine)
    metrics_host: str = "127.0.0.1"
    # log line format: "plain" (reference parity) | "json" (structured
    # lines carrying the active job_id — log_setup.JsonFormatter)
    log_format: str = "plain"
    # --- fault tolerance (outbox.py / worker watchdog / faults.py) ---
    # per-job execution deadline enforced by the slice watchdog; 0 disables.
    # On expiry the job returns the transient-error envelope and the slice
    # is quarantined until it passes a smoke probe
    job_deadline_s: float = 900.0
    # deadline multiplier when the job's model is not yet resident (first
    # compile of a big program legitimately takes minutes)
    job_deadline_compile_scale: float = 4.0
    # how long the quarantine probe waits for a wedged slice to come back
    # before writing it off (capacity stays shrunk if it never does)
    quarantine_probe_grace_s: float = 30.0
    # stop(drain=True)/SIGTERM: how long in-flight slices + the outbox get
    # to flush before the worker exits anyway (spooled envelopes survive)
    drain_deadline_s: float = 120.0
    # durable result spool directory (relative to $SDAAS_ROOT)
    outbox_dir: str = "outbox"
    # spooled-envelope count at which /healthz turns degraded (0 = never);
    # spooling itself never stops — saturation is a signal, not a limit
    outbox_max_entries: int = 512
    # deterministic fault-injection spec (faults.py), e.g.
    # "drop_submit=3,hang_denoise=1"; empty = no faults armed
    fault_injection: str = ""
    # --- embedded hive coordinator (hive_server/, tools/hive_serve.py) ---
    # bind address/port for the coordinator; the port default matches the
    # worker's sdaas_uri default, so `hive_serve` + a stock worker on one
    # host form a swarm with zero configuration (0 = ephemeral port)
    hive_host: str = "127.0.0.1"
    hive_port: int = 9511
    # how long a dispatched job may go without a result before its lease
    # expires and the job is re-queued for another worker
    hive_lease_deadline_s: float = 300.0
    # expired-lease redeliveries before the job parks as failed (a poison
    # job must not ping-pong around the swarm forever)
    hive_max_redeliveries: int = 3
    # total queued jobs past which POST /api/jobs answers 429 (admission
    # backpressure; 0 = unlimited)
    hive_queue_depth_limit: int = 256
    # how long a job waits for its model's WARM worker to poll before any
    # cold worker may steal it (residency-aware dispatch)
    hive_affinity_hold_s: float = 15.0
    # a worker unseen for this long stops counting as a live residency
    # holder (3-4 poll cadences; dead workers must not hold jobs hostage)
    hive_worker_ttl_s: float = 45.0
    # most jobs one /work poll may hand out (also capped by the worker's
    # advertised free capacity)
    hive_max_jobs_per_poll: int = 4
    # most jobs one gang-scheduled /work group may hold (hive-side
    # coalescing, ISSUE 9): same-model same-shape queued jobs leave in
    # ONE reply, pre-batched, sized to min(this, the worker's advertised
    # gang_rows appetite, hive_max_jobs_per_poll). <= 1 disables gang
    # scheduling and restores per-job dispatch
    hive_gang_max: int = 8
    # content-addressed artifact spool directory (relative to $SDAAS_ROOT)
    hive_spool_dir: str = "hive_spool"
    # finished (done/failed) job records kept in memory for
    # GET /api/jobs/{id}; older ones are forgotten so coordinator memory
    # is bounded by this, not by job history (0 = keep everything)
    hive_job_history_limit: int = 1000
    # admission-time job TTL: a job still QUEUED this many seconds after
    # submission is parked as `expired` instead of wasting a dispatch
    # (the submitter is presumed gone, or the answer stale). A per-job
    # `deadline_s` field on the submitted job dict overrides it; the
    # worker's slice watchdog also treats that per-job deadline as its
    # execution cap. 0 = no TTL (the pre-cancellation behavior)
    hive_job_ttl_s: float = 0.0
    # --- hive durability (hive_server/journal.py) ---
    # write-ahead journal directory (relative to $SDAAS_ROOT); every
    # queue/lease transition is appended so a crashed hive replays to its
    # pre-crash state on restart. "" disables (pure in-memory coordinator)
    hive_wal_dir: str = "hive_wal"
    # fsync each WAL append: flush-only (False) survives process death
    # incl. SIGKILL; fsync additionally survives power loss, at a
    # per-transition disk-sync cost
    hive_wal_fsync: bool = False
    # appends between WAL compactions (stream rewritten as a minimal
    # state snapshot); 0 = only compact on startup
    hive_wal_compact_every: int = 512
    # class-aware load shedding: per-class fractions of
    # hive_queue_depth_limit past which NEW submissions of that class
    # answer 429 — batch sheds first, interactive last
    hive_shed_watermarks: str = "interactive:1.0,default:0.85,batch:0.5"
    # artifact-spool retention sweep: total size / blob age bounds
    # (0 = keep everything); blobs referenced by a live job record are
    # never evicted
    hive_spool_max_bytes: int = 0
    hive_spool_max_age_s: float = 0.0
    # --- fleet observability plane (accounting.py / slo.py / fleet.py) ---
    # declarative per-class latency objectives, e.g.
    # "interactive:queue_wait_p95<2.0,e2e_p95<30;default:e2e_p95<120"
    # (classes split on ";", objectives on ","; metrics: queue_wait,
    # dispatch_to_settle, e2e). "" disables the SLO engine; GET /api/slo
    # still answers with enabled=false
    hive_slo: str = ""
    # sliding evaluation windows for compliance + burn rate: the fast
    # window drives /healthz degraded reasons, the slow one trend view
    hive_slo_fast_window_s: float = 60.0
    hive_slo_slow_window_s: float = 600.0
    # tenants named individually in the per-tenant usage gauges; the
    # rest fold into tenant="other" so cardinality stays bounded
    # (GET /api/usage always renders every tenant)
    hive_tenant_topk: int = 10
    # worker side: EWMA smoothing factor for the per-stage stats blob
    # piggybacked on /work polls (the hive's straggler detector input)
    hive_stats_ewma_alpha: float = 0.2
    # hive side: a worker is flagged a straggler when its per-stage EWMA
    # exceeds this multiple of the live peer median (plus an absolute
    # floor — fleet.py MIN_DELTA_S)
    hive_straggler_factor: float = 2.5
    # hive side: a worker whose leases expire this many CONSECUTIVE
    # times (no settle in between) stops receiving fresh seeds while a
    # healthy capable alternative is live — bounded by the affinity-hold
    # window exactly like straggler_hold, so a flapping worker is
    # preferred-against, never starved. 0 disables flap detection
    hive_flap_threshold: int = 3
    # --- hive replication & failover (hive_server/replication.py) ---
    # worker side: comma-separated hive site URIs in preference order
    # (primary first, standby after); the HiveClient pins to one and
    # fails over on consecutive transport errors or a not-primary 409.
    # Empty = the single sdaas_uri, the pre-replication behavior
    sdaas_uris: str = ""
    # hive side: set to the PRIMARY's site URI to run this hive as its
    # WAL-shipped standby (refuses work until promoted); "" = primary
    hive_standby_of: str = ""
    # how often the standby tails the primary's replication stream (and
    # therefore the failover-detection cadence)
    hive_replication_poll_s: float = 1.0
    # consecutive seconds of primary silence (no stream AND no /healthz
    # answer) before the standby promotes itself
    hive_failover_grace_s: float = 10.0
    # seconds without an APPLIED replication sync before a standby's
    # /healthz reports degraded (a silently stalled standby must be
    # visible before failover needs it); 0 disables the check
    hive_replication_lag_degraded_s: float = 30.0
    # worker side: consecutive transport errors on the pinned hive
    # endpoint before the client pins to the next one
    hive_failover_errors: int = 2
    # /healthz reports degraded when the worst device's free-HBM
    # fraction (memory_census.device_headroom) drops below this; 0
    # disables — some fleets legitimately run HBM near-full, so the
    # squeeze probe is an operator opt-in
    memory_headroom_degraded: float = 0.0
    # --- stage-graph serving (ISSUE 20, hive_server/dag.py) ---
    # worker side: which workflow stages this worker advertises on /work.
    # "auto" derives from hardware (chip hosts serve every stage; a
    # jax-free/CPU host serves only the host-path set — encode, decode,
    # postprocess, stitch, caption); "none" suppresses the advertisement
    # entirely (legacy poller: never sees stage-jobs); or an explicit
    # comma-separated stage list
    stage_roles: str = "auto"
    # worker side: concurrent host-path stage executions (encode/decode
    # jobs run beside the slice scheduler, so decode of pass N overlaps
    # denoise of pass N+1); 0 disables the side lane — CPU stages are
    # then refused by "auto" advertisement
    stage_workers: int = 2
    # hive side: terminal workflow graphs kept for GET /api/workflows
    # (running graphs never drop); bounds dag-table memory like
    # hive_job_history_limit bounds records
    hive_dag_history: int = 256

    @classmethod
    def field_names(cls) -> tuple[str, ...]:
        return tuple(f.name for f in dataclasses.fields(cls))


# env var -> settings attribute (reference swarm/settings.py:38-41).
# Every Settings field has exactly one override here (swarmlint SW004
# enforces it): SDAAS_* spellings are reference parity, CHIASWARM_*
# everything since.
_ENV_OVERRIDES = {
    "SDAAS_TOKEN": "sdaas_token",
    "CHIASWARM_LOG_LEVEL": "log_level",
    "CHIASWARM_LOG_FILENAME": "log_filename",
    "CHIASWARM_LORA_ROOT_DIR": "lora_root_dir",
    "CHIASWARM_MODEL_ROOT_DIR": "model_root_dir",
    "CHIASWARM_DEPTH_MODEL": "depth_model",
    "CHIASWARM_POSE_MODEL": "pose_model",
    "CHIASWARM_SAFETY_CHECKER_MODEL": "safety_checker_model",
    "CHIASWARM_PROFILER_PORT": "profiler_port",
    "CHIASWARM_JOB_DEADLINE_COMPILE_SCALE": "job_deadline_compile_scale",
    "CHIASWARM_QUARANTINE_PROBE_GRACE_S": "quarantine_probe_grace_s",
    "SDAAS_URI": "sdaas_uri",
    "SDAAS_WORKERNAME": "worker_name",
    "SDAAS_CHIPS_PER_JOB": "chips_per_job",
    "SDAAS_TENSOR_PARALLELISM": "tensor_parallelism",
    "SDAAS_SEQUENCE_PARALLELISM": "sequence_parallelism",
    "SDAAS_RING_MIN_SEQ": "ring_min_seq",
    "SDAAS_FLUX_STREAMING": "flux_streaming",
    "SDAAS_FLUX_STREAM_INT8": "flux_stream_int8",
    "SDAAS_DTYPE": "dtype",
    "SDAAS_BATCH_LINGER_MS": "batch_linger_ms",
    "SDAAS_MAX_COALESCE": "max_coalesce",
    "CHIASWARM_METRICS_PORT": "metrics_port",
    "CHIASWARM_METRICS_HOST": "metrics_host",
    "CHIASWARM_LOG_FORMAT": "log_format",
    "CHIASWARM_JOB_DEADLINE_S": "job_deadline_s",
    "CHIASWARM_DRAIN_DEADLINE_S": "drain_deadline_s",
    "CHIASWARM_OUTBOX_DIR": "outbox_dir",
    "CHIASWARM_OUTBOX_MAX_ENTRIES": "outbox_max_entries",
    "CHIASWARM_FAULTS": "fault_injection",
    "CHIASWARM_HIVE_HOST": "hive_host",
    "CHIASWARM_HIVE_PORT": "hive_port",
    "CHIASWARM_HIVE_LEASE_DEADLINE_S": "hive_lease_deadline_s",
    "CHIASWARM_HIVE_MAX_REDELIVERIES": "hive_max_redeliveries",
    "CHIASWARM_HIVE_QUEUE_DEPTH_LIMIT": "hive_queue_depth_limit",
    "CHIASWARM_HIVE_AFFINITY_HOLD_S": "hive_affinity_hold_s",
    "CHIASWARM_HIVE_WORKER_TTL_S": "hive_worker_ttl_s",
    "CHIASWARM_HIVE_MAX_JOBS_PER_POLL": "hive_max_jobs_per_poll",
    "CHIASWARM_HIVE_GANG_MAX": "hive_gang_max",
    "CHIASWARM_EMBED_CACHE_MB": "embed_cache_mb",
    "CHIASWARM_LORA_RUNTIME_DELTA": "lora_runtime_delta",
    "CHIASWARM_LORA_CACHE_MB": "lora_cache_mb",
    "CHIASWARM_LORA_OPERAND_CACHE_MB": "lora_operand_cache_mb",
    "CHIASWARM_LORA_SLOTS_MAX": "lora_slots_max",
    "CHIASWARM_LORA_RANK_MAX": "lora_rank_max",
    "CHIASWARM_PROGRAM_CACHE_MAX": "program_cache_max",
    "CHIASWARM_DENOISE_CHUNK_STEPS": "denoise_chunk_steps",
    "CHIASWARM_CHECKPOINT_EVERY_CHUNKS": "checkpoint_every_chunks",
    "CHIASWARM_CHECKPOINT_MAX_BYTES": "checkpoint_max_bytes",
    "CHIASWARM_PREVIEW_EVERY_CHUNKS": "preview_every_chunks",
    "CHIASWARM_SHARD_INTERACTIVE": "shard_interactive",
    "CHIASWARM_SHARD_TENSOR": "shard_tensor",
    "CHIASWARM_SHARD_SEQ": "shard_seq",
    "CHIASWARM_HIVE_JOB_TTL_S": "hive_job_ttl_s",
    "CHIASWARM_HIVE_SPOOL_DIR": "hive_spool_dir",
    "CHIASWARM_HIVE_JOB_HISTORY_LIMIT": "hive_job_history_limit",
    "CHIASWARM_HIVE_WAL_DIR": "hive_wal_dir",
    "CHIASWARM_HIVE_WAL_FSYNC": "hive_wal_fsync",
    "CHIASWARM_HIVE_WAL_COMPACT_EVERY": "hive_wal_compact_every",
    "CHIASWARM_HIVE_SHED_WATERMARKS": "hive_shed_watermarks",
    "CHIASWARM_HIVE_SPOOL_MAX_BYTES": "hive_spool_max_bytes",
    "CHIASWARM_HIVE_SPOOL_MAX_AGE_S": "hive_spool_max_age_s",
    "CHIASWARM_HIVE_SLO": "hive_slo",
    "CHIASWARM_HIVE_SLO_FAST_WINDOW_S": "hive_slo_fast_window_s",
    "CHIASWARM_HIVE_SLO_SLOW_WINDOW_S": "hive_slo_slow_window_s",
    "CHIASWARM_HIVE_TENANT_TOPK": "hive_tenant_topk",
    "CHIASWARM_HIVE_STATS_EWMA_ALPHA": "hive_stats_ewma_alpha",
    "CHIASWARM_HIVE_STRAGGLER_FACTOR": "hive_straggler_factor",
    "CHIASWARM_HIVE_FLAP_THRESHOLD": "hive_flap_threshold",
    "CHIASWARM_HIVE_URIS": "sdaas_uris",
    "CHIASWARM_HIVE_STANDBY_OF": "hive_standby_of",
    "CHIASWARM_HIVE_REPLICATION_POLL_S": "hive_replication_poll_s",
    "CHIASWARM_HIVE_FAILOVER_GRACE_S": "hive_failover_grace_s",
    "CHIASWARM_HIVE_FAILOVER_ERRORS": "hive_failover_errors",
    "CHIASWARM_HIVE_REPLICATION_LAG_DEGRADED_S":
        "hive_replication_lag_degraded_s",
    "CHIASWARM_PROFILER_CAPTURE": "profiler_capture",
    "CHIASWARM_MEMORY_HEADROOM_DEGRADED": "memory_headroom_degraded",
    "CHIASWARM_STAGE_ROLES": "stage_roles",
    "CHIASWARM_STAGE_WORKERS": "stage_workers",
    "CHIASWARM_HIVE_DAG_HISTORY": "hive_dag_history",
}


def get_settings_dir() -> Path:
    return Path(os.environ.get("SDAAS_ROOT") or "~/.sdaas/").expanduser()


def resolve_path(path: str | Path) -> Path:
    full_path = get_settings_dir() / path
    full_path.parent.mkdir(parents=True, exist_ok=True)
    return full_path


def get_settings_full_path() -> Path:
    return resolve_path("settings.json")


def settings_exist() -> bool:
    return get_settings_full_path().is_file()


def load_settings() -> Settings:
    try:
        raw = json.loads(get_settings_full_path().read_text())
    except FileNotFoundError:
        raw = {}
    except json.JSONDecodeError:
        raw = {}

    known = {k: v for k, v in raw.items() if k in Settings.field_names()}
    settings = Settings(**known)

    for env_key, attr in _ENV_OVERRIDES.items():
        value = os.getenv(env_key)
        if value is not None:
            field_type = type(getattr(settings, attr))
            if field_type is bool:
                # bool("0") is True — parse the usual spellings instead
                setattr(settings, attr,
                        value.strip().lower() in ("1", "true", "yes", "on"))
            else:
                setattr(settings, attr, field_type(value))

    return settings


def save_settings(settings: Settings) -> None:
    get_settings_full_path().write_text(
        json.dumps(dataclasses.asdict(settings), indent=2)
    )


def save_file(data, filename: str) -> None:
    resolve_path(filename).write_text(json.dumps(data, indent=2))
