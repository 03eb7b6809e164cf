"""ChipSet: the TPU analog of the reference's one-CUDA-device abstraction.

Where reference swarm/gpu/device.py:6-53 wraps one `cuda:{i}` device with a
busy mutex and a per-job seeded torch.Generator, a ChipSet wraps a *set* of
TPU chips as a `jax.sharding.Mesh` (so one job can be batch-parallel across
its slice), seeds via `jax.random.key`, and reports chip/HBM capability for
work advertisement. The 8 GB VRAM floor (:8-11) has no TPU analog — HBM per
chip is fixed by the platform — so capability is advertised rather than gated.
"""

from __future__ import annotations

import contextlib
import logging
import random
import threading
import time

import jax
from jax.sharding import Mesh

from .. import faults, telemetry

logger = logging.getLogger(__name__)

# wall clock under a slice's busy lock, solo vs coalesced pass — with
# swarm_job_stage_seconds (compile/denoise split stamped by the pipeline)
# this separates "slice occupied" from "slice computing usefully"
_EXECUTE_SECONDS = telemetry.histogram(
    "swarm_slice_execute_seconds",
    "Wall-clock seconds one job (or coalesced pass) held a chip slice",
    ("kind",),
)

# seconds a slice sat free between two passes: at each acquisition of the
# busy lock, the time since its last release (nothing before the first
# pass). With swarm_slice_execute_seconds it is the slice's duty cycle
# without a profiler, and it measures the poll quantisation directly.
_FREE_SECONDS = telemetry.counter(
    "swarm_slice_free_seconds_total",
    "Seconds the slice's busy lock was free between one pass's release "
    "and the next pass's acquisition",
    ("slice",),
)

# the mesh view the slice's LAST pass ran under, one series per axis
# (ISSUE 12): data = coalescing rows / CFG pair, tensor = Megatron-style
# kernel sharding, seq = ring-attention blocks. A slice serving batch
# traffic sits at tensor=1; an interactive sharded pass flips tensor>1
# for its duration — the gauge is how an operator sees the class-aware
# geometry actually switching.
_SLICE_GEOMETRY = telemetry.gauge(
    "swarm_slice_geometry",
    "Mesh degree of the slice's most recent pass, per axis "
    "(data | tensor | seq)",
    ("slice", "axis"),
)

# HBM per chip (GiB) by device kind. A TPU kind missing here is an error
# (add its row with the source), never a guessed 16: admission
# (chips/requirements.py) and the advertised capacity both hang off it.
_HBM_GB = {
    "TPU v2": 8,
    "TPU v3": 16,
    "TPU v4": 32,
    "TPU v5 lite": 16,
    "TPU v5": 95,
    "TPU v5p": 95,
    "TPU v6 lite": 32,
    "cpu": 4,
}


def _table_gb(device) -> int | None:
    kind = getattr(device, "device_kind", "cpu")
    for prefix, gb in _HBM_GB.items():
        if kind.startswith(prefix):
            return gb
    return None


def hbm_gb_of(device) -> int:
    gb = _table_gb(device)
    if gb is None:
        raise ValueError(
            f"no HBM size known for device kind "
            f"{getattr(device, 'device_kind', None)!r} "
            f"(platform {device.platform}); add it to chips/device.py _HBM_GB"
        )
    return gb


def runtime_report() -> dict:
    """What this process actually serves on, as jax reports it, plus the
    installed versions — the worker logs it at start-up and carries it on
    /healthz, so a worker that found no chip says so instead of serving on
    the CPU without a word."""
    from importlib import metadata

    def version(dist: str) -> str | None:
        try:
            return metadata.version(dist)
        except metadata.PackageNotFoundError:
            return None

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "jax": jax.__version__,
        "jaxlib": version("jaxlib"),
        "libtpu": version("libtpu"),
    }


def hbm_census() -> list[dict]:
    """Per-local-device memory view for the fleet census (ISSUE 17,
    memory_census.py): whatever ``device.memory_stats()`` reports —
    TPU runtimes give bytes_in_use / bytes_limit / peak_bytes_in_use,
    the CPU backend an allocator subset or nothing — normalised to ints
    with the HBM table as the limit fallback, so /debug/memory always
    has a per-chip row even where the runtime is silent."""
    import jax

    out = []
    for device in jax.local_devices():
        stats = {}
        try:
            stats = device.memory_stats() or {}
        except Exception:
            stats = {}
        limit = stats.get("bytes_limit")
        if not isinstance(limit, int) or limit <= 0:
            # the table speaks for TPU kinds; a CPU "limit" would fake
            # headroom where none is enforced
            gb = _table_gb(device) if device.platform != "cpu" else None
            limit = gb << 30 if gb else None
        row = {
            "device": f"{device.platform}:{device.id}",
            "kind": getattr(device, "device_kind", device.platform),
            "bytes_in_use": stats.get("bytes_in_use")
            if isinstance(stats.get("bytes_in_use"), int) else None,
            "bytes_limit": limit,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")
            if isinstance(stats.get("peak_bytes_in_use"), int) else None,
        }
        out.append(row)
    return out


@contextlib.contextmanager
def _pass_span():
    """Span "pass": the slice held, parent of every span the callback
    stamps on this thread. The process's first one is also the two
    startup marks `first_pass_start` / `first_pass_end`."""
    telemetry.mark_startup("first_pass_start")
    try:
        with telemetry.Span("pass") as held:
            yield held
    finally:
        telemetry.mark_startup("first_pass_end")


class ChipSet:
    """A fixed subset of local accelerator chips, meshed for one job at a time.

    The mesh is [data, tensor, seq] (scaling-book axis convention): pipelines
    shard the image batch (and CFG pair) over ``data``, Megatron-style
    attention/MLP kernels over ``tensor`` (parallel/tensor.py partition
    rules), and ring-attention sequence blocks over ``seq``. Degrees default
    to 1, so a plain ChipSet behaves exactly like the round-1 data-only mesh.
    """

    def __init__(self, devices: list, slice_id: int = 0, tensor: int = 1,
                 seq: int = 1):
        if not devices:
            raise ValueError("ChipSet requires at least one device")
        if tensor < 1 or seq < 1:
            raise ValueError(f"parallel degrees must be >= 1, got {tensor=} {seq=}")
        if len(devices) % (tensor * seq) != 0:
            raise ValueError(
                f"tensor*seq={tensor * seq} does not divide "
                f"slice size {len(devices)}"
            )
        self.devices = list(devices)
        self.slice_id = slice_id
        self.tensor = tensor
        self.seq = seq
        self._mutex = threading.Lock()
        # when the last pass released the busy lock (monotonic)
        self._released_at: float | None = None
        # geometry of the most recent pass (healthz / swarm_top column);
        # starts at the construction-time default
        self.last_geometry: tuple[int, int, int] = (
            len(devices) // (tensor * seq), tensor, seq)

    # --- identity / capability (reference swarm/gpu/device.py:17-27) ---

    @property
    def platform(self) -> str:
        return self.devices[0].platform

    @property
    def busy(self) -> bool:
        """A job currently holds this slice (healthz per-slice state)."""
        return self._mutex.locked()

    def identifier(self) -> str:
        ids = ",".join(str(d.id) for d in self.devices)
        return f"{self.platform}:{ids}"

    def name(self) -> str:
        return getattr(self.devices[0], "device_kind", self.platform)

    def descriptor(self) -> str:
        return f"{self.identifier()}:{self.name()}"

    def chip_count(self) -> int:
        return len(self.devices)

    def hbm_bytes(self) -> int:
        return sum(hbm_gb_of(d) for d in self.devices) << 30

    def memory(self) -> int:
        # legacy `memory` capability key (reference swarm/hive.py:19)
        return self.hbm_bytes()

    def capabilities(self) -> dict:
        return {
            # legacy keys a reference hive understands
            "memory": self.memory(),
            "gpu": self.name(),
            # TPU-native keys
            "chips": self.chip_count(),
            "hbm_gb": self.hbm_bytes() >> 30,
            "topology": f"{self.platform}x{self.chip_count()}",
        }

    def resident_models(self) -> list[str]:
        """Models whose residency entry (allocator residency map, fed by
        registry load + pipeline compile events) points at this slice —
        the warm state the dispatch board routes same-model groups to."""
        from .allocator import models_resident_on

        return models_resident_on(self.slice_id)

    def smoke_probe(self) -> bool:
        """Quarantine-recovery probe (worker watchdog): one tiny matmul on
        every chip of the slice, synchronously. True = the slice computes
        and may return to the allocator; False (busy, or any device error)
        = it stays quarantined."""
        if not self._mutex.acquire(blocking=False):
            return False
        try:
            import jax.numpy as jnp

            for d in self.devices:
                x = jax.device_put(jnp.eye(8, dtype=jnp.float32), d)
                jnp.matmul(x, x).block_until_ready()
            return True
        except Exception:
            logger.exception("smoke probe failed on %s", self.identifier())
            return False
        finally:
            self._mutex.release()

    # --- geometry (ISSUE 12: one slice, two views) ---

    @property
    def shard_capable(self) -> bool:
        """Whether this slice can run one job as a sharded program at
        all: more than one chip to spread attention heads / sequence
        blocks over. The worker ANDs this with Settings.shard_interactive
        before advertising `shard_capable` on /work polls."""
        return len(self.devices) > 1

    def resolve_geometry(self, tensor: int | None = None,
                         seq: int | None = None) -> tuple[int, int] | None:
        """Validate a requested (tensor, seq) view over THIS slice's
        chips; None when it cannot mesh (doesn't divide the chip count).
        tensor=0/None means "auto": the largest power-of-two degree that
        leaves a data axis of at least the CFG pair (2), so a batch-1
        interactive job still shards its uncond/cond rows over `data`
        while attention heads spread over `tensor`."""
        n = len(self.devices)
        seq = int(seq or 1)
        if seq < 1 or n % seq:
            return None
        if tensor:
            tensor = int(tensor)
            if tensor < 1 or n % (tensor * seq):
                return None
            return tensor, seq
        # auto: chips / (2 * seq), floored to a power of two >= 1
        room = n // (2 * seq)
        tensor = 1
        while tensor * 2 <= room and n % (tensor * 2 * seq) == 0:
            tensor *= 2
        return tensor, seq

    def note_geometry(self, data: int, tensor: int, seq: int) -> None:
        """Record the mesh view a pass is running under (called by the
        pipeline at dispatch): feeds the swarm_slice_geometry gauge and
        the healthz/swarm_top geometry column."""
        self.last_geometry = (int(data), int(tensor), int(seq))
        label = str(self.slice_id)
        _SLICE_GEOMETRY.set(data, slice=label, axis="data")
        _SLICE_GEOMETRY.set(tensor, slice=label, axis="tensor")
        _SLICE_GEOMETRY.set(seq, slice=label, axis="seq")

    def geometry_str(self) -> str:
        d, t, s = self.last_geometry
        return f"data{d}·tensor{t}·seq{s}"

    # --- execution ---

    def mesh(self, tensor: int | None = None, seq: int | None = None) -> Mesh:
        """The slice's device mesh — by default the construction-time
        [data, tensor, seq] view; pass `tensor`/`seq` to carve the SAME
        chips into a different geometry (the elastic view ISSUE 12 adds:
        a sharded interactive pass and a data-parallel coalesced pass
        run over identical hardware)."""
        from ..parallel.mesh import make_mesh

        return make_mesh(
            self.devices,
            tensor=self.tensor if tensor is None else tensor,
            seq=self.seq if seq is None else seq,
        )

    def _acquire_for_pass(self) -> None:
        if not self._mutex.acquire(blocking=False):
            logger.error("ChipSet %s is busy but got invoked.", self.identifier())
            raise Exception("busy")
        if self._released_at is not None:
            _FREE_SECONDS.inc(time.monotonic() - self._released_at,
                              slice=str(self.slice_id))

    def _release_after_pass(self) -> None:
        self._released_at = time.monotonic()
        self._mutex.release()

    def __call__(self, func, **kwargs):
        """Run one job on this slice under the busy lock.

        Mirrors reference swarm/gpu/device.py:29-50: pops model_name, draws a
        seed when the job didn't pin one, injects the RNG, and stamps the
        seed into the returned pipeline_config. Here the RNG is a counter-
        based `jax.random.key` (deterministic across chip counts) and the
        callback also receives this ChipSet for mesh placement.
        """
        self._acquire_for_pass()
        try:
            # fault-injection point: a hung compile/denoise holds the busy
            # lock exactly like the real failure would (faults.py)
            faults.hang("hang_denoise")
            model_name = kwargs.pop("model_name")
            seed = kwargs.pop("seed", None)
            if seed is None:
                seed = random.getrandbits(63)

            kwargs["rng"] = jax.random.key(seed)
            kwargs["chipset"] = self

            with _pass_span() as held:
                artifacts, pipeline_config = func(
                    self.identifier(), model_name, **kwargs)
            _EXECUTE_SECONDS.observe(held.elapsed, kind="solo")
            pipeline_config["seed"] = seed
            # per-job timing breadcrumb (reference has none; SURVEY §5 asks for it)
            pipeline_config.setdefault("timings", {})["job_s"] = round(
                held.elapsed, 3
            )
            return artifacts, pipeline_config
        finally:
            self._release_after_pass()

    def run_batched(self, func, requests: list[dict]):
        """Run a coalesced group of jobs on this slice under the busy lock.

        The batch analog of __call__: draws (or honors) a seed PER JOB,
        injects each job's own counter-based RNG plus this ChipSet, and
        stamps each returned pipeline_config with its job's seed — so a
        coalesced job's images depend only on its own seed, never on its
        batchmates (the batched path's noise stream is its own, distinct
        from the single-job path's draws for the same seed).

        `func(identifier, requests)` must return one (artifacts,
        pipeline_config) pair per request, in order.
        """
        self._acquire_for_pass()
        try:
            # fault-injection points: hang (watchdog path) and a coalesced
            # OOM raised before any request kwarg is mutated, so the
            # worker's per-job fallback reruns the group unchanged
            faults.hang("hang_denoise")
            faults.fire("oom_batched", exc=RuntimeError(
                "RESOURCE_EXHAUSTED: injected OOM (fault oom_batched)"))
            seeds = []
            for kw in requests:
                seed = kw.pop("seed", None)
                if seed is None:
                    seed = random.getrandbits(63)
                seeds.append(seed)
                kw["rng"] = jax.random.key(seed)
                kw["chipset"] = self

            with _pass_span() as held:
                results = func(self.identifier(), requests)
            if len(results) != len(requests):
                raise RuntimeError(
                    f"batched callback returned {len(results)} envelopes "
                    f"for {len(requests)} jobs"
                )
            _EXECUTE_SECONDS.observe(held.elapsed, kind="batched")
            elapsed = round(held.elapsed, 3)
            for (artifacts, pipeline_config), seed in zip(results, seeds):
                pipeline_config["seed"] = seed
                timings = pipeline_config.setdefault("timings", {})
                # the pass was shared: job_s is the group's wall clock
                timings["job_s"] = elapsed
            return results
        finally:
            self._release_after_pass()
