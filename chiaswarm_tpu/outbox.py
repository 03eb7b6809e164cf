"""Durable result outbox: a finished job's envelope survives anything.

A denoise pass costs seconds-to-minutes of accelerator time; the round-6
`result_worker` threw that work away on the first failed upload (caught,
logged, dropped) and a worker restart lost everything still queued. This
module makes delivery a write-ahead contract instead:

- every result envelope is SPOOLED to disk (atomic tmp+rename JSON under
  ``$SDAAS_ROOT/outbox/``) before the first upload attempt;
- the upload loop retries transient failures with capped exponential
  backoff + jitter (``backoff_delay``); a permanent hive refusal (4xx)
  PARKS the entry — renamed aside, out of the retry loop, still on disk;
- the spool file is unlinked ONLY on hive ACK;
- on worker start, ``recover()`` re-enqueues every spooled entry from the
  previous process (parked ones included — the hive may accept now), so
  delivery is at-least-once across restarts and the hive dedupes by job
  id as it always has for resubmitted work.

Depth / oldest-age / retry counters feed /metrics and /healthz
(``saturated`` flips the worker's health to degraded so an orchestrator
can see a hive-side delivery stall before the disk fills).
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import logging
import os
import random
import re
import time
from pathlib import Path

from . import telemetry

logger = logging.getLogger(__name__)

# capped exponential backoff between delivery attempts for one entry;
# module-level so tests (and the chaos harness) can shrink them
BACKOFF_BASE_S = 0.5
BACKOFF_CAP_S = 30.0

_DEPTH = telemetry.gauge(
    "swarm_outbox_depth", "Result envelopes spooled on disk awaiting hive ACK")
_OLDEST = telemetry.gauge(
    "swarm_outbox_oldest_age_seconds",
    "Age of the oldest spooled result envelope (0 when empty)")
_SPOOLED = telemetry.counter(
    "swarm_outbox_spooled_total", "Result envelopes written to the outbox")
_DELIVERED = telemetry.counter(
    "swarm_outbox_delivered_total",
    "Result envelopes unlinked after a hive ACK")
_RETRIES = telemetry.counter(
    "swarm_outbox_retries_total",
    "Delivery attempts retried after a transient failure")
_PARKED = telemetry.counter(
    "swarm_outbox_parked_total",
    "Envelopes parked after a permanent hive refusal (kept on disk)")
_RECOVERED = telemetry.counter(
    "swarm_outbox_recovered_total",
    "Envelopes re-enqueued from a previous process's spool")

_SAFE_ID = re.compile(r"[^A-Za-z0-9._-]+")


def backoff_delay(retries: int, base: float | None = None,
                  cap: float | None = None) -> float:
    """Delay before attempt `retries`+1: exponential, capped, with jitter
    in [ceiling/2, ceiling] so a fleet retrying the same hive outage does
    not re-POST in lockstep."""
    base = BACKOFF_BASE_S if base is None else base
    cap = BACKOFF_CAP_S if cap is None else cap
    ceiling = min(cap, base * (2 ** max(int(retries) - 1, 0)))
    return random.uniform(ceiling / 2, ceiling)


@dataclasses.dataclass
class OutboxEntry:
    result: dict
    job_id: str
    path: Path | None  # None = spool write failed; in-memory only
    spooled_at: float
    retries: int = 0
    parked: bool = False
    # envelopes of the same pass queued right behind this one: the
    # uploader takes them with it (worker.result_worker)
    followers: int = 0


class Outbox:
    def __init__(self, directory: str | Path, max_entries: int = 512):
        self.directory = Path(directory)
        self.directory.mkdir(parents=True, exist_ok=True)
        self.max_entries = int(max_entries)
        self._seq = itertools.count()
        # fleet memory census (ISSUE 17): the spool directory's resident
        # bytes next to the in-HBM stores; last-constructed outbox wins
        from . import memory_census

        memory_census.register("outbox", self.resident_bytes)

    def resident_bytes(self) -> dict:
        """Census provider: spooled envelope bytes on disk (delivery
        spool + parked), plus the file count."""
        files = self._files()
        total = 0
        for path in files:
            try:
                total += path.stat().st_size
            except OSError:
                continue
        return {"bytes": total, "entries": len(files)}

    # --- spool lifecycle ---

    def spool(self, result: dict) -> OutboxEntry:
        """Persist one envelope before its first upload attempt. A failed
        write (full disk, bad mount) degrades to an in-memory entry — the
        job is still delivered this process, just not restart-durable —
        and is logged loudly rather than failing the job."""
        job_id = str(result.get("id", "unknown"))
        now = time.time()
        name = (f"{time.time_ns():020d}-{next(self._seq):04d}-"
                f"{_SAFE_ID.sub('_', job_id)[:80]}.json")
        path: Path | None = self.directory / name
        try:
            payload = json.dumps({"spooled_at": now, "result": result})
            tmp = path.with_name(path.name + ".tmp")
            tmp.write_text(payload)
            os.replace(tmp, path)
        except OSError:
            logger.exception(
                "outbox spool failed for %s; delivery is NOT restart-durable "
                "for this envelope", job_id)
            path = None
        _SPOOLED.inc()
        entry = OutboxEntry(result, job_id, path, now)
        self.refresh_gauges()
        return entry

    def delivered(self, entry: OutboxEntry) -> None:
        """Hive ACKed: the spool file may finally go away."""
        if entry.path is not None:
            try:
                entry.path.unlink(missing_ok=True)
            except OSError:
                logger.warning("could not unlink delivered entry %s",
                               entry.path)
        _DELIVERED.inc()
        self.refresh_gauges()

    def park(self, entry: OutboxEntry, reason: str | None = None) -> None:
        """Permanent hive refusal: take the entry out of the in-process
        retry loop but KEEP it on disk (renamed aside). recover() picks
        parked entries up on the next start — never a silent drop. The
        payload is rewritten with the delivery history (retries, when and
        why it parked) so `tools/outbox_inspect.py` can show an operator
        what happened without the process that knew."""
        entry.parked = True
        if entry.path is not None and not entry.path.name.endswith(".parked"):
            try:
                payload = json.dumps({
                    "spooled_at": entry.spooled_at,
                    "parked_at": time.time(),
                    "retries": entry.retries,
                    "park_reason": reason,
                    "result": entry.result,
                })
                tmp = entry.path.with_name(entry.path.name + ".tmp")
                tmp.write_text(payload)
                os.replace(tmp, entry.path)
                parked = entry.path.with_name(entry.path.name + ".parked")
                os.replace(entry.path, parked)
                entry.path = parked
            except OSError:
                logger.warning("could not park entry %s", entry.path)
        _PARKED.inc()
        self.refresh_gauges()

    def requeue_parked(self, job_id: str | None = None) -> list[Path]:
        """Move parked envelopes back into the delivery spool (strip the
        `.parked` suffix) so the next `recover()` — a worker restart —
        retries them against a hive that may accept them now (e.g. after
        a failover to a fresh primary). `job_id` picks one envelope;
        None requeues every parked one. Returns the restored paths; the
        ops entry point is `tools/outbox_inspect.py --requeue`."""
        restored: list[Path] = []
        for path in sorted(self.directory.glob("*.json.parked")):
            if job_id is not None:
                try:
                    payload = json.loads(path.read_text())
                    result = payload.get("result") or {}
                except (OSError, ValueError):
                    continue
                if str(result.get("id")) != str(job_id):
                    continue
            target = path.with_name(path.name[: -len(".parked")])
            try:
                os.replace(path, target)
                restored.append(target)
            except OSError:
                logger.warning("could not requeue parked entry %s", path)
        self.refresh_gauges()
        return restored

    def recover(self) -> list[OutboxEntry]:
        """Entries spooled by a previous process, oldest first. Unreadable
        files are left in place and logged — an operator can still recover
        the artifacts by hand."""
        entries = []
        for path in self._files():
            try:
                payload = json.loads(path.read_text())
                result = payload["result"]
            except (OSError, ValueError, KeyError, TypeError):
                logger.exception(
                    "unreadable outbox entry %s; leaving it on disk", path)
                continue
            entries.append(OutboxEntry(
                result,
                str(result.get("id", "unknown")),
                path,
                float(payload.get("spooled_at", time.time())),
                retries=int(payload.get("retries", 0) or 0),
                parked=path.name.endswith(".parked"),
            ))
            _RECOVERED.inc()
        entries.sort(key=lambda e: (e.spooled_at, str(e.path)))
        self.refresh_gauges()
        return entries

    def note_retry(self) -> None:
        _RETRIES.inc()

    # --- state for healthz / metrics ---

    def _files(self) -> list[Path]:
        try:
            return sorted(self.directory.glob("*.json")) + sorted(
                self.directory.glob("*.json.parked"))
        except OSError:
            return []

    @property
    def depth(self) -> int:
        return len(self._files())

    def oldest_age_s(self) -> float | None:
        ages = []
        for path in self._files():
            try:
                ages.append(time.time() - path.stat().st_mtime)
            except OSError:
                continue
        return max(ages) if ages else None

    @property
    def saturated(self) -> bool:
        return self.max_entries > 0 and self.depth >= self.max_entries

    def refresh_gauges(self) -> None:
        files = self._files()
        _DEPTH.set(len(files))
        oldest = 0.0
        for path in files:
            try:
                oldest = max(oldest, time.time() - path.stat().st_mtime)
            except OSError:
                continue
        _OLDEST.set(round(oldest, 1))
