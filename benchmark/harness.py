"""One run of one cell: set-up, warm-up, the measured window, the checks.

Driven by data: the cell names a configuration file and a traffic file;
the metrics it reports are the entries of `BENCHMARK.json` that apply to
it, each computed by the reader of that name under `end_to_end/` or
`layer_metrics/`. Nothing here names a cell, a model or a metric, and
nothing knows a network: what does is the module under `families/` that
the configuration names (`load_family`).

The system under test runs in this process: a real `HiveServer` on a
loopback socket and one pristine `Worker` with one slice of the cell's
chips (`hive_server.harness.LocalSwarm`), driven over HTTP from the
client's side. See README.md for the order of events and why.
"""

from __future__ import annotations

import asyncio
import hashlib
import importlib.util
import json
import os
import re
import shutil
import sys
import time
from pathlib import Path

from . import measure

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
TERMINAL = ("done", "failed", "cancelled", "expired")
# warm-up passes: the probe rides in the first and the last, among other
# batchmates; the middle one has fresh prompts only. Three, because the
# encode program has two shapes (the shared negative prompt is new in the
# first pass and cached after) and the last pass must compile nothing.
WARMUP_PASSES = 3
JOB_TIMEOUT_S = 1150.0


class RunFailure(Exception):
    """The run cannot give a result; exit non-zero, print no result."""


def emit(**fields) -> None:
    """An earlier line of output: one JSON object, on the real stdout."""
    print(json.dumps(fields), file=sys.__stdout__, flush=True)


# --- the cell as data -------------------------------------------------------


def load_cell(name: str) -> dict:
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise RunFailure(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((REPO / entry["file"]).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())

    def applies(metric):
        return "workloads" not in metric or name in metric["workloads"]

    end_to_end = [m for m in bench["end_to_end"] if applies(m)]
    reported = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if applies(m) and m["moves"] in reported]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": end_to_end, "per_layer": per_layer}


def apply_rehearsal(spec: dict) -> None:
    """The CPU rehearsal runs the same control flow at a tiny size: each
    file's `rehearsal` block overrides its top-level keys."""
    for part in ("config", "traffic"):
        block = spec[part].get("rehearsal", {})
        for key, value in block.items():
            if isinstance(value, dict) and isinstance(spec[part].get(key), dict):
                spec[part][key] = {**spec[part][key], **value}
            else:
                spec[part][key] = value


# what a module under `families/` has to have (README, "A family")
FAMILY_CONTRACT = ("register", "PIPELINE_TYPE", "job_fields",
                   "check_artifact", "kernel_checks", "denoiser_inputs",
                   "denoiser_reference", "denoiser_serve",
                   "DENOISER_REL_L2_TOL", "compile_operands")


def load_family(config: dict):
    """The module that knows the configuration's network. A family that is
    not there or lacks a name of the contract fails here, before anything
    is built, and not after the window."""
    name = config["family"]
    module = f"benchmark.families.{name}"
    try:
        family = importlib.import_module(module)
    except ModuleNotFoundError as error:
        if error.name != module:
            raise
        raise RunFailure(f"no benchmark/families/{name}.py for family "
                         f"{name!r}") from None
    missing = [n for n in FAMILY_CONTRACT if not hasattr(family, n)]
    if missing:
        raise RunFailure(f"benchmark/families/{name}.py lacks "
                         f"{', '.join(missing)}")
    return family


def load_reader(kind: str, name: str):
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise RunFailure(f"no reader {path.relative_to(REPO)} for metric "
                         f"{name!r}")
    module_spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{re.sub(r'[^0-9a-zA-Z_]', '_', name)}", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module.read


def set_deployment(config: dict) -> None:
    """Every setting the benchmark sets, from the configuration's
    `deployment` block, as the environment the program reads; paths are
    made absolute under the checkout. Must run before `chiaswarm_tpu` is
    imported (`worker.POLL_SECONDS` is read at import)."""
    deployment = config["deployment"]
    for key, value in deployment["env"].items():
        if key in deployment.get("paths", ()):
            value = str(REPO / value)
        os.environ[key] = str(value)
    root = Path(os.environ["SDAAS_ROOT"])
    # a fresh root every run: no WAL, outbox or spool of an earlier one
    shutil.rmtree(root, ignore_errors=True)
    root.mkdir(parents=True)
    # libtpu logs under /tmp unless told otherwise; nothing of a run may
    # land outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", str(root.parent / "tpu_logs"))


# --- the client's side of the hive ------------------------------------------


class Client:
    """HTTP client of the hive's public API, as a submitter uses it."""

    def __init__(self, session, site_uri: str, token: str):
        self.session = session
        self.site = site_uri
        self.headers = {"Authorization": f"Bearer {token}",
                        "Content-type": "application/json"}

    async def _json(self, method: str, path: str, body=None) -> dict:
        data = None if body is None else json.dumps(body)
        async with self.session.request(
                method, f"{self.site}{path}", data=data,
                headers=self.headers) as reply:
            reply.raise_for_status()
            return await reply.json()

    async def submit(self, job: dict) -> str:
        return (await self._json("POST", "/api/jobs", job))["id"]

    async def status(self, job_id: str) -> dict:
        return await self._json("GET", f"/api/jobs/{job_id}")

    async def trace(self, job_id: str) -> dict:
        return await self._json("GET", f"/api/jobs/{job_id}/trace")

    async def cancel(self, job_id: str) -> dict:
        return await self._json("POST", f"/api/jobs/{job_id}/cancel")

    async def artifact(self, href: str) -> bytes:
        async with self.session.get(f"{self.site}{href}",
                                    headers=self.headers) as reply:
            reply.raise_for_status()
            return await reply.read()

    async def wait(self, job_ids: list[str], states=TERMINAL,
                   timeout: float = JOB_TIMEOUT_S) -> list[dict]:
        deadline = time.monotonic() + timeout
        out = []
        for job_id in job_ids:
            while True:
                status = await self.status(job_id)
                if status["status"] in states or status["status"] in TERMINAL:
                    out.append(status)
                    break
                if time.monotonic() > deadline:
                    raise RunFailure(f"job {job_id} still "
                                     f"{status['status']} after {timeout:.0f}s")
                await asyncio.sleep(0.05)
        return out


_SAMPLE = re.compile(r'^(\w+)(?:\{([^}]*)\})? (\S+)$')


def scrape() -> dict:
    """The worker's and hive's counters as `/metrics` would print them
    (one process, so the registry is read in place): {name: {labels:
    value}}, labels as their values joined by commas."""
    from chiaswarm_tpu import telemetry

    out: dict[str, dict[str, float]] = {}
    for line in telemetry.REGISTRY.render().splitlines():
        m = _SAMPLE.match(line)
        if m:
            labels = ",".join(re.findall(r'="([^"]*)"', m.group(2) or ""))
            try:
                out.setdefault(m.group(1), {})[labels] = float(m.group(3))
            except ValueError:
                pass
    out["at_wall"] = {"": time.time()}
    return out


def counter(scraped: dict, name: str, labels: str = "") -> float:
    return scraped.get(name, {}).get(labels, 0.0)


class Window:
    """The measured window: opened by the warm-up, `seconds` long."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.open_wall: float | None = None
        self.open_mono: float | None = None

    def open(self) -> None:
        self.open_wall, self.open_mono = time.time(), time.monotonic()

    @property
    def close_wall(self) -> float | None:
        return None if self.open_wall is None else self.open_wall + self.seconds

    def closed(self) -> bool:
        return (self.open_mono is not None
                and time.monotonic() >= self.open_mono + self.seconds)


# --- jobs from the seed -----------------------------------------------------


class JobMaker:
    """Jobs of the cell's traffic, drawn from the seed: the configuration's
    `job` block, the traffic's `job` block, a running id, and per job a
    seed and what the family's `job_fields` says differs from job to job.
    One generator makes every draw: the family's first, then the seed's."""

    def __init__(self, spec: dict, seed: int, family):
        import random

        self.config, self.traffic = spec["config"], spec["traffic"]
        self.tag = f"{spec['cell']['name']}-{seed}"
        self.fields = family.job_fields
        self.rng = random.Random(seed)
        self.count = 0

    def _job(self, probe: bool) -> dict:
        fields = self.fields(self.rng, self.traffic, self.count, probe)
        seed = (int(self.traffic["probe"]["seed"]) if probe
                else self.rng.getrandbits(31))
        self.count += 1
        job = {**self.config["job"], **self.traffic["job"]}
        job["parameters"] = {**self.config["job"].get("parameters", {}),
                             **self.traffic["job"].get("parameters", {})}
        job.update(id=f"{self.tag}-{self.count:05d}", **fields, seed=seed)
        return job

    def next(self) -> dict:
        return self._job(probe=False)

    def probe(self) -> dict:
        """One job that is the same whatever the running count: the
        traffic's `probe` block gives its seed, the family its fields."""
        return self._job(probe=True)


# --- the run ----------------------------------------------------------------


class Tracer:
    """Traces whole cycles of steady state: starts at the first job that
    ends inside the window, stops at the first job of the
    `trace_cycles`-th later pass to end (or after `trace_max_s`)."""

    def __init__(self, enabled: bool, traffic: dict, window: Window,
                 log_dir: Path):
        self.enabled = enabled
        self.cycles = int(traffic.get("trace_cycles", 1))
        self.max_s = float(traffic.get("trace_max_s", 20.0))
        self.window, self.log_dir = window, log_dir
        self.started_wall = self.stopped_wall = None
        self.passes_seen: list[str] = []
        self._stopper: asyncio.Task | None = None

    def observe(self, record: dict) -> None:
        if not self.enabled or self.window.open_wall is None \
                or self.stopped_wall is not None:
            return
        pass_id = measure.pass_id(record)
        if self.started_wall is None:
            self._start(pass_id)
        elif pass_id not in self.passes_seen:
            self.passes_seen.append(pass_id)
            if len(self.passes_seen) > self.cycles:
                self.stop_soon()

    def _start(self, pass_id: str) -> None:
        import jax

        from .trace.capture import profile_options

        shutil.rmtree(self.log_dir, ignore_errors=True)
        jax.profiler.start_trace(str(self.log_dir),
                                 profiler_options=profile_options())
        self.started_wall = time.time()
        self.passes_seen = [pass_id]
        with jax.profiler.TraceAnnotation(
                f"bench_sync wall={self.started_wall:.6f}"):
            pass
        self._stopper = asyncio.get_running_loop().call_later(
            self.max_s, self.stop_soon)

    def stop_soon(self) -> None:
        if self.started_wall is None or self.stopped_wall is not None:
            return
        self.stopped_wall = time.time()
        if self._stopper is not None:
            self._stopper.cancel()
        import jax

        with jax.profiler.TraceAnnotation(
                f"bench_stop wall={self.stopped_wall:.6f}"):
            pass
        # serialising the trace takes seconds: off the loop, so the hive
        # and the worker keep running (the traced run is not the timed one)
        self._stop_task = asyncio.get_running_loop().run_in_executor(
            None, jax.profiler.stop_trace)

    async def finish(self) -> Path | None:
        if not self.enabled or self.started_wall is None:
            return None
        self.stop_soon()
        await self._stop_task
        from .trace.capture import xplane_files

        files = xplane_files(self.log_dir)
        return files[0] if files else None


async def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
                   started_mono: float, rehearsal: bool = False) -> dict:
    """The whole run; returns the record the metric readers read."""
    import aiohttp
    import jax

    from chiaswarm_tpu.compile_cache import enable_compile_cache
    from chiaswarm_tpu.hive_server.harness import LocalSwarm
    from chiaswarm_tpu.settings import load_settings

    from . import checks

    config, traffic, cell = spec["config"], spec["traffic"], spec["cell"]
    family = load_family(config)
    record: dict = {"spec": spec, "seed": seed, "rehearsal": rehearsal,
                    "failures": []}
    loop = asyncio.get_running_loop()
    settings = load_settings()
    emit(phase="start", cache=str(enable_compile_cache()),
         since_start_s=time.monotonic() - started_mono)

    family.register(seed, record)
    from chiaswarm_tpu import registry

    for model in config.get("resident_models", ()):
        # a worker keeps several models resident (MAX_RESIDENT_PIPELINES):
        # weights only, nothing compiled, never asked for. A name is a
        # model of the cell's own family; one of another says its type
        if isinstance(model, str):
            model = {"model_name": model,
                     "pipeline_type": family.PIPELINE_TYPE}
        await loop.run_in_executor(
            None, registry.get_pipeline, model["model_name"],
            model["pipeline_type"])
    dtype = jax.numpy.dtype(config["kernel_dtype"])
    failures, readings = await loop.run_in_executor(
        None, family.kernel_checks, config, dtype, rehearsal)
    record["failures"] += failures
    record["kernel_readings"] = readings
    emit(phase="kernels", readings=readings,
         since_start_s=time.monotonic() - started_mono)
    record["scrape_before_worker"] = scrape()

    swarm = LocalSwarm(n_workers=0, chips_per_job=cell["chips"],
                       settings=settings)
    await swarm.start()
    session = aiohttp.ClientSession()
    try:
        client = Client(session, swarm.hive.uri, settings.sdaas_token)
        jobs = JobMaker(spec, seed, family)
        gang = min(int(traffic["clients"]),
                   int(settings.hive_max_jobs_per_poll))
        window = Window(seconds)
        tracer = Tracer(trace, traffic, window,
                        Path(os.environ["SDAAS_ROOT"]) / "profile")
        generator = importlib.import_module(
            f"benchmark.generators.{traffic['generator']}")

        # warm-up: every pass is submitted while the one before holds the
        # slice (the first before the worker exists), so the hive always
        # has a whole gang queued when the worker polls
        warm: list[list[dict]] = []
        probes: list[str] = []
        load_task = None

        async def submit_pass(with_probe: bool) -> None:
            batch = ([jobs.probe()] if with_probe else []) + [
                jobs.next() for _ in range(gang - int(with_probe))]
            if with_probe:
                probes.append(batch[0]["id"])
            for job in batch:
                await client.submit(job)
            warm.append(batch)

        await submit_pass(with_probe=True)
        swarm.add_worker("benchmark-worker")
        compiled = counter(scrape(), "swarm_xla_compiles_total")
        for n in range(1, WARMUP_PASSES + 1):
            ids = [job["id"] for job in warm[n - 1]]
            await client.wait(ids, states=("leased",))
            await asyncio.sleep(0.3)  # the slice is claimed by now
            if n < WARMUP_PASSES:
                await submit_pass(with_probe=(n + 1 == WARMUP_PASSES))
            else:
                # the load starts behind the last warm-up pass, so a
                # standing queue exists when the window opens
                load_task = asyncio.create_task(generator.run(
                    client, traffic, jobs.next, window, tracer.observe))
            statuses = await client.wait(ids)
            bad = [s for s in statuses if s["status"] != "done"
                   or s.get("attempts") != 1]
            if bad:
                raise RunFailure(f"warm-up job failed: {bad[0]}")
            before, compiled = compiled, counter(
                scrape(), "swarm_xla_compiles_total")
            emit(phase="warmup", number=n, rows=len(ids),
                 compiles=compiled - before,
                 since_start_s=time.monotonic() - started_mono)
        if compiled != before:
            raise RunFailure(
                f"{compiled - before:.0f} program(s) compiled in the last "
                "warm-up pass: this traffic needs a warm-up the harness "
                "does not have, and the window would not be compile-free")

        window.open()
        record["setup_s"] = window.open_mono - started_mono
        record["scrape_open"] = scrape()
        emit(phase="window_open", setup_s=record["setup_s"])
        closing = asyncio.create_task(
            at_window_close(window, record, swarm.workers[0], family))
        load = await load_task  # returns once the leased jobs have ended
        trace_file = await tracer.finish()

        # outside the window: timelines, artifacts, the probe, the reference
        for job in load:
            job["trace"] = await client.trace(job["id"])
        record["jobs"] = load
        record["window"] = {"open_wall": window.open_wall,
                            "close_wall": window.close_wall}
        await check_jobs(client, record, family, probes)
        record["scrape_end"] = scrape()
        check_kernel_paths_and_compiles(record, config)
        record["trace_file"] = trace_file
    finally:
        await session.close()
        await swarm.stop()

    pipe, inputs, want, seconds = await closing
    failures, reading = checks.denoiser(family, pipe, inputs, want)
    record["failures"] += failures
    record["denoiser_reading"] = reading
    emit(phase="reference", reading=reading, host_seconds=seconds)
    return record


async def at_window_close(window: Window, record: dict, worker, family):
    """What happens the moment the window closes: counters and the memory
    peak are read, and the plain reference's half of `correct` 5 starts
    (the family's, on the host CPU) while the last leased pass drains and
    the artifacts are checked, never inside the window."""
    from chiaswarm_tpu import registry

    await asyncio.sleep(max(window.close_wall - time.time(), 0.0))
    record["scrape_close"] = scrape()
    record["memory"] = memory_stats()
    started = time.monotonic()
    job = record["spec"]["config"]["job"]
    pipe = registry.get_pipeline(  # resident: the worker built it
        job["model_name"],
        job.get("parameters", {}).get("pipeline_type", family.PIPELINE_TYPE),
        chipset=worker.allocator.slices[0])
    inputs = family.denoiser_inputs(
        pipe, record["spec"]["config"], record["seed"])
    want = await asyncio.get_running_loop().run_in_executor(
        None, family.denoiser_reference, pipe, inputs)
    return pipe, inputs, want, time.monotonic() - started


def memory_stats() -> dict:
    import jax

    peak = 0
    for device in jax.local_devices():
        stats = device.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"peak_bytes": peak}


async def check_jobs(client: Client, record: dict, family,
                     probes: list[str]) -> None:
    """correct 1 and 3: every job of the window, and the probe's two
    rides. What a primary artifact has to be is the family's to say
    (`check_artifact`); that the probe's two are the same bytes is not."""
    config = record["spec"]["config"]
    open_wall = record["window"]["open_wall"]
    attempted = failed = 0
    for job in record["jobs"]:
        job["in_window"] = job["submit_wall"] >= open_wall
        if not job["in_window"] or job["withdrawn"]:
            continue
        attempted += 1
        status = job["status"]
        result = status.get("result") or {}
        why = None
        if status["status"] != "done" or status.get("attempts") != 1 \
                or result.get("fatal_error") \
                or "error" in (result.get("pipeline_config") or {}):
            why = (f"ended {status['status']} after "
                   f"{status.get('attempts')} attempt(s): "
                   f"{status.get('error')}")
        else:
            ref = result["artifacts"]["primary"]
            why = family.check_artifact(
                await client.artifact(ref["href"]), ref, config)
        if why:
            failed += 1
            job["failure"] = why
            record["failures"].append(f"job {job['id']}: {why}")
    record["attempted"], record["failed"] = attempted, failed
    if attempted == 0:
        record["failures"].append("no job was attempted in the window")

    digests = []
    for job_id in probes:
        status = await client.status(job_id)
        ref = status["result"]["artifacts"]["primary"]
        blob = await client.artifact(ref["href"])
        why = family.check_artifact(blob, ref, config)
        if why:
            record["failures"].append(f"probe {job_id}: {why}")
        digests.append(hashlib.sha256(blob).hexdigest())
    record["probe_sha256"] = digests
    if len(set(digests)) != 1:
        record["failures"].append(
            f"the probe (one job, one seed) gave {len(set(digests))} "
            f"artifacts among different batchmates: {digests}")


def check_kernel_paths_and_compiles(record: dict, config: dict) -> None:
    """correct 2: the kernel paths the worker's programs traced, and no
    compile inside the window."""
    before, end = record["scrape_before_worker"], record["scrape_end"]
    traced = {labels: end["swarm_kernel_traces_total"].get(labels, 0.0)
              - before.get("swarm_kernel_traces_total", {}).get(labels, 0.0)
              for labels in end.get("swarm_kernel_traces_total", {})}
    record["kernel_traces"] = traced
    missing = [labels for labels in config["expected_kernel_paths"]
               if not traced.get(labels, 0.0) > 0]
    record["kernel_paths_missing"] = len(missing)
    for labels in missing:
        record["failures"].append(
            f"the worker's programs never traced {labels}: {traced}")
    moved = (counter(record["scrape_close"], "swarm_xla_compiles_total")
             - counter(record["scrape_open"], "swarm_xla_compiles_total"))
    record["window_compiles"] = moved
    if moved:
        record["failures"].append(
            f"{moved:.0f} program(s) compiled inside the window")


# --- from the record to the last line ---------------------------------------


def device_block(record: dict) -> dict:
    device = dict(record["device"])
    device["memory_peak_bytes"] = record["memory"]["peak_bytes"]
    trace = record.get("trace")
    if trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    return device


def compared(record: dict) -> dict:
    """Every number `correct` compared, as `[number, limit]` under a short
    name: what the result line carries last and stderr ends with."""
    out = {}
    for reading in record["kernel_readings"]:
        kernel = next(iter(reading))  # {<kernel>: shape, max_abs, limit}
        shape = "x".join(str(n) for n in reading[kernel])
        out[f"{kernel}_{shape}_max_abs"] = [reading["max_abs"],
                                           reading["limit"]]
    reading = record["denoiser_reading"]
    out["denoiser_rel_l2"] = [reading["rel_l2"], reading["limit"]]
    out["jobs_failed"] = [record["failed"], 0]
    out["probe_distinct_sha256"] = [len(set(record["probe_sha256"])), 1]
    out["kernel_paths_missing"] = [record["kernel_paths_missing"], 0]
    out["window_compiles"] = [record["window_compiles"], 0]
    return out


# what a CPU rehearsal may print: counts. Everything else it computes (to
# walk the readers) and then withholds.
REHEARSAL_COUNTS = ("count", "rows")


def report(record: dict, traced: bool) -> dict:
    """Reduce the trace (if any), run the cell's readers, and build the
    last line. With `--trace 0` the metrics are the cell's end-to-end
    metrics, with `--trace 1` its per-layer metrics."""
    from . import breakdown
    from .trace.reduce import reduce_trace

    spec = record["spec"]
    trace_file = record.get("trace_file")
    if traced and trace_file:
        record["trace"] = reduce_trace(
            trace_file, stretch_marks=("bench_sync", "bench_stop"),
            kernels=tuple(spec["config"].get("traced_kernels", ())))
        # <log dir>/plugins/profile/<time>/<host>.xplane.pb
        shutil.rmtree(trace_file.parents[3], ignore_errors=True)
    if traced and not record.get("trace") and not record["rehearsal"]:
        raise RunFailure("the traced run holds no device operation")

    kind, wanted = (("layer_metrics", spec["per_layer"]) if traced
                    else ("end_to_end", spec["end_to_end"]))
    metrics = {}
    for metric in wanted:
        value = load_reader(kind, metric["name"])(record)
        if value is None:
            continue  # nothing to read: the metric is left out of the line
        if record["rehearsal"] and metric["unit"] not in REHEARSAL_COUNTS:
            value = "not measured"
        metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}

    jobs = record["jobs"]
    emit(phase="summary", failures=record["failures"],
         jobs=len(jobs), withdrawn=sum(j["withdrawn"] for j in jobs),
         before_window=sum(not j.get("in_window") for j in jobs),
         latency_samples=len([j for j in jobs if j.get("in_window")
                              and not j["withdrawn"]]),
         submit_to_settle_s=[
             round(measure.stamp(j, "settle") - j["submit_wall"], 3)
             for j in measure.window_jobs(record)
             if measure.stamp(j, "settle") is not None],
         probe_sha256=record.get("probe_sha256"),
         kernel_traces=record.get("kernel_traces"),
         weights_phases=record.get("weights_phases"),
         notes=record.get("notes"))
    result = {"correct": not record["failures"],
              "attempted": record["attempted"], "failed": record["failed"],
              "metrics": metrics}
    if record["rehearsal"]:
        result["device"] = {**record["device"], "rehearsal":
                            "CPU rehearsal: counts only, no device number"}
    else:
        result["device"] = device_block(record)
        built = breakdown.build(record) if traced else None
        if built:
            result["breakdown"] = built
    result["compared"] = compared(record)  # last in the line
    return result
