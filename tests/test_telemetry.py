"""Telemetry unit tests: registry semantics, Prometheus text rendering,
span/trace nesting, the /metrics + /healthz aiohttp app, and the JSON log
formatter (log_setup satellite)."""

import asyncio
import json
import logging
import time

import pytest

from chiaswarm_tpu.telemetry import (
    STAGE_METRIC,
    Registry,
    Span,
    build_metrics_app,
    trace_job,
)


# --- counter / gauge / histogram semantics ---


def test_counter_inc_and_labels():
    reg = Registry()
    c = reg.counter("jobs_total", "jobs", ("outcome",))
    c.inc(outcome="ok")
    c.inc(2, outcome="ok")
    c.inc(outcome="fatal")
    assert c.value(outcome="ok") == 3
    assert c.value(outcome="fatal") == 1
    assert c.value(outcome="never_seen") == 0
    assert c.total() == 4


def test_counter_rejects_negative_and_wrong_labels():
    reg = Registry()
    c = reg.counter("c_total", "", ("a",))
    with pytest.raises(ValueError):
        c.inc(-1, a="x")
    with pytest.raises(ValueError):
        c.inc(b="x")  # unknown label
    with pytest.raises(ValueError):
        c.inc()  # missing label


def test_gauge_set_inc_dec():
    reg = Registry()
    g = reg.gauge("depth")
    g.set(5)
    g.inc()
    g.dec(2)
    assert g.value() == 4


def test_histogram_buckets_sum_count():
    reg = Registry()
    h = reg.histogram("lat_seconds", "", ("stage",), buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.1, 0.5, 20.0):
        h.observe(v, stage="s")
    assert h.count(stage="s") == 4
    assert h.sum(stage="s") == pytest.approx(20.65)
    # a value equal to a bound lands in that bucket (le semantics)
    rendered = h.render()
    assert 'lat_seconds_bucket{stage="s",le="0.1"} 2' in rendered
    assert 'lat_seconds_bucket{stage="s",le="1"} 3' in rendered
    assert 'lat_seconds_bucket{stage="s",le="10"} 3' in rendered
    assert 'lat_seconds_bucket{stage="s",le="+Inf"} 4' in rendered
    assert 'lat_seconds_count{stage="s"} 4' in rendered


def test_registry_get_or_create_is_idempotent_and_type_safe():
    reg = Registry()
    a = reg.counter("x_total", "help", ("l",))
    b = reg.counter("x_total", "other help", ("l",))
    assert a is b
    with pytest.raises(ValueError):
        reg.gauge("x_total")  # same name, different type
    with pytest.raises(ValueError):
        reg.counter("x_total", "", ("other",))  # different label set


# --- Prometheus text rendering ---


def test_render_escapes_label_values():
    reg = Registry()
    c = reg.counter("esc_total", "", ("model",))
    c.inc(model='a"b\\c\nd')
    out = reg.render()
    assert 'esc_total{model="a\\"b\\\\c\\nd"} 1' in out


def test_render_label_ordering_is_declaration_order():
    reg = Registry()
    c = reg.counter("ord_total", "", ("zeta", "alpha"))
    c.inc(alpha="1", zeta="2")
    # declared order (zeta first), NOT alphabetical
    assert 'ord_total{zeta="2",alpha="1"} 1' in reg.render()


def test_render_help_and_type_lines():
    reg = Registry()
    reg.counter("a_total", "counts a\nthings").inc()
    reg.gauge("b").set(1)
    out = reg.render()
    assert "# HELP a_total counts a\\nthings" in out
    assert "# TYPE a_total counter" in out
    assert "# TYPE b gauge" in out
    # histograms put le LAST, after the declared labels
    h = reg.histogram("h_seconds", "", ("stage",), buckets=(1.0,))
    h.observe(0.5, stage="s")
    assert 'h_seconds_bucket{stage="s",le="1"} 1' in reg.render()


# --- spans / traces ---


def test_span_records_histogram_and_timings_dict():
    reg = Registry()
    timings = {}
    with Span("denoise", timings, key="denoise_decode_s", registry=reg):
        time.sleep(0.01)
    h = reg.get(STAGE_METRIC)
    assert h.count(stage="denoise") == 1
    assert timings["denoise_decode_s"] >= 0.01
    assert h.sum(stage="denoise") >= 0.01


def test_span_records_on_exception():
    reg = Registry()
    with trace_job("job-err") as trace:
        with pytest.raises(RuntimeError):
            with Span("compile", registry=reg):
                raise RuntimeError("trace failed")
    assert reg.get(STAGE_METRIC).count(stage="compile") == 1
    assert [s["name"] for s in trace.spans] == ["compile"]


def _ends(span):
    return span["start_wall"] + span["seconds"]


def test_span_records_when_where_and_how_long():
    reg = Registry()
    before = time.time()
    with trace_job("job-1") as trace:
        with Span("outer", registry=reg):
            with Span("inner", registry=reg) as inner:
                time.sleep(0.002)
    after = time.time()
    # children end first, so they are recorded first
    assert [s["name"] for s in trace.spans] == ["inner", "outer"]
    child, parent = trace.spans
    assert set(child) == {"name", "thread", "start_wall", "seconds"}
    # on the wall clock, unrounded, on the thread that opened the trace
    assert before <= parent["start_wall"] <= child["start_wall"] <= after
    assert child["seconds"] == inner.elapsed >= 0.002
    assert child["seconds"] != round(child["seconds"], 3)
    assert child["thread"] == parent["thread"] == "slice"
    # a child lies inside its parent (an end is a wall start plus a
    # perf_counter duration: 0.1 ms of slack)
    assert _ends(child) <= _ends(parent) + 1e-4
    # outside a trace a span still feeds the histogram, and nothing else
    with Span("outer", registry=reg):
        pass
    assert reg.get(STAGE_METRIC).count(stage="outer") == 2
    assert len(trace.spans) == 2


@pytest.mark.parametrize("how", ["entered", "measured_elsewhere"])
def test_trace_job_collects_stages_into_timings_and_spans(how):
    """One primitive for a stage timed in place and for one measured
    elsewhere (queue wait, stamped by the scheduler): histogram, timings
    key and span alike."""
    reg = Registry()
    timings = {}
    with trace_job("job-42") as trace:
        if how == "entered":
            with Span("queue_wait", timings, registry=reg):
                time.sleep(0.002)
            sealed = trace.spans
        else:
            sealed = []  # the envelope's list: the pass's trace is closed
            Span("queue_wait", timings, registry=reg, thread="wait",
                 spans=sealed).record(1000.5, 1.25)
            assert trace.spans == []
    assert reg.get(STAGE_METRIC).label_values("stage") == ["queue_wait"]
    [span] = sealed
    assert span["name"] == "queue_wait"
    assert timings["queue_wait_s"] == round(span["seconds"], 3)
    if how == "measured_elsewhere":
        assert span == {"name": "queue_wait", "thread": "wait",
                        "start_wall": 1000.5, "seconds": 1.25}


def test_spans_reach_their_own_trace_across_threads():
    """Two passes on two executor threads keep their spans apart; a helper
    thread running under a copy of a pass's context joins that pass under
    its own thread's name."""
    import contextvars
    import threading
    from concurrent.futures import ThreadPoolExecutor

    reg = Registry()
    gate = threading.Barrier(2)

    def ship():
        with Span("ship", registry=reg):
            pass

    def one_pass(job_id):
        with trace_job(job_id) as trace:
            gate.wait(timeout=5)
            with Span(f"work-{job_id}", registry=reg):
                helper = threading.Thread(
                    name=f"helper-{job_id}",
                    target=contextvars.copy_context().run, args=(ship,))
                helper.start()
                helper.join(timeout=5)
            gate.wait(timeout=5)
        return trace.spans

    with ThreadPoolExecutor(2) as pool:
        a, b = pool.map(one_pass, ["a", "b"])
    assert [(s["name"], s["thread"]) for s in a] == [
        ("ship", "helper-a"), ("work-a", "slice")]
    assert [(s["name"], s["thread"]) for s in b] == [
        ("ship", "helper-b"), ("work-b", "slice")]
    # a thread that copied no context belongs to no pass
    with trace_job("c") as trace:
        stray = threading.Thread(target=ship)
        stray.start()
        stray.join(timeout=5)
    assert trace.spans == []


def test_span_needs_no_jax(monkeypatch):
    """The hive's process never loads jax: no annotation, same record.
    Where jax is loaded the span opens a `swarm/<stage>` annotation."""
    import sys

    from chiaswarm_tpu import telemetry

    monkeypatch.setattr(telemetry, "_TraceAnnotation", None)
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    reg = Registry()
    with trace_job("hive-side") as trace:
        with Span("admit", registry=reg) as span:
            assert span._annotation is None
    assert [s["name"] for s in trace.spans] == ["admit"]
    assert telemetry._TraceAnnotation is None
    monkeypatch.undo()

    import jax

    opened = []

    class Recorder(jax.profiler.TraceAnnotation):
        def __init__(self, name):
            opened.append(name)
            super().__init__(name)

    monkeypatch.setattr(telemetry, "_TraceAnnotation", Recorder)
    with Span("denoise", registry=reg):
        pass
    assert opened == ["swarm/denoise"]


def test_a_span_recorded_across_an_await_opens_no_annotation(monkeypatch):
    """A stage that runs across an `await` on the event loop (the poll's
    round trip, a job's waits) is stamped with `record()` once it is
    over: histogram and envelope as any span, and no annotation, which
    opened on the loop's thread would nest wrongly across coroutines."""
    import jax

    from chiaswarm_tpu import telemetry

    opened = []

    class Recorder(jax.profiler.TraceAnnotation):
        def __init__(self, name):
            opened.append(name)
            super().__init__(name)

    monkeypatch.setattr(telemetry, "_TraceAnnotation", Recorder)
    reg = Registry()
    envelope = []

    async def other():  # runs on the loop inside the stage
        with Span("other", registry=reg):
            await asyncio.sleep(0)

    async def stage():
        sent = time.time()
        await asyncio.gather(asyncio.sleep(0.01), other())
        span = Span("poll", registry=reg, thread="poll", spans=envelope)
        span.record(sent, time.time() - sent)
        return sent, span

    sent, span = asyncio.run(stage())
    assert opened == ["swarm/other"]
    assert reg.get(STAGE_METRIC).count(stage="poll") == 1
    assert reg.get(STAGE_METRIC).sum(stage="poll") == span.elapsed >= 0.01
    assert envelope == [{"name": "poll", "thread": "poll",
                         "start_wall": sent, "seconds": span.elapsed}]


def test_slice_free_seconds_counts_between_passes():
    import jax

    from chiaswarm_tpu import telemetry
    from chiaswarm_tpu.chips.device import ChipSet

    chipset = ChipSet(jax.devices()[:1], slice_id=9041)
    free = telemetry.REGISTRY.get("swarm_slice_free_seconds_total")

    def callback(_device, _model, **_kwargs):
        return {}, {}

    with trace_job("p1") as trace:
        chipset(callback, model_name="m", seed=1)
    # nothing before the first pass
    assert free.value(slice="9041") == 0.0
    assert [s["name"] for s in trace.spans] == ["pass"]
    time.sleep(0.02)
    _, config = chipset(callback, model_name="m", seed=1)
    first = free.value(slice="9041")
    assert 0.02 <= first < 1.0
    assert config["timings"]["job_s"] >= 0.0
    chipset.run_batched(lambda _device, requests: [({}, {})], [{"seed": 2}])
    assert free.value(slice="9041") > first


def test_trace_job_pins_current_job_id():
    from chiaswarm_tpu.telemetry import current_job_id

    assert current_job_id.get() is None
    with trace_job("job-7"):
        assert current_job_id.get() == "job-7"
    assert current_job_id.get() is None


# --- HTTP endpoints (aiohttp.test_utils) ---


def test_metrics_and_healthz_endpoints():
    from aiohttp.test_utils import TestClient, TestServer

    reg = Registry()
    reg.counter("swarm_jobs_completed_total", "", ("outcome",)).inc(
        outcome="ok")

    health = {
        "last_poll_age_s": 2.5,
        "resident_models": ["test/tiny-sd"],
        "slices": [{"slice_id": 0, "busy": False}],
    }

    async def scenario():
        app = build_metrics_app(reg, health=lambda: dict(health))
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.get("/metrics")
            assert resp.status == 200
            assert "text/plain" in resp.headers["Content-Type"]
            body = await resp.text()
            assert 'swarm_jobs_completed_total{outcome="ok"} 1' in body

            resp = await client.get("/healthz")
            assert resp.status == 200
            payload = await resp.json()
            assert payload["status"] == "ok"
            assert payload["last_poll_age_s"] == 2.5
            assert payload["resident_models"] == ["test/tiny-sd"]
            assert payload["slices"][0]["busy"] is False
        finally:
            await client.close()

    asyncio.run(scenario())


def test_healthz_degrades_to_503():
    from aiohttp.test_utils import TestClient, TestServer

    async def scenario():
        app = build_metrics_app(
            Registry(), health=lambda: {"status": "stale"})
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.get("/healthz")
            assert resp.status == 503
            assert (await resp.json())["status"] == "stale"
        finally:
            await client.close()

    asyncio.run(scenario())

    async def broken():
        app = build_metrics_app(
            Registry(), health=lambda: 1 / 0)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.get("/healthz")
            assert resp.status == 503
            assert "ZeroDivisionError" in (await resp.json())["error"]
        finally:
            await client.close()

    asyncio.run(broken())


# --- JSON log formatter (log_setup satellite) ---


def _record(msg="hello", **extra):
    record = logging.LogRecord(
        "chiaswarm_tpu.worker", logging.INFO, __file__, 1, msg, (), None)
    for k, v in extra.items():
        setattr(record, k, v)
    return record


def test_json_formatter_carries_job_id_from_trace():
    from chiaswarm_tpu.log_setup import JsonFormatter

    fmt = JsonFormatter()
    with trace_job("job-99"):
        payload = json.loads(fmt.format(_record("working")))
    assert payload["message"] == "working"
    assert payload["job_id"] == "job-99"
    assert payload["level"] == "INFO"
    assert payload["logger"] == "chiaswarm_tpu.worker"

    # explicit extra beats the contextvar; no trace -> no job_id key
    payload = json.loads(fmt.format(_record("x", job_id="override")))
    assert payload["job_id"] == "override"
    payload = json.loads(fmt.format(_record("y")))
    assert "job_id" not in payload


def test_setup_logging_json_format(tmp_path):
    from chiaswarm_tpu.log_setup import setup_logging

    root = logging.getLogger()
    before = list(root.handlers)
    setup_logging(tmp_path / "w.log", "INFO", log_format="json")
    try:
        with trace_job("job-json"):
            logging.getLogger("t.json").info("structured %s", "line")
        handler = [h for h in root.handlers if h not in before][0]
        handler.flush()
        lines = (tmp_path / "w.log").read_text().strip().splitlines()
        payload = json.loads(lines[-1])
        assert payload["message"] == "structured line"
        assert payload["job_id"] == "job-json"
    finally:
        for h in list(root.handlers):
            if h not in before:
                root.removeHandler(h)
                h.close()


# --- on-demand profiler capture hook (ISSUE 8) ---


def test_debug_profile_route_gating_and_capture():
    """POST /debug/profile: absent without a callback, 403 while the
    Settings gate is closed (PermissionError), 409 while a capture runs
    (RuntimeError), 200 + detail when the capture callback succeeds,
    and 400 for nonsense durations."""
    from aiohttp.test_utils import TestClient, TestServer

    async def scenario():
        # no callback -> no route
        app = build_metrics_app(Registry())
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            assert (await client.post("/debug/profile")).status == 404
        finally:
            await client.close()

        calls = []

        async def capture(seconds):
            calls.append(seconds)
            return {"path": "/tmp/profiles/trace_x", "seconds": seconds}

        app = build_metrics_app(Registry(), profile=capture)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.post("/debug/profile?seconds=0.5")
            assert resp.status == 200
            payload = await resp.json()
            assert payload["status"] == "ok"
            assert payload["path"].endswith("trace_x")
            assert calls == [0.5]

            assert (await client.post(
                "/debug/profile?seconds=nope")).status == 400
            assert (await client.post(
                "/debug/profile?seconds=0")).status == 400
            assert (await client.post(
                "/debug/profile?seconds=1e9")).status == 400
        finally:
            await client.close()

        async def gated(seconds):
            raise PermissionError("profiler capture is disabled")

        app = build_metrics_app(Registry(), profile=gated)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.post("/debug/profile")
            assert resp.status == 403
            assert "disabled" in (await resp.json())["message"]
        finally:
            await client.close()

        async def busy(seconds):
            raise RuntimeError("a profiler capture is already running")

        app = build_metrics_app(Registry(), profile=busy)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            assert (await client.post("/debug/profile")).status == 409
        finally:
            await client.close()

        # /debug/profile MUTATES, so unlike the read-only GETs it
        # honors the worker's bearer token when one is configured
        app = build_metrics_app(Registry(), profile=capture, token="tok")
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            assert (await client.post("/debug/profile")).status == 401
            resp = await client.post(
                "/debug/profile?seconds=0.1",
                headers={"Authorization": "Bearer tok"})
            assert resp.status == 200
            # the GETs stay unauthenticated (scrape contract unchanged)
            assert (await client.get("/metrics")).status == 200
        finally:
            await client.close()

    asyncio.run(scenario())


def test_worker_capture_profile_knob_and_output(sdaas_root, monkeypatch):
    """The worker's capture callback: PermissionError while the
    profiler_capture knob is off; with it on, the (stubbed) jax.profiler
    trace context runs for the requested window and the reply names the
    output directory under $SDAAS_ROOT/profiles/."""
    import contextlib

    import jax.profiler

    from chiaswarm_tpu.chips.allocator import SliceAllocator
    from chiaswarm_tpu.settings import Settings
    from chiaswarm_tpu.worker import Worker

    traced_dirs = []

    @contextlib.contextmanager
    def fake_trace(path):
        traced_dirs.append(path)
        yield

    monkeypatch.setattr(jax.profiler, "trace", fake_trace)

    async def scenario():
        worker = Worker(
            settings=Settings(sdaas_token="t", metrics_port=0),
            allocator=SliceAllocator(chips_per_job=0),
            hive_uri="http://127.0.0.1:1/api")
        with pytest.raises(PermissionError):
            await worker._capture_profile(0.01)
        assert traced_dirs == []

        worker.settings = Settings(
            sdaas_token="t", metrics_port=0, profiler_capture=True)
        detail = await worker._capture_profile(0.01)
        assert detail["seconds"] == 0.01
        [path] = traced_dirs
        assert "/profiles/" in f"{path}/"
        assert detail["path"] == str(path)
        await worker.hive.close()

    asyncio.run(scenario())


# --- startup marks (ISSUE 52) ---


def test_a_startup_mark_set_twice_keeps_its_first_value():
    from chiaswarm_tpu import telemetry

    marks = telemetry.REGISTRY.get("swarm_startup_seconds")
    telemetry.mark_startup("test_mark_set_twice")
    first = marks.value(mark="test_mark_set_twice")
    # seconds since the process's start as the OS has it: this test did
    # not run before its process existed, nor a day after
    assert 0 < first < 86400
    time.sleep(0.02)
    telemetry.mark_startup("test_mark_set_twice")
    assert marks.value(mark="test_mark_set_twice") == first
    assert 'swarm_startup_seconds{mark="test_mark_set_twice"}' in (
        telemetry.REGISTRY.render())


def test_the_process_start_is_the_os_own_and_not_after_the_import():
    from chiaswarm_tpu import telemetry

    assert telemetry.PROCESS_START_WALL <= telemetry._IMPORTED_WALL
    # pytest had been up a while when the first test imported the module:
    # where /proc can be read the start lies before the import, not at it
    import os

    if os.path.exists("/proc/self/stat") and os.path.exists("/proc/uptime"):
        assert telemetry.PROCESS_START_WALL < telemetry._IMPORTED_WALL


def test_the_first_pass_span_sets_both_pass_marks_once():
    from chiaswarm_tpu import telemetry
    from chiaswarm_tpu.chips.device import _pass_span

    marks = telemetry.REGISTRY.get("swarm_startup_seconds")
    with _pass_span() as held:
        time.sleep(0.01)
    start = marks.value(mark="first_pass_start")
    end = marks.value(mark="first_pass_end")
    assert 0 < start < end
    assert held.elapsed >= 0.01
    with pytest.raises(RuntimeError):
        with _pass_span():  # a failed pass ends too, and is no first one
            raise RuntimeError("pass failed")
    assert marks.value(mark="first_pass_start") == start
    assert marks.value(mark="first_pass_end") == end
