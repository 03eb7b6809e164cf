"""Serving-path cost plane (ISSUE 17): the shared FLOPs/MFU vocabulary
(costs.py), the compiled-program ledger with its analytic-vs-XLA
cross-check (programs.py), the fleet memory census (memory_census.py),
the /debug/{programs,memory} endpoints, and the worker's low-headroom
health degradation."""

import asyncio
import time

import pytest

from chiaswarm_tpu import costs, memory_census, programs, telemetry


@pytest.fixture(autouse=True)
def clean_ledger():
    programs.reset()
    yield
    programs.reset()


# --- costs.py: peak table, pass/job stamps, divergence -----------------------


class FakeDevice:
    def __init__(self, kind):
        self.device_kind = kind


def test_peak_tflops_prefix_match_and_unknown():
    assert costs.peak_tflops(FakeDevice("TPU v4")) == 275.0
    # generation suffixes ride the prefix: "TPU v5 lite" devices report
    # chip counts etc. after the kind
    assert costs.peak_tflops(FakeDevice("TPU v5 lite")) == 197.0
    assert costs.peak_tflops(FakeDevice("TPU v5p")) == 459.0
    assert costs.peak_tflops(FakeDevice("TPU v6 lite")) == 918.0
    # a platform with no peak (CPU) reports None — MFU must read null,
    # never a made-up ratio against the wrong denominator
    assert costs.peak_tflops(FakeDevice("cpu")) is None
    assert costs.peak_tflops(FakeDevice("")) is None
    assert costs.peak_tflops(object()) is None


def test_peak_tflops_unknown_tpu_kind_is_an_error(monkeypatch):
    # no default and no environment override: a TPU the table does not
    # know must be added with its source, not guessed
    monkeypatch.setenv("BENCH_PEAK_TFLOPS", "123.5")
    assert costs.peak_tflops(FakeDevice("cpu")) is None
    with pytest.raises(ValueError, match="TPU v9"):
        costs.peak_tflops(FakeDevice("TPU v9"))
    unknown = FakeDevice("some-new-kind")
    unknown.platform = "tpu"
    with pytest.raises(ValueError, match="some-new-kind"):
        costs.peak_tflops(unknown)


def test_pass_cost_math_and_metrics():
    flops_metric = telemetry.REGISTRY.get("swarm_pass_flops_total")
    before = flops_metric.value(model="m-test")
    figures = costs.pass_cost(
        model="m-test", pass_flops=2e12, denoise_s=4.0, chips=2,
        device=FakeDevice("TPU v4"), geometry="tensor2")
    assert figures["pass_flops"] == 2_000_000_000_000
    assert figures["denoise_s"] == 4.0
    # 2e12 flops / 4 s = 0.5 TFLOP/s achieved; 275 peak * 2 chips
    assert figures["tflops_per_s"] == 0.5
    assert figures["chips"] == 2
    assert figures["peak_tflops_per_chip"] == 275.0
    assert figures["mfu"] == 0.0009
    assert flops_metric.value(model="m-test") == before + 2e12
    mfu_metric = telemetry.REGISTRY.get("swarm_pass_mfu")
    assert mfu_metric.value(model="m-test", geometry="tensor2") == 0.0009


def test_pass_cost_degrades_without_span_or_peak():
    # a span that rounds to 0 on toy configs: no rate, no MFU, but the
    # FLOPs are still counted (pure work accounting)
    z = costs.pass_cost(model="m-z", pass_flops=1e9, denoise_s=0.0,
                        chips=1, device=FakeDevice("TPU v4"))
    assert z["pass_flops"] == 1_000_000_000
    assert z["tflops_per_s"] is None and z["mfu"] is None
    n = costs.pass_cost(model="m-z", pass_flops=1e9, denoise_s=None,
                        chips=1, device=FakeDevice("TPU v4"))
    assert n["tflops_per_s"] is None and n["mfu"] is None
    # no peak entry (CPU): achieved rate reported, MFU null
    c = costs.pass_cost(model="m-z", pass_flops=1e9, denoise_s=2.0,
                        chips=1, device=FakeDevice("cpu"))
    assert c["tflops_per_s"] == 0.0005
    assert c["peak_tflops_per_chip"] is None and c["mfu"] is None
    # defensive clamps: negative flops -> 0, chips floor of 1
    d = costs.pass_cost(model="m-z", pass_flops=-5, denoise_s=1.0, chips=0)
    assert d["pass_flops"] == 0 and d["chips"] == 1


def test_job_cost_stamps_own_flops_over_shared_pass_figures():
    figures = {"pass_flops": 100, "mfu": 0.5, "denoise_s": 1.0}
    stamp = costs.job_cost(figures, 25.4)
    assert stamp["flops"] == 25  # the JOB's own integer count
    assert stamp["pass_flops"] == 100  # the shared pass figure survives
    assert stamp["mfu"] == 0.5
    assert costs.job_cost(figures, -3)["flops"] == 0


def test_note_divergence_ratio_and_guards():
    assert costs.note_divergence("m-d", 100.0, 102.0) == pytest.approx(1.02)
    gauge = telemetry.REGISTRY.get("swarm_flops_divergence_ratio")
    assert gauge.value(model="m-d") == 1.02
    # either side unusable -> None, not divergence 0
    assert costs.note_divergence("m-d", 0, 102.0) is None
    assert costs.note_divergence("m-d", 100.0, -1) is None
    assert costs.note_divergence("m-d", None, 102.0) is None
    assert costs.note_divergence("m-d", "bogus", 102.0) is None


# --- programs.py: the compiled-program ledger --------------------------------


class FakeProgram:
    """Stands in for a jitted callable: lowerable, analysable,
    cache-clearable."""

    def __init__(self, flops=1000.0, fail=False):
        self.flops = flops
        self.fail = fail
        self.cleared = False
        self.calls = 0

    def __call__(self, *args, **kwargs):
        self.calls += 1
        return "out"

    def lower(self, *args, **kwargs):
        if self.fail:
            raise RuntimeError("no lowering here")
        return self

    def cost_analysis(self):
        return {"flops": self.flops, "bytes accessed": 4096.0}

    def compile(self):
        return self

    def memory_analysis(self):
        class Stats:
            argument_size_in_bytes = 100
            output_size_in_bytes = 50
            temp_size_in_bytes = 25
            generated_code_size_in_bytes = 7
        return Stats()

    def clear_cache(self):
        self.cleared = True


def test_ledger_first_call_captures_analysis_and_divergence():
    fake = FakeProgram(flops=1040.0)
    wrapped = programs.instrument(
        fake, model="m-led", kind="fused", key=("k", 1),
        analytic_flops=1000.0)
    assert wrapped(1, 2) == "out"
    assert wrapped(3) == "out"
    snap = programs.snapshot()
    [entry] = [e for e in snap["programs"] if e["model"] == "m-led"]
    assert entry["state"] == "live"
    assert entry["kind"] == "fused" and entry["key"] == repr(("k", 1))
    assert entry["calls"] == 2
    assert entry["compile_s"] is not None and entry["compile_s"] >= 0
    assert entry["xla"] == {"flops": 1040.0, "bytes_accessed": 4096.0}
    assert entry["memory"] == {
        "argument_bytes": 100, "output_bytes": 50, "temp_bytes": 25,
        "generated_code_bytes": 7, "peak_bytes": 175}
    assert entry["divergence"] == 1.04
    assert snap["divergence"]["m-led"] == 1.04
    assert snap["live"] == 1 and snap["evicted"] == 0
    # the census provider totals live generated code
    assert programs.resident_code_bytes() == {"bytes": 7, "entries": 1}


def test_ledger_records_analysis_failure_without_breaking_the_call():
    fake = FakeProgram(fail=True)
    wrapped = programs.instrument(fake, model="m-err", kind="chunk")
    assert wrapped() == "out"  # the pass survives
    [entry] = programs.snapshot()["programs"]
    assert entry["state"] == "live"
    assert entry["error"].startswith("lower: RuntimeError")
    assert entry["xla"] is None and entry["divergence"] is None


def test_ledger_eviction_forwards_clear_cache_and_flips_state():
    fake = FakeProgram()
    wrapped = programs.instrument(fake, model="m-ev", kind="fused")
    wrapped()
    live_gauge = telemetry.REGISTRY.get("swarm_programs_live")
    assert live_gauge.value(model="m-ev") == 1
    wrapped.clear_cache()
    assert fake.cleared  # the real executable was freed
    snap = programs.snapshot()
    [entry] = [e for e in snap["programs"] if e["model"] == "m-ev"]
    assert entry["state"] == "evicted"
    assert snap["live"] == 0 and snap["evicted"] == 1
    assert live_gauge.value(model="m-ev") == 0
    assert programs.resident_code_bytes() == {"bytes": 0, "entries": 0}
    # drop-in surface: attributes of the wrapped callable pass through
    assert wrapped.calls == fake.calls


def test_ledger_bounded_by_max_entries(monkeypatch):
    monkeypatch.setattr(programs, "MAX_ENTRIES", 4)
    for i in range(10):
        programs.instrument(FakeProgram(), model="m-b", kind="fused", key=i)
    snap = programs.snapshot()
    assert len(snap["programs"]) == 4
    # oldest entries fell off the front (LRU by registration)
    assert [e["key"] for e in snap["programs"]] == ["6", "7", "8", "9"]


def test_analytic_flops_cross_check_against_real_xla():
    """Acceptance: on a real jitted program, XLA's cost_analysis agrees
    with the analytic count within a pinned tolerance — the serving
    path's MFU denominator is corroborated, not just asserted."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    n = 64
    fn = jax.jit(lambda a, b: a @ b)
    x = jnp.ones((n, n), dtype=jnp.float32)
    analytic = 2.0 * n * n * n  # dense matmul, the models/flops.py idiom
    wrapped = programs.instrument(
        fn, model="m-xla", kind="fused", analytic_flops=analytic)
    wrapped(x, x)
    [entry] = [e for e in programs.snapshot()["programs"]
               if e["model"] == "m-xla"]
    assert entry["error"] is None, entry["error"]
    xla_flops = entry["xla"]["flops"]
    assert xla_flops and xla_flops > 0
    # XLA counts n*n*(2n-1) for the dot — within 10% of 2n^3 at n=64
    assert 0.9 <= xla_flops / analytic <= 1.1
    assert entry["divergence"] == pytest.approx(xla_flops / analytic,
                                                abs=1e-3)


def test_every_envelope_of_a_coalesced_pass_carries_its_own_cost_stamp():
    """What the hive bills from: each job of one batched pass stamps its
    OWN rows' FLOPs beside the shared pass figures, and on a platform
    with no peak entry the work is still counted while MFU reads null."""
    jax = pytest.importorskip("jax")
    from chiaswarm_tpu.pipelines.stable_diffusion import SDPipeline

    pipe = SDPipeline("test/tiny-sd")
    outs = pipe.run_batched(
        [dict(prompt="one row", rng=jax.random.key(1)),
         dict(prompt="two rows", rng=jax.random.key(2),
              num_images_per_prompt=2)],
        height=64, width=64, num_inference_steps=2)
    one, two = (cfg["cost"] for _, cfg in outs)
    assert isinstance(one["flops"], int) and one["flops"] > 0
    assert abs(two["flops"] - 2 * one["flops"]) <= 1
    # padding rows are nobody's bill
    assert one["flops"] + two["flops"] <= one["pass_flops"]
    for stamp in (one, two):
        assert stamp["pass_flops"] == one["pass_flops"]
        assert stamp["tflops_per_s"] > 0
        assert stamp["peak_tflops_per_chip"] is None  # CPU: no peak entry
        assert stamp["mfu"] is None


# --- memory_census.py --------------------------------------------------------


def test_census_totals_builtin_and_registered_stores():
    memory_census.register("test_store", lambda: {"bytes": 1234, "n": 2})
    try:
        payload = memory_census.census()
        stores = payload["stores"]
        # the builtin byte-capped stores are always present
        for name in ("embed_cache", "lora_factor_cache",
                     "lora_operand_cache", "program_ledger"):
            assert name in stores, sorted(stores)
            assert isinstance(stores[name]["bytes"], int)
        assert stores["test_store"] == {"bytes": 1234, "n": 2}
        assert payload["total_bytes"] == sum(
            s["bytes"] for s in stores.values())
        assert payload["total_bytes"] >= 1234
        gauge = telemetry.REGISTRY.get("swarm_memory_store_bytes")
        assert gauge.value(store="test_store") == 1234
    finally:
        memory_census.unregister("test_store")
    assert "test_store" not in memory_census.census()["stores"]


def test_census_registered_provider_overrides_builtin():
    memory_census.register("embed_cache", lambda: {"bytes": 99})
    try:
        assert memory_census.census()["stores"]["embed_cache"] == {
            "bytes": 99}
    finally:
        memory_census.unregister("embed_cache")


def test_census_survives_broken_provider():
    memory_census.register("broken", lambda: 1 / 0)
    try:
        detail = memory_census.census()["stores"]["broken"]
        assert detail["bytes"] == 0
        assert detail["error"].startswith("ZeroDivisionError")
    finally:
        memory_census.unregister("broken")


def test_device_headroom_none_on_cpu(sdaas_root):
    # CPU devices report no bytes_limit -> the squeeze probe never fires
    assert memory_census.device_headroom() is None


# --- /debug endpoints + worker health degradation ----------------------------


def test_debug_endpoints_serve_provider_payloads():
    from aiohttp.test_utils import TestClient, TestServer

    from chiaswarm_tpu.telemetry import Registry, build_metrics_app

    async def scenario():
        app = build_metrics_app(
            Registry(),
            programs=lambda: {"programs": [], "live": 0},
            memory=lambda: {"stores": {}, "total_bytes": 0})
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.get("/debug/programs")
            assert resp.status == 200
            assert (await resp.json())["live"] == 0
            resp = await client.get("/debug/memory")
            assert resp.status == 200
            assert (await resp.json())["total_bytes"] == 0
        finally:
            await client.close()

    asyncio.run(scenario())

    async def absent_and_broken():
        # no providers wired -> the routes simply don't exist
        app = build_metrics_app(Registry())
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            assert (await client.get("/debug/programs")).status == 404
            assert (await client.get("/debug/memory")).status == 404
        finally:
            await client.close()
        # a broken ledger answers 500, it must not kill the app
        app = build_metrics_app(Registry(), programs=lambda: 1 / 0)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.get("/debug/programs")
            assert resp.status == 500
            assert "ZeroDivisionError" in (await resp.json())["message"]
            assert (await client.get("/metrics")).status == 200
        finally:
            await client.close()

    asyncio.run(absent_and_broken())


def test_worker_health_degrades_on_low_headroom(sdaas_root, monkeypatch):
    from chiaswarm_tpu.chips.allocator import SliceAllocator
    from chiaswarm_tpu.settings import Settings
    from chiaswarm_tpu.worker import Worker

    async def scenario():
        settings = Settings(sdaas_token="t", worker_name="w",
                            metrics_port=0, memory_headroom_degraded=0.1)
        w = Worker(settings=settings,
                   allocator=SliceAllocator(chips_per_job=8),
                   hive_uri="http://127.0.0.1:9/api")
        w._last_poll_monotonic = time.monotonic()
        try:
            monkeypatch.setattr(
                memory_census, "device_headroom", lambda: 0.02)
            h = w._health()
            assert h["status"] == "degraded"
            assert any("headroom" in r for r in h["degraded_reasons"])
            assert h["memory_headroom_ratio"] == 0.02
            # comfortable headroom: healthy, ratio still reported
            monkeypatch.setattr(
                memory_census, "device_headroom", lambda: 0.5)
            h = w._health()
            assert h["status"] == "ok"
            assert h["memory_headroom_ratio"] == 0.5
            # CPU smoke (no limit): the probe never fires
            monkeypatch.setattr(
                memory_census, "device_headroom", lambda: None)
            assert w._health()["status"] == "ok"
            # threshold 0 = off: the probe is not even consulted
            w.settings = Settings(sdaas_token="t", worker_name="w",
                                  metrics_port=0)
            monkeypatch.setattr(
                memory_census, "device_headroom",
                lambda: pytest.fail("probe consulted while disabled"))
            assert w._health()["status"] == "ok"
        finally:
            w._executor.shutdown(wait=False)

    asyncio.run(scenario())
