"""The persistent compile cache across a process restart, as counts.

Two child processes share one empty ``JAX_COMPILATION_CACHE_DIR``. Each
builds the tiny SD pipeline and runs one job of the same shapes: the first
is a cold start, the second a restarted worker. What is compared is the
directory's entries and jax's own cache events (``swarm_xla_cache_total``,
compile_cache.py); no seconds.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest


def _child(program: str) -> None:
    import jax

    from chiaswarm_tpu import telemetry
    from chiaswarm_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()
    # the worker's floor is a spam guard; every program of the tiny
    # pipeline compiles in under it, and all of them are to persist
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from chiaswarm_tpu.chips.device import ChipSet
    from chiaswarm_tpu.pipelines.stable_diffusion import SDPipeline

    pipe = SDPipeline("test/tiny-sd", chipset=ChipSet(jax.devices()),
                      allow_random_init=True)
    shared = dict(height=64, width=64, num_inference_steps=4,
                  scheduler_type="EulerDiscreteScheduler")
    if program == "solo":
        pipe.run(prompt="restart", rng=jax.random.key(0), **shared)
    else:
        pipe.run_batched(
            [{"prompt": f"row {i}", "rng": jax.random.key(i)}
             for i in range(4)], **shared)
    lookups = telemetry.REGISTRY.get("swarm_xla_cache_total")
    print(json.dumps({"hit": lookups.value(event="hit"),
                      "miss": lookups.value(event="miss")}))


def _start(program: str, cache_dir) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(cache_dir)}
    env.pop("XLA_FLAGS", None)  # one CPU device: the smallest compile
    code = f"from tests.test_compile_cache import _child; _child({program!r})"
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("program", ["solo", "batched"])
def test_restarted_process_reads_its_programs_back(tmp_path, program):
    cache_dir = tmp_path / "xla-cache"
    cache_dir.mkdir()
    assert os.listdir(cache_dir) == []

    cold = _start(program, cache_dir)
    written = sorted(os.listdir(cache_dir))
    # every program it compiled is one entry; its few hits are programs it
    # had written itself a moment before (two jit sites, one HLO)
    assert cold["miss"] == len(written) > 0, cold
    assert cold["hit"] < cold["miss"], cold

    restarted = _start(program, cache_dir)
    # same shapes, same directory: every look-up is a read, nothing is
    # compiled, so nothing new is written
    assert restarted["miss"] == 0, restarted
    assert restarted["hit"] == cold["hit"] + cold["miss"], (cold, restarted)
    assert sorted(os.listdir(cache_dir)) == written


# --- jax's staging events as the program's spans (ISSUE 52) ----------------
#
# In this process, on tiny jits: the listeners `enable_compile_cache`
# registers, the stage histogram they feed and the table by function.
# Counts and sums are taken as differences: histogram and table are the
# process's, and other tests of this worker stage programs too.

STAGES = ("xla_trace", "xla_lower", "xla_compile", "xla_cache_read")
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@pytest.fixture()
def staged(tmp_path, monkeypatch):
    """This process listening, its persistent cache in `tmp_path` with
    every program persisted; jax's settings put back afterwards (the
    listeners stay: jax has no public way to take one off)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from chiaswarm_tpu import compile_cache

    kept = {name: getattr(jax.config, name) for name in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.setattr(compile_cache, "DEFAULT_DIR", tmp_path / "xla")
    compile_cache.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    yield compile_cache
    for name, value in kept.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


def _stage_sums():
    from chiaswarm_tpu import telemetry

    stages = telemetry.histogram(
        telemetry.STAGE_METRIC, telemetry._STAGE_HELP, ("stage",))
    return {stage: (stages.count(stage=stage), stages.sum(stage=stage))
            for stage in STAGES}


def _row(compile_cache, name):
    rows = {row["function"]: row for row in compile_cache.staging()}
    return rows.get(name)


def _named(name):
    """A fresh function object under `name`: jax has staged no such
    object yet, whatever its name and code."""
    import jax

    def fn(x):
        return jax.lax.mul(jax.lax.sin(x), x)

    fn.__name__ = fn.__qualname__ = name
    return fn


def test_a_first_call_is_three_spans_and_a_row_and_a_second_is_nothing(
        staged):
    import jax
    import jax.numpy as jnp

    program, x = jax.jit(_named("staged_once")), jnp.ones(8)
    before = _stage_sums()
    program(x).block_until_ready()
    after = _stage_sums()
    for stage in ("xla_trace", "xla_lower", "xla_compile"):
        assert after[stage][0] == before[stage][0] + 1, stage
        assert after[stage][1] > before[stage][1], stage
    row = _row(staged, "staged_once")  # `jit(...)` stripped
    assert row["events"] == 3
    assert min(row["trace_s"], row["lower_s"], row["compile_s"]) > 0
    assert row["cache_read_s"] == 0.0  # compiled, not read back
    assert row["first_wall"] <= row["last_wall"]

    program(x).block_until_ready()
    assert _stage_sums() == after
    assert _row(staged, "staged_once") == row


def test_a_program_read_back_is_a_cache_read_inside_its_compile(staged):
    """Two function objects, one HLO and one module name: the second is a
    restarted worker's view of the first."""
    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu import telemetry

    x = jnp.ones(8)
    jax.jit(_named("staged_twice"))(x).block_until_ready()
    lookups = telemetry.REGISTRY.get("swarm_xla_cache_total")
    hits, before = lookups.value(event="hit"), _stage_sums()
    jax.jit(_named("staged_twice"))(x).block_until_ready()
    after = _stage_sums()
    assert lookups.value(event="hit") == hits + 1
    read = after["xla_cache_read"][1] - before["xla_cache_read"][1]
    assert after["xla_cache_read"][0] == before["xla_cache_read"][0] + 1
    assert read > 0
    # the read-back lies inside the `xla_compile` that follows it
    assert after["xla_compile"][1] - before["xla_compile"][1] >= read
    row = _row(staged, "staged_twice")
    assert row["events"] == 6
    assert row["cache_read_s"] == pytest.approx(read, abs=1e-5)
    assert row["compile_s"] >= row["cache_read_s"]


def test_a_trace_inside_a_trace_is_counted_once(staged):
    """The nesting rule: an outer function's `xla_trace` is its self time,
    so the three rows tile the outer event's span and do not double it."""
    import jax
    import jax.numpy as jnp

    def work(x):
        for _ in range(150):  # lax alone: a jnp function is a jit itself
            x = jax.lax.add(jax.lax.sin(x), x)
        return x

    def nest_a(x):
        return work(x)

    def nest_b(x):
        return work(work(x))

    def nest_outer(x):
        return work(jax.lax.add(jax.jit(nest_a)(x), jax.jit(nest_b)(x)))

    spans = {}

    def listen(event, start, end, fun_name="", **_):
        if event == TRACE_EVENT:
            spans[fun_name] = end - start

    x = jnp.ones(8)
    jax.monitoring.register_event_time_span_listener(listen)
    try:
        before = _stage_sums()
        jax.jit(nest_outer)(x).block_until_ready()
        after = _stage_sums()
    finally:
        jax.monitoring.unregister_event_time_span_listener(listen)
    rows = {name: _row(staged, name)
            for name in ("nest_outer", "nest_a", "nest_b")}
    whole = spans["nest_outer"]
    assert spans["nest_a"] + spans["nest_b"] < whole
    # the outer row holds what the inner ones do not
    assert rows["nest_outer"]["trace_s"] == pytest.approx(
        whole - spans["nest_a"] - spans["nest_b"], abs=1e-5)
    tiled = sum(row["trace_s"] for row in rows.values())
    assert tiled == pytest.approx(whole, rel=0.05)
    assert after["xla_trace"][0] == before["xla_trace"][0] + 3
    assert after["xla_trace"][1] - before["xla_trace"][1] == pytest.approx(
        whole, rel=0.05)


def test_the_table_holds_256_names_and_the_rest_under_other(
        staged, monkeypatch):
    monkeypatch.setattr(staged, "_TABLE", {})
    for n in range(300):
        staged._stage_span(COMPILE_EVENT, 100.0 + n, 100.5 + n,
                           fun_name=f"jit(fn_{n})")
    rows = {row["function"]: row for row in staged.staging()}
    assert len(rows) == staged.MAX_FUNCTIONS + 1
    assert rows[staged.OTHER]["events"] == 300 - staged.MAX_FUNCTIONS
    assert sum(row["events"] for row in rows.values()) == 300
    assert sum(row["compile_s"] for row in rows.values()) == pytest.approx(
        150.0)
    assert "fn_0" in rows and "fn_299" not in rows  # pmap(...) strips too
    staged._stage_span(COMPILE_EVENT, 1.0, 2.0, fun_name="pmap(fn_0)")
    assert _row(staged, "fn_0")["events"] == 2


def test_a_long_event_is_a_child_in_the_envelope_and_a_short_one_is_not(
        staged):
    import time

    from chiaswarm_tpu import telemetry

    before = _stage_sums()
    with telemetry.trace_job("job-staging") as trace:
        with telemetry.Span("prefill"):
            start = time.time()
            staged._stage_span(COMPILE_EVENT, start, start + 0.004,
                               fun_name="jit(short_one)")
            staged._stage_span(COMPILE_EVENT, start + 0.004, start + 0.054,
                               fun_name="jit(long_one)")
            time.sleep(0.06)
    after = _stage_sums()
    assert after["xla_compile"][0] == before["xla_compile"][0] + 2
    spans = {span["name"]: span for span in trace.spans}
    assert set(spans) == {"xla_compile", "prefill"}
    child, parent = spans["xla_compile"], spans["prefill"]
    assert child["seconds"] == pytest.approx(0.050)
    assert child["thread"] == "slice"
    assert parent["start_wall"] <= child["start_wall"]
    assert (child["start_wall"] + child["seconds"]
            <= parent["start_wall"] + parent["seconds"])


def test_a_listener_that_raises_does_not_fail_the_jit(staged, monkeypatch):
    import jax
    import jax.numpy as jnp

    def broken(*args, **kwargs):
        raise RuntimeError("the table is broken")

    x = jnp.ones(8)
    monkeypatch.setattr(staged, "_note", broken)
    before = _stage_sums()
    out = jax.jit(_named("staged_under_a_broken_table"))(x)
    after = _stage_sums()
    assert np.asarray(out)[0] == pytest.approx(0.8414709848)
    assert _row(staged, "staged_under_a_broken_table") is None
    # the span is stamped before the table is touched
    assert after["xla_compile"][0] == before["xla_compile"][0] + 1


def test_the_ledgers_snapshot_serves_the_table(staged):
    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu import programs

    jax.jit(_named("staged_for_the_snapshot"))(
        jnp.ones(8)).block_until_ready()
    table = programs.snapshot()["staging"]
    totals = [row["trace_s"] + row["lower_s"] + row["compile_s"]
              for row in table]
    assert totals == sorted(totals, reverse=True)  # dearest first
    row = next(r for r in table if r["function"] == "staged_for_the_snapshot")
    assert set(row) == {"function", "events", "trace_s", "lower_s",
                        "compile_s", "cache_read_s", "first_wall",
                        "last_wall"}
    json.dumps(table)  # what GET /debug/programs sends
