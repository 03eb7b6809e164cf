"""The readers of the program's own spans and of the slice-free counter, on
a hand-made record (two gangs of two; a `loop` span overlapping the `slice`
spans; idle gaps inside `artifact_encode`, inside `pass` under no child,
inside `denoise`, and between the passes) and, for the clock mapping, on
the trace recorded on a v5e (`trace/small.xplane.pb`)."""

from pathlib import Path

import pytest

from benchmark import harness
from benchmark.trace import reduce

TRACE = Path(__file__).resolve().parents[1] / "trace" / "small.xplane.pb"
SYNC_WALL, SYNC_NS = 90.0, 5e9  # the tracer's mark: wall 90.0 at 5 s


def span(name, start, seconds, thread="slice"):
    return {"name": name, "thread": thread, "start_wall": start,
            "seconds": seconds}


def pass_spans(t0):
    """One 10 s pass of two jobs from wall `t0`: holes at +0.5..0.6
    (between text encode and the program), +8.6..8.7 (program to decode)
    and +9.9..10 (after the last decode)."""
    return [
        span("load", t0, 0.0),
        span("text_encode", t0, 0.5),
        span("compile", t0 + 0.6, 0.0),
        span("denoise", t0 + 0.6, 8.0),
        span("safety", t0 + 8.7, 0.1),
        span("artifact_encode", t0 + 8.8, 0.5),
        span("decode", t0 + 8.7, 0.6),
        span("safety", t0 + 9.3, 0.1),
        span("artifact_encode", t0 + 9.4, 0.5),
        span("decode", t0 + 9.3, 0.6),
        span("pass", t0, 10.0),
        # the uploader's thread works while the slice does: never a child
        span("ship", t0 + 8.0, 1.0, thread="loop"),
    ]


def job(n, gang, t0, settle, spans=True):
    config = {"batch_rows": [0, 1], "timings": {"job_s": 10.0},
              "trace": {"gang": {"id": gang}}}
    if spans:
        # each envelope carries the whole pass's spans and its own wait
        config["spans"] = pass_spans(t0) + [
            span("queue_wait", t0 - 0.05 - 0.01 * n, 0.05 + 0.01 * n, "wait")]
    return {"id": f"j{n}", "withdrawn": False, "in_window": True,
            "submit_wall": t0 - 1.0,
            "trace": {"events": [{"event": "settle", "wall": settle}]},
            "status": {"status": "done",
                       "result": {"pipeline_config": config}}}


def gaps(*walls):
    return [((lo - SYNC_WALL) * 1e9 + SYNC_NS, (hi - SYNC_WALL) * 1e9 + SYNC_NS)
            for lo, hi in walls]


def record(spans=True):
    jobs = [job(0, "g0", 100.0, 110.15, spans), job(1, "g0", 100.0, 110.25, spans),
            job(2, "g1", 110.4, 120.55, spans), job(3, "g1", 110.4, 120.65, spans)]
    return {
        "jobs": jobs, "window": {"open_wall": 95.0, "close_wall": 145.0},
        "scrape_open": {
            "swarm_job_stage_seconds_sum": (
                {"registry_build": 29.25, "denoise": 50.0} if spans
                else {"denoise": 50.0}),
            "swarm_slice_execute_seconds_count": {"batched": 3.0, "solo": 1.0},
            **({"swarm_slice_free_seconds_total": {"0": 1.0}} if spans else {}),
        },
        "scrape_close": {
            "swarm_slice_execute_seconds_count": {"batched": 5.0, "solo": 1.0},
            **({"swarm_slice_free_seconds_total": {"0": 1.8}} if spans else {}),
        },
        "trace": {
            "annotations": [(f"bench_sync wall={SYNC_WALL:.6f}", SYNC_NS, 0.0)],
            "gaps_ns": gaps(
                (108.9, 109.2),      # under artifact_encode (and decode)
                (108.62, 108.68),    # in the pass, under no child: `loop`'s
                                     # span over it does not count
                (110.0, 110.4),      # between the passes
                (103.0, 103.001),    # inside the program
                (119.5, 119.6)),     # under the second pass's decode
        },
    }


def read(name, rec):
    return harness.load_reader("layer_metrics", name)(rec)


def test_artifact_encode_sums_a_pass_s_distinct_spans():
    # two jobs a pass, 0.5 s each, carried by both envelopes: 1.0, not 2.0
    assert read("artifact_encode_s_per_pass", record()) == pytest.approx(1.0)
    assert read("solo_artifact_encode_s", record()) == pytest.approx(1.0)


def test_slice_free_is_the_counter_over_the_passes_that_ended():
    assert read("slice_free_s_per_pass", record()) == pytest.approx(0.4)
    assert read("solo_slice_free_s", record()) == pytest.approx(0.4)


def test_idle_untraced_is_idle_in_a_pass_under_no_child_of_its_thread():
    idle = 0.3 + 0.06 + 0.4 + 0.001 + 0.1
    assert read("idle_untraced_share", record()) == pytest.approx(
        100.0 * 0.06 / idle, rel=1e-6)
    assert read("solo_idle_untraced_share", record()) == pytest.approx(
        100.0 * 0.06 / idle, rel=1e-6)


def test_registry_build_and_upload_settle():
    assert read("registry_build_s", record()) == 29.25
    # settle less the pass's end (110.0, 120.4): 0.15, 0.25, 0.15, 0.25
    assert read("solo_upload_settle_s", record()) == pytest.approx(0.2)


def test_a_program_without_spans_or_counter_reads_nothing():
    rec = record(spans=False)
    for name in ("artifact_encode_s_per_pass", "solo_artifact_encode_s",
                 "slice_free_s_per_pass", "solo_slice_free_s",
                 "idle_untraced_share", "solo_idle_untraced_share",
                 "registry_build_s", "solo_upload_settle_s"):
        assert read(name, rec) is None, name
    rec = record()
    rec["trace"] = None  # an untraced run
    assert read("idle_untraced_share", rec) is None


def test_the_recorded_trace_s_gaps_land_on_the_wall_clock():
    """On the v5e trace (three steps, the host asleep between them): a
    pass over the whole stretch whose one child covers exactly the first
    sleep leaves every other idle nanosecond untraced."""
    from benchmark import breakdown

    trace = reduce.reduce_trace(TRACE)
    to_wall = breakdown.clock(trace)
    lo, hi = (to_wall(ns) for ns in trace["stretch_ns"])
    first_lo, first_hi = (to_wall(ns) for ns in trace["gaps_ns"][0])
    rec = record()
    rec["trace"] = trace
    rec["jobs"] = [job(0, "g0", lo, hi)]
    rec["jobs"][0]["status"]["result"]["pipeline_config"]["spans"] = [
        span("pass", lo - 0.001, hi - lo + 0.002),
        span("decode", first_lo, first_hi - first_lo)]
    idle_ns = sum(b - a for a, b in trace["gaps_ns"])
    first_ns = trace["gaps_ns"][0][1] - trace["gaps_ns"][0][0]
    assert first_ns / 1e6 == pytest.approx(11.97, abs=0.01)
    assert read("idle_untraced_share", rec) == pytest.approx(
        100.0 * (idle_ns - first_ns) / idle_ns, rel=1e-4)
