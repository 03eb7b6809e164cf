"""`breakdown.build` on a hand-made record: an idle gap takes the name of
the span the program stamped over its middle. Two passes of two jobs; the
first pass's artifacts are packaged on the host thread while the second
holds the slice; the second's after it, with the slice free."""

import pytest

from benchmark import breakdown

SYNC_WALL, SYNC_NS = 90.0, 5e9  # the tracer's mark: wall 90.0 at 5 s


def span(name, start, seconds, thread="slice"):
    return {"name": name, "thread": thread, "start_wall": start,
            "seconds": seconds}


def pass_spans(t0):
    """A 10 s pass from wall `t0`; its packaging from +10.5 to +11.5."""
    return [
        span("prefill", t0, 0.5),
        span("decode_steps", t0 + 0.6, 8.0),
        span("sample", t0 + 4.0, 1.0),        # inside `decode_steps`
        span("readback", t0 + 8.7, 1.2),
        span("pass", t0, 10.0),
        span("package", t0 + 10.5, 1.0, thread="host"),
        # the uploader's thread works while the slice does: never a label
        span("ship", t0 + 3.0, 9.0, thread="loop"),
        span("queue_wait", t0 - 0.4, 0.4, thread="wait")]


def job(n, gang, t0, settle, spans=True):
    config = {"trace": {"gang": {"id": gang}}}
    if spans:
        config["spans"] = pass_spans(t0)
    return {"id": f"j{n}",
            "trace": {"events": [{"event": "settle", "wall": settle}]},
            "status": {"status": "done",
                       "result": {"pipeline_config": config}}}


def record(walls, spans=True, sync=True):
    """Passes at 100-110 and 110.4-120.4; settles at 111.6 / 111.7 and
    122.0 / 122.3."""
    jobs = [job(0, "g0", 100.0, 111.6, spans), job(1, "g0", 100.0, 111.7, spans),
            job(2, "g1", 110.4, 122.0, spans), job(3, "g1", 110.4, 122.3, spans)]
    marks = [(f"bench_sync wall={SYNC_WALL:.6f}", SYNC_NS, 0.0)] if sync else []
    return {"jobs": jobs, "trace": {
        "annotations": marks, "op_seconds": {"matmul": 3.0, "copy": 1.0},
        "gaps_ns": [((lo - SYNC_WALL) * 1e9 + SYNC_NS,
                     (hi - SYNC_WALL) * 1e9 + SYNC_NS) for lo, hi in walls]}}


WALLS = [(100.1, 100.3),     # under a child of the pass
         (104.2, 104.4),     # under a child of a child: the innermost
         (100.52, 100.58),   # in the pass, under none of its children
         (110.1, 110.3),     # the slice free, g0 on its way to the hive
         (110.6, 110.8),     # the host packages g0 while g1 holds the slice
         (120.95, 121.05),   # the slice free, the host packages g1
         (121.9, 122.0),     # packaged, not yet settled at the hive
         (122.1, 122.2),     # between the pass's two settles
         (122.4, 122.9)]     # after the last settle


def test_a_gap_takes_the_name_of_the_span_over_its_middle():
    built = breakdown.build(record(WALLS))
    assert built["device_ops"] == [["matmul", 3.0], ["copy", 1.0]]
    assert {label: pytest.approx(seconds)
            for label, seconds in built["idle_gaps"]} == {
        "prefill x2": 0.4,            # g0's and, under the packaging, g1's
        "sample x1": 0.2,
        "pass (no child span) x1": 0.06,
        "between passes (poll + hive) x1": 0.5,
        "package x1": 0.1,
        "upload + settle x3": 0.4}
    # most seconds first
    assert built["idle_gaps"][0][0] == "between passes (poll + hive) x1"


def test_the_slice_thread_s_span_wins_over_the_host_thread_s():
    """While a pass holds the slice, what the host thread does for the
    pass before is no reason for the device to wait."""
    built = breakdown.build(record([(110.6, 110.8)]))
    assert built["idle_gaps"] == [["prefill x1", pytest.approx(0.2)]]


@pytest.mark.parametrize("spans, sync", [(False, True), (True, False)],
                         ids=["no-spans", "no-clock"])
def test_without_spans_or_without_the_clock_every_gap_is_unknown(spans, sync):
    built = breakdown.build(record(WALLS, spans=spans, sync=sync))
    assert built["idle_gaps"] == [
        [f"unknown x{len(WALLS)}", pytest.approx(1.66)]]


def test_an_untraced_record_has_no_breakdown():
    assert breakdown.build({"jobs": [], "trace": None}) is None


def test_the_breakdown_names_no_stage():
    """Not one name of `pass_spans` but `pass` is in the module."""
    code = open(breakdown.__file__).read()
    for name in ("prefill", "decode", "sample", "readback", "package",
                 "text_encode", "denoise", "safety", "artifact_encode"):
        assert name not in code, name
