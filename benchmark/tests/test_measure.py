"""`images_per_s`' whole-pass arithmetic, and the readers that share it, on
a synthetic run record."""

import pytest

from benchmark import harness, measure


def job(n, gang, settle, submit=0.0, rows=1, in_window=True, **timings):
    return {
        "id": f"j{n}", "previous": None, "withdrawn": False,
        "in_window": in_window, "submit_wall": submit,
        "accepted_wall": submit + 0.001,
        "trace": {"events": [{"event": "admit", "wall": submit},
                             {"event": "settle", "wall": settle}]},
        "status": {"status": "done", "attempts": 1, "queue_wait_s": 0.25,
                   "result": {"pipeline_config": {
                       "batch_rows": [0, rows], "timings": timings,
                       "trace": {"gang": {"id": gang} if gang else None}}}},
    }


def record(jobs, lo=100.0, hi=150.0):
    return {"jobs": jobs, "window": {"open_wall": lo, "close_wall": hi}}


def test_rate_counts_whole_passes_only():
    # three gangs of 4 ending at 110, 125, 140; their members settle over
    # 0.3 s; a fourth gang ends after the window closes
    jobs = [job(4 * g + i, f"g{g}", end - 0.1 * (3 - i))
            for g, end in enumerate((110.0, 125.0, 140.0, 155.0))
            for i in range(4)]
    rec = record(jobs)
    passes = measure.passes(measure.settled_in_window(rec))
    assert [p["images"] for p in passes] == [4, 4, 4]
    assert [p["end_wall"] for p in passes] == [110.0, 125.0, 140.0]
    # 8 images of the second and third pass over the 30 s between ends
    assert measure.whole_pass_rate(passes) == pytest.approx(8 / 30.0)
    read = harness.load_reader("end_to_end", "images_per_s")
    assert read(rec) == pytest.approx(8 / 30.0)


def test_a_single_pass_gives_no_rate():
    rec = record([job(0, "g", 120.0)])
    assert harness.load_reader("end_to_end", "images_per_s")(rec) is None


def test_solo_jobs_are_each_a_pass():
    rec = record([job(n, None, 102.0 + 2.5 * n, submit=100.0 + 2.5 * n)
                  for n in range(5)])
    assert harness.load_reader("layer_metrics", "rows_per_pass")(rec) == 1.0
    assert harness.load_reader("end_to_end", "job_latency_p50_s")(
        rec) == pytest.approx(2.0)
    assert harness.load_reader("end_to_end", "images_per_s")(
        rec) == pytest.approx(4 / 10.0)


def test_per_pass_timings_divide_by_the_pass_rows():
    jobs = [job(i, "g0", 110.0, denoise_decode_s=12.0, job_s=14.0)
            for i in range(4)]
    jobs += [job(4 + i, "g1", 125.0, denoise_decode_s=12.4, job_s=14.2)
             for i in range(4)]
    rec = record(jobs)
    per_image = harness.load_reader("layer_metrics",
                                    "denoise_decode_s_per_image")
    assert per_image(rec) == pytest.approx((3.0 + 3.1) / 2)
    host = harness.load_reader("layer_metrics", "host_s_per_pass")
    assert host(rec) == pytest.approx((2.0 + 1.8) / 2)


def test_generator_lateness_is_settle_to_next_accept():
    first = job(0, None, 110.0, submit=105.0)
    second = job(1, None, 120.0, submit=110.004)
    second["previous"] = "j0"
    second["accepted_wall"] = 110.010
    read = harness.load_reader("layer_metrics", "generator_late_ms")
    assert read(record([first, second])) == pytest.approx(10.0)


def test_a_reader_with_nothing_to_read_returns_nothing():
    rec = record([])
    rec["trace"] = None
    for name in ("device_idle_share", "flash_attention_device_share",
                 "flash_attention_roofline"):
        assert harness.load_reader("layer_metrics", name)(rec) is None
