"""The nine readers that part `setup_s`, on a hand-made record: two scrapes
with `at_wall` (before the worker exists, and at the window's opening),
the staging stages' sums and counts, and the startup marks. A program
without the spans (the parent of the PR that brought them) reads nothing."""

import pytest

from benchmark import harness

NAMES = ("setup_xla_trace_s", "setup_xla_lower_s", "setup_xla_cache_read_s",
         "setup_xla_compile_s", "setup_programs_staged",
         "worker_xla_staging_s", "setup_before_worker_s", "setup_worker_s",
         "setup_first_pass_s")


def scrape(at_wall, sums, counts=None, marks=None):
    out = {"at_wall": {"": at_wall},
           "swarm_job_stage_seconds_sum": {"registry_build": 20.0, **sums},
           "swarm_job_stage_seconds_count": {"registry_build": 1.0,
                                             **(counts or {})}}
    if marks:
        out["swarm_startup_seconds"] = marks
    return out


def record():
    """Set-up of 100 s: 60 s before the worker (kernel checks staged 2 + 3
    + 10 s), 40 s of worker (its programs 4 + 6 + 18 s, of the 18 s 15 s
    read back); the first pass ran from 63 to 85 s after the start."""
    return {
        "setup_s": 100.0,
        "scrape_before_worker": scrape(
            1060.0, {"xla_trace": 2.0, "xla_lower": 3.0,
                     "xla_compile": 10.0, "xla_cache_read": 1.0},
            {"xla_compile": 115.0}),
        "scrape_open": scrape(
            1100.0, {"xla_trace": 6.0, "xla_lower": 9.0,
                     "xla_compile": 28.0, "xla_cache_read": 16.0},
            {"xla_compile": 140.0},
            {"worker_started": 61.0, "first_poll": 61.5,
             "first_pass_start": 63.0, "first_pass_end": 85.0}),
    }


def read(name, rec):
    return harness.load_reader("layer_metrics", name)(rec)


@pytest.mark.parametrize("name, want", [
    ("setup_xla_trace_s", 6.0),
    ("setup_xla_lower_s", 9.0),
    ("setup_xla_cache_read_s", 16.0),
    ("setup_xla_compile_s", 12.0),
    ("setup_programs_staged", 140.0),
    ("worker_xla_staging_s", (6.0 + 9.0 + 28.0) - (2.0 + 3.0 + 10.0)),
    ("setup_before_worker_s", 60.0),
    ("setup_worker_s", 40.0),
    ("setup_first_pass_s", 22.0),
])
def test_a_reader_reads_its_part_of_set_up(name, want):
    assert read(name, record()) == pytest.approx(want)


def test_the_parts_add_up_as_the_issue_says():
    rec = record()
    assert (read("setup_before_worker_s", rec) + read("setup_worker_s", rec)
            == pytest.approx(rec["setup_s"]))
    # the read-back and the backend's own compiles make what `compile_s`
    # reads from the counter of the same events
    assert (read("setup_xla_cache_read_s", rec)
            + read("setup_xla_compile_s", rec) == pytest.approx(28.0))


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_spans_reads_nothing(name):
    """The parent's scrapes: no staging stage, no mark, and (a record
    from before `at_wall`) no wall stamp."""
    bare = {"setup_s": 100.0,
            "scrape_before_worker": {
                "swarm_job_stage_seconds_sum": {"registry_build": 20.0}},
            "scrape_open": {
                "swarm_job_stage_seconds_sum": {"registry_build": 20.0},
                "swarm_job_stage_seconds_count": {"registry_build": 1.0}}}
    assert read(name, bare) is None


def test_the_host_clock_readers_need_no_span():
    """`setup_before_worker_s` and `setup_worker_s` read the harness's own
    stamps: the parent reports them too."""
    rec = record()
    for key in ("scrape_before_worker", "scrape_open"):
        rec[key] = {"at_wall": rec[key]["at_wall"]}
    assert read("setup_worker_s", rec) == pytest.approx(40.0)
    assert read("setup_before_worker_s", rec) == pytest.approx(60.0)
    assert read("setup_xla_trace_s", rec) is None


def test_a_cold_start_reads_no_read_back_as_zero():
    """Nothing was hit, so no `xla_cache_read` span exists: the program
    has the spans all the same, and the line keeps the metric."""
    rec = record()
    for key in ("scrape_before_worker", "scrape_open"):
        del rec[key]["swarm_job_stage_seconds_sum"]["xla_cache_read"]
    assert read("setup_xla_cache_read_s", rec) == 0.0
    assert read("setup_xla_compile_s", rec) == pytest.approx(28.0)


def test_every_new_reader_has_its_entry():
    import json

    bench = json.loads((harness.REPO / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NAMES:
        assert entries[name]["moves"] == "setup_s"
        assert entries[name]["better"] == "lower"
        assert "workloads" not in entries[name]
