"""The readers of a job's life between passes (`lifecycle.py`), on a
hand-made record: two hive gangs of two and a pair the worker's linger
joined over two polls, each job with the chain a worker stamps
(`tick_wait`, `poll`, `queue_wait` tiled by `linger`, `claim`,
`package_wait`, `format_args`, `pass`, `handoff`, `artifact_encode`) and a
`settle` event with `received_wall`; idle gaps under each kind of stretch;
and the same record as a parent's program would leave it (`queue_wait`,
`pass` and `artifact_encode` only, no `received_wall`, no counter)."""

import pytest

from benchmark import harness

SYNC_WALL, SYNC_NS = 90.0, 5e9  # the tracer's mark: wall 90.0 at 5 s
NEW = ("tick_wait", "poll", "linger", "claim", "package_wait",
       "format_args", "handoff")


def span(name, start, seconds, thread):
    return {"name": name, "thread": thread, "start_wall": start,
            "seconds": seconds}


def chain(polled, tick_s, linger_s, pass_s=10.0):
    """What one poll's jobs share, from the poll's request at `polled`:
    a 0.01 s poll, the wait (`linger_s`, 0.002 of claim, 0.001 behind
    undelivered passes), 0.004 of arguments, the pass."""
    got = polled + 0.01
    picked = got + linger_s + 0.003
    return [
        span("tick_wait", polled - tick_s, tick_s, "wait"),
        span("poll", polled, 0.01, "poll"),
        span("linger", got, linger_s, "wait"),
        span("claim", got + linger_s, 0.002, "wait"),
        span("package_wait", got + linger_s + 0.002, 0.001, "wait"),
        span("queue_wait", got, linger_s + 0.003, "wait"),
        span("format_args", picked, 0.004, "wait"),
        span("pass", picked + 0.004, pass_s, "slice"),
    ]


def job(n, gang, shared, admit, handoff_s, settle, in_window=True):
    """Job `n` of pass `gang`: the pass's spans, its own hand-off and a
    0.1 s encode after it, received 0.03 s before `settle`."""
    held = next(s for s in shared if s["name"] == "pass")
    ended = held["start_wall"] + held["seconds"]
    own = [span("handoff", ended, handoff_s, "deliver"),
           span("artifact_encode", ended + handoff_s, 0.1, "host")]
    return {
        "id": f"j{n}", "withdrawn": False, "in_window": in_window,
        "submit_wall": admit,
        "trace": {"events": [
            {"event": "admit", "wall": admit},
            {"event": "settle", "wall": settle,
             "received_wall": settle - 0.03}]},
        "status": {"status": "done", "result": {"pipeline_config": {
            "batch_rows": [0, 1], "timings": {},
            "trace": {"gang": {"id": gang}}, "spans": shared + own}}}}


def gaps(*walls):
    return [((lo - SYNC_WALL) * 1e9 + SYNC_NS, (hi - SYNC_WALL) * 1e9 + SYNC_NS)
            for lo, hi in walls]


def record():
    # g0: polled at 100.0 after 0.08 s asleep; its jobs admitted 0.2 s and
    # 0.03 s before the poll: 0.03 s of the sleep cost the pass anything
    g0 = chain(100.0, 0.08, 0.0)
    # g1: polled at 111.0 after 0.05 s asleep, both jobs queued long before
    g1 = chain(111.0, 0.05, 0.0)
    # g2, joined by the linger: the first job's poll at 122.0, the second's
    # at 122.1 after 0.09 s asleep (admitted 0.06 s before it); the first
    # lingered 0.1 s, the second not at all; one pass
    first, second = chain(122.0, 0.02, 0.1), chain(122.1, 0.09, 0.0)
    g2_pass = [s for s in second if s["name"] in ("format_args", "pass")]
    g2 = [[s for s in first if s["name"] not in ("format_args", "pass")]
          + g2_pass,
          second]
    jobs = [
        job(0, "g0", g0, 99.8, 0.002, 110.40),
        job(1, "g0", g0, 99.97, 0.102, 110.40),
        job(2, "g1", g1, 105.0, 0.002, 121.40),
        job(3, "g1", g1, 105.0, 0.102, 121.40),
        job(4, "g2", g2[0], 121.9, 0.002, 132.50),
        job(5, "g2", g2[1], 122.04, 0.102, 132.50),
    ]
    return {
        "jobs": jobs, "window": {"open_wall": 95.0, "close_wall": 145.0},
        "scrape_open": {
            "swarm_poll_overshoot_seconds_total": {"": 0.5},
            "swarm_job_stage_seconds_count": {"poll": 100.0, "pass": 3.0}},
        "scrape_close": {
            "swarm_poll_overshoot_seconds_total": {"": 0.75},
            "swarm_job_stage_seconds_count": {"poll": 600.0, "pass": 6.0}},
        "trace": {
            "annotations": [(f"bench_sync wall={SYNC_WALL:.6f}", SYNC_NS, 0.0)],
            "gaps_ns": gaps(
                (105.0, 105.5),      # under g0's pass: the other reader's
                (110.25, 110.30),    # under a g0 `artifact_encode`
                (110.38, 110.39),    # g0's delivery, before its settle
                (110.40, 110.90),    # settled, the client thinking: unnamed
                (110.96, 110.99),    # under g1's `tick_wait`
                (111.0, 111.005)),   # under g1's `poll`
        },
    }


def parent_record():
    """The same run by a program from before the spans."""
    rec = record()
    for entry in rec["jobs"]:
        config = entry["status"]["result"]["pipeline_config"]
        config["spans"] = [s for s in config["spans"] if s["name"] not in NEW]
        del entry["trace"]["events"][-1]["received_wall"]
    for scraped in (rec["scrape_open"], rec["scrape_close"]):
        del scraped["swarm_poll_overshoot_seconds_total"]
    return rec


def read(name, rec):
    return harness.load_reader("layer_metrics", name)(rec)


def test_tick_wait_is_clipped_to_the_admit_of_the_jobs_the_poll_brought():
    # g0 0.03 (of 0.08: its last job came 0.03 s before the poll), g1 all
    # of its 0.05, g2 the later poll's 0.09 cut to 0.06 by its job's admit
    assert read("poll_tick_wait_s_per_pass", record()) == pytest.approx(0.05)
    assert read("solo_poll_tick_wait_s", record()) == pytest.approx(0.05)
    rec = record()
    rec["jobs"] = rec["jobs"][:2]
    assert read("poll_tick_wait_s_per_pass", rec) == pytest.approx(0.03)
    rec = record()
    rec["jobs"] = rec["jobs"][4:]
    assert read("poll_tick_wait_s_per_pass", rec) == pytest.approx(0.06)


def test_linger_is_the_groups_and_a_mean_where_it_comes_one_pass_in_three():
    # a hive gang's members share a linger of nothing; the joined pair's
    # is the first job's 0.1 s, not the second's 0
    assert read("linger_s_per_pass", record()) == pytest.approx(0.1 / 3)
    assert read("solo_linger_s", record()) == 0.0


def test_the_poll_the_arguments_and_the_handoff():
    assert read("solo_poll_rtt_ms", record()) == pytest.approx(10.0)
    assert read("solo_format_args_ms", record()) == pytest.approx(4.0)
    # a job's own: 2 ms for a pass's first, 102 behind a batchmate's encode
    assert read("solo_handoff_ms", record()) == pytest.approx(52.0)


def test_delivery_and_the_hives_settle_meet_at_received_wall():
    rec = record()
    # g0's passes end at 110.017: +0.002 +0.1 = 110.119, +0.102 +0.1 = .219
    sent = [110.119, 110.219, 121.119, 121.219, 132.219, 132.319]
    got = [110.37, 110.37, 121.37, 121.37, 132.47, 132.47]
    waits = sorted(g - s for g, s in zip(got, sent))
    assert read("solo_deliver_s", rec) == pytest.approx(
        (waits[2] + waits[3]) / 2)
    assert read("solo_hive_settle_ms", rec) == pytest.approx(30.0)


def test_overshoot_is_the_counters_movement_a_poll():
    assert read("solo_poll_overshoot_ms", record()) == pytest.approx(0.5)


def test_slice_free_untraced_is_idle_outside_every_pass_and_named_stretch():
    # 0.5 s under a pass counts nowhere here; outside: 0.05 (encode) + 0.01
    # (delivery) + 0.5 (nobody's) + 0.03 (tick) + 0.005 (poll)
    free = 0.05 + 0.01 + 0.5 + 0.03 + 0.005
    assert read("slice_free_untraced_share", record()) == pytest.approx(
        100.0 * 0.5 / free, rel=1e-6)
    assert read("solo_slice_free_untraced_share", record()) == pytest.approx(
        100.0 * 0.5 / free, rel=1e-6)
    rec = record()
    rec["trace"]["gaps_ns"] = rec["trace"]["gaps_ns"][:1]  # all under a pass
    assert read("slice_free_untraced_share", rec) is None


@pytest.mark.parametrize("name", [
    "poll_tick_wait_s_per_pass", "solo_poll_tick_wait_s", "linger_s_per_pass",
    "solo_linger_s", "solo_poll_rtt_ms", "solo_format_args_ms",
    "solo_handoff_ms", "solo_deliver_s", "solo_hive_settle_ms",
    "solo_poll_overshoot_ms", "slice_free_untraced_share",
    "solo_slice_free_untraced_share"])
def test_a_parents_envelopes_read_nothing_and_raise_nothing(name):
    assert read(name, parent_record()) is None
    rec = record()
    rec["trace"] = None  # an untraced run: the span readers still read
    assert (read(name, rec) is None) == name.endswith("untraced_share")


def test_the_new_entries_name_their_cells_and_have_their_readers():
    import json

    bench = json.loads((harness.REPO / "BENCHMARK.json").read_text())
    moves = {"images_per_s": {"sdxl-backlog", "sdxl-fewstep", "sd21-backlog",
                              "flux-backlog"},
             "job_latency_p50_s": {"sd21-interactive", "kimi-batch-decode",
                                   "exaone-long-documents"}}
    new = bench["per_layer"][-12:]
    assert [m["name"] for m in new] == [
        "poll_tick_wait_s_per_pass", "solo_poll_tick_wait_s",
        "linger_s_per_pass", "solo_linger_s", "solo_poll_rtt_ms",
        "solo_format_args_ms", "solo_handoff_ms", "solo_deliver_s",
        "solo_hive_settle_ms", "solo_poll_overshoot_ms",
        "slice_free_untraced_share", "solo_slice_free_untraced_share"]
    layers = {m["layer"] for m in bench["per_layer"][:-12]}
    for metric in new:
        assert set(metric["workloads"]) == moves[metric["moves"]]
        assert metric["layer"] in layers
        harness.load_reader("layer_metrics", metric["name"])
