"""The `kimi` family away from the chip: its draws are a function of the
seed, what `check_artifact` takes and refuses, the expert matmul's cost on
hand-counted cases, the new readers on a hand-made record, the weight
factory's rules, and the configuration file against the published config
and the program's own `KimiConfig`."""

import hashlib
import json
import random
from pathlib import Path

import pytest

from benchmark import harness
from benchmark.costs import expert_matmul as cost
from benchmark.costs.peaks import least_seconds
from benchmark.families import kimi as family

REPO = Path(__file__).resolve().parents[2]
CONFIG = json.loads(
    (REPO / "benchmark" / "configs" / "kimi-k2.6-ep32.json").read_text())
TRAFFIC = json.loads(
    (REPO / "benchmark" / "traffic" / "batch-decode.json").read_text())


# --- job_fields ---------------------------------------------------------------


def jobs(seed, count=3):
    rng = random.Random(seed)
    return [family.job_fields(rng, TRAFFIC, n, False)["prompt_ids"]
            for n in range(count)]


def test_a_jobs_rows_are_a_function_of_the_seed():
    assert jobs(5) == jobs(5)
    assert jobs(5) != jobs(6)
    first = jobs(5)[0]
    tokens = TRAFFIC["tokens"]
    assert len(first) == tokens["sequences"] == 64
    assert all(tokens["length_min"] <= len(row) <= tokens["length_max"]
               for row in first)
    assert all(0 <= i < tokens["vocabulary"] for row in first for i in row)
    # log-uniform lengths: as many rows under the geometric mean as over
    lengths = [len(row) for job in jobs(5, 12) for row in job]
    assert 0.4 < sum(n <= 64 for n in lengths) / len(lengths) < 0.6
    assert max(lengths) > 128  # so a pass pads to the 256-slot bucket


def test_ids_are_zipf_through_a_permutation_of_the_runs_own():
    from collections import Counter

    def hot(seed):
        counts = Counter(i for job in jobs(seed, 6) for row in job
                         for i in row)
        total = sum(counts.values())
        top = counts.most_common(8)
        return [i for i, _ in top], sum(n for _, n in top) / total

    ids_a, share_a = hot(5)
    ids_b, share_b = hot(6)
    # the eight hottest of 20480 ids hold over a third of the draws
    # (Zipf(1.1): H(8) / H(20480) = 2.55 / 6.88), and which is the seed's
    assert 0.3 < share_a < 0.44 and 0.3 < share_b < 0.44
    assert set(ids_a) != set(ids_b)


def test_the_probe_draws_nothing_and_is_the_same_whatever_the_seed():
    rng = random.Random(9)
    state = rng.getstate()
    probe = family.job_fields(rng, TRAFFIC, 7, True)
    assert rng.getstate() == state
    assert probe == family.job_fields(random.Random(10), TRAFFIC, 0, True)
    assert len(probe["prompt_ids"]) == 64
    # a job made after the probe is the job made without it
    assert family.job_fields(rng, TRAFFIC, 0, False) == family.job_fields(
        random.Random(9), TRAFFIC, 0, False)


def test_the_job_maker_sends_a_txt2txt_job():
    spec = {"cell": {"name": "kimi-batch-decode"}, "config": CONFIG,
            "traffic": TRAFFIC}
    job = harness.JobMaker(spec, 11, family).next()
    assert job["workflow"] == "txt2txt"
    assert job["model_name"] == "test/Kimi-K2.6"
    assert job["max_new_tokens"] == 256 and job["temperature"] == 1.0
    assert len(job["prompt_ids"]) == 64 and 0 <= job["seed"] < 2 ** 31
    from chiaswarm_tpu.coalesce import coalesce_key, job_rows

    assert coalesce_key(job) == ("test/Kimi-K2.6", "kimi_k2", "txt2txt",
                                 256, 256, 1.0)
    assert job_rows(job) == 64


# --- check_artifact -----------------------------------------------------------


def artifact(rows):
    blob = json.dumps({"token_ids": rows}).encode()
    return blob, {"sha256": hashlib.sha256(blob).hexdigest()}


def good_rows():
    return [[(7 * r + c) % 20480 for c in range(256)] for r in range(64)]


def test_check_artifact_takes_sixty_four_rows_of_new_tokens():
    assert family.check_artifact(*artifact(good_rows()), CONFIG) is None


@pytest.mark.parametrize("spoil, says", [
    (lambda rows: rows[:63], "63 rows"),
    (lambda rows: [rows[0][:255]] + rows[1:], "255 ids"),
    (lambda rows: [[20480] + rows[0][1:]] + rows[1:], "outside"),
    (lambda rows: [[-1] + rows[0][1:]] + rows[1:], "outside"),
    (lambda rows: [[1.5] + rows[0][1:]] + rows[1:], "outside"),
    (lambda rows: [rows[0]] * 64, "same ids"),
], ids=["a_row_missing", "a_short_row", "id_past_the_slice", "negative_id",
        "no_whole_number", "rows_all_alike"])
def test_check_artifact_refuses(spoil, says):
    assert says in family.check_artifact(*artifact(spoil(good_rows())),
                                         CONFIG)


def test_check_artifact_refuses_a_picture_and_a_wrong_name():
    import io

    from PIL import Image

    buffer = io.BytesIO()
    Image.new("RGB", (64, 64), 3).save(buffer, "PNG")
    blob = buffer.getvalue()
    ref = {"sha256": hashlib.sha256(blob).hexdigest()}
    assert "no JSON" in family.check_artifact(blob, ref, CONFIG)
    blob, ref = artifact(good_rows())
    assert "hash" in family.check_artifact(blob, {"sha256": "0" * 64}, CONFIG)
    caption = json.dumps({"caption": "a fox"}).encode()
    assert "no JSON object with token_ids" in family.check_artifact(
        caption, {"sha256": hashlib.sha256(caption).hexdigest()}, CONFIG)


# --- the cost function --------------------------------------------------------


def test_expert_matmul_cost_by_hand():
    # a decode step of one layer: 64 pairs over 12 experts that all had one
    flops, nbytes = cost.needed(64, 12, 7168, 2048)
    # gate, up, down: 3 matmuls of 7168 x 2048 MACs a pair, 2 flops a MAC
    assert flops == 64 * 3 * 7168 * 2048 * 2 == 5_637_144_576
    # 12 experts' three bf16 matrices, and a pair's row in and out (7168
    # each), its inner activation written and read (2048 each)
    assert nbytes == (12 * 3 * 7168 * 2048 + 64 * (2 * 7168 + 2 * 2048)) * 2
    seconds, bound = least_seconds(flops, nbytes, "TPU v5 lite")
    assert bound == "memory"
    assert seconds == pytest.approx(1_059_323_904 / 819e9, rel=1e-3)
    # a prefill chunk: 2000 pairs are still under the matrices' read
    assert least_seconds(*cost.needed(2000, 12, 7168, 2048),
                         "TPU v5 lite")[1] == "memory"
    # experts without a pair are not read, and nothing routed costs nothing
    assert cost.needed(0, 0, 7168, 2048) == (0.0, 0.0)
    assert cost.needed(5, 1, 7168, 2048)[1] < cost.needed(
        5, 5, 7168, 2048)[1]


# --- the readers --------------------------------------------------------------


def span(name, start, seconds, thread="slice"):
    return {"name": name, "thread": thread, "start_wall": start,
            "seconds": seconds}


def envelope_job(n, gang, t0, routing):
    config = {
        "batch_rows": [64 * (n % 4), 64], "sequences": 64,
        "decode_steps": 255,
        "timings": {"prefill_s": 2.0, "decode_s": 5.1, "job_s": 7.2},
        "trace": {"gang": {"id": gang}}, "routing": routing,
        "spans": [span("prefill", t0, 2.0), span("decode", t0 + 2.0, 5.1),
                  span("readback", t0 + 7.1, 0.05), span("pass", t0, 7.2)]}
    return {"id": f"j{n}", "withdrawn": False, "in_window": True,
            "submit_wall": t0 - 7.0,
            "trace": {"events": [{"event": "settle", "wall": t0 + 7.4}]},
            "status": {"status": "done",
                       "result": {"pipeline_config": config}}}


ROUTING = {"pairs": 200_000, "routed": 6_400_000, "pairs_max": 40_000,
           "active": 19_500, "calls": 1626,
           "prefill": {"pairs": 100_000, "active": 1_152, "calls": 96},
           "decode": {"pairs": 100_000, "active": 18_348, "calls": 1530}}


def record():
    jobs = [envelope_job(n, "a", 100.0, ROUTING) for n in range(4)] \
        + [envelope_job(4 + n, "b", 107.3, ROUTING) for n in range(4)]
    label = "test/Kimi-K2.6"
    counters = lambda scale: {
        "swarm_expert_pairs_total": {label: 200_000 * scale},
        "swarm_routed_tokens_total": {label: 6_400_000 * scale},
        "swarm_expert_pairs_max_total": {label: 40_000 * scale},
        "swarm_pass_cache_bytes": {label: 1_056_964_608}}
    return {"jobs": jobs, "window": {"open_wall": 99.0, "close_wall": 120.0},
            "scrape_open": counters(1), "scrape_close": counters(3),
            "spec": {"config": CONFIG}, "device": {"kind": "TPU v5 lite"}}


def read(name, rec):
    return harness.load_reader("layer_metrics", name)(rec)


def test_span_and_counter_readers_on_a_hand_made_record():
    rec = record()
    assert read("prefill_s_per_pass", rec) == 2.0
    assert read("decode_ms_per_step", rec) == pytest.approx(20.0)
    assert read("sequences_per_pass", rec) == 256.0
    assert read("held_expert_pair_share", rec) == pytest.approx(3.125)
    # the fullest of 12 held experts had 40 of every 200 pairs: 2.4 means
    assert read("expert_load_max_over_mean", rec) == pytest.approx(2.4)
    assert read("pass_cache_gb", rec) == pytest.approx(1.056964608)


def test_the_roofline_reader_prorates_a_pass_by_its_spans_in_the_stretch():
    rec = record()
    # the stretch: wall 104.55 (half of pass a's decode left) to 109.3
    # (pass b's prefill whole); the tracer's mark says wall 104.55 at 1 s
    rec["trace"] = {
        "busy_s": 4.0, "stretch_ns": [1e9, 5.75e9],
        "annotations": [("bench_sync wall=104.550000", 1e9, 0.0)],
        "op_seconds": {"expert_matmul": 2.0, "fusion": 2.0},
        "kernel_calls": {"expert_matmul": [
            {"seconds": 1.0, "shapes": []}, {"seconds": 1.0, "shapes": []}]}}
    assert read("expert_matmul_device_share", rec) == pytest.approx(50.0)
    pairs = 0.5 * 100_000 + 100_000
    active = 0.5 * 18_348 + 1_152
    least, bound = least_seconds(*cost.needed(pairs, active, 7168, 2048),
                                 "TPU v5 lite")
    assert read("expert_matmul_roofline", rec) == pytest.approx(
        100.0 * least / 2.0)
    assert rec["notes"]["expert_matmul_roofline"]["bound_by"] == bound
    assert rec["notes"]["expert_matmul_roofline"]["pairs"] == pytest.approx(
        pairs)


def test_the_new_readers_find_nothing_on_a_program_without_the_counters():
    rec = record()
    for job in rec["jobs"]:
        config = job["status"]["result"]["pipeline_config"]
        for key in ("sequences", "decode_steps", "routing"):
            del config[key]
        config["timings"] = {"job_s": 7.2}
        config["spans"] = [span("pass", 100.0, 7.2)]
    rec["scrape_open"] = rec["scrape_close"] = {}
    rec["trace"] = {"busy_s": 4.0, "stretch_ns": [1e9, 5e9],
                    "annotations": [], "op_seconds": {"fusion": 4.0},
                    "kernel_calls": {"expert_matmul": []}}
    for name in ("prefill_s_per_pass", "decode_ms_per_step",
                 "sequences_per_pass", "held_expert_pair_share",
                 "expert_load_max_over_mean", "pass_cache_gb",
                 "expert_matmul_device_share", "expert_matmul_roofline"):
        assert read(name, rec) is None, name


# --- the weight factory and the configuration file ----------------------------


def test_seeded_leaves_follow_the_programs_rules_and_the_seed():
    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu.pipelines.text_generation import (
        TextGenerationPipeline,
    )

    made = {}

    def weights(seed):
        def make(shapes, shardings):
            made[seed] = family.seeded_leaves(shapes, shardings, seed)
            return made[seed]
        return make

    pipe = TextGenerationPipeline("test/tiny-kimi", dtype=jnp.float32,
                                  weights=weights(5))
    TextGenerationPipeline("test/tiny-kimi", dtype=jnp.float32,
                           weights=weights(6))
    tree = pipe.params
    assert (jax.tree_util.tree_structure(tree)
            == jax.tree_util.tree_structure(pipe.param_shapes()))
    moe = tree["layers"][1]["moe"]
    assert float(tree["layers"][0]["input_norm"].min()) == 1.0
    assert float(jnp.abs(moe["router_bias"]).max()) < 0.06
    # a stack of experts is scaled by one expert's rows, not the stack's
    assert float(moe["experts"]["gate"].std()) == pytest.approx(
        64 ** -0.5, rel=0.1)
    assert float(tree["layers"][0]["mlp"]["down"].std()) == pytest.approx(
        128 ** -0.5, rel=0.1)
    other = made[6]["layers"][1]["moe"]["experts"]["gate"]
    assert not bool((moe["experts"]["gate"] == other).all())
    again = family.seeded_leaves(pipe.param_shapes(), pipe.param_shardings(),
                                 5)
    assert bool((again["head"] == tree["head"]).all())


def test_the_configuration_is_the_published_config_but_for_the_cut():
    import dataclasses

    from chiaswarm_tpu.models.kimi import KIMI_K2_EP32, KimiConfig

    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        row = next(json.loads(line) for line in catalog.read_text().splitlines()
                   if json.loads(line)["name"] == "Kimi-K2.6")
        assert CONFIG["source"] == row["source_url"]
        differs = {key for key, value in row["config"].items()
                   if CONFIG.get(key) != value}
        assert differs == set(CONFIG["reduced"]) == {
            "num_hidden_layers", "n_routed_experts", "vocab_size"}
        assert CONFIG["published"]["num_hidden_layers"] == row["layers"]
        assert CONFIG["published"]["vocab_size"] == row["vocab_size"]
    # and the program's own preset is the file, name for name
    for field in dataclasses.fields(KimiConfig):
        if field.name in CONFIG and field.name != "n_routed_experts":
            assert getattr(KIMI_K2_EP32, field.name) == CONFIG[field.name]
    share = CONFIG["deployment_share"]
    assert KIMI_K2_EP32.experts_held == tuple(share["experts_held"])
    assert KIMI_K2_EP32.n_routed_experts == share["router_width"] == 384
    assert CONFIG["n_routed_experts"] == share["experts_held"][1] == 12
    assert KIMI_K2_EP32.rope_factor == CONFIG["rope_scaling"]["factor"]
    assert "one of 32 chips that share each layer" in share["what"]
    assert "the layers left out lie on further stages" in share["what"]


def test_the_parents_program_fails_register_with_a_run_failure(monkeypatch):
    """No `pipelines/text_generation.py` (the parent of PR 32): a
    `RunFailure` from `register`, before anything is built."""
    import sys

    monkeypatch.setitem(
        sys.modules, "chiaswarm_tpu.pipelines.text_generation", None)
    with pytest.raises(harness.RunFailure, match="text_generation"):
        family.register(1, {})
