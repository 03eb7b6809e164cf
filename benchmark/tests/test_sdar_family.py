"""The `sdar` family away from the chip: its traffic's draws, the cell as
the issue names it, the three new readers on a hand-made record and on a
program without the counters, the configuration file against the published
config and the program's own `SdarConfig`, the network's half of `correct`
5 at the tiny preset (both forwards of every given block against the one
full forward), and the parent's clean failure."""

import json
import random
from pathlib import Path

import numpy as np
import pytest

from benchmark import harness
from benchmark.families import sdar as family

REPO = Path(__file__).resolve().parents[2]
CONFIG = json.loads((REPO / "benchmark" / "configs"
                     / "sdar-30b-a3b-pp8.json").read_text())
TRAFFIC = json.loads(
    (REPO / "benchmark" / "traffic" / "block-decode.json").read_text())
CELL = "sdar-block-decode"


def test_a_job_is_64_rows_of_one_slot_bucket_and_tails_of_every_length():
    from chiaswarm_tpu.coalesce import coalesce_key, job_rows, prompt_slots

    rng = random.Random(5)
    jobs = [family.job_fields(rng, TRAFFIC, n, False)["prompt_ids"]
            for n in range(6)]
    assert all(len(job) == 64 for job in jobs)
    lengths = [len(row) for job in jobs for row in job]
    assert all(16 <= n <= 256 for n in lengths)
    assert {prompt_slots(max(len(row) for row in job)) for job in jobs} == {
        256}
    assert {n % 4 for n in lengths} == {0, 1, 2, 3}
    assert all(0 <= i < 151936 for job in jobs for row in job for i in row)
    probe = family.job_fields(random.Random(1), TRAFFIC, 0, True)
    assert probe == family.job_fields(random.Random(2), TRAFFIC, 9, True)
    # the job as the harness makes it: one key for every job of the cell
    spec = harness.load_cell(CELL)
    maker = harness.JobMaker(spec, 7, family)
    made = [maker.next() for _ in range(3)] + [maker.probe()]
    assert {coalesce_key(job) for job in made} == {(
        "test/SDAR-30B-A3B-Chat", "sdar_moe", "txt2txt", 256, 256, 1.0, 2,
        None)}
    assert {job_rows(job) for job in made} == {64}


def test_the_cell_is_what_the_issue_names():
    spec = harness.load_cell(CELL)
    assert spec["cell"]["chips"] == 1
    assert (spec["cell"]["config"], spec["cell"]["traffic"]) == (
        "sdar-30b-a3b-pp8", "block-decode")
    assert (TRAFFIC["clients"], TRAFFIC["think_s"],
            TRAFFIC["status_poll_s"]) == (8, 0, 0.02)
    assert TRAFFIC["job"] == {
        "max_new_tokens": 256, "temperature": 1.0, "denoising_steps": 2,
        "content_type": "application/json"}
    tokens = TRAFFIC["tokens"]
    assert (tokens["sequences"], tokens["length_min"], tokens["length_max"],
            tokens["vocabulary"], tokens["zipf_exponent"]) == (
                64, 16, 256, 151936, 1.1)
    assert {m["name"] for m in spec["end_to_end"]} == {
        "job_latency_p50_s", "hbm_peak_gb", "setup_s"}
    names = {m["name"] for m in spec["per_layer"]}
    assert {"tokens_per_forward", "commit_forward_share", "idle_slot_share",
            "prefill_s_per_pass", "decode_ms_per_step", "sequences_per_pass",
            "held_expert_pair_share", "pass_cache_gb",
            "expert_matmul_device_share", "expert_matmul_roofline",
            "client_turnaround_ms", "hive_queue_wait_s",
            "worker_queue_wait_s", "solo_device_idle_share"} <= names
    # Kimi's key, no banded call in the window, no ring, one bucket
    assert not names & {
        "expert_load_max_over_mean", "banded_attention_roofline",
        "banded_attention_device_share", "window_cache_gb",
        "prefill_padding_share"}
    benchmark = json.loads((REPO / "BENCHMARK.json").read_text())
    assert len(benchmark["workloads"]) == 8
    assert sum(cell["chips"] == 4 for cell in benchmark["workloads"]) == 1


def _record(open_, close):
    return {"spec": {"config": CONFIG}, "scrape_open": open_,
            "scrape_close": close}


def test_the_three_readers_on_a_hand_made_record():
    """A pass of 256 rows at 2 denoise forwards a block: 130 denoise and 64
    commit forwards for 256 ids a row; of the denoise forwards' positions
    4 a block took an id and 2 were fixed before their forward."""
    model = CONFIG["job"]["model_name"]
    rows, tokens = "swarm_block_forward_rows_total", (
        "swarm_generated_tokens_total")
    slots = "swarm_block_slots_total"
    open_ = {rows: {f"{model},denoise": 1000.0, f"{model},commit": 500.0},
             tokens: {model: 2000.0},
             slots: {f"{model},unmasked": 10.0, f"{model},idle": 5.0}}
    close = {rows: {f"{model},denoise": 1000.0 + 256 * 130,
                    f"{model},commit": 500.0 + 256 * 64},
             tokens: {model: 2000.0 + 256 * 256},
             slots: {f"{model},unmasked": 10.0 + 256 * 65 * 4,
                     f"{model},idle": 5.0 + 256 * 65 * 2}}
    record = _record(open_, close)
    read = {name: harness.load_reader("layer_metrics", name) for name in (
        "tokens_per_forward", "commit_forward_share", "idle_slot_share")}
    assert read["tokens_per_forward"](record) == pytest.approx(256 / 194)
    assert read["commit_forward_share"](record) == pytest.approx(
        100 * 64 / 194)
    assert read["idle_slot_share"](record) == pytest.approx(100 / 3)
    # every expert held: the share that has to read 100
    pairs = {"swarm_expert_pairs_total": {model: 8192.0},
             "swarm_routed_tokens_total": {model: 8192.0}}
    assert harness.load_reader("layer_metrics", "held_expert_pair_share")(
        _record({}, pairs)) == pytest.approx(100.0)


def test_the_new_readers_find_nothing_on_a_program_without_the_counters():
    """The parent of PR 40, and a window in which no block decode ran."""
    model = CONFIG["job"]["model_name"]
    still = {"swarm_block_forward_rows_total": {f"{model},denoise": 5.0},
             "swarm_generated_tokens_total": {model: 9.0},
             "swarm_block_slots_total": {f"{model},idle": 3.0}}
    for name in ("tokens_per_forward", "commit_forward_share",
                 "idle_slot_share"):
        read = harness.load_reader("layer_metrics", name)
        assert read(_record({}, {})) is None
        assert read(_record(still, still)) is None


def test_the_configuration_is_the_published_config_but_for_the_depth():
    import dataclasses

    from chiaswarm_tpu.coalesce import TEXT_FAMILIES
    from chiaswarm_tpu.models.sdar import SDAR_30B_PP8, SdarConfig

    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        row = next(json.loads(line) for line in catalog.read_text().splitlines()
                   if json.loads(line)["name"] == "SDAR-30B-A3B-Chat")
        assert CONFIG["source"] == row["source_url"]
        differs = {key for key, value in row["config"].items()
                   if CONFIG.get(key) != value}
        assert differs == set(CONFIG["reduced"]) == {"num_hidden_layers"}
        assert CONFIG["published"]["num_hidden_layers"] == row["layers"] == 48
    assert (CONFIG["hidden_size"], CONFIG["num_attention_heads"],
            CONFIG["num_key_value_heads"], CONFIG["head_dim"],
            CONFIG["num_experts"], CONFIG["moe_intermediate_size"],
            CONFIG["num_experts_per_tok"], CONFIG["vocab_size"]) == (
                2048, 32, 4, 128, 128, 768, 8, 151936)
    for field in dataclasses.fields(SdarConfig):
        if field.name in CONFIG:
            assert getattr(SDAR_30B_PP8, field.name) == CONFIG[field.name]
    share = CONFIG["deployment_share"]
    assert SDAR_30B_PP8.experts_held == tuple(share["experts_held"]) == (
        0, 128)
    assert share["pipeline_stages"] * CONFIG["num_hidden_layers"] == 48
    assert "8-stage pipeline" in share["what"]
    sizes = CONFIG["assumed_sizes"]
    assert (SDAR_30B_PP8.block_length, SDAR_30B_PP8.mask_token_id) == (
        sizes["block_length"], sizes["mask_token_id"]) == (4, 151669)
    assert TEXT_FAMILIES["sdar_moe"]["block_length"] == sizes["block_length"]
    assumed = " ".join(CONFIG["assumed"])
    for said in ("block_length 4", "denoising_steps default", "mask id",
                 "masked is a state", "no shift", "commit forward",
                 "norm placement", "softmax over all 128"):
        assert said in assumed
    assert CONFIG["expected_kernel_paths"] == [
        "attention,reference", "expert_matmul,grouped"]
    assert CONFIG["job"]["model_name"] == "test/SDAR-30B-A3B-Chat"


def test_both_forwards_of_every_given_block_are_the_references(monkeypatch):
    """`correct` 5's two halves at the rehearsal's size: the serve side's
    `[2, kept positions, vocabulary]` (without commit, with) against the
    reference's one full forward a row, and the positions a margin leaves
    out are left out on both sides."""
    import jax.numpy as jnp

    from chiaswarm_tpu.pipelines.text_generation import (
        TextGenerationPipeline,
    )

    spec = harness.load_cell(CELL)
    harness.apply_rehearsal(spec)
    config = spec["config"]
    pipe = TextGenerationPipeline("test/tiny-sdar", allow_random_init=True)
    inputs = family.denoiser_inputs(pipe, config, 11)
    lengths, given = inputs["lengths"], inputs["given"]
    assert given.shape == (16, 2, 4)
    assert (given == 127).mean() > 0.25  # a seeded half the mask id
    for row, length in enumerate(lengths):  # a first block opens as given
        tail = length % 4
        assert (given[row, 0, :tail]
                == inputs["ids"][row, length - tail:length]).all()
    # a margin that leaves some positions out and keeps some
    monkeypatch.setattr(family, "ROUTING_MARGIN", 0.002)
    want = family.denoiser_reference(pipe, inputs)
    kept = inputs["kept"]
    assert kept.shape == (2, 8) and 0 < kept.sum() < kept.size
    assert (inputs["margins"][kept] >= 0.002).all()
    assert (inputs["margins"][~kept] < 0.002).all()
    got = family.denoiser_serve(pipe, inputs)
    assert got.shape == want.shape == (2, int(kept.sum()), 128)
    got, want = np.asarray(got), np.asarray(want)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 2e-5
    assert np.array_equal(got[0], got[1])
    # weights rounded to 8 bits read far over that
    rounded = np.asarray(family.int8_control(pipe, inputs))
    assert np.linalg.norm(rounded - want) / np.linalg.norm(want) > 1e-3
    assert isinstance(jnp.asarray(rounded), jnp.ndarray)


def test_the_parents_program_fails_register_with_a_run_failure(monkeypatch):
    """No `models/sdar.py` (the parent of PR 40): a `RunFailure` from
    `register`, before anything is built."""
    import sys

    monkeypatch.setitem(sys.modules, "chiaswarm_tpu.models.sdar", None)
    with pytest.raises(harness.RunFailure, match="models/sdar.py"):
        family.register(1, {})
