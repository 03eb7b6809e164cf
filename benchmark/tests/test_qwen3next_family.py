"""The `qwen3_next` family away from the chip: its traffic's draws, the cell
as the issue names it, the three new readers on a hand-made record and on a
program without the gauge and the kernel, the step kernel's cost against a
hand count, the configuration file against the published config and the
program's own `Qwen3NextConfig`, the operations' comparison and the
network's half of `correct` 5 at the tiny preset, and the parent's clean
failure."""

import json
import random
from pathlib import Path

import numpy as np
import pytest

from benchmark import harness
from benchmark.costs import gated_delta_rule as cost
from benchmark.families import qwen3_next as family

REPO = Path(__file__).resolve().parents[2]
CONFIG = json.loads((REPO / "benchmark" / "configs"
                     / "qwen3-next-80b-ep4.json").read_text())
TRAFFIC = json.loads((REPO / "benchmark" / "traffic"
                      / "batch-decode-37984.json").read_text())
CELL = "qwen3next-batch-decode"
NEW = ("state_cache_gb", "gated_delta_device_share", "gated_delta_roofline")


def test_a_job_is_64_ragged_rows_of_the_held_vocabulary_in_one_bucket():
    from chiaswarm_tpu.coalesce import coalesce_key, job_rows, prompt_slots

    rng = random.Random(5)
    jobs = [family.job_fields(rng, TRAFFIC, n, False)["prompt_ids"]
            for n in range(6)]
    assert all(len(job) == 64 for job in jobs)
    lengths = [len(row) for job in jobs for row in job]
    assert all(16 <= n <= 256 for n in lengths)
    # ragged against the rule's 64-position chunk: every pass hands the
    # state over at rows' own lengths
    assert len({n % 64 for n in lengths}) > 32
    assert {prompt_slots(max(len(row) for row in job)) for job in jobs} == {
        256}
    assert all(0 <= i < 37984 for job in jobs for row in job for i in row)
    assert max(i for job in jobs for row in job for i in row) > 20480
    probe = family.job_fields(random.Random(1), TRAFFIC, 0, True)
    assert probe == family.job_fields(random.Random(2), TRAFFIC, 9, True)
    spec = harness.load_cell(CELL)
    maker = harness.JobMaker(spec, 2 ** 31 + 5, family)
    made = [maker.next() for _ in range(3)] + [maker.probe()]
    assert {coalesce_key(job) for job in made} == {(
        "test/Qwen3-Next-80B-A3B-Instruct", "qwen3_next", "txt2txt", 256,
        256, 1.0)}
    assert {job_rows(job) for job in made} == {64}


def test_the_cell_is_what_the_issue_names():
    spec = harness.load_cell(CELL)
    assert spec["cell"]["chips"] == 1
    assert (spec["cell"]["config"], spec["cell"]["traffic"]) == (
        "qwen3-next-80b-ep4", "batch-decode-37984")
    assert (TRAFFIC["generator"], TRAFFIC["clients"], TRAFFIC["think_s"],
            TRAFFIC["status_poll_s"], TRAFFIC["trace_cycles"],
            TRAFFIC["trace_max_s"], TRAFFIC["probe"]["seed"]) == (
                "closed_loop", 8, 0, 0.02, 1, 30, 1234)
    assert TRAFFIC["job"] == {"max_new_tokens": 256, "temperature": 1.0,
                              "content_type": "application/json"}
    tokens = TRAFFIC["tokens"]
    assert (tokens["sequences"], tokens["length_min"], tokens["length_max"],
            tokens["vocabulary"], tokens["zipf_exponent"]) == (
                64, 16, 256, 37984, 1.1)
    # `batch-decode` with the vocabulary this chip holds, and nothing else
    kimi = json.loads((REPO / "benchmark" / "traffic"
                       / "batch-decode.json").read_text())
    ours = json.loads(json.dumps(TRAFFIC))
    kimi["tokens"]["vocabulary"] = ours["tokens"]["vocabulary"]
    kimi["what"] = ours["what"]
    assert ours == kimi
    assert {m["name"] for m in spec["end_to_end"]} == {
        "job_latency_p50_s", "hbm_peak_gb", "setup_s"}
    names = {m["name"] for m in spec["per_layer"]}
    assert {*NEW, "prefill_s_per_pass", "decode_ms_per_step",
            "sequences_per_pass", "held_expert_pair_share", "pass_cache_gb",
            "expert_matmul_device_share", "expert_matmul_roofline",
            "client_turnaround_ms", "hive_queue_wait_s",
            "worker_queue_wait_s", "solo_device_idle_share"} <= names
    # Kimi's key, SDAR's three, the banded kernel's, the rings'
    assert not names & {
        "expert_load_max_over_mean", "tokens_per_forward",
        "commit_forward_share", "idle_slot_share",
        "banded_attention_roofline", "banded_attention_device_share",
        "window_cache_gb", "prefill_padding_share"}
    benchmark = json.loads((REPO / "BENCHMARK.json").read_text())
    assert len(benchmark["workloads"]) == 9 and len(benchmark["configs"]) == 7
    assert sum(cell["chips"] == 4 for cell in benchmark["workloads"]) == 1
    for metric in benchmark["per_layer"]:
        if metric["name"] in NEW:
            assert metric["workloads"] == [CELL]
    assert [metric["name"] for metric in benchmark["per_layer"][-3:]] == list(
        NEW)


def test_the_step_kernels_cost_is_a_hand_count():
    """The cell's call: 256 rows x 32 heads of [128, 128] float32. The
    state is 134,217,728 values, read once and written once; a head's q
    and k (128 each), v in and o out (128 each), g and beta: 514 values;
    seven operations a value of the state."""
    flops, nbytes = cost.needed(256, 32, 128, 128)
    state = 256 * 32 * 128 * 128
    assert state == 134217728
    assert nbytes == 4 * (2 * state + 256 * 32 * 514) == 1090584576
    assert flops == 7 * state == 939524096
    # memory bound on a v5e: 1.33 ms a call, six calls a step
    from benchmark.costs.peaks import least_seconds

    seconds, bound = least_seconds(flops, nbytes, "TPU v5 lite")
    assert bound == "memory" and seconds == pytest.approx(1.3316e-3, rel=1e-3)
    # from the traced instruction's own shapes: results first (o, state),
    # then the operands the kernel hands itself, whatever their layout
    shapes = [(256, 32, 128), (256, 32, 128, 128), (256, 128, 64),
              (256, 32, 128), (256, 32, 128), (256, 32, 128),
              (256, 32, 128, 128)]
    assert cost.call_of(shapes) == (256, 32, 128, 128)
    assert cost.call_of([(256, 32, 128), (4,)]) is None


def _trace(calls, busy=10.0):
    return {"busy_s": busy, "kernel_calls": {"gated_delta_step": calls},
            "op_seconds": {"gated_delta_step": sum(
                call["seconds"] for call in calls)} if calls else {}}


def test_the_three_readers_on_a_hand_made_record():
    model = CONFIG["job"]["model_name"]
    read = {name: harness.load_reader("layer_metrics", name) for name in NEW}
    shapes = [(256, 32, 128), (256, 32, 128, 128), (256, 128, 64),
              (256, 32, 128, 128)]
    # six calls a step at 1.6 ms each where 1.3316 is the least
    calls = [{"seconds": 1.6e-3, "shapes": shapes} for _ in range(6 * 255)]
    record = {"spec": {"config": CONFIG},
              "device": {"kind": "TPU v5 lite"}, "trace": _trace(calls),
              "scrape_close": {"swarm_pass_state_bytes": {
                  model: 3296722944.0, "another": 1.0}}}
    assert read["state_cache_gb"](record) == pytest.approx(3.296722944)
    assert read["gated_delta_device_share"](record) == pytest.approx(
        100 * 6 * 255 * 1.6e-3 / 10.0)
    assert read["gated_delta_roofline"](record) == pytest.approx(
        100 * 1.3316 / 1.6, rel=1e-3)
    assert record["notes"]["gated_delta_roofline"] == {
        "calls": 1530, "bound_by": {"compute": 0, "memory": 1530}}
    # a share of a roofline stays under 100 % while a call takes its least
    calls = [{"seconds": 1.3317e-3, "shapes": shapes}]
    assert 99.9 < read["gated_delta_roofline"](
        {**record, "trace": _trace(calls)}) < 100.0


def test_the_new_readers_find_nothing_on_a_program_without_them():
    """The parent of PR 42 under this PR's files (no gauge, no kernel), an
    untraced run, and another model's gauge."""
    for name in NEW:
        read = harness.load_reader("layer_metrics", name)
        for trace in (None, _trace([]), {"busy_s": 5.0, "kernel_calls": {},
                                         "op_seconds": {"fusion": 1.0}}):
            record = {"spec": {"config": CONFIG},
                      "device": {"kind": "TPU v5 lite"}, "trace": trace,
                      "scrape_close": {"swarm_pass_state_bytes": {
                          "test/Kimi-K2.6": 0.0}}}
            assert read(record) is None
        assert read({"spec": {"config": CONFIG}, "scrape_close": {},
                     "device": {"kind": "TPU v5 lite"}}) is None


def test_the_configuration_is_the_published_config_but_for_the_cut():
    import dataclasses

    from chiaswarm_tpu.coalesce import TEXT_FAMILIES
    from chiaswarm_tpu.models.qwen3_next import (
        QWEN3_NEXT_80B_EP4,
        Qwen3NextConfig,
    )

    cut = {"num_hidden_layers", "num_experts", "vocab_size"}
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        row = next(json.loads(line) for line in catalog.read_text().splitlines()
                   if json.loads(line)["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert CONFIG["source"] == row["source_url"]
        differs = {key for key, value in row["config"].items()
                   if CONFIG.get(key, "absent") != value}
        assert differs == set(CONFIG["reduced"]) == cut
        assert all(CONFIG["published"][key] == row["config"][key]
                   for key in cut)
    assert (CONFIG["hidden_size"], CONFIG["head_dim"],
            CONFIG["num_attention_heads"], CONFIG["num_key_value_heads"],
            CONFIG["linear_num_key_heads"], CONFIG["linear_num_value_heads"],
            CONFIG["linear_key_head_dim"], CONFIG["linear_value_head_dim"],
            CONFIG["linear_conv_kernel_dim"], CONFIG["moe_intermediate_size"],
            CONFIG["shared_expert_intermediate_size"],
            CONFIG["num_experts_per_tok"], CONFIG["full_attention_interval"],
            CONFIG["partial_rotary_factor"]) == (
                2048, 256, 16, 2, 16, 32, 128, 128, 4, 512, 512, 10, 4, 0.25)
    share = CONFIG["deployment_share"]
    for field in dataclasses.fields(Qwen3NextConfig):
        if field.name == "num_experts":  # the file's is the experts held
            assert QWEN3_NEXT_80B_EP4.num_experts == share["router_width"]
        elif field.name in CONFIG:
            assert getattr(QWEN3_NEXT_80B_EP4, field.name) == CONFIG[
                field.name], field.name
    assert QWEN3_NEXT_80B_EP4.experts_held == tuple(
        share["experts_held"]) == (0, CONFIG["num_experts"]) == (0, 128)
    assert share["vocabulary_rows_held"] == [0, CONFIG["vocab_size"]]
    assert (share["chips_sharing_a_layer"], share["pipeline_stages"]) == (4, 6)
    assert share["pipeline_stages"] * CONFIG["num_hidden_layers"] == 48
    assert share["chips_sharing_a_layer"] * CONFIG["num_experts"] == 512
    assert "4-fold" in share["overstated"]
    assumed = " ".join(CONFIG["assumed"])
    for said in ("pre-norm residuals", "zero-centred", "float32 [32 value",
                 "L2-normalised", "chunk form", "first 0.25 x 256 = 64",
                 "A_log = log U(0, 16)", "no multi-token-prediction head"):
        assert said in assumed, said
    assert set(CONFIG["leaf_layout"]) == {"qkvz", "ba", "conv", "q_gate",
                                          "norms"}
    assert CONFIG["expected_kernel_paths"] == [
        "attention,reference", "expert_matmul,grouped",
        "gated_delta_step,pallas"]
    assert CONFIG["traced_kernels"] == ["expert_matmul", "gated_delta_step"]
    assert CONFIG["job"]["model_name"] == "test/Qwen3-Next-80B-A3B-Instruct"
    assert TEXT_FAMILIES[family.FAMILY]["wire"] == family.PIPELINE_TYPE
    assert CONFIG["kernel_shapes"]["gated_delta_step"][0][:4] == [
        256, 32, 128, 128]


def _rehearsal():
    spec = harness.load_cell(CELL)
    harness.apply_rehearsal(spec)
    return spec["config"]


def test_the_operations_are_the_references_and_a_bfloat16_state_is_not():
    import jax.numpy as jnp

    config = _rehearsal()
    failures, readings = family.kernel_checks(config, jnp.float32, True)
    assert failures == []
    assert [next(iter(reading)) for reading in readings] == [
        "gated_delta_step", "gated_delta_chunks", "expert_matmul",
        "expert_matmul", "causal_attention"]
    # the control rounds the state between chunks of 64: two chunks here
    config["kernel_shapes"]["gated_delta_chunks"] = [[2, 128, 4, 8, 8]]
    _, readings = family.kernel_checks(config, jnp.float32, True)
    sound = {next(iter(r)): r["max_abs"] for r in readings}
    control = family.low_precision_controls(config)
    for kernel in ("gated_delta_step", "gated_delta_chunks"):
        assert sound[kernel] < 1e-6
        assert control[kernel][0] > 100 * sound[kernel]


def test_the_served_logits_are_the_references(monkeypatch):
    """`correct` 5's two halves at the rehearsal's size: ragged rows
    through the pipeline's prefill and step programs against the
    reference's one full forward, the positions a margin leaves out left
    out on both sides."""
    from chiaswarm_tpu.pipelines.text_generation import (
        TextGenerationPipeline,
    )

    config = _rehearsal()
    pipe = TextGenerationPipeline("test/tiny-qwen3-next",
                                  allow_random_init=True)
    inputs = family.denoiser_inputs(pipe, config, 2 ** 31 + 11)
    assert inputs["ids"].shape == (16, 16) and inputs["given"].shape == (
        16, 3)
    assert len(set(inputs["lengths"].tolist())) > 4
    assert inputs["held"] == (0, 8)
    assert inputs["sizes"]["num_experts"] == 32
    monkeypatch.setattr(family, "ROUTING_MARGIN", 0.002)
    want = family.denoiser_reference(pipe, inputs)
    kept = inputs["kept"]
    assert kept.shape == (2, 4) and 0 < kept.sum() < kept.size
    assert (inputs["margins"][kept] >= 0.002).all()
    assert (inputs["margins"][~kept] < 0.002).all()
    got = np.asarray(family.denoiser_serve(pipe, inputs))
    want = np.asarray(want)
    assert got.shape == want.shape == (int(kept.sum()), 128)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 2e-5
    rounded = np.asarray(family.int8_control(pipe, inputs))
    assert np.linalg.norm(rounded - want) / np.linalg.norm(want) > 1e-3


def test_the_seeded_weights_go_through_the_programs_own_finish(monkeypatch):
    """`register`'s factory: Kimi's pool, then `A_log` as the published
    `log U(0, 16)` and every other leaf as the pool gave it."""
    import jax

    from chiaswarm_tpu import registry

    made = {}
    monkeypatch.setattr(registry, "register_family",
                        lambda name: lambda factory: made.update(
                            {name: factory}))
    record: dict = {}
    family.register(2 ** 31 + 3, record)
    pipe = made["qwen3_next"]("test/tiny-qwen3-next", None)
    mixer = pipe.params["layers"][0]["mixer"]
    a = np.exp(np.asarray(mixer["A_log"]))
    assert ((0 < a) & (a < 16)).all()
    assert (np.asarray(mixer["dt_bias"]) == 1).all()
    offset = np.asarray(pipe.params["final_norm_offset"])
    assert 0.03 < offset.std() < 0.3
    assert "test/tiny-qwen3-next" in record["weights_ready_s"]
    assert jax.tree_util.tree_structure(pipe.params) == \
        jax.tree_util.tree_structure(pipe.param_shapes())


def test_the_parents_program_fails_register_with_a_run_failure(monkeypatch):
    """No `models/qwen3_next.py` (the parent of PR 42): a `RunFailure`
    from `register`, before anything is built."""
    import sys

    monkeypatch.setitem(sys.modules, "chiaswarm_tpu.models.qwen3_next", None)
    with pytest.raises(harness.RunFailure, match="models/qwen3_next.py"):
        family.register(1, {})
