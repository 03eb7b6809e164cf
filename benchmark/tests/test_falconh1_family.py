"""The `falcon_h1` family away from the chip: its traffic's draws, the cell
as the issue names it, the new readers on a hand-made record and on a
program without the kernel, the step kernel's cost against a hand count,
the configuration file against the published config and the program's own
`FalconH1Config`, the operations' comparison and the network's half of
`correct` 5 at the tiny preset, the cell's whole rehearsal on the CPU, and
the parent's clean failure."""

import json
import os
import random
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark import harness
from benchmark.costs import ssd_step as cost
from benchmark.families import falcon_h1 as family

REPO = Path(__file__).resolve().parents[2]
CONFIG = json.loads((REPO / "benchmark" / "configs"
                     / "falcon-h1-34b-pp18.json").read_text())
TRAFFIC = json.loads((REPO / "benchmark" / "traffic"
                      / "batch-decode-261120.json").read_text())
CELL = "falconh1-batch-decode"
NEW = ("ssd_step_device_share", "ssd_step_roofline")
# the four-dimensional arrays of a traced call: the state among the
# results and the operands, `B | C` a group as columns
SHAPES = [(256, 32, 128), (256, 32, 256, 128), (256, 2, 256, 2),
          (256, 32, 128), (256, 32, 128), (256, 32, 256, 128)]


def test_a_job_is_64_ragged_rows_of_the_whole_vocabulary_in_one_bucket():
    from chiaswarm_tpu.coalesce import coalesce_key, job_rows, prompt_slots

    rng = random.Random(5)
    jobs = [family.job_fields(rng, TRAFFIC, n, False)["prompt_ids"]
            for n in range(6)]
    assert all(len(job) == 64 for job in jobs)
    lengths = [len(row) for job in jobs for row in job]
    assert all(16 <= n <= 256 for n in lengths)
    # ragged against the 128-position chunk: every pass hands the state
    # over at rows' own lengths
    assert len({n % 128 for n in lengths}) > 64
    assert {prompt_slots(max(len(row) for row in job)) for job in jobs} == {
        256}
    assert all(0 <= i < 261120 for job in jobs for row in job for i in row)
    assert max(i for job in jobs for row in job for i in row) > 200000
    probe = family.job_fields(random.Random(1), TRAFFIC, 0, True)
    assert probe == family.job_fields(random.Random(2), TRAFFIC, 9, True)
    spec = harness.load_cell(CELL)
    maker = harness.JobMaker(spec, 2 ** 31 + 5, family)
    made = [maker.next() for _ in range(3)] + [maker.probe()]
    assert {coalesce_key(job) for job in made} == {(
        "test/Falcon-H1-34B-Instruct", "falcon_h1", "txt2txt", 256, 256,
        1.0)}
    assert {job_rows(job) for job in made} == {64}


def test_the_cell_is_what_the_issue_names():
    spec = harness.load_cell(CELL)
    assert spec["cell"]["chips"] == 1
    assert (spec["cell"]["config"], spec["cell"]["traffic"]) == (
        "falcon-h1-34b-pp18", "batch-decode-261120")
    assert (TRAFFIC["generator"], TRAFFIC["clients"], TRAFFIC["think_s"],
            TRAFFIC["status_poll_s"], TRAFFIC["trace_cycles"],
            TRAFFIC["trace_max_s"], TRAFFIC["probe"]["seed"]) == (
                "closed_loop", 8, 0, 0.02, 1, 30, 1234)
    assert TRAFFIC["job"] == {"max_new_tokens": 256, "temperature": 1.0,
                              "content_type": "application/json"}
    tokens = TRAFFIC["tokens"]
    assert (tokens["sequences"], tokens["length_min"], tokens["length_max"],
            tokens["vocabulary"], tokens["zipf_exponent"]) == (
                64, 16, 256, 261120, 1.1)
    # `batch-decode` with the model's vocabulary, and nothing else
    kimi = json.loads((REPO / "benchmark" / "traffic"
                       / "batch-decode.json").read_text())
    ours = json.loads(json.dumps(TRAFFIC))
    kimi["tokens"]["vocabulary"] = ours["tokens"]["vocabulary"]
    kimi["what"] = ours["what"]
    assert ours == kimi
    assert {m["name"] for m in spec["end_to_end"]} == {
        "job_latency_p50_s", "hbm_peak_gb", "setup_s"}
    names = {m["name"] for m in spec["per_layer"]}
    assert set(NEW) | {"state_cache_gb", "pass_cache_gb",
                       "decode_ms_per_step", "prefill_s_per_pass",
                       "sequences_per_pass"} <= names
    # a dense model lists no reader of the experts, nor the other
    # recurrent family's kernel
    assert not names & {
        "held_expert_pair_share", "expert_matmul_device_share",
        "expert_matmul_roofline", "expert_load_max_over_mean",
        "gated_delta_device_share", "gated_delta_roofline"}
    whole = json.loads((REPO / "BENCHMARK.json").read_text())
    mine = [m for m in whole["per_layer"] if m["name"] in NEW]
    assert [(m["moves"], m["workloads"], m["unit"], m["source"])
            for m in mine] == [("job_latency_p50_s", [CELL], "%",
                                "device_trace")] * 2
    assert len(spec["cell"]["why"]) <= 200
    entry = next(c for c in whole["configs"]
                 if c["name"] == "falcon-h1-34b-pp18")
    assert entry["reduced"] == CONFIG["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == CONFIG["source"] and len(entry["why"]) <= 200


def test_the_step_kernels_cost_is_a_hand_count():
    """The cell's call: 256 rows x 32 heads of [256, 128] float32 in 2
    groups. The state is 268,435,456 values, read once and written once; a
    head's `x` in and `y` out (128 each), its step, rate and skip: 259
    values; a group's `B` and `C`, 512 values a row a group; five
    operations a value of the state."""
    flops, nbytes = cost.needed(256, 32, 256, 128, 2)
    state = 256 * 32 * 256 * 128
    assert state == 268435456
    assert nbytes == 4 * (2 * state + 256 * 32 * 259 + 256 * 2 * 512) \
        == 2157019136
    assert flops == 5 * state == 1342177280
    # memory bound on a v5e: 2.63 ms a call, four calls a step
    from benchmark.costs.peaks import least_seconds

    seconds, bound = least_seconds(flops, nbytes, "TPU v5 lite")
    assert bound == "memory" and seconds == pytest.approx(2.6337e-3, rel=1e-3)
    # from the traced instruction's own shapes, whatever their order
    assert cost.call_of(SHAPES) == (256, 32, 256, 128, 2)
    assert cost.call_of(list(reversed(SHAPES))) == (256, 32, 256, 128, 2)
    assert cost.call_of([(256, 32, 128), (4,)]) is None
    assert cost.call_of([(256, 32, 256, 128)]) is None


def _trace(calls, busy=10.0):
    return {"busy_s": busy, "kernel_calls": {"ssd_step": calls},
            "op_seconds": {"ssd_step": sum(
                call["seconds"] for call in calls)} if calls else {}}


def test_the_readers_on_a_hand_made_record():
    model = CONFIG["job"]["model_name"]
    read = {name: harness.load_reader("layer_metrics", name)
            for name in NEW + ("state_cache_gb",)}
    # four calls a step at 3.0 ms each where 2.6337 is the least
    calls = [{"seconds": 3.0e-3, "shapes": SHAPES} for _ in range(4 * 255)]
    record = {"spec": {"config": CONFIG},
              "device": {"kind": "TPU v5 lite"}, "trace": _trace(calls),
              "scrape_close": {"swarm_pass_state_bytes": {
                  model: 4326424576.0, "another": 1.0}}}
    assert read["state_cache_gb"](record) == pytest.approx(4.326424576)
    assert read["ssd_step_device_share"](record) == pytest.approx(
        100 * 4 * 255 * 3.0e-3 / 10.0)
    assert read["ssd_step_roofline"](record) == pytest.approx(
        100 * 2.6337 / 3.0, rel=1e-3)
    assert record["notes"]["ssd_step_roofline"] == {
        "calls": 1020, "bound_by": {"compute": 0, "memory": 1020}}
    # a share of a roofline stays under 100 % while a call takes its least
    calls = [{"seconds": 2.6338e-3, "shapes": SHAPES}]
    assert 99.9 < read["ssd_step_roofline"](
        {**record, "trace": _trace(calls)}) < 100.0


def test_the_new_readers_find_nothing_on_a_program_without_them():
    """The parent of PR 46 under this PR's files (no kernel), an untraced
    run, and a call that does not show its state."""
    for name in NEW:
        read = harness.load_reader("layer_metrics", name)
        for trace in (None, _trace([]), {"busy_s": 5.0, "kernel_calls": {},
                                         "op_seconds": {"fusion": 1.0}}):
            record = {"spec": {"config": CONFIG},
                      "device": {"kind": "TPU v5 lite"}, "trace": trace,
                      "scrape_close": {}}
            assert read(record) is None
        assert read({"spec": {"config": CONFIG}, "scrape_close": {},
                     "device": {"kind": "TPU v5 lite"}}) is None
    hidden = _trace([{"seconds": 1e-3, "shapes": [(256, 32, 128)]}])
    assert harness.load_reader("layer_metrics", "ssd_step_roofline")(
        {"device": {"kind": "TPU v5 lite"}, "trace": hidden}) is None


def test_the_configuration_is_the_published_config_but_for_the_cut():
    import dataclasses

    from chiaswarm_tpu.coalesce import TEXT_FAMILIES
    from chiaswarm_tpu.models.falcon_h1 import (
        FALCON_H1_34B_PP18,
        FalconH1Config,
    )

    cut = {"num_hidden_layers"}
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        row = next(json.loads(line) for line in catalog.read_text().splitlines()
                   if json.loads(line)["name"] == "Falcon-H1-34B-Instruct")
        assert CONFIG["source"] == row["source_url"]
        differs = {key for key, value in row["config"].items()
                   if CONFIG.get(key, "absent") != value}
        assert differs == set(CONFIG["reduced"]) == cut
        assert CONFIG["published"]["num_hidden_layers"] == row["config"][
            "num_hidden_layers"] == 72
    assert (CONFIG["hidden_size"], CONFIG["head_dim"],
            CONFIG["num_attention_heads"], CONFIG["num_key_value_heads"],
            CONFIG["intermediate_size"], CONFIG["mamba_d_ssm"],
            CONFIG["mamba_n_heads"], CONFIG["mamba_d_head"],
            CONFIG["mamba_n_groups"], CONFIG["mamba_d_state"],
            CONFIG["mamba_d_conv"], CONFIG["mamba_chunk_size"],
            CONFIG["vocab_size"], CONFIG["num_hidden_layers"]) == (
                5120, 128, 20, 4, 21504, 4096, 32, 128, 2, 256, 4, 128,
                261120, 4)
    # every field of the program's config that the file has is the file's:
    # the widths and all fourteen multipliers
    seen = 0
    for field in dataclasses.fields(FalconH1Config):
        if field.name in CONFIG:
            mine = getattr(FALCON_H1_34B_PP18, field.name)
            assert (list(mine) if isinstance(mine, tuple) else mine) == \
                CONFIG[field.name], field.name
            seen += 1
    assert seen == 25
    share = CONFIG["deployment_share"]
    assert (share["chips_sharing_a_layer"], share["pipeline_stages"]) == (
        1, 18)
    assert share["pipeline_stages"] * CONFIG["num_hidden_layers"] == 72
    assert "first stage" in share["overstated"] and "fifth" in share[
        "overstated"]
    assumed = " ".join(CONFIG["assumed"])
    for said in ("summed before the one residual add", "where each multiplier",
                 "over each of the mamba_n_groups 2 groups",
                 "overrides mamba_expand", "float32 [32 heads, 256, 128]",
                 "chunk form", "rope_theta 1e11", "A_log = log U(0, 16)",
                 "no stop token", "512 positions are cached"):
        assert said in assumed, said
    assert "4,394,354,048 parameters" in CONFIG["as_run"]
    assert set(CONFIG["leaf_layout"]) == {"zxbc", "dt", "conv", "norm",
                                          "state"}
    assert CONFIG["expected_kernel_paths"] == [
        "attention,reference", "ssd_step,pallas"]
    assert CONFIG["traced_kernels"] == ["ssd_step"]
    assert CONFIG["job"]["model_name"] == "test/Falcon-H1-34B-Instruct"
    assert TEXT_FAMILIES[family.FAMILY]["wire"] == family.PIPELINE_TYPE
    assert CONFIG["kernel_shapes"]["ssd_step"][0][:5] == [
        256, 32, 256, 128, 2]
    # every position of the compared rows, none left out
    assert CONFIG["denoiser"]["compared_rows"] * (
        1 + CONFIG["denoiser"]["given_tokens"]) == 776


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
        "ssd_step", "ssd_chunks", "mixer", "attention", "feed_forward",
        "causal_attention"]
    # the control rounds the state between chunks of 8: four chunks here
    config["kernel_shapes"]["ssd_chunks"] = [[2, 32, 4, 16, 8, 2]]
    _, readings = family.kernel_checks(config, jnp.float32, True)
    sound = {next(iter(r)): r["max_abs"] for r in readings}
    control = family.low_precision_controls(config)
    for kernel in ("ssd_step", "ssd_chunks"):
        assert sound[kernel] < 2e-5
        assert control[kernel]["bfloat16_state"][0] > 100 * sound[kernel]
        assert control[kernel]["bfloat16_decays"][0] > 100 * sound[kernel]


def test_a_multiplier_left_out_of_a_layers_part_is_not_that_part():
    """`correct` 4's three parts of one seeded layer: the program's are the
    reference's, and each of the eight multipliers inside them, left out
    of the reference, moves what is compared by far more than float32
    does: the five that the logits of a few seeded layers hardly feel
    among them."""
    import jax.numpy as jnp

    readings = family.sublayer_controls(_rehearsal(), jnp.float32)
    assert set(readings) == set(family.SUBLAYER_TOLS) == {
        "mixer", "attention", "feed_forward"}
    assert sum(len(part) - 1 for part in readings.values()) == 9
    for part in readings.values():
        assert part["sound"] < 2e-6
        assert min(share for name, share in part.items()
                   if name != "sound") > 5e-3


def test_the_served_logits_are_the_references():
    """`correct` 5's two halves at the rehearsal's size: ragged rows
    through the pipeline's prefill and step programs against the
    reference's one full forward, every position compared; and a
    multiplier left out of the reference is no such agreement."""
    from chiaswarm_tpu.pipelines.text_generation import (
        TextGenerationPipeline,
    )

    config = _rehearsal()
    pipe = TextGenerationPipeline("test/tiny-falcon-h1",
                                  allow_random_init=True)
    inputs = family.denoiser_inputs(pipe, config, 2 ** 31 + 11)
    assert inputs["ids"].shape == (16, 16) and inputs["given"].shape == (
        16, 3)
    assert len(set(inputs["lengths"].tolist())) > 4
    assert inputs["sizes"]["ssm_multipliers"] == config["ssm_multipliers"]
    assert inputs["sizes"]["key_multiplier"] == 0.4  # the tiny preset's
    want = np.asarray(family.denoiser_reference(pipe, inputs))
    got = np.asarray(family.denoiser_serve(pipe, inputs))
    assert got.shape == want.shape == (2, 4, 128)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 5e-6
    readings = family.multiplier_controls(pipe, inputs, got)
    assert len(readings) == 14 and min(readings.values()) > 2e-4
    rounded = np.asarray(family.int8_control(pipe, inputs))
    assert np.linalg.norm(rounded - want) / np.linalg.norm(want) > 1e-3


def test_the_seeded_weights_go_through_the_programs_own_finish(monkeypatch):
    """`register`'s factory: Kimi's pool, then `A_log` as `log U(0, 16)`
    and every other leaf as the pool gave it under the program's rule."""
    import jax

    from chiaswarm_tpu import registry

    made = {}
    monkeypatch.setattr(registry, "register_family",
                        lambda name: lambda factory: made.update(
                            {name: factory}))
    record: dict = {}
    family.register(2 ** 31 + 3, record)
    pipe = made["falcon_h1"]("test/tiny-falcon-h1", None)
    mixer = pipe.params["layers"][0]["mixer"]
    a = np.exp(np.asarray(mixer["A_log"]))
    assert ((0 < a) & (a < 16)).all()
    assert (np.asarray(mixer["dt_bias"]) == 1).all()
    assert 0.6 < float(np.asarray(mixer["D"]).min()) < 1.4
    assert 0.03 < np.asarray(mixer["conv_bias"]).std() < 0.3
    assert "test/tiny-falcon-h1" in record["weights_ready_s"]
    assert jax.tree_util.tree_structure(pipe.params) == \
        jax.tree_util.tree_structure(pipe.param_shapes())


def test_the_parents_program_fails_register_with_a_run_failure(monkeypatch):
    """No `models/falcon_h1.py` (the parent of PR 46): a `RunFailure`
    from `register`, before anything is built."""
    monkeypatch.setitem(sys.modules, "chiaswarm_tpu.models.falcon_h1", None)
    with pytest.raises(harness.RunFailure, match="models/falcon_h1.py"):
        family.register(1, {})


def test_the_cell_rehearsed_whole_on_the_cpu():
    """`rehearse.py` walks every phase of the cell at the tiny preset:
    every job settles, the probe gives one hash among other batchmates,
    the kernels and the served logits are the references', the kernel's
    path is the one the rehearsal expects. (Gangs of two to four tiny jobs
    pad to other row buckets, so the window compiles: that limit is the
    chip's.)"""
    done = subprocess.run(
        [sys.executable, str(REPO / "benchmark" / "rehearse.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 46), "--seconds", "4",
         "--trace", "1"], capture_output=True, text=True, timeout=900,
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(line) for line in done.stdout.splitlines()
             if line.startswith("{")]
    result = lines[-1]
    assert result["attempted"] >= 4 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    compared = result["compared"]
    assert {name.split("_")[0] for name in compared} >= {"ssd", "causal",
                                                         "denoiser"}
    for name, (number, limit) in compared.items():
        if name != "window_compiles":
            assert number <= limit, name
    summary = next(line for line in lines if line.get("phase") == "summary")
    assert summary["failures"] == [] or all(
        "compil" in failure for failure in summary["failures"])
    assert summary["kernel_traces"]["ssd_step,reference"] > 0
    assert len(set(summary["probe_sha256"])) == 1
    assert {"sequences_per_pass", "state_cache_gb", "pass_cache_gb",
            "decode_ms_per_step"} <= set(result["metrics"])
