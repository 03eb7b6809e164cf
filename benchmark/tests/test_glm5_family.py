"""The `glm_moe_dsa` family away from the chip: its traffic's draws, the
cell as the issue names it, the new readers on a hand-made record and on a
program without the kernels or counters, the two cost functions against
hand counts, the configuration file against the published config and the
program's own `GlmMoeDsaConfig`, the operations' comparison with its two
controls and the network's half of `correct` 5 at the tiny preset, the
cell's whole rehearsal on the CPU, and the parent's clean failure."""

import json
import os
import random
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark import harness
from benchmark.costs import lightning_indexer as index_cost
from benchmark.costs import sparse_latent_attention as attention_cost
from benchmark.families import glm_moe_dsa as family

REPO = Path(__file__).resolve().parents[2]
CONFIG = json.loads((REPO / "benchmark" / "configs"
                     / "glm-5-ep16.json").read_text())
TRAFFIC = json.loads((REPO / "benchmark" / "traffic"
                      / "long-context-19360.json").read_text())
CELL = "glm5-long-context"
NEW = ("lightning_indexer_roofline", "sparse_latent_attention_roofline",
       "lightning_indexer_device_share", "index_select_device_share",
       "sparse_latent_attention_device_share", "selected_key_share",
       "index_cache_gb")
# the arrays of the cell's traced calls (every span's are alike: the span's
# first position is handed to the kernel as it runs), the result first,
# and behind the operands the kernel's own metadata
INDEXER = [(1, 4096, 32768), (1,), (1, 4096, 4096), (1, 4096, 32),
           (1, 32768, 128), (4096,), (32768,), (32,)]
ATTENTION = [(1, 4096, 16384), (1,), (1, 4096, 16384), (1, 32768, 16384),
             (1, 32768, 16384), (1, 4096, 32768), (4096,), (32768,), (64,)]


def test_a_job_is_one_long_row_of_the_held_vocabulary_in_one_bucket():
    from chiaswarm_tpu.coalesce import coalesce_key, job_rows, prompt_slots

    rng = random.Random(5)
    jobs = [family.job_fields(rng, TRAFFIC, n, False)["prompt_ids"]
            for n in range(12)]
    assert all(len(job) == 1 for job in jobs)
    lengths = [len(row) for job in jobs for row in job]
    assert all(28673 <= n <= 32768 for n in lengths)
    assert len(set(lengths)) == 12
    # one bucket, and every row runs all eight 4096-position spans
    assert {prompt_slots(n) for n in lengths} == {32768}
    assert all(-(-n // 4096) == 8 for n in lengths)
    assert all(0 <= i < 19360 for job in jobs for row in job for i in row)
    assert max(i for job in jobs for row in job for i in row) > 19000
    probe = family.job_fields(random.Random(1), TRAFFIC, 0, True)
    assert probe == family.job_fields(random.Random(2), TRAFFIC, 9, True)
    spec = harness.load_cell(CELL)
    maker = harness.JobMaker(spec, 2 ** 31 + 5, family)
    made = [maker.next() for _ in range(2)] + [maker.probe()]
    assert {coalesce_key(job) for job in made} == {(
        "test/GLM-5", "glm_moe_dsa", "txt2txt", 32768, 128, 1.0)}
    assert {job_rows(job) for job in made} == {1}


def test_the_cell_is_what_the_issue_names():
    from chiaswarm_tpu.chips import requirements

    spec = harness.load_cell(CELL)
    assert spec["cell"]["chips"] == 1
    assert (spec["cell"]["config"], spec["cell"]["traffic"]) == (
        "glm-5-ep16", "long-context-19360")
    assert (TRAFFIC["generator"], TRAFFIC["clients"], TRAFFIC["think_s"],
            TRAFFIC["status_poll_s"], TRAFFIC["trace_cycles"],
            TRAFFIC["trace_max_s"], TRAFFIC["probe"]["seed"]) == (
                "closed_loop", 4, 0, 0.02, 1, 30, 4321)
    assert TRAFFIC["job"] == {"max_new_tokens": 128, "temperature": 1.0,
                              "content_type": "application/json"}
    tokens = TRAFFIC["tokens"]
    assert (tokens["sequences"], tokens["length_min"], tokens["length_max"],
            tokens["vocabulary"], tokens["zipf_exponent"]) == (
                1, 28673, 32768, 19360, 1.1)
    # what follows from the program: 2 rows x 32896 positions a pass
    assert requirements.SEQUENCE_PASS_POSITIONS // 32896 == 3
    assert requirements.coalesce_rows_limit(None, "test/GLM-5", 32896) == 2
    assert {m["name"] for m in spec["end_to_end"]} == {
        "job_latency_p50_s", "hbm_peak_gb", "setup_s"}
    names = {m["name"] for m in spec["per_layer"]}
    assert set(NEW) | {
        "pass_cache_gb", "decode_ms_per_step", "prefill_s_per_pass",
        "sequences_per_pass", "held_expert_pair_share",
        "expert_matmul_device_share", "expert_matmul_roofline",
        "prefill_padding_share"} <= names
    # it runs no banded kernel and keeps no ring
    assert not names & {"banded_attention_device_share",
                        "banded_attention_roofline", "window_cache_gb"}
    whole = json.loads((REPO / "BENCHMARK.json").read_text())
    mine = [m for m in whole["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in mine] == list(NEW)
    assert {(m["moves"], tuple(m["workloads"])) for m in mine} == {
        ("job_latency_p50_s", (CELL,))}
    assert [(m["unit"], m["source"]) for m in mine] == (
        [("%", "device_trace")] * 5 + [("%", "program_counter"),
                                       ("GB", "program_counter")])
    assert len(spec["cell"]["why"]) <= 200
    entry = next(c for c in whole["configs"] if c["name"] == "glm-5-ep16")
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert entry["source"] == CONFIG["source"] and len(entry["why"]) <= 200


def test_the_two_costs_are_hand_counts():
    """The cell's last span: 4096 queries at offset 28672 against 32768
    keys. Visible pairs: 4096 x 28672 + 4096 x 4097 / 2 = 125,831,168;
    an index head's dot product of 128 is 256 operations, its ReLU, weight
    and add 3. Selected pairs: 4096 x 2048 (every query sees more than
    2048); a head's QK^T and PV over 256 dims are 4 x 256 operations."""
    pairs = 4096 * 28672 + 4096 * 4097 // 2
    assert pairs == 125831168
    flops, nbytes = index_cost.needed(1, 4096, 32768, 32, 128)
    assert flops == pairs * 32 * 259 == 1042888720384
    assert nbytes == (4096 * 32 * (128 * 2 + 4) + 32768 * 128 * 2
                      + 4 * pairs) == 545792000
    from benchmark.costs.peaks import least_seconds

    seconds, bound = least_seconds(flops, nbytes, "TPU v5 lite")
    assert bound == "compute" and seconds == pytest.approx(5.294e-3, rel=1e-3)
    # the first span: a triangle
    assert index_cost.needed(2, 4096, 4096, 32, 128)[0] == (
        2 * (4096 * 4097 // 2) * 32 * 259)
    assert index_cost.call_of(INDEXER) == (4096, 32768, 32)
    assert index_cost.call_of(INDEXER[:5]) is None
    # a row's eight spans in turn, five layers' calls a span, row on row
    assert [index_cost.span_of(n, 5, 4096, 32768)
            for n in (0, 4, 5, 39, 40, 79)] == [0, 0, 1, 7, 0, 7]
    assert index_cost.span_of(7, 5, 64, 64) == 0
    chosen = 4096 * 2048
    assert attention_cost.selected_pairs(4096, 32768, 2048) == chosen
    # the first span: queries 0-2047 see t + 1 keys, the others pick 2048
    assert attention_cost.selected_pairs(4096, 4096, 2048) == (
        2048 * 2049 // 2 + 2048 * 2048)
    assert attention_cost.selected_pairs(5, 9, 3) == 15
    assert attention_cost.selected_pairs(4, 4, 8) == 10
    flops, nbytes = attention_cost.needed(1, 64, 4096, 32768, 2048, 256)
    assert flops == 4 * 64 * chosen * 256 == 549755813888
    assert nbytes == 2 * 64 * 256 * (2 * 4096 + 2 * 32768) + 4 * chosen \
        == 2449473536
    seconds, bound = least_seconds(flops, nbytes, "TPU v5 lite")
    assert bound == "memory" and seconds == pytest.approx(2.991e-3, rel=1e-3)
    assert attention_cost.call_of(ATTENTION) == (4096, 32768, 64)
    assert attention_cost.call_of(ATTENTION[:6]) is None


def _trace(indexer=(), select_s=0.0, attention=(), busy=10.0):
    ops = {"lightning_indexer": sum(c["seconds"] for c in indexer),
           "index_select": select_s,
           "sparse_latent_attention": sum(c["seconds"] for c in attention)}
    return {"busy_s": busy,
            "kernel_calls": {"lightning_indexer": list(indexer),
                             "sparse_latent_attention": list(attention)},
            "op_seconds": {name: s for name, s in ops.items() if s}}


def test_the_readers_on_a_hand_made_record():
    model = CONFIG["job"]["model_name"]
    read = {name: harness.load_reader("layer_metrics", name) for name in NEW}
    # two rows' passes: eight spans a row, five layers a span
    indexer = [{"seconds": 4.0e-3, "shapes": INDEXER} for _ in range(80)]
    attention = [{"seconds": 30.0e-3, "shapes": ATTENTION}
                 for _ in range(80)]
    record = {
        "spec": {"config": CONFIG}, "device": {"kind": "TPU v5 lite"},
        "trace": _trace(indexer, 0.5, attention),
        "scrape_open": {
            "swarm_sparse_visible_positions_total": {
                f"{model},prefill": 1e9, f"{model},decode": 1e7},
            "swarm_sparse_selected_positions_total": {
                f"{model},prefill": 1e8, f"{model},decode": 1e6}},
        "scrape_close": {
            "swarm_sparse_visible_positions_total": {
                f"{model},prefill": 9e9, f"{model},decode": 9e7},
            "swarm_sparse_selected_positions_total": {
                f"{model},prefill": 1.1e9, f"{model},decode": 1.1e7},
            "swarm_pass_index_cache_bytes": {model: 84213760.0,
                                             "another": 1.0}}}
    assert read["index_cache_gb"](record) == pytest.approx(0.08421376)
    assert read["selected_key_share"](record) == pytest.approx(
        100 * (1.0e9 + 1.0e7) / (8e9 + 8e7))
    assert read["lightning_indexer_device_share"](record) == pytest.approx(
        3.2)
    assert read["index_select_device_share"](record) == pytest.approx(5.0)
    assert read["sparse_latent_attention_device_share"](
        record) == pytest.approx(24.0)
    # a call's span is its place among the calls: the least of a row's
    # pass is the eight spans' summed, five times
    from benchmark.costs.peaks import least_seconds

    least = [sum(least_seconds(*needed(span), "TPU v5 lite")[0]
                 for span in range(8)) * 5
             for needed in (
                 lambda span: index_cost.needed(
                     1, 4096, (span + 1) * 4096, 32, 128),
                 lambda span: attention_cost.needed(
                     1, 64, 4096, (span + 1) * 4096, 2048, 256))]
    assert least == pytest.approx([0.112937, 0.109140], rel=1e-4)
    assert read["lightning_indexer_roofline"](record) == pytest.approx(
        100 * least[0] / (40 * 4.0e-3))
    # a kernel that computes every visible pair reads low against the
    # pairs the selection lets through
    assert read["sparse_latent_attention_roofline"](
        record) == pytest.approx(100 * least[1] / (40 * 30.0e-3))
    assert record["notes"] == {
        "lightning_indexer_roofline": {
            "calls": 80, "bound_by": {"compute": 80, "memory": 0}},
        "sparse_latent_attention_roofline": {
            "calls": 80, "bound_by": {"compute": 70, "memory": 10}}}
    # a share of a roofline stays under 100 % while a call takes its least:
    # the first span's (a call alone is its row's first)
    fast = _trace([{"seconds": 0.36e-3, "shapes": INDEXER}], 0.0,
                  [{"seconds": 2.1e-3, "shapes": ATTENTION}])
    assert 98.0 < read["lightning_indexer_roofline"](
        {**record, "trace": fast}) < 100.0
    assert 99.0 < read["sparse_latent_attention_roofline"](
        {**record, "trace": fast}) < 100.0


def test_the_new_readers_find_nothing_on_a_program_without_them():
    """The parent of PR 49 under this PR's files (no kernel, no counter),
    an untraced run, and a call that does not say what it was asked."""
    for name in NEW:
        read = harness.load_reader("layer_metrics", name)
        for trace in (None, _trace(), {"busy_s": 5.0, "kernel_calls": {},
                                       "op_seconds": {"fusion": 1.0}}):
            record = {"spec": {"config": CONFIG},
                      "device": {"kind": "TPU v5 lite"}, "trace": trace,
                      "scrape_open": {}, "scrape_close": {}}
            assert read(record) is None
        assert read({"spec": {"config": CONFIG}, "scrape_open": {},
                     "scrape_close": {},
                     "device": {"kind": "TPU v5 lite"}}) is None
    hidden = _trace([{"seconds": 1e-3, "shapes": INDEXER[:5]}], 0.0,
                    [{"seconds": 1e-3, "shapes": ATTENTION[:6]}])
    for name in NEW[:2]:
        assert harness.load_reader("layer_metrics", name)(
            {"spec": {"config": CONFIG}, "device": {"kind": "TPU v5 lite"},
             "trace": hidden}) is None


def test_the_configuration_is_the_published_config_but_for_the_cut():
    import dataclasses

    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu.coalesce import TEXT_FAMILIES
    from chiaswarm_tpu.models.glm_moe_dsa import (
        GLM5_EP16,
        GlmMoeDsaConfig,
        param_shapes,
    )

    cut = {"num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size", "num_nextn_predict_layers"}
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        row = next(json.loads(line) for line in catalog.read_text().splitlines()
                   if json.loads(line)["name"] == "GLM-5")
        assert CONFIG["source"] == row["source_url"]
        differs = {key for key, value in row["config"].items()
                   if CONFIG.get(key, "absent") != value}
        assert differs == set(CONFIG["reduced"]) == cut
        assert {key: row["config"][key] for key in cut} == {
            key: CONFIG["published"][key] for key in cut} == {
                "num_hidden_layers": 78, "first_k_dense_replace": 3,
                "n_routed_experts": 256, "vocab_size": 154880,
                "num_nextn_predict_layers": 1}
    # no width is cut
    assert (CONFIG["hidden_size"], CONFIG["q_lora_rank"],
            CONFIG["kv_lora_rank"], CONFIG["num_attention_heads"],
            CONFIG["qk_nope_head_dim"], CONFIG["qk_rope_head_dim"],
            CONFIG["v_head_dim"], CONFIG["index_n_heads"],
            CONFIG["index_head_dim"], CONFIG["index_topk"],
            CONFIG["intermediate_size"], CONFIG["moe_intermediate_size"],
            CONFIG["num_experts_per_tok"]) == (
                6144, 2048, 512, 64, 192, 64, 256, 32, 128, 2048, 12288,
                2048, 8)
    # every field of the program's config that the file has is the file's
    # (the router's width stands in the share, the held experts in the file)
    seen = 0
    for field in dataclasses.fields(GlmMoeDsaConfig):
        if field.name in CONFIG and field.name != "n_routed_experts":
            assert getattr(GLM5_EP16, field.name) == CONFIG[field.name], \
                field.name
            seen += 1
    assert seen == 20
    share = CONFIG["deployment_share"]
    assert (share["chips_sharing_a_layer"], share["router_width"],
            share["experts_held"], share["vocabulary_rows_held"]) == (
                16, 256, [0, 16], [0, 19360])
    assert GLM5_EP16.n_routed_experts == share["router_width"]
    assert list(GLM5_EP16.experts_held) == share["experts_held"]
    assert CONFIG["n_routed_experts"] == share["experts_held"][1]
    assert GLM5_EP16.rope_theta == CONFIG["rope_parameters"]["rope_theta"]
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(
        param_shapes(GLM5_EP16, jnp.bfloat16)))
    assert count == 3909632768 and "3,909,632,768 parameters" in CONFIG[
        "as_run"]
    assumed = " ".join(CONFIG["assumed"])
    for said in ("LayerNorm", "bias", "first 64 dims", "already permuted",
                 "Hadamard", "FP8", "index_n_heads^-1/2", "ties to the lower",
                 "multi-token-prediction layer left out", "no stop token",
                 "32896 positions are cached", "random, from --seed"):
        assert said in assumed, said
    assert "128 tokens an expert" in CONFIG["overstated"]
    assert CONFIG["expected_kernel_paths"] == [
        "lightning_indexer,pallas", "lightning_indexer,einsum",
        "index_select,pallas",
        "sparse_latent_attention,pallas", "sparse_latent_attention,gathered",
        "expert_matmul,grouped"]
    assert CONFIG["traced_kernels"] == [
        "lightning_indexer", "index_select", "sparse_latent_attention",
        "expert_matmul"]
    assert CONFIG["job"]["model_name"] == "test/GLM-5"
    assert TEXT_FAMILIES[family.FAMILY]["wire"] == family.PIPELINE_TYPE
    shapes = CONFIG["kernel_shapes"]
    assert shapes["lightning_indexer"] == [[4096, 32768, 32, 128]]
    assert shapes["sparse_latent_attention"] == [[4096, 32768, 64, 256]]
    assert shapes["decode"] == [[2, 32896, 64, 512, 64]]
    assert (CONFIG["denoiser"]["rows"], CONFIG["denoiser"]["prompt_slots"],
            CONFIG["denoiser"]["positions"]) == (2, 32768, 32896)


def _rehearsal():
    spec = harness.load_cell(CELL)
    harness.apply_rehearsal(spec)
    return spec["config"]


def test_the_operations_are_the_references_and_the_two_controls_fail():
    import jax.numpy as jnp

    config = _rehearsal()
    failures, readings = family.kernel_checks(config, jnp.float32, True)
    assert failures == []
    names = [next(iter(reading)) for reading in readings]
    assert names == [
        "lightning_indexer", "index_select", "sparse_latent_attention",
        "control_no_selection", "control_selection_from_8_bit_scores",
        "index_select_decode", "sparse_decode_attention"]
    for name, reading in zip(names, readings):
        if name.startswith("control"):
            assert reading["has_to_exceed"]
            assert reading["max_abs"] > 10 * reading["limit"]
        else:
            assert reading["max_abs"] <= reading["limit"] / 100
    # a selection of every visible key is dense attention: the first
    # control then reads nothing, and the run is not `correct`
    config["index_topk"] = 64
    failures, _ = family.kernel_checks(config, jnp.float32, True)
    assert len(failures) == 2 and all(
        "has to fail" in failure for failure in failures)


def test_the_served_logits_are_the_references():
    """`correct` 5's two halves at the rehearsal's size: rows of 33 to 64
    ids (four to eight times the 8 selected keys) through the pipeline's
    prefill and step programs against the reference's one full forward;
    the reference without the selection, or with one from 8-bit index
    scores, is no such agreement."""
    from chiaswarm_tpu.pipelines.text_generation import (
        TextGenerationPipeline,
    )

    config = _rehearsal()
    pipe = TextGenerationPipeline("test/tiny-glm-5", allow_random_init=True)
    inputs = family.denoiser_inputs(pipe, config, 2 ** 31 + 11)
    assert inputs["ids"].shape == (2, 64) and inputs["given"].shape == (2, 6)
    assert all(33 <= n <= 64 for n in inputs["lengths"])
    assert inputs["sizes"]["index_topk"] == 8
    assert inputs["sizes"]["n_routed_experts"] == 32
    want = np.asarray(family.denoiser_reference(pipe, inputs))
    kept = inputs["kept"]
    assert kept.shape == (1, 7) and kept.any()
    got = np.asarray(family.denoiser_serve(pipe, inputs))
    assert got.shape == want.shape == (int(kept.sum()), 128)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 5e-6
    for control in ("none", "int8"):
        other = np.asarray(family.denoiser_reference(pipe, inputs, control))
        assert np.linalg.norm(got - other) / np.linalg.norm(other) > 1e-2
    rounded = np.asarray(family.int8_control(pipe, inputs))
    assert np.linalg.norm(rounded - want) / np.linalg.norm(want) > 1e-3


def test_the_parents_program_fails_register_with_a_run_failure(monkeypatch):
    """No `models/glm_moe_dsa.py` (the parent of PR 49): a `RunFailure`
    from `register`, before anything is built."""
    monkeypatch.setitem(sys.modules, "chiaswarm_tpu.models.glm_moe_dsa", None)
    with pytest.raises(harness.RunFailure, match="models/glm_moe_dsa.py"):
        family.register(1, {})


def test_the_cell_rehearsed_whole_on_the_cpu():
    """`rehearse.py` walks every phase of the cell at the tiny preset:
    every job settles, the probe gives one hash among other batchmates,
    the kernels and the served logits are the references', the two
    controls fail, the three operations' paths are the ones the rehearsal
    expects."""
    done = subprocess.run(
        [sys.executable, str(REPO / "benchmark" / "rehearse.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 49), "--seconds", "4",
         "--trace", "1"], capture_output=True, text=True, timeout=900,
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(line) for line in done.stdout.splitlines()
             if line.startswith("{")]
    result = lines[-1]
    assert result["attempted"] >= 4 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    compared = result["compared"]
    assert {name.split("_")[0] for name in compared} >= {
        "lightning", "index", "sparse", "control", "denoiser"}
    for name, (number, limit) in compared.items():
        if name.startswith("control"):
            assert number > limit, name
        elif name != "window_compiles":
            assert number <= limit, name
    summary = next(line for line in lines if line.get("phase") == "summary")
    assert summary["failures"] == [] or all(
        "compil" in failure for failure in summary["failures"])
    for path in ("lightning_indexer", "index_select",
                 "sparse_latent_attention"):
        assert summary["kernel_traces"][f"{path},reference"] > 0
    assert len(set(summary["probe_sha256"])) == 1
    assert {"sequences_per_pass", "pass_cache_gb", "index_cache_gb",
            "selected_key_share", "decode_ms_per_step"} <= set(
                result["metrics"])
