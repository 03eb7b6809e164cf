"""The `mimo_v2` family away from the chip: its traffic's draws, the cell as
the issue names it, the two new readers on a hand-made record and on a
program without the kernel, the cost function against a brute-force count
of a small mask and against hand counts at the cell's shapes, the
configuration file against the published config and the program's own
`MimoV2Config`, the operation's comparison with its control and the
network's half of `correct` 5 at the tiny preset, and the parent's clean
failure."""

import json
import random
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark import harness
from benchmark.costs import wide_key_attention as cost
from benchmark.costs.peaks import least_seconds
from benchmark.families import mimo_v2 as family

REPO = Path(__file__).resolve().parents[2]
CONFIG = json.loads((REPO / "benchmark" / "configs"
                     / "mimo-v2.5-ep16.json").read_text())
TRAFFIC = json.loads((REPO / "benchmark" / "traffic"
                      / "long-context-19072.json").read_text())
CELL = "mimo-long-context"
NEW = ("wide_key_attention_device_share", "wide_key_attention_roofline")
# the arrays of the cell's two traced calls, the result first, and behind
# the operands (the two scalars, a window layer's sinks, queries and keys
# padded to 256 lanes) the kernel's own metadata
FULL = [(1, 4096, 8192), (2,), (1, 4096, 16384), (1, 32768, 1024),
        (1, 32768, 512), (4096,), (32768,), (0,), (0,), (64,), (4,), (192,),
        (128,)]
WINDOW = [(1, 4096, 8192), (2,), (64, 128), (1, 4096, 16384),
          (1, 4352, 2048), (1, 4352, 1024), (4096,), (4224,), (128,), (1,),
          (64,), (8,), (192,), (128,)]


def test_a_job_is_one_long_row_of_the_held_vocabulary_in_one_bucket():
    from chiaswarm_tpu.coalesce import coalesce_key, job_rows, prompt_slots

    rng = random.Random(5)
    jobs = [family.job_fields(rng, TRAFFIC, n, False)["prompt_ids"]
            for n in range(12)]
    lengths = [len(row) for job in jobs for row in job]
    assert all(len(job) == 1 for job in jobs) and len(set(lengths)) == 12
    assert all(28673 <= n <= 32768 for n in lengths)
    assert {prompt_slots(n) for n in lengths} == {32768}
    assert all(0 <= i < 19072 for job in jobs for row in job for i in row)
    assert max(i for job in jobs for row in job for i in row) > 18800
    spec = harness.load_cell(CELL)
    maker = harness.JobMaker(spec, 2 ** 31 + 5, family)
    made = [maker.next() for _ in range(2)] + [maker.probe()]
    assert {coalesce_key(job) for job in made} == {(
        "test/MiMo-V2.5", "mimo_v2", "txt2txt", 32768, 128, 1.0)}
    assert {job_rows(job) for job in made} == {1}


def test_the_cell_is_what_the_issue_names():
    from chiaswarm_tpu.chips import requirements

    spec = harness.load_cell(CELL)
    assert spec["cell"]["chips"] == 1
    assert (spec["cell"]["config"], spec["cell"]["traffic"]) == (
        "mimo-v2.5-ep16", "long-context-19072")
    assert (TRAFFIC["generator"], TRAFFIC["clients"], TRAFFIC["think_s"],
            TRAFFIC["status_poll_s"], TRAFFIC["probe"]["seed"]) == (
                "closed_loop", 4, 0, 0.02, 4321)
    assert TRAFFIC["job"] == {"max_new_tokens": 128, "temperature": 1.0,
                              "content_type": "application/json"}
    tokens = TRAFFIC["tokens"]
    assert (tokens["sequences"], tokens["length_min"], tokens["length_max"],
            tokens["vocabulary"], tokens["zipf_exponent"]) == (
                1, 28673, 32768, 19072, 1.1)
    # it is `long-context-19360` at this chip's vocabulary
    twin = json.loads((REPO / "benchmark" / "traffic"
                       / "long-context-19360.json").read_text())
    twin["tokens"]["vocabulary"] = 19072
    assert {**TRAFFIC, "what": ""} == {**twin, "what": ""}
    # what follows from the program: 2 rows x 32896 positions a pass
    assert requirements.coalesce_rows_limit(None, "test/MiMo-V2.5",
                                            32896) == 2
    assert {m["name"] for m in spec["end_to_end"]} == {
        "job_latency_p50_s", "hbm_peak_gb", "setup_s"}
    names = {m["name"] for m in spec["per_layer"]}
    assert set(NEW) | {
        "pass_cache_gb", "window_cache_gb", "decode_ms_per_step",
        "prefill_s_per_pass", "sequences_per_pass", "held_expert_pair_share",
        "expert_matmul_device_share", "expert_matmul_roofline",
        "prefill_padding_share"} <= names
    # it runs neither the banded kernel nor a selection
    assert not names & {"banded_attention_device_share",
                        "banded_attention_roofline", "selected_key_share",
                        "index_cache_gb", "lightning_indexer_roofline"}
    whole = json.loads((REPO / "BENCHMARK.json").read_text())
    assert whole["workloads"][-1]["name"] == CELL
    assert whole["configs"][-1]["name"] == "mimo-v2.5-ep16"
    mine = whole["per_layer"][-2:]
    assert [m["name"] for m in mine] == list(NEW)
    assert {(m["moves"], tuple(m["workloads"]), m["unit"], m["source"],
             m["layer"]) for m in mine} == {
        ("job_latency_p50_s", (CELL,), "%", "device_trace", "kernels (ops)")}
    entry = whole["configs"][-1]
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == CONFIG["source"]
    assert len(entry["why"]) <= 200 and len(spec["cell"]["why"]) <= 200


def _brute(queries, offset, window, floor=0):
    """(visible pairs, keys some query sees) of a mask, counted pair by
    pair."""
    pairs, seen = 0, set()
    for i in range(queries):
        t = offset + i
        for u in range(floor, t + 1):
            if not window or t - u < window:
                pairs += 1
                seen.add(u)
    return pairs, len(seen)


@pytest.mark.parametrize("queries, keys, window", [
    (7, 7, 0), (5, 12, 0), (9, 13, 4), (6, 6, 4), (3, 40, 8), (16, 20, 4)])
def test_the_cost_is_a_brute_force_count_of_the_mask(queries, keys, window):
    pairs, band = _brute(queries, keys - queries, window)
    flops, nbytes = cost.needed(2, 8, 2, queries, keys, window, 24, 16,
                                sink=bool(window))
    assert flops == 2 * 2 * 8 * pairs * (24 + 16)
    assert nbytes == 2 * 2 * (8 * queries + 2 * band) * (24 + 16) + (
        4 * 8 if window else 0)


def test_the_costs_at_the_cells_shapes_are_hand_counts():
    """A full layer's last span: 4096 queries at offset 28672: 125,831,168
    visible pairs, 64 heads, 2 x (192 + 128) operations a pair. A window
    layer's span behind its tail: 128 keys a query."""
    flops, nbytes = cost.needed(1, 64, 4, 4096, 32768, 0, 192, 128)
    assert flops == 2 * 64 * 125831168 * 320 == 5154044641280
    assert nbytes == 2 * (64 * 4096 + 4 * 32768) * 320 == 251658240
    seconds, bound = least_seconds(flops, nbytes, "TPU v5 lite")
    assert bound == "compute" and seconds == pytest.approx(26.16e-3, rel=1e-3)
    flops, nbytes = cost.needed(1, 64, 8, 4096, 4224, 128, 192, 128, True)
    assert flops == 2 * 64 * 4096 * 128 * 320
    assert nbytes == 2 * (64 * 4096 + 8 * 4223) * 320 + 256
    assert least_seconds(flops, nbytes, "TPU v5 lite")[1] == "memory"
    assert cost.call_of(FULL) == dict(zip(cost.FIELDS, (
        4096, 32768, 0, 0, 64, 4, 192, 128)))
    assert cost.call_of(WINDOW)["key_heads"] == 8
    assert cost.call_of(FULL[:5]) is None and cost.call_of([]) is None
    # a row's eight spans in turn, two full layers' calls a span
    assert [cost.span_of(n, 2, 4096, 32768)
            for n in (0, 1, 2, 15, 16, 31)] == [
        0, 0, 1, 7, 0, 7]


def _trace(full=(), window=(), busy=10.0):
    calls = []
    # a span's calls in the layers' order: full, five windows, full
    for n in range(0, len(full), 2):
        calls += [full[n], *window[n // 2 * 5:n // 2 * 5 + 5], full[n + 1]]
    seconds = sum(call["seconds"] for call in calls)
    return {"busy_s": busy,
            "kernel_calls": {"wide_key_attention": calls},
            "op_seconds": {"wide_key_attention": seconds} if calls else {}}


def test_the_readers_on_a_hand_made_record():
    read = {name: harness.load_reader("layer_metrics", name) for name in NEW}
    # one row's pass: eight spans, two full and five window layers a span
    full = [{"seconds": 20.0e-3, "shapes": FULL} for _ in range(16)]
    window = [{"seconds": 2.0e-3, "shapes": WINDOW} for _ in range(40)]
    record = {"spec": {"config": CONFIG}, "device": {"kind": "TPU v5 lite"},
              "trace": _trace(full, window)}
    assert read["wide_key_attention_device_share"](record) == pytest.approx(
        100 * (16 * 20e-3 + 40 * 2e-3) / 10.0)
    least_full = 2 * sum(least_seconds(*cost.needed(
        1, 64, 4, 4096, (span + 1) * 4096, 0, 192, 128), "TPU v5 lite")[0]
        for span in range(8))
    least_window = 5 * sum(least_seconds(*cost.needed(
        1, 64, 8, 4096, 4224 if span else 4096, 128, 192, 128, True),
        "TPU v5 lite")[0] for span in range(8))
    assert read["wide_key_attention_roofline"](record) == pytest.approx(
        100 * (least_full + least_window) / (16 * 20e-3 + 40 * 2e-3))
    notes = record["notes"]["wide_key_attention_roofline"]
    assert notes["calls"] == 56
    assert notes["bound_by"] == {"compute": 16, "memory": 40}
    assert notes["by_kind"]["full"]["calls"] == 16
    assert notes["by_kind"]["window"]["seconds"] == pytest.approx(0.08)
    assert notes["by_kind"]["full"]["share_pct"] == pytest.approx(
        100 * least_full / 0.32)
    # a share of a roofline stays under 100 % while a call takes its least:
    # the first span's (a call alone is its row's first)
    fast = {"busy_s": 1.0, "op_seconds": {"wide_key_attention": 1.8e-3},
            "kernel_calls": {"wide_key_attention": [
                {"seconds": 1.8e-3, "shapes": FULL}]}}
    assert 95.0 < read["wide_key_attention_roofline"](
        {**record, "trace": fast}) < 100.0


def test_the_new_readers_find_nothing_on_a_program_without_the_kernel():
    """The parent of PR 57 under this PR's files, an untraced run, and a
    call that does not say what it was asked."""
    for name in NEW:
        read = harness.load_reader("layer_metrics", name)
        for trace in (None, _trace(), {"busy_s": 5.0, "kernel_calls": {},
                                       "op_seconds": {"fusion": 1.0}}):
            assert read({"spec": {"config": CONFIG}, "trace": trace,
                         "device": {"kind": "TPU v5 lite"}}) is None
        assert read({"spec": {"config": CONFIG},
                     "device": {"kind": "TPU v5 lite"}}) is None
    hidden = {"busy_s": 1.0, "op_seconds": {},
              "kernel_calls": {"wide_key_attention": [
                  {"seconds": 1e-3, "shapes": FULL[:5]}]}}
    assert harness.load_reader("layer_metrics", NEW[1])(
        {"spec": {"config": CONFIG}, "device": {"kind": "TPU v5 lite"},
         "trace": hidden}) is None


def test_the_configuration_is_the_published_config_but_for_the_cut():
    import dataclasses

    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu.coalesce import TEXT_FAMILIES
    from chiaswarm_tpu.models.mimo_v2 import (
        MIMO_V25_EP16,
        MimoV2Config,
        param_shapes,
    )

    cut = {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        row = next(json.loads(line) for line in catalog.read_text().splitlines()
                   if json.loads(line)["name"] == "MiMo-V2.5")
        assert CONFIG["source"] == row["source_url"]
        differs = {key for key, value in row["config"].items()
                   if CONFIG.get(key, "absent") != value}
        assert differs == set(CONFIG["reduced"]) == cut
        assert {key: row["config"][key] for key in cut} == {
            key: CONFIG["published"][key] for key in cut} == {
                "num_hidden_layers": 48, "n_routed_experts": 256,
                "vocab_size": 152576}
        # the published pattern is the program's default
        assert tuple(row["config"]["hybrid_layer_pattern"]) == (
            MimoV2Config().hybrid_layer_pattern)
        assert tuple(row["config"]["moe_layer_freq"]) == (
            MimoV2Config().moe_layer_freq)
    # no width is cut
    assert (CONFIG["hidden_size"], CONFIG["num_attention_heads"],
            CONFIG["num_key_value_heads"], CONFIG["swa_num_key_value_heads"],
            CONFIG["head_dim"], CONFIG["v_head_dim"],
            CONFIG["intermediate_size"], CONFIG["moe_intermediate_size"],
            CONFIG["num_experts_per_tok"], CONFIG["sliding_window"]) == (
                4096, 64, 4, 8, 192, 128, 16384, 2048, 8, 128)
    # every field of the program's config that the file has is the file's
    # (the router's width and the seven layers run stand in the share; the
    # published null scaling factor is the program's 1.0)
    seen = 0
    elsewhere = {"n_routed_experts", "hybrid_layer_pattern",
                 "moe_layer_freq", "routed_scaling_factor"}
    for field in dataclasses.fields(MimoV2Config):
        if field.name in CONFIG and field.name not in elsewhere:
            assert getattr(MIMO_V25_EP16, field.name) == CONFIG[field.name], \
                field.name
            seen += 1
    assert seen == 17
    assert CONFIG["routed_scaling_factor"] is None
    assert MIMO_V25_EP16.rms_norm_eps == CONFIG["layernorm_epsilon"]
    share = CONFIG["deployment_share"]
    assert (share["chips_sharing_a_layer"], share["router_width"],
            share["experts_held"], share["vocabulary_rows_held"]) == (
                16, 256, [0, 16], [0, 19072])
    assert MIMO_V25_EP16.n_routed_experts == share["router_width"]
    assert list(MIMO_V25_EP16.experts_held) == share["experts_held"]
    assert CONFIG["n_routed_experts"] == share["experts_held"][1]
    run = share["layers_run"]
    assert list(MIMO_V25_EP16.hybrid_layer_pattern) == run[
        "hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 1, 0]
    assert list(MIMO_V25_EP16.moe_layer_freq) == run["moe_layer_freq"]
    assert [CONFIG["hybrid_layer_pattern"][n]
            for n in run["published_layers"]] == run["hybrid_layer_pattern"]
    assert [CONFIG["moe_layer_freq"][n]
            for n in run["published_layers"]] == run["moe_layer_freq"]
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(
        param_shapes(MIMO_V25_EP16, jnp.bfloat16)))
    assert count == 3429955392 and "3,429,955,392 parameters" in CONFIG[
        "as_run"]
    assumed = " ".join(CONFIG["assumed"])
    for said in ("sink's form", "has no value", "multiplies the values",
                 "no query / key norm", "first int(192 x 0.334) = 64 dims",
                 "pre-norm residuals", "attention_chunk_size",
                 "multi-token-prediction layers", "vision and audio towers",
                 "sinks N(2, 1)", "no stop token",
                 "32896 positions are cached", "random, from --seed"):
        assert said in assumed, said
    assert "2 of the 7" in CONFIG["overstated"]
    assert "128 tokens an expert" in CONFIG["overstated"]
    assert CONFIG["expected_kernel_paths"] == [
        "attention,wide_key", "expert_matmul,grouped"]
    assert CONFIG["traced_kernels"] == ["wide_key_attention", "expert_matmul"]
    assert CONFIG["job"]["model_name"] == "test/MiMo-V2.5"
    assert TEXT_FAMILIES[family.FAMILY]["wire"] == family.PIPELINE_TYPE
    assert CONFIG["kernel_shapes"]["wide_key_attention"] == [
        [4096, 32768, 64, 4, 192, 128, 0, 0],
        [4096, 4224, 64, 8, 192, 128, 128, 1]]
    assert CONFIG["kernel_shapes"]["expert_matmul"] == [
        [2, 4096, 2048], [4096, 4096, 2048]]
    assert (CONFIG["denoiser"]["rows"], CONFIG["denoiser"]["prompt_slots"],
            CONFIG["denoiser"]["positions"],
            CONFIG["denoiser"]["compared_rows"]) == (2, 32768, 32896, 1)


def _rehearsal():
    spec = harness.load_cell(CELL)
    harness.apply_rehearsal(spec)
    return spec["config"]


def test_the_contract_and_the_operations_with_the_control_that_fails():
    import jax.numpy as jnp

    assert harness.load_family(CONFIG) is family
    config = _rehearsal()
    failures, readings = family.kernel_checks(config, jnp.float32, True)
    assert failures == []
    names = [next(iter(reading)) for reading in readings]
    assert names == ["wide_key_attention", "wide_key_attention",
                     "control_no_sink", "expert_matmul", "expert_matmul"]
    for name, reading in zip(names, readings):
        if name.startswith("control"):
            assert reading["has_to_exceed"]
            assert reading["max_abs"] > 10 * reading["limit"]
        else:
            assert reading["max_abs"] <= reading["limit"] / 100


def test_the_served_logits_are_the_references():
    """`correct` 5's two halves at the rehearsal's size: rows of 33 to 64
    ids (eight to sixteen windows of 4) through the pipeline's prefill and
    step programs against the reference's one full forward; the reference
    under any of its three controls is no such agreement."""
    from chiaswarm_tpu.pipelines.text_generation import (
        TextGenerationPipeline,
    )

    config = _rehearsal()
    pipe = TextGenerationPipeline("test/tiny-mimo", allow_random_init=True)
    inputs = family.denoiser_inputs(pipe, config, 2 ** 31 + 11)
    assert inputs["ids"].shape == (2, 64) and inputs["given"].shape == (2, 6)
    assert all(33 <= n <= 64 for n in inputs["lengths"])
    assert inputs["sizes"]["n_routed_experts"] == 32
    assert inputs["sizes"]["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 1, 0]
    want = np.asarray(family.denoiser_reference(pipe, inputs))
    kept = inputs["kept"]
    assert kept.shape == (1, 7) and kept.any()
    got = np.asarray(family.denoiser_serve(pipe, inputs))
    assert got.shape == want.shape == (int(kept.sum()), 128)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 5e-6
    for control in ("no_sink", "no_value_scale", "swapped_theta"):
        other = np.asarray(family.denoiser_reference(pipe, inputs, control))
        assert np.linalg.norm(got - other) / np.linalg.norm(other) > 1e-2
    rounded = np.asarray(family.int8_control(pipe, inputs))
    assert np.linalg.norm(rounded - want) / np.linalg.norm(want) > 1e-3


def test_the_parents_program_fails_register_with_a_run_failure(monkeypatch):
    """No `models/mimo_v2.py` (the parent of PR 57): a `RunFailure` from
    `register`, before anything is built."""
    monkeypatch.setitem(sys.modules, "chiaswarm_tpu.models.mimo_v2", None)
    with pytest.raises(harness.RunFailure, match="models/mimo_v2.py"):
        family.register(1, {})
