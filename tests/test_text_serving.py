"""`txt2txt` jobs through the serving plane (ISSUE 32): what a text job's
coalesce key and rows are, admission by weights held and a row's cache
bytes, the appetite a worker advertises for a family whose rows are
sequences and what the hive's dispatcher makes of it, and four multi-row
jobs end to end through `LocalSwarm` on `test/tiny-kimi`: one gang, one
pass, four JSON artifacts, the same ids for the same seed among other
batchmates."""

import asyncio
import hashlib
import json

import numpy as np
import pytest

from chiaswarm_tpu.chips import requirements
from chiaswarm_tpu.coalesce import coalesce_key, job_rows, prompt_slots


def _job(number, seed, rows=3, new_tokens=6, low=3, high=16, **extra):
    rng = np.random.default_rng(number)
    return {"id": f"text-{number}", "workflow": "txt2txt",
            "model_name": "test/tiny-kimi",
            "prompt_ids": [rng.integers(0, 128, int(rng.integers(
                low, high))).tolist() for _ in range(rows)],
            "max_new_tokens": new_tokens, "temperature": 1.0, "seed": seed,
            **extra}


# --- the key and the rows ----------------------------------------------------


def test_a_text_jobs_key_is_model_bucket_new_tokens_and_sampling():
    a, b = _job(1, 5), _job(2, 9, rows=7)
    assert coalesce_key(a) == coalesce_key(b) == (
        "test/tiny-kimi", "kimi_k2", "txt2txt", 16, 6, 1.0)
    assert job_rows(a) == 3 and job_rows(b) == 7


@pytest.mark.parametrize("change, same", [
    ({"seed": 77}, True),
    ({"max_new_tokens": 7}, False),
    ({"temperature": 0.0}, False),
    ({"model_name": "test/tiny-kimi-b"}, False),
    ({"prompt_ids": [[1] * 17]}, False),  # the 32-slot bucket
    ({"prompt_ids": [[1] * 16, [2]]}, True),
    ({"parameters": {"max_new_tokens": 6}}, True),
], ids=["seed", "new_tokens", "temperature", "model", "longer_prompt",
        "other_rows", "in_parameters"])
def test_what_splits_a_text_bucket_and_what_rides_per_row(change, same):
    base = _job(1, 5)
    other = {**base, **change}
    assert (coalesce_key(other) == coalesce_key(base)) is same


@pytest.mark.parametrize("broken", [
    {"prompt_ids": []}, {"prompt_ids": [[]]}, {"prompt_ids": "ids"},
    {"prompt_ids": None}, {"model_name": "test/tiny-sd"},
    {"parameters": {"scheduler_args": {}}}, {"max_new_tokens": 0},
], ids=["no_rows", "empty_row", "not_rows", "none", "no_text_family",
        "unknown_parameter", "no_new_tokens"])
def test_a_text_job_that_cannot_batch_has_no_key(broken):
    assert coalesce_key({**_job(1, 5), **broken}) is None


def _block_job(**extra):
    return {**_job(1, 5), "model_name": "test/tiny-sdar", **extra}


def test_a_block_decodes_key_carries_its_denoising_steps_behind_the_rest():
    """Behind the elements the hive reads by place (`rows_per_pass`:
    prompt slots at 3, new tokens at 4), and for this family only."""
    assert coalesce_key(_block_job()) == (
        "test/tiny-sdar", "sdar_moe", "txt2txt", 16, 6, 1.0, 4, None)
    assert coalesce_key(_block_job(denoising_steps=2)) == (
        "test/tiny-sdar", "sdar_moe", "txt2txt", 16, 6, 1.0, 2, None)
    assert coalesce_key(_block_job(
        parameters={"denoising_steps": 2, "confidence_threshold": 0.9})) == (
        "test/tiny-sdar", "sdar_moe", "txt2txt", 16, 6, 1.0, 2, 0.9)


@pytest.mark.parametrize("change, same", [
    ({"denoising_steps": 4}, True),  # the default, said
    ({"denoising_steps": 2}, False),
    ({"confidence_threshold": 0.9}, False),
    ({"seed": 77}, True),
], ids=["default_steps", "other_steps", "threshold", "seed"])
def test_rows_that_differ_in_denoising_steps_do_not_share_a_pass(change,
                                                                 same):
    assert (coalesce_key(_block_job(**change))
            == coalesce_key(_block_job())) is same


@pytest.mark.parametrize("broken", [
    {"denoising_steps": 3}, {"denoising_steps": 0}, {"denoising_steps": 8},
    {"denoising_steps": "2"}, {"denoising_steps": True},
    {"confidence_threshold": "high"},
    {"model_name": "test/tiny-kimi", "denoising_steps": 2},
    {"model_name": "test/tiny-exaone",
     "parameters": {"confidence_threshold": 0.9}},
    {"model_name": "test/tiny-qwen3-next", "denoising_steps": 2},
], ids=["no_divisor", "zero", "over_the_block", "a_string", "a_bool",
        "threshold_no_number", "kimi_takes_none", "exaone_takes_none",
        "qwen3_next_takes_none"])
def test_denoising_steps_that_cannot_be_have_no_key_and_the_formatters_error(
        broken):
    from chiaswarm_tpu.job_arguments import format_txt2txt_args

    job = _block_job(**broken)
    assert coalesce_key(job) is None
    with pytest.raises((TypeError, ValueError)):
        format_txt2txt_args(dict(job))


def test_the_formatter_hands_a_block_decode_its_two_parameters():
    from chiaswarm_tpu.job_arguments import format_txt2txt_args

    _, args = format_txt2txt_args(_block_job(
        parameters={"denoising_steps": 2}, confidence_threshold=0.5))
    assert (args["denoising_steps"], args["confidence_threshold"]) == (2, 0.5)
    _, args = format_txt2txt_args(_block_job())
    assert (args["denoising_steps"], args["confidence_threshold"]) == (
        4, None)
    _, args = format_txt2txt_args(_job(1, 5))
    assert "denoising_steps" not in args


@pytest.mark.parametrize(
    "family", ["kimi_k2", "exaone_moe", "sdar_moe", "qwen3_next",
               "falcon_h1", "glm_moe_dsa", "mimo_v2"])
def test_a_text_family_is_a_row_and_a_module_that_gives_the_interface(
        family, monkeypatch):
    """ISSUE 45: what the four lists that had to agree were is one row of
    `TEXT_FAMILIES`, and the module it names gives everything
    models/text_model.py says a family's module gives: every name, one way
    to decode and nothing of the other, three numbers for a pass's cache
    whose bytes a row are the row's footprint, and a host-side account of
    a pass's prefill chunks that is what `prefill` runs on the device."""
    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu import coalesce, registry, text_families
    from chiaswarm_tpu.models import text_model
    from chiaswarm_tpu.pipelines import text_generation

    assert coalesce.TEXT_FAMILIES is text_families.TEXT_FAMILIES
    what = text_families.TEXT_FAMILIES[family]
    name = f"test/tiny-{what['name']}"
    registry._ensure_builtin_families()
    assert family in registry._FACTORIES
    assert registry.family_of(what["wire"]) == family
    assert registry._auto_family(name) == family
    assert requirements._family_key(name) == family
    assert coalesce.text_family_of(name.upper()) == family
    # the key resolves to itself as a model's name: a worker reckons
    # the appetite it advertises with it (on a chip by the family's
    # own table, and not as a 1.8 GB diffusion model's)
    assert requirements._family_key(family) == family
    assert requirements.coalesce_rows_limit(
        _Slice(), family, requirements.SEQUENCE_REFERENCE_POSITIONS) == 256
    # the interface: every name, and one way to decode as the row says
    model = text_model.family_module(family)
    assert model.__name__.endswith(f".models.{what['module']}")
    by_blocks = "block_length" in what
    mine, other = ((text_model.BY_BLOCKS, text_model.BY_TOKEN) if by_blocks
                   else (text_model.BY_TOKEN, text_model.BY_BLOCKS))
    assert all(hasattr(model, name_) for name_ in text_model.INTERFACE + mine)
    assert not any(hasattr(model, name_) for name_ in other)
    # ... and what a family that selects keys gives besides, where its row
    # says so and nowhere else
    assert all(hasattr(model, name_) == bool(what.get("selects"))
               for name_ in text_model.SELECTS)
    pipe = text_generation.TextGenerationPipeline(
        name, allow_random_init=True)
    assert pipe.model is model and pipe.by_blocks is by_blocks
    assert pipe.selects is bool(what.get("selects"))
    # ... and the host's account of a decode bounded by the mask, likewise
    assert all(hasattr(model, name_) == bool(what.get("bounds_decode"))
               for name_ in text_model.BOUNDS_DECODE)
    assert pipe.bounds_decode is bool(what.get("bounds_decode"))
    # ... and of prefill spans whose key side is bounded by the span's end
    assert all(hasattr(model, name_) == bool(what.get("bounds_prefill"))
               for name_ in text_model.BOUNDS_PREFILL)
    assert pipe.bounds_prefill is bool(what.get("bounds_prefill"))
    cfg, whole = model.config_for(name), model.config_for("test/whole")
    if by_blocks:
        assert cfg.block_length == whole.block_length == what["block_length"]
    # a module that lacks a name is refused when it is asked for
    monkeypatch.delattr(model, "prefill_account")
    with pytest.raises(AttributeError, match="prefill_account"):
        text_model.family_module(family)
    monkeypatch.undo()
    # three numbers, and the whole of them a row is the table's footprint
    # (from a window's length on: a ring is its window however short the row)
    for positions in (128, 512, 16512):
        sizes = model.cache_bytes(whole, 1, positions, 2)
        assert len(sizes) == 3 and all(isinstance(n, int) for n in sizes)
        assert sizes[0] == requirements.sequence_row_bytes(family, positions)
        assert sizes[2] == what.get("row_bytes", 0)
    # the host's account of a pass's chunks is what `prefill` runs: every
    # chunk that runs calls `feed_forward` once a layer (a block model's
    # prefill stops before the last layer's) with its rows x its width
    seen = []
    feed_forward = model.feed_forward

    def counted(layer, cfg, h, *rest):
        jax.debug.callback(lambda tokens: seen.append(int(tokens)),
                           jnp.int32(h.shape[0]))
        return feed_forward(layer, cfg, h, *rest)

    monkeypatch.setattr(model, "feed_forward", counted)
    params = model.init_params(cfg, jax.random.key(0), jnp.float32)
    for chunk_tokens, slots, lengths in (
            # whole rows, two a chunk: ragged, and rows of no length
            (32, 16, [16, 13, 9, 8, 5, 2, 0, 0]),
            # spans of 16 positions where the family's prefill takes them:
            # a row past one span, one that ends with it, one of no length
            (16, 32, [29, 16, 7, 0])):
        monkeypatch.setattr(text_generation, "PREFILL_CHUNK_TOKENS",
                            chunk_tokens)
        rows = len(lengths)
        chunk = text_generation.prefill_chunk(rows, slots,
                                              model.POSITION_CHUNKS)
        assert chunk[1] == (16 if model.POSITION_CHUNKS else slots)
        rng = np.random.default_rng(slots)
        ids = rng.integers(1, cfg.vocab_size - 1, (rows, slots)).astype(
            np.int32)
        lengths = np.array(lengths, np.int32)
        del seen[:]
        jax.block_until_ready(jax.jit(
            lambda p, i, n: model.prefill(p, cfg, i, n, slots + 4, *chunk))(
            params, ids, lengths))
        jax.effects_barrier()
        calls = cfg.num_hidden_layers - by_blocks
        ran = {}
        for tokens in seen:
            width = str(tokens // chunk[0])
            ran[width] = ran.get(width, 0) + 1
        assert all(count % calls == 0 for count in ran.values())
        ran = {width: count // calls for width, count in ran.items()}
        widths, skipped, computed = model.prefill_account(
            lengths, slots, *chunk)
        assert widths == ran and sum(ran.values()) > 1
        assert skipped >= 1  # the rows of no length, at the least
        assert lengths.sum() <= computed <= rows * slots - 16 * skipped


def test_a_text_familys_key_is_spelt_in_one_file():
    """ISSUE 45: the four keys stand as string literals in one file of the
    package (text_families.py); whatever else has to know a family reads
    its row."""
    import ast
    import pathlib

    import chiaswarm_tpu
    from chiaswarm_tpu.text_families import TEXT_FAMILIES

    root = pathlib.Path(chiaswarm_tpu.__file__).parent
    spelt = {}
    for path in root.rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Constant) and node.value in set(
                    TEXT_FAMILIES):
                spelt.setdefault(node.value, set()).add(
                    str(path.relative_to(root)))
    assert spelt == {family: {"text_families.py"}
                     for family in ("kimi_k2", "exaone_moe", "sdar_moe",
                                    "qwen3_next", "falcon_h1",
                                    "glm_moe_dsa", "mimo_v2")}
    from chiaswarm_tpu.coalesce import text_family_of

    assert text_family_of("test/tiny-sd") is None


def test_prompt_slots_are_powers_of_two_from_sixteen():
    assert [prompt_slots(n) for n in (1, 16, 17, 128, 129, 256)] == [
        16, 16, 32, 128, 256, 256]


# --- admission ---------------------------------------------------------------


class _Slice:
    platform, tensor, seq = "tpu", 1, 1

    def __init__(self, gib=15.75, chips=1):
        self._bytes, self._chips = int(gib * (1 << 30)) * chips, chips

    def hbm_bytes(self):
        return self._bytes

    def chip_count(self):
        return self._chips


def test_admission_is_the_weights_held_and_a_rows_cache_bytes():
    name = "test/Kimi-K2.6"
    costs = requirements.TEXT_FAMILIES["kimi_k2"]
    # a position is 576 values x 2 bytes x 7 layers, every layer whole
    assert costs["cache_layers"] == ((576 * 2 * 7, 0),)
    free = 15.75 - costs["params_gb"] - costs["working_gb"]
    for positions in (512, 4096, 32768):
        per_row = positions * 8064 / (1 << 30)
        fit = requirements.fit_batch(_Slice(), name, 10 ** 9, positions)
        assert fit == int(free / per_row)
    assert requirements.fit_batch(_Slice(), name, 200, 512) == 200
    # the pow2 budget a pass is held to, under the default ceiling
    # ... and to the cached positions 256 rows x 512 mean, at its own
    assert requirements.coalesce_rows_limit(_Slice(), name, 512) == 256
    assert requirements.coalesce_rows_limit(_Slice(), name, 1024) == 128
    assert requirements.coalesce_rows_limit(_Slice(), name, 32768) == 4
    assert requirements.coalesce_rows_limit(_Slice(), name, 10 ** 6) == 1
    # a chip the weights do not fit: the single-job gate says so
    assert requirements.fit_batch(_Slice(gib=8), name, 4, 512) == 0
    with pytest.raises(ValueError, match="does not fit"):
        requirements.check_capacity(_Slice(gib=8), name, 4, 512)
    # more chips of a slice hold no more: every chip holds the share whole
    assert requirements.fit_batch(_Slice(chips=4), name, 10 ** 9, 512) \
        == requirements.fit_batch(_Slice(), name, 10 ** 9, 512)
    # the tiny stand-in is a few MB whatever the table says
    assert requirements.fit_batch(_Slice(gib=1), "test/tiny-kimi", 64,
                                  512) == 64
    assert requirements.required_hbm_gb(name, 256, 512) == pytest.approx(
        costs["params_gb"] + costs["working_gb"] + 256 * 512 * 8064 / 2 ** 30)


def test_a_rows_bytes_are_reckoned_by_layer_kind():
    """K-EXAONE's share: one full layer keeps every position, four
    sliding layers a ring of 128 whatever the length."""
    name = "test/K-EXAONE-236B-A23B"
    assert requirements._family_key(name) == "exaone_moe"
    assert requirements.sequence_row_bytes("exaone_moe", 16512) == (
        4096 * 16512 + 4 * 4096 * 128)
    # shorter than the window: every layer keeps what there is
    assert requirements.sequence_row_bytes("exaone_moe", 100) == 5 * 4096 * 100
    assert requirements.sequence_row_bytes("kimi_k2", 512) == 8064 * 512
    costs = requirements.TEXT_FAMILIES["exaone_moe"]
    free = 15.75 - costs["params_gb"] - costs["working_gb"]
    per_row = (4096 * 16512 + 4 * 4096 * 128) / (1 << 30)
    assert requirements.fit_batch(_Slice(), name, 10 ** 9, 16512) == int(
        free / per_row)
    # kept whole on all five layers the same row would be 4.7 times that
    assert 5 * 4096 * 16512 / (4096 * 16512 + 4 * 4096 * 128) > 4.7
    # by memory ~88 rows fit; the pass is budgeted in positions: 4 rows
    assert requirements.fit_batch(_Slice(), name, 256, 16512) > 64
    assert requirements.coalesce_rows_limit(_Slice(), name, 16512) == 4
    assert requirements.coalesce_rows_limit(_Slice(), name, 512) == 256
    # the same on a slice that is no TPU: what a pass is is not memory's
    assert requirements.coalesce_rows_limit(None, name, 16512) == 4
    assert requirements.pass_positions_limit(_Slice(), "exaone_moe") == (
        requirements.pass_positions_limit(_Slice(), "kimi_k2")) == 131072
    # a chip with little left beside the weights holds fewer positions
    assert 0 < requirements.pass_positions_limit(
        _Slice(gib=10.5), "exaone_moe") < 131072


def test_a_row_of_a_recurrent_state_costs_bytes_whatever_its_positions():
    """Qwen3-Next's share: six linear layers hold a row a float32 state
    and a convolution's tail whatever the row's length, two full layers
    4096 B a position."""
    name = "test/Qwen3-Next-80B-A3B-Instruct"
    assert requirements._family_key(name) == "qwen3_next"
    costs = requirements.TEXT_FAMILIES["qwen3_next"]
    state = 6 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)
    assert costs["row_bytes"] == state == 12877824
    assert costs["cache_layers"] == ((2 * 2 * 2 * 256 * 2, 0),)
    for positions in (0, 1, 16, 512, 16512, 262144):
        assert requirements.sequence_row_bytes("qwen3_next", positions) == (
            state + 4096 * max(positions, 1)) > state
    # the other families keep nothing a row: their tables read as before
    assert requirements.sequence_row_bytes("kimi_k2", 1) == 8064
    # what the model's own module counts for the cell's pass
    from chiaswarm_tpu.models import qwen3_next

    cfg = qwen3_next.QWEN3_NEXT_80B_EP4
    assert qwen3_next.cache_bytes(cfg, 256, 512, 2)[0] == 256 * (
        requirements.sequence_row_bytes("qwen3_next", 512))
    import jax

    shapes = qwen3_next.param_shapes(cfg, jax.numpy.bfloat16)
    held = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(shapes))
    assert costs["params_gb"] == pytest.approx(held / 2 ** 30, abs=0.01)
    # 256 rows of 512 fit beside weights and working set, and the pass is
    # budgeted in positions as any family's
    free = 15.75 - costs["params_gb"] - costs["working_gb"]
    per_row = (state + 4096 * 512) / (1 << 30)
    assert requirements.fit_batch(_Slice(), name, 10 ** 9, 512) == int(
        free / per_row) >= 256
    assert requirements.fit_batch(_Slice(), name, 256, 512) == 256
    assert requirements.coalesce_rows_limit(_Slice(), name, 512) == 256
    assert requirements.coalesce_rows_limit(_Slice(), name, 16512) == 4
    assert requirements.pass_positions_limit(_Slice(), "qwen3_next") == 131072
    # a row of one position still costs its state: a chip with 1 GiB left
    # beside the weights holds 83 of them, not any number
    tight = _Slice(gib=costs["params_gb"] + costs["working_gb"] + 1.0)
    assert requirements.fit_batch(tight, name, 10 ** 9, 1) == int(
        (1 << 30) / (state + 4096))
    assert 0 < requirements.pass_positions_limit(tight, "qwen3_next") < 131072
    # what 16.9 GB cannot hold is refused: 1024 rows of 512 would be 14 GiB
    assert requirements.fit_batch(_Slice(), name, 1024, 512) < 1024
    assert requirements.fit_batch(_Slice(gib=8), name, 4, 512) == 0
    with pytest.raises(ValueError, match="does not fit"):
        requirements.check_capacity(_Slice(gib=8), name, 4, 512)
    assert requirements.fit_batch(_Slice(gib=1), "test/tiny-qwen3-next", 64,
                                  512) == 64


def test_a_row_of_two_mixers_costs_a_state_and_keys_on_every_layer():
    """Falcon-H1's stage (ISSUE 46): each of the four layers holds a row
    BOTH a float32 state with a convolution's tail whatever the row's
    length AND 2048 B of keys and values a position; the row is 80 %
    constant at the cell's 512 positions."""
    import jax

    from chiaswarm_tpu.models import falcon_h1

    name = "test/Falcon-H1-34B-Instruct"
    assert requirements._family_key(name) == "falcon_h1"
    costs = requirements.TEXT_FAMILIES["falcon_h1"]
    state = 4 * (32 * 256 * 128 * 4 + 3 * 5120 * 2)
    assert costs["row_bytes"] == state == 16900096
    assert costs["cache_layers"] == ((4 * 2 * 4 * 128 * 2, 0),)
    for positions in (0, 1, 16, 512, 16512, 262144):
        assert requirements.sequence_row_bytes("falcon_h1", positions) == (
            state + 8192 * max(positions, 1)) > state
    assert 0.80 < state / requirements.sequence_row_bytes(
        "falcon_h1", 512) < 0.81
    # what the model's own module counts for the cell's pass
    cfg = falcon_h1.FALCON_H1_34B_PP18
    whole, rings, kept = falcon_h1.cache_bytes(cfg, 256, 512, 2)
    assert whole == 256 * requirements.sequence_row_bytes("falcon_h1", 512)
    assert (rings, kept) == (0, 256 * state)
    shapes = falcon_h1.param_shapes(cfg, jax.numpy.bfloat16)
    held = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(shapes))
    assert costs["params_gb"] == pytest.approx(held / 2 ** 30, abs=0.01)
    # 256 rows of 512 fit beside weights and working set: the fullest
    # pass the table admits, 283 rows at the most
    free = 15.75 - costs["params_gb"] - costs["working_gb"]
    per_row = (state + 8192 * 512) / (1 << 30)
    assert requirements.fit_batch(_Slice(), name, 10 ** 9, 512) == int(
        free / per_row) >= 256
    assert requirements.fit_batch(_Slice(), name, 256, 512) == 256
    assert requirements.coalesce_rows_limit(_Slice(), name, 512) == 256
    assert requirements.coalesce_rows_limit(_Slice(), name, 16512) == 4
    assert requirements.pass_positions_limit(_Slice(), "falcon_h1") == 131072
    # a row of one position still costs its state
    tight = _Slice(gib=costs["params_gb"] + costs["working_gb"] + 1.0)
    assert requirements.fit_batch(tight, name, 10 ** 9, 1) == int(
        (1 << 30) / (state + 8192)) == 63
    assert 0 < requirements.pass_positions_limit(tight, "falcon_h1") < 131072
    assert requirements.fit_batch(_Slice(), name, 512, 512) < 512
    with pytest.raises(ValueError, match="does not fit"):
        requirements.check_capacity(_Slice(gib=8), name, 4, 512)
    assert requirements.fit_batch(_Slice(gib=1), "test/tiny-falcon-h1", 64,
                                  512) == 64


@pytest.mark.parametrize("name, family", [
    ("test/Kimi-K2.6", "kimi_k2"),
    ("test/K-EXAONE-236B-A23B", "exaone_moe"),
    ("test/SDAR-30B-A3B-Chat", "sdar_moe"),
    ("test/Qwen3-Next-80B-A3B-Instruct", "qwen3_next"),
    ("test/Falcon-H1-34B-Instruct", "falcon_h1"),
    ("test/GLM-5", "glm_moe_dsa"),
    ("test/MiMo-V2.5", "mimo_v2"),
])
def test_a_full_size_test_name_is_no_stand_in(name, family):
    """Every text family gives a `test/` name its published widths, so
    admission reckons it by the table (`sdar_moe` was missing from the
    list before PR 42, and `fit_batch` admitted any batch of it)."""
    assert requirements._family_key(name) == family
    assert family in requirements._PUBLISHED_TEST_FAMILIES
    assert not requirements._is_stand_in(name)
    assert requirements._is_stand_in(f"test/tiny-{family}")
    fit = requirements.fit_batch(_Slice(), name, 10 ** 9, 512)
    assert 256 <= fit < 10 ** 9
    assert requirements.fit_batch(_Slice(gib=6), name, 4, 512) == 0


# --- the appetite between worker and hive ------------------------------------


def test_the_dispatcher_sizes_a_text_gang_by_the_familys_own_appetite():
    from chiaswarm_tpu.hive_server.dispatch import (
        Dispatcher,
        WorkerDirectory,
    )
    from chiaswarm_tpu.hive_server.queue import PriorityJobQueue

    directory = WorkerDirectory(ttl_s=45.0)
    dispatcher = Dispatcher(directory, affinity_hold_s=0.0,
                            max_jobs_per_poll=4)
    queue = PriorityJobQueue()
    for n in range(6):
        queue.submit(_job(n, n, rows=64, low=20, high=60))
    poll = {"worker_name": "w", "slices": "1", "busy_slices": "0",
            "queue_depth": "0", "gang_rows": "8"}
    # no appetite of the family's own: 64 rows are over the 8-row appetite
    # of image jobs, and one job leaves alone
    alone = dispatcher.select(directory.observe(poll), queue)
    assert [gang for _, _, gang in alone] == [None]
    info = directory.observe({**poll, "family_gang_rows": "kimi_k2:256"})
    assert info.family_rows == {"kimi_k2": 256}
    assert info.rows_per_pass(("m", "kimi_k2")) == 256
    assert info.rows_per_pass(("m", "sdxl")) == info.rows_per_pass(None) == 8
    handed = dispatcher.select(info, queue)
    assert len(handed) == 4 and {g["size"] for _, _, g in handed} == {4}
    assert len({g["id"] for _, _, g in handed}) == 1
    # a pass of 256 in flight leaves a text job nothing, 128 leave two
    busy = directory.observe({**poll, "family_gang_rows": "kimi_k2:256",
                              "queue_depth": "256"})
    assert dispatcher.select(busy, queue) == []
    half = directory.observe({**poll, "family_gang_rows": "kimi_k2:256",
                              "queue_depth": "128"})
    assert len(dispatcher.select(half, queue)) == 2
    # what does not parse is left out
    assert directory.observe(
        {**poll, "family_gang_rows": "x:,:3,y:0,z:5"}).family_rows == {"z": 5}


@pytest.mark.parametrize(
    "family, model, slots, new, job_rows_, want_rows, want_jobs", [
        ("kimi_k2", "test/tiny-kimi", 256, 256, 64, 256, 4),
        ("exaone_moe", "test/tiny-exaone", 16384, 128, 1, 4, 4),
        ("exaone_moe", "test/tiny-exaone", 32768, 128, 1, 2, 2),  # 3.98 fit
        ("kimi_k2", "test/tiny-kimi", 16, 6, 3, 256, 8),  # the poll's cap
        ("sdar_moe", "test/tiny-sdar", 256, 256, 64, 256, 4),
    ], ids=["kimi_batch_decode", "exaone_long_documents", "longer", "short",
            "sdar_block_decode"])
def test_the_hives_gang_is_reckoned_at_the_jobs_own_positions(
        family, model, slots, new, job_rows_, want_rows, want_jobs):
    from chiaswarm_tpu.hive_server.dispatch import (
        Dispatcher,
        WorkerDirectory,
    )
    from chiaswarm_tpu.hive_server.queue import PriorityJobQueue

    directory = WorkerDirectory(ttl_s=45.0)
    dispatcher = Dispatcher(directory, affinity_hold_s=0.0,
                            max_jobs_per_poll=8)
    queue = PriorityJobQueue()
    jobs = [{"id": f"j{n}", "workflow": "txt2txt", "model_name": model,
             "max_new_tokens": new, "temperature": 1.0, "seed": n,
             "prompt_ids": [[1] * slots] * job_rows_} for n in range(10)]
    for job in jobs:
        queue.submit(job)
    info = directory.observe({
        "worker_name": "w", "slices": "1", "busy_slices": "0",
        "queue_depth": "0", "gang_rows": "8",
        "family_gang_rows": "kimi_k2:256,exaone_moe:256,sdar_moe:256",
        "family_gang_positions":
            "kimi_k2:131072,exaone_moe:131072,sdar_moe:131072"})
    assert info.family_positions == {
        "kimi_k2": 131072, "exaone_moe": 131072, "sdar_moe": 131072}
    # a block decode's key goes on behind these (its steps, its threshold)
    key = coalesce_key(jobs[0])
    assert key[:6] == (model, family, "txt2txt", slots, new, 1.0)
    assert info.rows_per_pass(key) == want_rows
    handed = dispatcher.select(info, queue)
    assert len(handed) == want_jobs
    assert {gang["size"] for _, _, gang in handed} == {want_jobs}
    # what the worker's batcher holds the same group to
    assert requirements.coalesce_rows_limit(
        None, model, slots + new) == want_rows


def test_a_worker_advertises_the_sequence_families_appetite(sdaas_root):
    from chiaswarm_tpu.chips.allocator import SliceAllocator
    from chiaswarm_tpu.settings import Settings
    from chiaswarm_tpu.worker import Worker

    worker = Worker(settings=Settings(sdaas_token="t", worker_name="w"),
                    allocator=SliceAllocator(chips_per_job=8),
                    hive_uri="http://127.0.0.1:1")
    caps = worker._capabilities()
    # not HBM on the CPU: the ceiling; the job cap stays what it was
    assert caps["family_gang_rows"] == (
        "kimi_k2:256,exaone_moe:256,sdar_moe:256,qwen3_next:256,"
        "falcon_h1:256,glm_moe_dsa:256,mimo_v2:256")
    assert caps["family_gang_positions"] == (
        "kimi_k2:131072,exaone_moe:131072,sdar_moe:131072,"
        "qwen3_next:131072,falcon_h1:131072,glm_moe_dsa:131072,"
        "mimo_v2:131072")
    assert caps["gang_rows"] == 8
    # the batcher's own budget is the job's true positions
    assert worker._coalesce_rows_limit(_job(1, 2)) == 256


# --- end to end ---------------------------------------------------------------


def test_four_text_jobs_are_one_gang_one_pass_and_seeded(sdaas_root,
                                                         monkeypatch):
    from chiaswarm_tpu import worker as worker_module
    from chiaswarm_tpu.hive_server.harness import LocalSwarm
    from chiaswarm_tpu.settings import Settings

    monkeypatch.setattr(worker_module, "POLL_SECONDS", 0.1)

    async def scenario():
        swarm = LocalSwarm(n_workers=0, settings=Settings(
            sdaas_token="t", worker_name="w", hive_port=0, metrics_port=0))
        await swarm.start()
        try:
            first = [await swarm.submit(_job(n, 100 + n)) for n in range(4)]
            # the first job again, among other batchmates of other sizes:
            # all eight reach the hive before a worker can take any, so
            # each poll finds a whole gang of four (submitted to a live
            # worker, a poll could fall between two submits and split it)
            again = [await swarm.submit(dict(_job(0, 100), id="again"))] + [
                await swarm.submit(dict(_job(n, n, rows=2), id=f"other-{n}"))
                for n in range(5, 8)]
            swarm.add_worker("text-worker")
            done = [await swarm.wait_done(i, timeout=300) for i in first]
            done.append(await swarm.wait_done(again[0], timeout=300))
            blobs = [await swarm.artifact(
                status["result"]["artifacts"]["primary"]["href"])
                for status in done]
            return done, blobs
        finally:
            await swarm.stop()

    done, blobs = asyncio.run(scenario())
    configs = [status["result"]["pipeline_config"] for status in done]
    assert all(status["status"] == "done" and status["attempts"] == 1
               for status in done)
    # one gang of four left the hive, and ran as one pass of 12 rows
    gangs = [config["trace"]["gang"] for config in configs[:4]]
    assert len({gang["id"] for gang in gangs}) == 1
    assert [gang["size"] for gang in gangs] == [4] * 4
    assert {config["pass_rows"] for config in configs[:4]} == {12}
    assert [config["batch_rows"] for config in configs[:4]] == [
        [0, 3], [3, 3], [6, 3], [9, 3]]
    for config in configs:
        names = [span["name"] for span in config["spans"]]
        assert {"pass", "prefill", "decode", "readback",
                "artifact_encode"} <= set(names)
        assert {"prefill_s", "decode_s", "readback_s"} <= set(
            config["timings"])
        routing = config["routing"]
        assert routing["pairs"] == (routing["prefill"]["pairs"]
                                    + routing["decode"]["pairs"])
        assert 0 < routing["pairs"] < routing["routed"]
    host = [span for span in configs[0]["spans"]
            if span["name"] == "artifact_encode"]
    assert host[0]["thread"] == "host"
    # four JSON artifacts: 3 rows of 6 ids of the vocabulary each
    for status, blob in zip(done, blobs):
        ref = status["result"]["artifacts"]["primary"]
        assert ref["content_type"] == "application/json"
        assert hashlib.sha256(blob).hexdigest() == ref["sha256"]
        rows = json.loads(blob)["token_ids"]
        assert len(rows) == 3 and all(len(row) == 6 for row in rows)
        assert all(0 <= i < 128 for row in rows for i in row)
    assert len({blob for blob in blobs[:4]}) == 4
    # one job, one seed: the same bytes in a pass of 9 rows as of 12
    assert configs[4]["pass_rows"] == 9
    assert blobs[4] == blobs[0]


def test_block_decode_jobs_go_through_hive_worker_and_pipeline(sdaas_root,
                                                               monkeypatch):
    """`test/tiny-sdar`: three jobs of two denoise forwards a block are
    one gang and one pass, a fourth of four forwards a block rides alone;
    JSON artifacts of `max_new_tokens` ids a row, the envelope says what
    the forwards were (no commit is a forward of its own: every block
    behind the first commits the one before it inside its first forward),
    the benchmark's three readers read the same from the scrape, and one
    seed gives one answer among other batchmates."""
    from benchmark import harness
    from chiaswarm_tpu import worker as worker_module
    from chiaswarm_tpu.hive_server.harness import LocalSwarm
    from chiaswarm_tpu.settings import Settings

    monkeypatch.setattr(worker_module, "POLL_SECONDS", 0.1)

    async def scenario():
        swarm = LocalSwarm(n_workers=0, settings=Settings(
            sdaas_token="t", worker_name="w", hive_port=0, metrics_port=0))
        await swarm.start()
        try:
            opened = harness.scrape()
            ids = [await swarm.submit(_block_job(
                id=f"block-{n}", seed=100 + n, denoising_steps=2))
                for n in range(3)]
            ids.append(await swarm.submit(_block_job(id="block-3", seed=100)))
            swarm.add_worker("text-worker")
            done = [await swarm.wait_done(i, timeout=300) for i in ids]
            again = await swarm.submit(_block_job(
                id="again", seed=100, denoising_steps=2))
            done.append(await swarm.wait_done(again, timeout=300))
            blobs = [await swarm.artifact(
                status["result"]["artifacts"]["primary"]["href"])
                for status in done]
            return done, blobs, {"scrape_open": opened,
                                 "scrape_close": harness.scrape()}
        finally:
            await swarm.stop()

    done, blobs, record = asyncio.run(scenario())
    configs = [status["result"]["pipeline_config"] for status in done]
    assert all(status["status"] == "done" and status["attempts"] == 1
               for status in done)
    assert [config["pass_rows"] for config in configs] == [9, 9, 9, 3, 3]
    assert [config["denoising_steps"] for config in configs] == [
        2, 2, 2, 4, 2]
    blocks = 3  # six ids behind a tail of up to three
    for config in configs:
        steps = config["denoising_steps"]
        # a first block behind a given tail may need fewer forwards
        forwards = config["forwards"]
        assert (forwards["commit"], forwards["fused"]) == (0, blocks - 1)
        assert (blocks - 1) * steps < forwards["denoise"] <= blocks * steps
        assert config["decode_steps"] == forwards["denoise"]
        assert (config["block_length"], config["blocks"]) == (4, blocks)
        names = {span["name"] for span in config["spans"]}
        assert {"pass", "prefill", "decode", "readback",
                "artifact_encode"} <= names
        # every expert held: all that is routed is computed here
        assert config["routing"]["pairs"] == config["routing"]["routed"] > 0
    for blob in blobs:
        rows = json.loads(blob)["token_ids"]
        assert len(rows) == 3 and all(len(row) == 6 for row in rows)
        assert all(0 <= i < 128 for row in rows for i in row)
    assert blobs[4] == blobs[0] and blobs[3] != blobs[0]
    # the three passes (of 9, 3 and 3 rows) as the benchmark's readers see
    # them in what `/metrics` prints
    passes = [configs[0], configs[3], configs[4]]
    forward_rows = sum(config["pass_rows"] * config["forwards"]["denoise"]
                       for config in passes)
    read = {name: harness.load_reader("layer_metrics", name) for name in (
        "tokens_per_forward", "commit_forward_share", "idle_slot_share")}
    assert read["commit_forward_share"](record) == 0.0
    assert read["tokens_per_forward"](record) == pytest.approx(
        15 * 6 / forward_rows)
    assert 0.0 < read["idle_slot_share"](record) < 100.0
    model = configs[0]["model_name"]
    close = record["scrape_close"]
    assert f"{model},commit" in close["swarm_block_forward_rows_total"]
    assert harness.counter(
        close, "swarm_block_fused_commit_rows_total", model) - harness.counter(
            record["scrape_open"], "swarm_block_fused_commit_rows_total",
            model) == 15 * (blocks - 1)
    # the denoised block's positions alone (each takes its id once): a
    # fused forward's finished positions are in neither kind
    unmasked = harness.counter(
        close, "swarm_block_slots_total", f"{model},unmasked"
    ) - harness.counter(record["scrape_open"], "swarm_block_slots_total",
                        f"{model},unmasked")
    assert unmasked == 5 * sum(
        4 * blocks - len(row) % 4 for row in _block_job()["prompt_ids"])


def test_selected_key_jobs_go_through_hive_worker_and_pipeline(sdaas_root,
                                                               monkeypatch):
    """`test/tiny-glm-5` (ISSUE 49) through hive, worker and pipeline with
    no setting of its own: three jobs of one row each (33 to 64 ids, four
    to eight times the 8 keys a query selects; the prefill constant shrunk
    to 16 tokens: four spans a 64-slot row, each attending to the latents
    and index keys the spans before it cached) are one gang and one pass;
    the envelope says what the queries saw and what attention read, by
    phase, and how much of the cache is index keys; one seed gives one
    answer alone as among batchmates."""
    from chiaswarm_tpu import telemetry
    from chiaswarm_tpu import worker as worker_module
    from chiaswarm_tpu.hive_server.harness import LocalSwarm
    from chiaswarm_tpu.pipelines import text_generation
    from chiaswarm_tpu.settings import Settings

    monkeypatch.setattr(worker_module, "POLL_SECONDS", 0.1)
    monkeypatch.setattr(text_generation, "PREFILL_CHUNK_TOKENS", 16)
    model, new = "test/tiny-glm-5", 5
    lengths = [41, 64, 33]

    def job(number, **extra):
        rng = np.random.default_rng(number)
        return {"id": f"glm-{number}", "workflow": "txt2txt",
                "model_name": model, "max_new_tokens": new,
                "temperature": 1.0, "seed": 100 + number,
                "prompt_ids": [rng.integers(0, 128, lengths[number]).tolist()],
                **extra}

    assert coalesce_key(job(0)) == (model, "glm_moe_dsa", "txt2txt", 64, new,
                                    1.0)
    label = {"model": model}
    counters = (text_generation.SPARSE_VISIBLE,
                text_generation.SPARSE_SELECTED)
    before = [[counter.value(phase=phase, **label)
               for phase in ("prefill", "decode")] for counter in counters]

    async def scenario():
        swarm = LocalSwarm(n_workers=0, settings=Settings(
            sdaas_token="t", worker_name="w", hive_port=0, metrics_port=0))
        await swarm.start()
        try:
            ids = [await swarm.submit(job(n)) for n in range(3)]
            swarm.add_worker("text-worker")
            done = [await swarm.wait_done(i, timeout=300) for i in ids]
            again = await swarm.submit(job(0, id="again"))
            done.append(await swarm.wait_done(again, timeout=300))
            blobs = [await swarm.artifact(
                status["result"]["artifacts"]["primary"]["href"])
                for status in done]
            return done, blobs
        finally:
            await swarm.stop()

    done, blobs = asyncio.run(scenario())
    configs = [status["result"]["pipeline_config"] for status in done]
    assert all(status["status"] == "done" and status["attempts"] == 1
               for status in done)
    assert [config["pass_rows"] for config in configs] == [3, 3, 3, 1]
    assert len({config["trace"]["gang"]["id"] for config in configs[:3]}) == 1
    for blob in blobs:
        (row,) = json.loads(blob)["token_ids"]
        assert len(row) == new and all(0 <= i < 128 for i in row)
    # one job, one seed: the same bytes alone as among batchmates
    assert blobs[3] == blobs[0] and len(set(blobs[:3])) == 3
    layers, topk = 3, 8

    def counts(rows):
        """(visible, selected) by phase of a pass of rows of these
        lengths: a query at position t sees t + 1 and reads 8 at most; a
        decode step feeds generated token n at position length + n."""
        seen = {"prefill": [np.arange(1, n + 1) for n in rows],
                "decode": [n + 1 + np.arange(new - 1) for n in rows]}
        return [{phase: layers * sum(int(pick(s).sum()) for s in parts)
                 for phase, parts in seen.items()}
                for pick in (lambda s: s, lambda s: np.minimum(s, topk))]

    for config, rows in ((configs[0], lengths), (configs[3], lengths[:1])):
        visible, selected = counts(rows)
        # spans of 16: every span of a real row that holds an id ran, its
        # key side up to its own end (ISSUE 50) of the 64 slots
        ran = [-(-n // 16) for n in rows]
        spans = sum(ran)
        assert config["selection"] == {
            "visible": visible, "selected": selected,
            "prefill_key_extent": {
                "walked": layers * sum(16 * n * (n + 1) // 2 for n in ran),
                "bucket": layers * spans * 64}}
        assert 2 * selected["prefill"] < visible["prefill"]
        assert config["prefill_chunk_widths"] == {"16": spans}
        assert config["routing"]["prefill"]["calls"] == spans * 2
        # two caches a layer: latents of 24 and index keys of 16, float32
        positions = 64 + new
        assert config["cache_bytes"] == (
            config["padded_rows"] * positions * (24 + 16) * 4 * layers)
        assert (config["cache_bytes_window"],
                config["cache_bytes_state"]) == (0, 0)
    both = [counts(lengths), counts(lengths[:1])]
    moved = [[counter.value(phase=phase, **label) - was
              for phase, was in zip(("prefill", "decode"), start)]
             for counter, start in zip(counters, before)]
    assert moved == [[sum(run[kind][phase] for run in both)
                      for phase in ("prefill", "decode")]
                     for kind in (0, 1)]
    # the last pass placed: one row of 69 positions
    assert text_generation.PASS_INDEX_CACHE_BYTES.value(**label) == (
        1 * 69 * 16 * 4 * layers)
    rendered = telemetry.REGISTRY.render()
    assert "swarm_sparse_selected_positions_total" in rendered
    assert "swarm_pass_index_cache_bytes" in rendered
    traces = text_generation.platform.KERNEL_TRACES
    for op in ("lightning_indexer", "index_select",
               "sparse_latent_attention"):
        assert traces.value(op=op, path="reference") > 0


def test_one_long_row_goes_through_in_position_chunks(sdaas_root,
                                                      monkeypatch):
    """`test/tiny-exaone` through hive, worker and pipeline: a row longer
    than a prefill chunk (the constant shrunk to 16 tokens: 4 spans of a
    64-slot row, window 4), greedy, gives the ids the pipeline gives with
    the whole row in one chunk, and the envelope says what was cached."""
    import jax

    from chiaswarm_tpu import worker as worker_module
    from chiaswarm_tpu.hive_server.harness import LocalSwarm
    from chiaswarm_tpu.pipelines import text_generation
    from chiaswarm_tpu.settings import Settings

    monkeypatch.setattr(worker_module, "POLL_SECONDS", 0.1)
    rng = np.random.default_rng(11)
    rows = [rng.integers(0, 128, 41).tolist()]
    job = {"id": "long-row", "workflow": "txt2txt",
           "model_name": "test/tiny-exaone", "prompt_ids": rows,
           "max_new_tokens": 7, "temperature": 0.0, "seed": 3}
    assert coalesce_key(job) == (
        "test/tiny-exaone", "exaone_moe", "txt2txt", 64, 7, 0.0)

    label = {"model": "test/tiny-exaone"}
    before = {kind: text_generation.PREFILL_SLOTS.value(kind=kind, **label)
              for kind in ("real", "padding", "skipped")}
    pipe = text_generation.TextGenerationPipeline(
        "test/tiny-exaone", allow_random_init=True)
    assert text_generation.prefill_chunk(1, 64) == (1, 64)
    ((want, whole),) = pipe.run_batched(
        [{"prompt_ids": rows, "rng": jax.random.key(3)}],
        max_new_tokens=7, temperature=0.0)
    assert whole["prefill_chunks"] == 1
    assert whole["prefill_chunks_skipped"] == 0
    assert whole["prefill_chunk_widths"] == {"64": 1}

    monkeypatch.setattr(text_generation, "PREFILL_CHUNK_TOKENS", 16)
    assert text_generation.prefill_chunk(1, 64) == (1, 16)
    assert text_generation.prefill_chunk(4, 8) == (2, 8)
    assert text_generation.prefill_chunk(2, 64, spans=False) == (1, 64)

    async def scenario():
        swarm = LocalSwarm(n_workers=0, settings=Settings(
            sdaas_token="t", worker_name="w", hive_port=0, metrics_port=0))
        await swarm.start()
        try:
            job_id = await swarm.submit(job)
            swarm.add_worker("text-worker")
            status = await swarm.wait_done(job_id, timeout=300)
            blob = await swarm.artifact(
                status["result"]["artifacts"]["primary"]["href"])
            return status, blob
        finally:
            await swarm.stop()

    status, blob = asyncio.run(scenario())
    assert status["status"] == "done" and status["attempts"] == 1
    config = status["result"]["pipeline_config"]
    assert json.loads(blob)["token_ids"] == want.tolist()
    # the chunks that ran (ISSUE 43): the span from 48 on is past the
    # row's 41 ids and was not run: three calls of each of the four expert
    # layers, and six decode steps'
    assert config["prefill_chunks"] == 3 and config["prompt_slots"] == 64
    assert config["prefill_chunks_skipped"] == 1
    assert config["prefill_chunk_widths"] == {"16": 3}
    assert config["routing"]["prefill"]["calls"] == 3 * 4
    assert config["routing"]["calls"] == (3 + 6) * 4
    assert config["padded_rows"] == 1 and config["prompt_tokens"] == 41
    # one full layer of 71 positions and four rings of 4: keys and values
    # of 2 heads x 16, float32
    a_position = 2 * 2 * 16 * 4
    assert config["cache_bytes_window"] == 4 * 4 * a_position
    assert config["cache_bytes"] == (71 + 4 * 4) * a_position
    from chiaswarm_tpu import telemetry

    assert text_generation.PASS_WINDOW_CACHE_BYTES.value(**label) == 4096
    assert text_generation.PASS_CACHE_BYTES.value(**label) == 87 * 256
    # twice 41 real ids; 23 of padding computed in the run above, and in
    # this one 7 computed and the last span's 16 left out
    moved = {kind: text_generation.PREFILL_SLOTS.value(kind=kind, **label)
             - was for kind, was in before.items()}
    assert moved == {"real": 82, "padding": 23 + 7, "skipped": 16}
    assert "swarm_pass_window_cache_bytes" in telemetry.REGISTRY.render()


def test_sink_and_wide_key_jobs_go_through_hive_worker_and_pipeline(
        sdaas_root, monkeypatch):
    """`test/tiny-mimo` (ISSUE 57) through hive, worker and pipeline with no
    setting of its own: three jobs of one row each (33 to 64 ids, eight to
    sixteen times the window of 4; the prefill constant shrunk to 16
    tokens: four spans a 64-slot row as one traced body, a full layer's
    span attending to the row's cache up to its own end, a window layer's
    to the span before's last four keys, with the sink) are one gang and
    one pass; the envelope says how much of the cache is rings and how far
    the full layers' spans walked; one seed gives one answer alone as among
    batchmates."""
    from chiaswarm_tpu import worker as worker_module
    from chiaswarm_tpu.hive_server.harness import LocalSwarm
    from chiaswarm_tpu.pipelines import text_generation
    from chiaswarm_tpu.settings import Settings

    monkeypatch.setattr(worker_module, "POLL_SECONDS", 0.1)
    monkeypatch.setattr(text_generation, "PREFILL_CHUNK_TOKENS", 16)
    model, new = "test/tiny-mimo", 5
    lengths = [41, 64, 33]

    def job(number, **extra):
        rng = np.random.default_rng(number)
        return {"id": f"mimo-{number}", "workflow": "txt2txt",
                "model_name": model, "max_new_tokens": new,
                "temperature": 1.0, "seed": 100 + number,
                "prompt_ids": [rng.integers(0, 128, lengths[number]).tolist()],
                **extra}

    assert coalesce_key(job(0)) == (model, "mimo_v2", "txt2txt", 64, new,
                                    1.0)
    label = {"model": model}
    before = {extent: text_generation.PREFILL_KEY_EXTENT.value(
        extent=extent, **label) for extent in ("walked", "bucket")}

    async def scenario():
        swarm = LocalSwarm(n_workers=0, settings=Settings(
            sdaas_token="t", worker_name="w", hive_port=0, metrics_port=0))
        await swarm.start()
        try:
            ids = [await swarm.submit(job(n)) for n in range(3)]
            swarm.add_worker("text-worker")
            done = [await swarm.wait_done(i, timeout=300) for i in ids]
            again = await swarm.submit(job(0, id="again"))
            done.append(await swarm.wait_done(again, timeout=300))
            blobs = [await swarm.artifact(
                status["result"]["artifacts"]["primary"]["href"])
                for status in done]
            return done, blobs
        finally:
            await swarm.stop()

    done, blobs = asyncio.run(scenario())
    configs = [status["result"]["pipeline_config"] for status in done]
    assert all(status["status"] == "done" and status["attempts"] == 1
               for status in done)
    assert [config["pass_rows"] for config in configs] == [3, 3, 3, 1]
    assert len({config["trace"]["gang"]["id"] for config in configs[:3]}) == 1
    for blob in blobs:
        (row,) = json.loads(blob)["token_ids"]
        assert len(row) == new and all(0 <= i < 128 for i in row)
    # one job, one seed: the same bytes alone as among batchmates
    assert blobs[3] == blobs[0] and len(set(blobs[:3])) == 3
    full, windows = 2, 5
    total = {"walked": 0, "bucket": 0}
    for config, rows in ((configs[0], lengths), (configs[3], lengths[:1])):
        # spans of 16: every span of a real row that holds an id ran, a
        # full layer's key side up to the span's own end of the 64 slots
        ran = [-(-n // 16) for n in rows]
        extent = {"walked": full * sum(16 * n * (n + 1) // 2 for n in ran),
                  "bucket": full * sum(ran) * 64}
        assert config["prefill_key_extent"] == extent
        assert "selection" not in config
        total = {key: total[key] + extent[key] for key in total}
        assert config["prefill_chunk_widths"] == {"16": sum(ran)}
        # two geometries: a full layer one key head of 24 + 16 a position,
        # a window layer a ring of 4 columns of two key heads, float32
        rings = config["padded_rows"] * 4 * 2 * (24 + 16) * 4 * windows
        assert config["cache_bytes_window"] == rings
        assert config["cache_bytes"] == rings + (
            config["padded_rows"] * (64 + new) * (24 + 16) * 4 * full)
        assert config["cache_bytes_state"] == 0
    assert {extent: text_generation.PREFILL_KEY_EXTENT.value(
        extent=extent, **label) - was
            for extent, was in before.items()} == total
    assert text_generation.PASS_WINDOW_CACHE_BYTES.value(**label) > 0


@pytest.mark.parametrize(
    "model, chunk_tokens, lengths, widths, skipped, skipped_slots", [
        # spans of 16 of a 64-slot row, a row a chunk: 1 + 2 + 3 spans past
        # the rows' ends and the 4 of the row that pads the pass to 4
        ("test/tiny-exaone", 16, [41, 17, 5], {"16": 6}, 10, 160),
        # whole rows, two a chunk, 5 rows in a pass of 8: the two rows that
        # only pad the pass come last and share the chunk that is not run
        ("test/tiny-exaone", 32, [9, 3, 16, 2, 7], {"16": 3}, 1, 32),
        # every span has a row's token
        ("test/tiny-exaone", 16, [64, 49], {"16": 8}, 0, 0),
        # ISSUE 43: Kimi's chunks are whole rows, two a chunk at most,
        # longest first, each row at its own width: (16, 9) at 16 slots,
        # (7) at 8, (3, 2) at 4, and the three rows that only pad the pass
        # are not run
        ("test/tiny-kimi", 32, [9, 3, 16, 2, 7], {"16": 1, "8": 1, "4": 1},
         2, 128 - 32 - 8 - 8),
        # ... a pass smaller than a chunk: its rows of a width a chunk
        ("test/tiny-kimi", 4096, [3, 7, 5], {"8": 1, "4": 1}, 1, 64 - 16 - 4),
        ("test/tiny-kimi", 4096, [3, 4, 2], {"4": 1}, 1, 64 - 12),
        # SDAR and Qwen3-Next offer the bucket's width alone: chunks of
        # their real rows, and those of the rows that only pad not run
        ("test/tiny-sdar", 32, [13, 2, 6, 5, 4], {"16": 3}, 2, 128 - 5 * 16),
        ("test/tiny-qwen3-next", 32, [16, 1, 9, 3, 2, 2], {"16": 3}, 1,
         128 - 6 * 16),
        # ... and so does Falcon-H1: spans of 16 of a 32-slot row, a row
        # a chunk, every span of a real row run (the state passes
        # through), the row that only pads the pass not run
        ("test/tiny-falcon-h1", 16, [29, 16, 7], {"16": 6}, 2, 32),
        # ... and GLM-5, where a span attends to the latents and index
        # keys the spans before it cached and one that no row reaches is
        # not run: the second of the rows of 16 and 7, both of the row
        # that only pads the pass
        ("test/tiny-glm-5", 16, [29, 16, 7], {"16": 4}, 4, 64),
        # ... and MiMo-V2, whose spans are one traced body too: a full
        # layer's span attends to the row's cache up to its own end, a
        # window layer's to the four keys the span before left
        ("test/tiny-mimo", 16, [29, 16, 7], {"16": 4}, 4, 64),
    ], ids=["spans", "whole_rows", "nothing_to_skip", "kimi", "one_chunk",
            "narrowest", "sdar", "qwen3_next", "falcon_h1", "glm_moe_dsa",
            "mimo_v2"])
def test_a_pass_counts_real_padding_and_skipped_slots(
        monkeypatch, model, chunk_tokens, lengths, widths, skipped,
        skipped_slots):
    import jax

    from chiaswarm_tpu.pipelines import text_generation

    monkeypatch.setattr(text_generation, "PREFILL_CHUNK_TOKENS", chunk_tokens)
    pipe = text_generation.TextGenerationPipeline(
        model, allow_random_init=True)
    rng = np.random.default_rng(len(lengths))
    prompts = [rng.integers(0, 128, n).tolist() for n in lengths]
    kinds = ("real", "padding", "skipped")
    before = [text_generation.PREFILL_SLOTS.value(kind=kind, model=model)
              for kind in kinds]
    ran = {width: text_generation.PREFILL_CHUNKS.value(
        width=width, model=model) for width in widths}
    ((ids, config),) = pipe.run_batched(
        [{"prompt_ids": prompts, "rng": jax.random.key(1)}],
        max_new_tokens=3, temperature=0.0)
    real, padding, left_out = (
        text_generation.PREFILL_SLOTS.value(kind=kind, model=model) - was
        for kind, was in zip(kinds, before))
    rows, slots = config["padded_rows"], config["prompt_slots"]
    chunks = sum(widths.values())
    assert ids.shape == (len(lengths), 3)
    assert (real, left_out) == (sum(lengths), skipped_slots)
    assert real + padding + left_out == rows * slots
    # the chunks that ran, each at its width, and those that did not
    assert config["prefill_chunks"] == chunks
    assert config["prefill_chunk_widths"] == widths
    assert config["prefill_chunks_skipped"] == skipped
    assert {width: text_generation.PREFILL_CHUNKS.value(
        width=width, model=model) - was for width, was in ran.items()
            } == widths
    # a block model's prefill stops before its last layer's experts
    layers = pipe.config.expert_layers - pipe.by_blocks
    prefill = config["routing"]["prefill"]
    assert prefill["calls"] == chunks * layers
    assert config["routing"]["calls"] == (
        prefill["calls"] + config["decode_steps"] * pipe.config.expert_layers)
    # the device's own tally: a call counts the held experts that had a
    # pair, so no more of them than the calls made hold
    # (a dense model makes no call and holds nothing: every count is 0)
    held = pipe.config.experts_held[1]
    assert bool(held) == (prefill["active"] > 0)
    assert prefill["active"] <= prefill["calls"] * held
    # padding is routed nowhere, run or not; SDAR routes whole blocks
    whole = [n // 4 * 4 for n in lengths] if pipe.by_blocks else lengths
    assert prefill["routed"] == sum(whole) * layers * getattr(
        pipe.config, "num_experts_per_tok", 0)


@pytest.mark.parametrize("model, extra", [
    ("test/tiny-kimi", {}), ("test/tiny-sdar", {"denoising_steps": 2}),
    ("test/tiny-qwen3-next", {})],
    ids=["kimi", "sdar", "qwen3_next"])
def test_the_tally_counts_the_row_tiles_plan_reports(monkeypatch, model,
                                                     extra):
    """ISSUE 44: the routing's fourth sum. `routing.prefill.tiles` and
    `routing.decode.tiles` are the row tiles `ops.expert_matmul.plan` told
    the grouped kernel to visit (`n_tiles` of every expert-layer call, read
    off the device as the programs run), an expert that had a pair has at
    least one, and the counter moves by the pass's."""
    import jax

    from chiaswarm_tpu.models import experts
    from chiaswarm_tpu.pipelines import text_generation

    seen, program = {"prefill": 0, "decode": 0}, ["prefill"]

    def count(n_tiles):
        seen[program[0]] += int(n_tiles)

    def counted(local, groups, tm):
        where = plan(local, groups, tm)
        jax.debug.callback(count, where.n_tiles)
        return where

    plan = experts.plan
    monkeypatch.setattr(experts, "plan", counted)
    pipe = text_generation.TextGenerationPipeline(
        model, allow_random_init=True)
    name = "block_decode_program" if pipe.by_blocks else "decode_program"
    decode_program = getattr(pipe, name)

    def after_prefill(*key):
        # `run_batched` asks for it once the prefill's tally is ready
        jax.effects_barrier()
        program[0] = "decode"
        return decode_program(*key)

    monkeypatch.setattr(pipe, name, after_prefill)
    rng = np.random.default_rng(44)
    prompts = [rng.integers(0, 128, n).tolist()
               for n in [16, 15, 16, 12, 16, 14, 16, 13, 16, 16, 9, 16]]
    was = text_generation.EXPERT_ROW_TILES.value(model=model)
    ((_, config),) = pipe.run_batched(
        [{"prompt_ids": prompts, "rng": jax.random.key(3)}],
        max_new_tokens=8, temperature=1.0, **extra)
    jax.effects_barrier()
    routing = config["routing"]
    assert {part: routing[part]["tiles"] for part in seen} == seen
    assert min(seen.values()) > 0
    assert routing["tiles"] == sum(seen.values()) == (
        text_generation.EXPERT_ROW_TILES.value(model=model) - was)
    for part in (routing, routing["prefill"], routing["decode"]):
        # 16-row tiles at this size: no fewer than the pairs fill, and no
        # more than one partly filled tile an expert that had a pair
        assert part["active"] <= part["tiles"] < (
            part["active"] + part["pairs"] / 16)
        assert part["tiles"] >= part["pairs"] / 16
    # a chunk of sixteen rows hands an expert more pairs than a tile holds
    assert routing["prefill"]["tiles"] > routing["prefill"]["active"]


@pytest.mark.parametrize("model, extra", [
    ("test/tiny-kimi", {}), ("test/tiny-sdar", {"denoising_steps": 2})],
    ids=["a_token_a_step", "by_blocks"])
def test_a_jobs_ids_do_not_depend_on_where_its_rows_stand(monkeypatch, model,
                                                          extra):
    """ISSUE 43: a pass runs its rows longest first and hands the ids back
    in the jobs' order. A job of a long, a short and a middling row draws
    the same ids alone, first and last among batchmates of other lengths
    (two rows a prefill chunk, so its rows share chunks with others')."""
    import jax

    from chiaswarm_tpu.pipelines import text_generation

    monkeypatch.setattr(text_generation, "PREFILL_CHUNK_TOKENS", 32)
    pipe = text_generation.TextGenerationPipeline(
        model, allow_random_init=True)
    rng = np.random.default_rng(43)

    def job(seed, *lengths):
        return {"prompt_ids": [rng.integers(0, 128, n).tolist()
                               for n in lengths], "rng": jax.random.key(seed)}

    mine, long, short = job(7, 3, 14, 6), job(8, 16, 11), job(9, 2, 5, 1)

    def served(*requests):
        results = pipe.run_batched(list(requests), max_new_tokens=6,
                                   temperature=1.0, **extra)
        return {id(request): (ids.tolist(), config)
                for request, (ids, config) in zip(requests, results)}

    alone, config = served(mine)[id(mine)]
    assert np.asarray(alone).shape == (3, 6) and config["batch_rows"] == [0, 3]
    for requests in ((mine, long, short), (short, long, mine)):
        got = served(*requests)
        assert got[id(mine)][0] == alone, requests.index(mine)
        # the envelope's rows are the jobs' order, not the pass's
        assert [got[id(request)][1]["batch_rows"] for request in requests] == [
            [sum(len(before["prompt_ids"]) for before in requests[:n]),
             len(request["prompt_ids"])]
            for n, request in enumerate(requests)]
        widths = got[id(mine)][1]["prefill_chunk_widths"]
        assert sum(widths.values()) > 1 and (len(widths) > 1) == (
            not pipe.by_blocks)  # Kimi's chunks have a width each
    # and every job its own ids, whoever else was in the pass
    assert got[id(long)][0] == served(long)[id(long)][0]


def test_one_prefill_program_serves_passes_of_any_lengths(monkeypatch):
    """ISSUE 43: a chunk's width is data. Two passes of one bucket whose
    rows differ in length run the one compiled program, each at its own
    widths, and the envelope's chunks, widths and calls are the device's:
    with eight dense rows a chunk all but one or two of the held experts
    have a pair in every call, so the tally's `active` tells the calls."""
    import jax

    from chiaswarm_tpu.pipelines import text_generation

    monkeypatch.setattr(text_generation, "PREFILL_CHUNK_TOKENS", 128)
    model = "test/tiny-kimi"
    pipe = text_generation.TextGenerationPipeline(
        model, allow_random_init=True)
    rng = np.random.default_rng(2)
    kinds = ("real", "padding", "skipped")

    def served(lengths):
        before = [text_generation.PREFILL_SLOTS.value(kind=kind, model=model)
                  for kind in kinds]
        ((_, config),) = pipe.run_batched(
            [{"prompt_ids": [rng.integers(0, 128, n).tolist()
                             for n in lengths], "rng": jax.random.key(0)}],
            max_new_tokens=2, temperature=0.0)
        return config, dict(zip(kinds, (
            text_generation.PREFILL_SLOTS.value(kind=kind, model=model) - was
            for kind, was in zip(kinds, before))))

    ragged = list(range(9, 17)) + [8, 7, 8, 7, 8, 7, 8, 7]
    first, slots = served(ragged)
    assert (first["padded_rows"], first["prompt_slots"]) == (16, 16)
    assert first["prefill_chunk_widths"] == {"16": 1, "8": 1}
    assert slots == {"real": sum(ragged), "skipped": 8 * 8,
                     "padding": 8 * 16 + 8 * 8 - sum(ragged)}
    program = pipe.prefill_program(16, 16, 16 + 2)
    assert program._cache_size() == 1
    programs = len(pipe._programs)
    second, slots = served([16] * 15 + [12])
    assert second["prefill_chunk_widths"] == {"16": 2}
    assert slots["skipped"] == 0
    third, slots = served([4, 3] * 4 + [1])  # seven rows only pad the pass
    assert third["prefill_chunk_widths"] == {"4": 2}
    assert third["prefill_chunks_skipped"] == 1
    assert slots["skipped"] == 16 * 16 - 9 * 4
    assert program._cache_size() == 1 and len(pipe._programs) == programs
    layers, held = pipe.config.expert_layers, pipe.config.experts_held[1]
    for config in (first, second, third):
        prefill = config["routing"]["prefill"]
        assert prefill["calls"] == config["prefill_chunks"] * layers == sum(
            config["prefill_chunk_widths"].values()) * layers
        assert prefill["active"] <= prefill["calls"] * held
    for config in (first, second):
        prefill = config["routing"]["prefill"]
        assert (prefill["calls"] - 1) * held < prefill["active"]
