"""Qwen3-Next's language model (ISSUE 42): Gated DeltaNet layers that keep a
recurrent state a row and no keys beside gated softmax attention, a gated
shared expert, zero-centred norms, on the CPU at the tiny preset (one
period L L L F, 4 value heads on 2 key heads of 8, a rotary on a quarter
of the head, 8 of 32 experts held), float32, seeded, against the plain
reference (benchmark/reference/gated_delta_moe.py, the DeltaNet as the
position-by-position recurrence):

(a) prefill + given tokens through state, tail and keys against the
    reference's one full forward pass, by logits, for rows of different
    lengths in one pass: lengths that are no multiple of the chunk,
    lengths of 1-3 (shorter than the convolution), a full bucket; whole
    rows a chunk, and a prompt prefilled in several spans;
(b) the chunk rule is the recurrence, at the state it leaves too; a padded
    slot and a padded row change no state and no tail;
(c) `gated_delta_step` interpreted is the recurrence, and a bfloat16 state
    fails the same limit;
(d) a row's ids do not depend on its batchmates, and the pipeline's gauge,
    envelope and kernel counter say what the readers read;
(e) the four shares of an expert layer add up to the uncut layer, the
    gated shared expert counted once; a forgotten `1 +` of a norm or a
    forgotten gate fails.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import gated_delta_moe as reference
from chiaswarm_tpu.models import experts, qwen3_next
from chiaswarm_tpu.ops import gated_delta_rule as rule
from chiaswarm_tpu.ops import platform
from chiaswarm_tpu.pipelines import text_generation
from chiaswarm_tpu.pipelines.text_generation import TextGenerationPipeline

CFG = qwen3_next.QWEN3_NEXT_TINY
SIZES = {key: getattr(CFG, key) for key in (
    "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "partial_rotary_factor", "rope_theta", "full_attention_interval",
    "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
    "linear_value_head_dim", "linear_conv_kernel_dim", "num_experts",
    "num_experts_per_tok", "rms_norm_eps")}
NAME = "test/tiny-qwen3-next"


def _remembering(params):
    """The seeded tree with decays that remember: the published init's
    `A = U(0, 16)` forgets a position within one or two, and a state
    handed over wrongly would then hide behind `exp(-10)`. Every other
    head's `A` is set a hundred times smaller."""
    def slower(path, leaf):
        if experts.leaf_name(path) != "A_log":
            return leaf
        return leaf.at[::2].add(-np.log(100.0))

    return jax.tree_util.tree_map_with_path(slower, params)


@pytest.fixture(scope="module")
def pipe():
    pipe = TextGenerationPipeline(NAME, allow_random_init=True)
    pipe.params = _remembering(pipe.params)
    return pipe


def test_the_tiny_preset_is_the_cut_in_small():
    full = qwen3_next.QWEN3_NEXT_80B_EP4
    assert (full.hidden_size, full.num_attention_heads,
            full.num_key_value_heads, full.head_dim, full.rotary_dim,
            full.linear_num_key_heads, full.linear_num_value_heads,
            full.linear_key_head_dim, full.linear_value_head_dim,
            full.linear_conv_kernel_dim, full.moe_intermediate_size,
            full.shared_expert_intermediate_size, full.num_experts,
            full.num_experts_per_tok, full.vocab_size, full.experts_held
            ) == (2048, 16, 2, 256, 64, 16, 32, 128, 128, 4, 512, 512, 512,
                  10, 37984, (0, 128))
    assert full.linear_layers == (True, True, True, False) * 2
    published = qwen3_next.Qwen3NextConfig()
    assert (published.num_hidden_layers, published.vocab_size,
            published.experts_held) == (48, 151936, (0, 512))
    shapes = qwen3_next.param_shapes(full, jnp.bfloat16)
    count = sum(int(np.prod(leaf.shape))
                for leaf in jax.tree_util.tree_leaves(shapes))
    assert round(count / 1e9, 3) == 3.667
    assert set(shapes["layers"][0]) == {
        "input_norm_offset", "post_norm_offset", "mixer", "moe"}
    assert set(shapes["layers"][3]) == {
        "input_norm_offset", "post_norm_offset", "attn", "moe"}
    assert set(shapes["layers"][0]["moe"]) == {
        "router", "experts", "shared", "shared_gate"}
    # the state a row: 32 matrices of [128, 128] float32 and a tail of
    # three inputs of 8192 channels, whatever the positions
    assert qwen3_next.state_row_bytes(full, 2) == 2097152 + 49152
    whole, rings, state = qwen3_next.cache_bytes(full, 256, 512, 2)
    assert (rings, state) == (0, 256 * 6 * 2146304)
    assert whole - state == 256 * 512 * 2 * 2048
    assert qwen3_next.cache_bytes(full, 256, 16384, 2)[2] == state
    # the cut in small: a period, two value heads a key head, a partial
    # rotary, more experts than held, every kind of leaf
    assert CFG.linear_layers == (True, True, True, False)
    assert CFG.linear_num_value_heads == 2 * CFG.linear_num_key_heads
    assert 0 < CFG.rotary_dim < CFG.head_dim
    assert CFG.experts_held[1] < CFG.num_experts
    assert CFG.scoring_func == full.scoring_func == "softmax"
    tiny = qwen3_next.param_shapes(CFG, jnp.float32)
    assert jax.tree_util.tree_structure(tiny["layers"]) == \
        jax.tree_util.tree_structure(shapes["layers"][:4])


def test_the_seeded_leaves_follow_their_rules():
    params = qwen3_next.init_params(
        dataclasses.replace(CFG, linear_num_value_heads=64,
                            linear_num_key_heads=32), jax.random.key(5),
        jnp.float32)
    mixer = params["layers"][0]["mixer"]
    a = np.exp(np.asarray(mixer["A_log"]))
    # `A` uniform over (0, 16): the published init
    assert 0 < a.min() < 2 and 14 < a.max() < 16 and 6 < a.mean() < 10
    assert np.all(np.asarray(mixer["dt_bias"]) == 1)
    assert np.all(np.asarray(mixer["norm"]) == 1)
    # a zero-centred norm's offset is seeded off zero
    offset = np.asarray(params["layers"][0]["input_norm_offset"])
    assert 0.05 < offset.std() < 0.2 and abs(offset.mean()) < 0.05
    assert mixer["A_log"].dtype == mixer["norm"].dtype == jnp.float32


def _served(pipe, prefill, ids, lengths, given, slots):
    """Logits of the last prompt position and of every given token's step,
    [rows, 1 + given, vocab], and the cache they leave."""
    rows = ids.shape[0]
    positions = slots + given.shape[1] + 1
    logits, cache, _ = prefill(pipe.params, ids, lengths)
    step = pipe.step_program(rows, slots, positions)
    out = [logits]
    for number in range(given.shape[1]):
        logits, cache = step(pipe.params, cache, given[:, number], lengths,
                             number)
        out.append(logits)
    return np.stack([np.asarray(x) for x in out], 1), cache


@pytest.mark.parametrize("lengths, slots, chunk", [
    # one pass, rows of every kind: shorter than the convolution (1-3),
    # no multiple of the 64-position chunk, one chunk and a position, a
    # full bucket; a row of padding (0)
    ([1, 2, 3, 5, 63, 64, 65, 100, 128, 129, 200, 0], 256, None),
    # a prompt prefilled in spans of 64 and of 128 positions: state, tail
    # and keys carried from span to span, rows ending inside a span
    ([1, 2, 3, 5, 63, 64, 65, 100, 128, 129, 200, 0], 256, (4, 64)),
    ([256, 130, 3, 77], 256, (1, 128)),
    # a bucket shorter than a chunk
    ([16, 1, 9, 3], 16, None),
], ids=["ragged", "spans_of_64", "spans_of_128", "short_bucket"])
def test_prefill_and_given_tokens_give_the_references_logits(
        pipe, lengths, slots, chunk):
    rng = np.random.default_rng(11)
    lengths = np.array(lengths, np.int32)
    rows, steps = len(lengths), 5
    ids = np.zeros((rows, slots), np.int32)
    for row, length in enumerate(lengths):
        ids[row, :length] = rng.integers(0, CFG.vocab_size, length)
    given = rng.integers(0, CFG.vocab_size, (rows, steps)).astype(np.int32)
    positions = slots + steps + 1
    prefill = pipe.prefill_program(rows, slots, positions) if chunk is None \
        else jax.jit(lambda p, i, n: qwen3_next.prefill(
            p, CFG, i, n, positions, *chunk))
    got, _ = _served(pipe, prefill, ids, lengths, given, slots)
    real = np.flatnonzero(lengths)
    want = reference.forward_rows(
        pipe.params, SIZES,
        [np.concatenate([ids[row, :lengths[row]], given[row]])
         for row in real], held=CFG.experts_held,
        positions=[np.arange(lengths[row] - 1, lengths[row] + steps)
                   for row in real])
    for row, logits in zip(real, want):
        logits = np.asarray(logits)
        # float32 on both sides: what differs is the order of the sums
        # (the chunk form against the recurrence, a softmax in blocks); a
        # row whose top-4 of 32 is a near tie reads a few 1e-5
        assert np.linalg.norm(got[row] - logits) / np.linalg.norm(
            logits) < 1e-4, (row, lengths[row])


def test_a_prompt_in_spans_leaves_the_cache_of_one_prefilled_whole(pipe):
    rng = np.random.default_rng(3)
    lengths = np.array([256, 130, 3, 77], np.int32)
    ids = np.zeros((4, 256), np.int32)
    for row, length in enumerate(lengths):
        ids[row, :length] = rng.integers(0, CFG.vocab_size, length)
    whole, spans = (jax.jit(lambda p, i, n, chunk=chunk: qwen3_next.prefill(
        p, CFG, i, n, 260, *chunk))(pipe.params, ids, lengths)
        for chunk in ((4, 256), (1, 64)))
    # the same pairs on the same experts (the fullest expert's pairs are
    # summed a call, and the calls differ)
    assert np.array_equal(np.asarray(whole[2][0]), np.asarray(spans[2][0]))
    assert int(whole[2][1][0]) == int(spans[2][1][0]) == int(
        lengths.sum()) * CFG.num_experts_per_tok * CFG.expert_layers
    np.testing.assert_allclose(np.asarray(whole[0]), np.asarray(spans[0]),
                               atol=2e-5)
    real = (np.arange(260)[None, :] < lengths[:, None])[..., None, None]
    for linear, one, other in zip(CFG.linear_layers, whole[1], spans[1]):
        for mine, theirs in zip(one, other):
            # the same sums in another order, float32: a few 1e-5 of an
            # entry of size 1. A full layer's columns past a row's length
            # hold whatever the padding gave, and are shown to nobody
            mine, theirs = np.asarray(mine), np.asarray(theirs)
            np.testing.assert_allclose(
                mine if linear else np.where(real, mine, 0),
                theirs if linear else np.where(real, theirs, 0),
                rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("order", ["ordered", "shuffled"])
def test_chunks_as_wide_as_their_rows_leave_what_one_wide_chunk_leaves(
        pipe, monkeypatch, order):
    """ISSUE 43: two rows a chunk at most, taken by `prefill_by_length`
    (this model offers the bucket's width alone), rows that only pad the
    pass not run: the logits, state and tail at each row's own length, a
    full layer's keys and values (the cache started from NaN: an element
    no chunk wrote would show) and the pairs of the same rows in one chunk
    of all eight."""
    rng = np.random.default_rng(5)
    lengths = np.array([256, 130, 128, 65, 64, 3, 0, 0], np.int32)
    if order == "shuffled":
        lengths = lengths[rng.permutation(len(lengths))]
    rows, slots, positions = len(lengths), 256, 260
    ids = np.zeros((rows, slots), np.int32)
    for row, length in enumerate(lengths):
        ids[row, :length] = rng.integers(0, CFG.vocab_size, length)
    assert qwen3_next.prefill_widths(slots) == (256,)
    made = qwen3_next.new_cache
    monkeypatch.setattr(
        qwen3_next, "new_cache", lambda cfg, rows, positions, dtype:
        jax.tree_util.tree_map(lambda x: x + jnp.nan,
                               made(cfg, rows, positions, dtype))
        if positions else made(cfg, rows, positions, dtype))
    logits, cache, load = jax.jit(lambda p, i, n: qwen3_next.prefill(
        p, CFG, i, n, positions, 2))(pipe.params, ids, lengths)
    last, entries, told = jax.jit(lambda p, i, n: qwen3_next.prefill_rows(
        p, CFG, i, n, slots, qwen3_next.empty_load(CFG)))(
            pipe.params, ids, lengths)
    real = lengths > 0
    np.testing.assert_allclose(
        np.asarray(logits)[real],
        np.asarray(experts.logits_of(pipe.params, CFG, last))[real],
        atol=5e-5)
    seen = (np.arange(slots)[None, :] < lengths[:, None])[..., None, None]
    for linear, layer, written in zip(CFG.linear_layers, cache, entries):
        for mine, entry in zip(layer, written):
            mine, entry = np.asarray(mine), np.asarray(entry)
            assert np.isfinite(mine).all()
            if linear:
                # a row of padding leaves zeros
                assert not mine[~real].any()
                np.testing.assert_allclose(mine[real], entry[real],
                                           rtol=1e-4, atol=2e-5)
            else:
                assert not mine[:, slots:].any()
                np.testing.assert_allclose(
                    np.where(seen, mine[:, :slots], 0),
                    np.where(seen, entry, 0), rtol=1e-4, atol=2e-5)
    assert np.array_equal(np.asarray(load[0]), np.asarray(told[0]))
    assert int(load[1][0]) == int(told[1][0]) == int(
        lengths.sum()) * CFG.num_experts_per_tok * CFG.expert_layers


def test_padded_slots_and_padded_rows_change_no_state(pipe):
    """The state and tail a row leaves are those at its own last id: the
    same row alone in a bucket as long as itself leaves them too. A row
    of padding leaves zeros."""
    rng = np.random.default_rng(4)
    row = rng.integers(0, CFG.vocab_size, 64).astype(np.int32)
    ids = np.zeros((2, 256), np.int32)
    ids[0, :64] = row
    ids[:, 64:] = rng.integers(0, CFG.vocab_size, (2, 192))  # never read
    padded = jax.jit(lambda p, i, n: qwen3_next.prefill(
        p, CFG, i, n, 260, 2))(pipe.params, ids, np.array([64, 0], np.int32))
    alone = jax.jit(lambda p, i, n: qwen3_next.prefill(
        p, CFG, i, n, 68, 1))(pipe.params, row[None], np.array([64], np.int32))
    for layer, (a, b) in enumerate(zip(padded[1], alone[1])):
        if not CFG.linear_layers[layer]:
            continue
        for mine, theirs in zip(a, b):
            np.testing.assert_allclose(np.asarray(mine[0]),
                                       np.asarray(theirs[0]), atol=1e-6)
            assert not np.asarray(mine[1]).any()
    np.testing.assert_allclose(np.asarray(padded[0][0]),
                               np.asarray(alone[0][0]), atol=2e-5)


def _operands(key, rows, slots, heads=4, keys=8, values=8, decay=0.3):
    ks = jax.random.split(key, 5)

    def unit(key):
        x = jax.random.normal(key, (rows, slots, heads, keys))
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    return (unit(ks[0]) * keys ** -0.5, unit(ks[1]),
            jax.random.normal(ks[2], (rows, slots, heads, values)),
            -jax.random.uniform(ks[3], (rows, slots, heads), maxval=decay),
            jax.random.uniform(ks[4], (rows, slots, heads)))


@pytest.mark.parametrize("slots, chunk, decay", [
    (64, 64, 0.3), (200, 64, 0.3), (37, 8, 0.3), (128, 64, 12.0),
], ids=["a_chunk", "ragged_chunks", "small_chunks", "published_decays"])
def test_the_chunk_rule_is_the_recurrence(slots, chunk, decay):
    rows = 3
    operands = _operands(jax.random.key(slots), rows, slots, decay=decay)
    lengths = jnp.array([slots, slots // 3, 1])
    start = jax.random.normal(jax.random.key(1), (rows, 4, 8, 8))
    got, state = rule.gated_delta_chunks(*operands, lengths, start,
                                         chunk=chunk)

    def recurrence(state, xs):
        o, moved = rule.step_reference(*xs[:-1], state)
        real = xs[-1][:, None, None, None]
        return jnp.where(real, moved, state), o

    real = jnp.arange(slots)[None, :] < lengths[:, None]
    want_state, want = jax.lax.scan(
        recurrence, start,
        tuple(jnp.moveaxis(x, 1, 0) for x in (*operands, real)))
    want = jnp.moveaxis(want, 0, 1)
    # float32 both: the chunk form sums a chunk's positions at once
    assert float(jnp.max(jnp.where(real[..., None, None],
                                   jnp.abs(got - want), 0))) < 2e-5
    assert float(jnp.max(jnp.abs(state - want_state))) < 2e-5


def test_the_step_kernel_interpreted_is_the_recurrence():
    """Five positions one after another, state handed on: the kernel's
    float32 state reads rounding; a state kept in bfloat16 fails the same
    limit by two orders."""
    rows, slots, heads, keys, values = 3, 5, 4, 8, 128
    operands = _operands(jax.random.key(8), rows, slots, heads, keys, values,
                         decay=0.1)
    zero = jnp.zeros((rows, heads, keys, values))

    def run(step, state_dtype=None):
        state, out = zero, []
        for t in range(slots):
            o, state = step(*(x[:, t] for x in operands), state)
            if state_dtype is not None:
                state = state.astype(state_dtype).astype(jnp.float32)
            out.append(o)
        return jnp.stack(out, 1), state

    want, want_state = run(rule.step_reference)
    traced = platform.KERNEL_TRACES.value(op="gated_delta_step",
                                          path="pallas")
    got, state = run(lambda *xs: rule.gated_delta_step(*xs, interpret=True))
    assert platform.KERNEL_TRACES.value(
        op="gated_delta_step", path="pallas") == traced + slots
    limit = 1e-5
    assert float(jnp.max(jnp.abs(got - want))) < limit
    assert float(jnp.max(jnp.abs(state - want_state))) < limit
    rounded, _ = run(rule.step_reference, jnp.bfloat16)
    assert float(jnp.max(jnp.abs(rounded - want))) > 100 * limit
    # the same against the reference file's own scan
    np.testing.assert_allclose(
        np.asarray(reference.delta_rule(*operands)), np.asarray(want),
        atol=limit)


def test_a_rows_ids_do_not_depend_on_its_batchmates_and_the_pass_says_what_it_cached(pipe):
    rng = np.random.default_rng(9)
    mine = [rng.integers(0, CFG.vocab_size, n).tolist() for n in (5, 70)]
    others = [rng.integers(0, CFG.vocab_size, n).tolist()
              for n in (1, 33, 128)]
    key = jax.random.key(42)
    alone = pipe.run_batched([{"prompt_ids": mine, "rng": key}],
                             max_new_tokens=6)
    among = pipe.run_batched(
        [{"prompt_ids": others, "rng": jax.random.key(7)},
         {"prompt_ids": mine, "rng": key}], max_new_tokens=6)
    assert np.array_equal(alone[0][0], among[1][0])
    envelope = among[1][1]
    rows, positions = 8, 128 + 6
    whole, rings, state = qwen3_next.cache_bytes(CFG, rows, positions, 4)
    assert (envelope["cache_bytes"], envelope["cache_bytes_window"],
            envelope["cache_bytes_state"]) == (whole, rings, state)
    assert state == rows * 3 * (4 * 4 * 8 * 8 + 4 * 3 * 64) and state < whole
    assert text_generation.PASS_STATE_BYTES.value(model=NAME) == state
    assert text_generation.PASS_CACHE_BYTES.value(model=NAME) == whole
    assert text_generation.PASS_WINDOW_CACHE_BYTES.value(model=NAME) == 0
    # the decode on this platform took the recurrence in `jax.numpy`
    assert platform.KERNEL_TRACES.value(op="gated_delta_step",
                                        path="reference") > 0
    # a family that keeps no state says so
    kimi = TextGenerationPipeline("test/tiny-kimi", allow_random_init=True)
    assert kimi.cache_bytes(4, 32)[2] == 0
    with pytest.raises(ValueError, match="denoising_steps"):
        pipe.run_batched([{"prompt_ids": mine, "rng": key}],
                         max_new_tokens=2, denoising_steps=2)


def test_the_four_shares_add_up_to_the_uncut_layer(pipe):
    """Four chips of eight experts each: their routed parts summed, the
    gated shared expert counted once, are the reference's layer with all
    32 experts."""
    moe = pipe.params["layers"][1]["moe"]
    h = jax.random.normal(jax.random.key(3), (24, CFG.hidden_size))
    stacks = {name: jax.random.normal(
        jax.random.key(10 + n), (32, *moe["experts"][name].shape[1:]))
        / np.sqrt(moe["experts"][name].shape[1])
        for n, name in enumerate(("gate", "up", "down"))}
    gate = jax.nn.sigmoid(h @ moe["shared_gate"])
    shared = np.asarray(gate * experts.swiglu(moe["shared"], h))
    total = np.zeros_like(shared)
    for share in range(4):
        cfg = dataclasses.replace(CFG, experts_held=(8 * share, 8))
        mine = dict(moe, experts={name: stack[8 * share:8 * share + 8]
                                  for name, stack in stacks.items()})
        out, _ = experts.expert_layer(mine, cfg, h)
        total += np.asarray(out) - shared
    want = np.asarray(reference.experts(
        dict(moe, experts=stacks), SIZES, h, (0, 32)))
    np.testing.assert_allclose(total + shared, want, atol=2e-5)
    # the gate is no plain shared expert's, and the tree without the leaf
    # is the other families' layer
    plain, _ = experts.expert_layer(
        {k: v for k, v in moe.items() if k != "shared_gate"}, CFG, h)
    gated, _ = experts.expert_layer(moe, CFG, h)
    np.testing.assert_allclose(
        np.asarray(plain - gated),
        np.asarray((1 - gate) * experts.swiglu(moe["shared"], h)), atol=2e-5)
    assert float(jnp.max(jnp.abs(plain - gated))) > 0.01


def test_a_zero_centred_norm_multiplies_by_one_plus_its_weight():
    x = jax.random.normal(jax.random.key(0), (5, 64))
    offset = 0.1 * jax.random.normal(jax.random.key(1), (64,))
    got = experts.rms_norm(x, offset, 1e-6, zero_centred=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(reference.norm(x, offset, 1e-6)),
        atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(experts.rms_norm(x, 1 + offset, 1e-6)),
        atol=1e-6)
    # a forgotten `1 +` is nowhere near
    assert float(jnp.max(jnp.abs(
        got - experts.rms_norm(x, offset, 1e-6)))) > 0.5


def test_jobs_go_through_hive_worker_and_pipeline(sdaas_root, monkeypatch):
    """Two jobs of ragged rows by the family's wire name and by the
    model's name alone: one gang, one pass, the spans and the envelope of
    any text family, the state's bytes beside the cache's."""
    import asyncio
    import json

    from chiaswarm_tpu import worker as worker_module
    from chiaswarm_tpu.hive_server.harness import LocalSwarm
    from chiaswarm_tpu.settings import Settings

    monkeypatch.setattr(worker_module, "POLL_SECONDS", 0.1)
    rng = np.random.default_rng(2)

    def job(number, **extra):
        return {"id": f"qwen-{number}", "workflow": "txt2txt",
                "model_name": NAME, "max_new_tokens": 5, "seed": number,
                "prompt_ids": [rng.integers(0, CFG.vocab_size, n).tolist()
                               for n in (1, 3, 20)], **extra}

    async def scenario():
        swarm = LocalSwarm(n_workers=0, settings=Settings(
            sdaas_token="t", worker_name="w", hive_port=0, metrics_port=0))
        await swarm.start()
        try:
            ids = [await swarm.submit(job(0)), await swarm.submit(job(
                1, parameters={"pipeline_type": "Qwen3NextForCausalLM"}))]
            swarm.add_worker("text-worker")
            done = [await swarm.wait_done(i, timeout=300) for i in ids]
            return done, [await swarm.artifact(
                status["result"]["artifacts"]["primary"]["href"])
                for status in done]
        finally:
            await swarm.stop()

    done, blobs = asyncio.run(scenario())
    assert all(status["status"] == "done" and status["attempts"] == 1
               for status in done)
    configs = [status["result"]["pipeline_config"] for status in done]
    assert len({config["trace"]["gang"]["id"] for config in configs}) == 1
    for config, blob in zip(configs, blobs):
        assert {"pass", "prefill", "decode", "readback"} <= {
            span["name"] for span in config["spans"]}
        assert (config["pass_rows"], config["prompt_slots"],
                config["decode_steps"]) == (6, 32, 4)
        assert 0 < config["cache_bytes_state"] < config["cache_bytes"]
        assert config["cache_bytes_window"] == 0
        assert 0 < config["routing"]["pairs"] < config["routing"]["routed"]
        rows = json.loads(blob)["token_ids"]
        assert len(rows) == 3 and all(len(row) == 5 for row in rows)
        assert all(0 <= i < CFG.vocab_size for row in rows for i in row)
