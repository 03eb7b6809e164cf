"""Falcon-H1's language model (ISSUE 46): in every layer a Mamba-2
state-space mixer that keeps a recurrent state a row AND softmax attention
that keeps keys, on one normed input, behind a dense SwiGLU and fourteen
multipliers, on the CPU at the tiny preset (2 layers, 4 state-space heads
in 2 groups, state 16 x head 8, 4 query heads on 2 key heads of 16),
float32, seeded, against the plain reference
(benchmark/reference/ssd_hybrid.py, the mixer as the position-by-position
recurrence):

(a) `ssd_step` interpreted is the recurrence, and a bfloat16 state is not;
    `ssd_chunks` is the recurrence at ragged lengths, from a state that is
    not zero, across a span's boundary, and rows of no length leave state
    and tail as they were;
(b) prefill + given tokens through state, tail and keys against the
    reference's one full forward pass, by logits, whole rows a chunk and a
    prompt prefilled in spans; a cache started from NaN;
(c) each of the fourteen multipliers is in the program;
(d) a dense family's pass has an empty tally, an envelope whose `routing`
    reads 0, and says what it cached.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import ssd_hybrid as reference
from chiaswarm_tpu.models import experts, falcon_h1
from chiaswarm_tpu.ops import platform, ssd
from chiaswarm_tpu.pipelines import text_generation
from chiaswarm_tpu.pipelines.text_generation import TextGenerationPipeline

CFG = falcon_h1.FALCON_H1_TINY
NAME = "test/tiny-falcon-h1"
MULTIPLIERS = (
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers")


def _sizes(cfg):
    return dataclasses.asdict(cfg)


def _telling(params):
    """The seeded tree with what would hide a fault taken away: every
    other head's `A` a hundred times smaller (the seeded `A = U(0, 16)`
    forgets a position within one or two, and a state handed over wrongly
    would hide behind `exp(-10)`), and every norm's weight, seeded at one,
    moved off it a channel (a weight read at another channel or another
    group would pass at one)."""
    def told(path, leaf):
        name = experts.leaf_name(path)
        if name == "A_log":
            return leaf.at[::2].add(-np.log(100.0))
        if name.endswith("norm"):
            return leaf * (1.0 + 0.2 * jnp.sin(
                jnp.arange(leaf.size, dtype=jnp.float32)).astype(leaf.dtype))
        return leaf

    return jax.tree_util.tree_map_with_path(told, params)


@pytest.fixture(scope="module")
def pipe():
    pipe = TextGenerationPipeline(NAME, allow_random_init=True)
    pipe.params = _telling(pipe.params)
    return pipe


def test_the_cut_is_the_published_widths_and_the_tiny_preset_is_it_in_small():
    full = falcon_h1.FALCON_H1_34B_PP18
    published = falcon_h1.FalconH1Config()
    assert dataclasses.replace(full, num_hidden_layers=72) == published
    assert (full.hidden_size, full.num_attention_heads,
            full.num_key_value_heads, full.head_dim, full.intermediate_size,
            full.mamba_d_ssm, full.mamba_n_heads, full.mamba_d_head,
            full.mamba_n_groups, full.mamba_d_state, full.mamba_d_conv,
            full.mamba_chunk_size, full.vocab_size, full.num_hidden_layers
            ) == (5120, 20, 4, 128, 21504, 4096, 32, 128, 2, 256, 4, 128,
                  261120, 4)
    assert full.mamba_n_heads * full.mamba_d_head == full.mamba_d_ssm
    assert (full.conv_width, full.expert_layers, full.experts_held) == (
        5120, 0, (0, 0))
    shapes = falcon_h1.param_shapes(full, jnp.bfloat16)

    def count(tree):
        return sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree_util.tree_leaves(tree))

    layer = shapes["layers"][0]
    assert (count(layer["mixer"]), count(layer["attn"]),
            count(layer["mlp"]) + 2 * 5120) == (
                68351072, 31457280, 330311680)
    assert count(layer) == 430120032 and count(shapes) == 4394354048
    # the in-projection is the published 9248 columns, in two leaves
    mixer = layer["mixer"]
    assert mixer["zxbc"].shape[1] + mixer["dt"].shape[1] == 9248
    # a row: 32 matrices of [256, 128] float32 and a tail of three inputs
    # of 5120 channels a layer whatever the positions, 2048 B of keys and
    # values a layer a position
    assert falcon_h1.state_row_bytes(full, 2) == 4194304 + 30720
    whole, rings, state = falcon_h1.cache_bytes(full, 256, 512, 2)
    assert (rings, state) == (0, 256 * 16900096)
    assert whole - state == 256 * 512 * 8192
    assert falcon_h1.cache_bytes(full, 256, 16384, 2)[2] == state
    # the cut in small: every kind of leaf, groups of several heads,
    # query heads that share a key head, and no multiplier at one
    tiny = falcon_h1.param_shapes(CFG, jnp.float32)
    assert jax.tree_util.tree_structure(tiny["layers"][0]) == \
        jax.tree_util.tree_structure(layer)
    assert CFG.mamba_n_heads > CFG.mamba_n_groups > 1
    assert CFG.num_attention_heads > CFG.num_key_value_heads > 1
    flat = [value for name in MULTIPLIERS for value in (
        getattr(CFG, name) if isinstance(getattr(CFG, name), tuple)
        else (getattr(CFG, name),))]
    assert len(flat) == 14 and all(value != 1 for value in flat)


def test_the_seeded_leaves_follow_their_rules():
    params = falcon_h1.init_params(
        dataclasses.replace(CFG, mamba_n_heads=64, mamba_d_ssm=512),
        jax.random.key(5), jnp.float32)
    mixer = params["layers"][0]["mixer"]
    a = np.exp(np.asarray(mixer["A_log"]))
    assert 0 < a.min() < 2 and 14 < a.max() < 16 and 6 < a.mean() < 10
    assert np.all(np.asarray(mixer["dt_bias"]) == 1)
    assert np.all(np.asarray(mixer["norm"]) == 1)
    # the skip is seeded about one and apart a head, the bias about zero
    skip, bias = np.asarray(mixer["D"]), np.asarray(mixer["conv_bias"])
    assert 0.05 < skip.std() < 0.2 and abs(skip.mean() - 1) < 0.05
    assert 0.05 < bias.std() < 0.2 and abs(bias.mean()) < 0.05
    assert (mixer["A_log"].dtype == mixer["D"].dtype == mixer["norm"].dtype
            == jnp.float32)


# --- (a) the recurrence's two forms ------------------------------------------


def _operands(key, rows, slots, heads=4, size=16, dim=8, groups=2, rate=0.3):
    ks = jax.random.split(key, 6)
    return (jax.random.normal(ks[0], (rows, slots, heads, dim)),
            jax.random.uniform(ks[1], (rows, slots, heads), minval=0.1,
                               maxval=1.0),
            -jax.random.uniform(ks[2], (heads,), minval=0.01, maxval=rate),
            jax.random.normal(ks[3], (rows, slots, groups, size)),
            jax.random.normal(ks[4], (rows, slots, groups, size)),
            1.0 + 0.1 * jax.random.normal(ks[5], (heads,)))


def _recurrence(x, dt, a, b, c, d, lengths, state, first=0):
    """`ssd.step_reference` a position after another; a position at or
    past a row's length changes nothing."""
    def position(state, xs):
        x, dt, b, c, real = xs
        y, moved = ssd.step_reference(x, dt, a, b, c, d, state)
        return jnp.where(real[:, None, None, None], moved, state), y

    real = first + jnp.arange(x.shape[1])[None, :] < lengths[:, None]
    state, y = jax.lax.scan(position, state, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c, real)))
    return jnp.moveaxis(y, 0, 1), state, real


@pytest.mark.parametrize("slots, chunk, rate", [
    (8, 8, 0.3), (37, 8, 0.3), (200, 128, 0.3), (64, 16, 16.0),
], ids=["a_chunk", "ragged_chunks", "published_chunk", "seeded_rates"])
def test_the_chunk_form_is_the_recurrence(slots, chunk, rate):
    """Ragged lengths, a row of length 0, from a state that is not zero:
    the outputs at every real position and the state each row leaves."""
    rows = 4
    operands = _operands(jax.random.key(slots), rows, slots, rate=rate)
    lengths = jnp.array([slots, slots // 3, 1, 0])
    start = jax.random.normal(jax.random.key(1), (rows, 4, 16, 8))
    got, state = ssd.ssd_chunks(*operands, lengths, start, chunk=chunk)
    want, want_state, real = _recurrence(*operands, lengths, start)
    # float32 both: the chunk form sums a chunk's positions at once
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.where(real[..., None, None],
                                   jnp.abs(got - want), 0))) < 2e-6 * scale
    assert float(jnp.max(jnp.abs(state - want_state))) < 1e-5
    # the row of no length keeps the state it came with, to the bit
    assert np.array_equal(np.asarray(state[3]), np.asarray(start[3]))


def test_the_chunk_form_carries_its_state_across_a_spans_boundary():
    rows, slots, cut = 3, 40, 24
    operands = _operands(jax.random.key(2), rows, slots)
    x, dt, a, b, c, d = operands
    lengths = jnp.array([40, 30, 7])
    start = jax.random.normal(jax.random.key(3), (rows, 4, 16, 8))
    whole, whole_state = ssd.ssd_chunks(*operands, lengths, start, chunk=8)
    first, state = ssd.ssd_chunks(x[:, :cut], dt[:, :cut], a, b[:, :cut],
                                  c[:, :cut], d, lengths, start, 0, 8)
    # the third row ended in the first span: the second leaves it alone
    kept = np.asarray(state[2])
    second, state = ssd.ssd_chunks(x[:, cut:], dt[:, cut:], a, b[:, cut:],
                                   c[:, cut:], d, lengths, state, cut, 8)
    assert np.array_equal(np.asarray(state[2]), kept)
    real = (jnp.arange(slots)[None, :] < lengths[:, None])[..., None, None]
    got = jnp.concatenate([first, second], 1)
    assert float(jnp.max(jnp.where(real, jnp.abs(got - whole), 0))) < 2e-5
    assert float(jnp.max(jnp.abs(state - whole_state))) < 1e-5


def test_the_step_kernel_interpreted_is_the_recurrence():
    """Five positions one after another, state handed on: the kernel's
    float32 state reads rounding; a state kept in bfloat16 fails the same
    limit by two orders."""
    rows, slots, heads, size, dim, groups = 3, 5, 4, 16, 128, 2
    operands = _operands(jax.random.key(8), rows, slots, heads, size, dim,
                         groups, rate=0.1)
    x, dt, a, b, c, d = operands
    zero = jnp.zeros((rows, heads, size, dim))

    def run(step, state_dtype=None):
        state, out = zero, []
        for t in range(slots):
            y, state = step(x[:, t], dt[:, t], a, b[:, t], c[:, t], d, state)
            if state_dtype is not None:
                state = state.astype(state_dtype).astype(jnp.float32)
            out.append(y)
        return jnp.stack(out, 1), state

    want, want_state = run(ssd.step_reference)
    traced = platform.KERNEL_TRACES.value(op="ssd_step", path="pallas")
    got, state = run(lambda *xs: ssd.ssd_step(*xs, interpret=True))
    assert platform.KERNEL_TRACES.value(
        op="ssd_step", path="pallas") == traced + slots
    limit = 2e-5
    assert float(jnp.max(jnp.abs(got - want))) < limit
    assert float(jnp.max(jnp.abs(state - want_state))) < limit
    rounded, _ = run(ssd.step_reference, jnp.bfloat16)
    assert float(jnp.max(jnp.abs(rounded - want))) > 100 * limit
    # the same against the reference file's own scan, which keeps a
    # head's matrix the other way round
    np.testing.assert_allclose(
        np.asarray(reference.recurrence(*operands)), np.asarray(want),
        atol=limit)
    # and the chunk form is the same function
    chunks, chunk_state = ssd.ssd_chunks(
        *operands, jnp.full((rows,), slots), zero, chunk=4)
    assert float(jnp.max(jnp.abs(chunks - want))) < limit
    assert float(jnp.max(jnp.abs(chunk_state - want_state))) < limit


def test_the_convolution_of_a_span_is_the_convolution_of_its_steps():
    rows, slots, channels = 2, 9, 12
    ks = jax.random.split(jax.random.key(4), 4)
    x = jax.random.normal(ks[0], (rows, slots, channels))
    tail = jax.random.normal(ks[1], (rows, 3, channels))
    weight = jax.random.normal(ks[2], (4, channels))
    bias = jax.random.normal(ks[3], (channels,))
    whole, behind = ssd.causal_conv(x, tail, weight, bias)
    assert np.array_equal(np.asarray(behind[:, -3:]), np.asarray(x[:, -3:]))
    out = []
    for t in range(slots):
        mixed, behind = ssd.causal_conv(x[:, t:t + 1], tail, weight, bias)
        tail = behind[:, 1:]
        out.append(mixed)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(out, 1)),
                               np.asarray(whole), atol=1e-6)


# --- (b) the network against the plain reference -----------------------------


def _served(pipe, prefill, ids, lengths, given, slots):
    """Logits of the last prompt position and of every given token's step,
    [rows, 1 + given, vocab], and the cache they leave."""
    rows = ids.shape[0]
    positions = slots + given.shape[1] + 1
    logits, cache, load = prefill(pipe.params, ids, lengths)
    assert load[0].shape == (0, 0) and not np.asarray(load[1]).any()
    step = pipe.step_program(rows, slots, positions)
    out = [logits]
    for number in range(given.shape[1]):
        logits, cache = step(pipe.params, cache, given[:, number], lengths,
                             number)
        out.append(logits)
    return np.stack([np.asarray(x) for x in out], 1), cache


def _pass(lengths, slots, steps=5, seed=11):
    rng = np.random.default_rng(seed)
    lengths = np.array(lengths, np.int32)
    ids = np.zeros((len(lengths), slots), np.int32)
    for row, length in enumerate(lengths):
        ids[row, :length] = rng.integers(0, CFG.vocab_size, length)
    given = rng.integers(0, CFG.vocab_size, (len(lengths), steps)).astype(
        np.int32)
    return ids, lengths, given


def _wanted(params, cfg, ids, lengths, given):
    real = np.flatnonzero(lengths)
    steps = given.shape[1]
    return real, reference.forward_rows(
        params, _sizes(cfg),
        [np.concatenate([ids[row, :lengths[row]], given[row]])
         for row in real],
        positions=[np.arange(lengths[row] - 1, lengths[row] + steps)
                   for row in real])


@pytest.mark.parametrize("lengths, slots, chunk", [
    # one pass, rows of every kind: shorter than the convolution (1-3),
    # no multiple of the 8-position chunk, a chunk and a position, a full
    # bucket; a row of padding (0)
    ([1, 2, 3, 5, 7, 8, 9, 20, 32, 33, 64, 0], 64, None),
    # a prompt prefilled in spans of 16 and of 32 positions: state, tail
    # and keys carried from span to span, rows ending inside a span
    ([1, 2, 3, 5, 7, 8, 9, 20, 32, 33, 64, 0], 64, (4, 16)),
    ([64, 33, 3, 20], 64, (1, 32)),
    # a bucket of two chunks
    ([16, 1, 9, 3], 16, None),
], ids=["ragged", "spans_of_16", "spans_of_32", "short_bucket"])
def test_prefill_and_given_tokens_give_the_references_logits(
        pipe, lengths, slots, chunk):
    ids, lengths, given = _pass(lengths, slots)
    positions = slots + given.shape[1] + 1
    prefill = pipe.prefill_program(len(lengths), slots, positions) \
        if chunk is None else jax.jit(lambda p, i, n: falcon_h1.prefill(
            p, CFG, i, n, positions, *chunk))
    got, _ = _served(pipe, prefill, ids, lengths, given, slots)
    real, want = _wanted(pipe.params, CFG, ids, lengths, given)
    for row, logits in zip(real, want):
        logits = np.asarray(logits)
        # float32 on both sides: what differs is the order of the sums
        # (the chunk form against the recurrence, a softmax in blocks):
        # 2e-7 to 4e-7 here, and the network has no discontinuity
        assert np.linalg.norm(got[row] - logits) / np.linalg.norm(
            logits) < 5e-6, (row, lengths[row])


def test_a_prompt_in_spans_leaves_the_cache_of_one_prefilled_whole(pipe):
    ids, lengths, _ = _pass([64, 33, 3, 20], 64, seed=3)
    whole, spans = (jax.jit(lambda p, i, n, chunk=chunk: falcon_h1.prefill(
        p, CFG, i, n, 70, *chunk))(pipe.params, ids, lengths)
        for chunk in ((4, 64), (1, 16)))
    assert falcon_h1.POSITION_CHUNKS
    np.testing.assert_allclose(np.asarray(whole[0]), np.asarray(spans[0]),
                               atol=2e-5)
    real = (np.arange(70)[None, :] < lengths[:, None])[..., None, None]
    for one, other in zip(whole[1], spans[1]):
        for kind, (mine, theirs) in enumerate(zip(one, other)):
            # state and tail whole; keys' and values' columns past a row's
            # length hold whatever the padding gave, and are shown to
            # nobody
            mine, theirs = np.asarray(mine), np.asarray(theirs)
            np.testing.assert_allclose(
                mine if kind < 2 else np.where(real, mine, 0),
                theirs if kind < 2 else np.where(real, theirs, 0),
                rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("order", ["ordered", "shuffled"])
def test_a_cache_started_from_nan_is_written_wherever_it_is_read(
        pipe, monkeypatch, order):
    """PERF.md section 7, "A loop's carried zeros": two rows a chunk at
    most, taken by `prefill_by_length`, rows that only pad the pass not
    run, the cache started from NaN: an element no chunk wrote would show.
    State and tail at each row's own length, zeros for a row of padding;
    every column of keys and values finite, those past the prompt's slots
    zero; and a decode step from that cache is the reference's."""
    ids, lengths, given = _pass([64, 33, 32, 17, 16, 3, 0, 0], 64, steps=2,
                                seed=5)
    if order == "shuffled":
        mixed = np.random.default_rng(5).permutation(len(lengths))
        ids, lengths, given = ids[mixed], lengths[mixed], given[mixed]
    rows, slots, positions = len(lengths), 64, 67
    made = falcon_h1.new_cache
    monkeypatch.setattr(
        falcon_h1, "new_cache", lambda cfg, rows, positions, dtype:
        jax.tree_util.tree_map(lambda x: x + jnp.nan,
                               made(cfg, rows, positions, dtype))
        if positions else made(cfg, rows, positions, dtype))
    prefill = jax.jit(lambda p, i, n: falcon_h1.prefill(
        p, CFG, i, n, positions, 2))
    logits, cache, _ = prefill(pipe.params, ids, lengths)
    last, entries = jax.jit(lambda p, i, n: falcon_h1.prefill_rows(
        p, CFG, i, n, slots))(pipe.params, ids, lengths)
    real = lengths > 0
    seen = (np.arange(slots)[None, :] < lengths[:, None])[..., None, None]
    for layer, written in zip(cache, entries):
        for kind, (mine, entry) in enumerate(zip(layer, written)):
            mine, entry = np.asarray(mine), np.asarray(entry)
            assert np.isfinite(mine).all()
            if kind < 2:
                assert not mine[~real].any()
                np.testing.assert_allclose(mine[real], entry[real],
                                           rtol=1e-4, atol=2e-5)
            else:
                assert not mine[:, slots:].any()
                np.testing.assert_allclose(
                    np.where(seen, mine[:, :slots], 0),
                    np.where(seen, entry, 0), rtol=1e-4, atol=2e-5)
    got, _ = _served(pipe, prefill, ids, lengths, given, slots)
    rows_, want = _wanted(pipe.params, CFG, ids, lengths, given)
    for row, wanted in zip(rows_, want):
        wanted = np.asarray(wanted)
        assert np.linalg.norm(got[row] - wanted) / np.linalg.norm(
            wanted) < 5e-6, (row, lengths[row])


def test_padded_slots_and_padded_rows_change_no_state(pipe):
    """The state and tail a row leaves are those at its own last id: the
    same row alone in a bucket as long as itself leaves them too. A row
    of padding leaves zeros."""
    rng = np.random.default_rng(4)
    row = rng.integers(0, CFG.vocab_size, 16).astype(np.int32)
    ids = np.zeros((2, 64), np.int32)
    ids[0, :16] = row
    ids[:, 16:] = rng.integers(0, CFG.vocab_size, (2, 48))  # never read
    padded = jax.jit(lambda p, i, n: falcon_h1.prefill(
        p, CFG, i, n, 68, 2))(pipe.params, ids, np.array([16, 0], np.int32))
    alone = jax.jit(lambda p, i, n: falcon_h1.prefill(
        p, CFG, i, n, 20, 1))(pipe.params, row[None], np.array([16], np.int32))
    for a, b in zip(padded[1], alone[1]):
        for mine, theirs in zip(a[:2], b[:2]):
            np.testing.assert_allclose(np.asarray(mine[0]),
                                       np.asarray(theirs[0]), atol=1e-6)
            assert not np.asarray(mine[1]).any()
    np.testing.assert_allclose(np.asarray(padded[0][0]),
                               np.asarray(alone[0][0]), atol=2e-5)


# --- (c) the multipliers ------------------------------------------------------


def _without(name, index):
    value = getattr(CFG, name)
    if index is None:
        return dataclasses.replace(CFG, **{name: 1.0})
    return dataclasses.replace(CFG, **{name: tuple(
        1.0 if n == index else v for n, v in enumerate(value))})


@pytest.mark.parametrize("name, index", [
    (name, None) for name in MULTIPLIERS[:7]] + [
    ("ssm_multipliers", n) for n in range(5)] + [
    ("mlp_multipliers", n) for n in range(2)],
    ids=lambda value: "" if value is None else str(value))
def test_each_multiplier_is_in_the_program(pipe, name, index):
    """The program under the tiny preset's multipliers is the reference
    under them, and neither is the reference with this one set to 1: a
    multiplier taken out of the program would read as the second."""
    ids, lengths, given = _pass([20, 9], 32, steps=2, seed=7)
    prefill = pipe.prefill_program(2, 32, 35)
    got, _ = _served(pipe, prefill, ids, lengths, given, 32)
    _, want = _wanted(pipe.params, CFG, ids, lengths, given)
    _, left_out = _wanted(pipe.params, _without(name, index), ids, lengths,
                          given)
    for row, (with_it, without) in enumerate(zip(want, left_out)):
        with_it, without = np.asarray(with_it), np.asarray(without)
        scale = np.linalg.norm(with_it)
        assert np.linalg.norm(got[row] - with_it) / scale < 5e-6
        # the least is the step's multiplier, 9e-4: its bias of one
        # stands beside it under the softplus
        assert np.linalg.norm(got[row] - without) / scale > 2e-4, (
            name, index)


# --- (d) a dense family's pass ------------------------------------------------


def test_a_dense_familys_pass_has_an_empty_tally_and_says_what_it_cached(
        pipe):
    pairs, sums = falcon_h1.empty_load(CFG)
    assert pairs.shape == (0, 0) and sums.shape == (4,)
    rng = np.random.default_rng(9)
    mine = [rng.integers(0, CFG.vocab_size, n).tolist() for n in (5, 40)]
    others = [rng.integers(0, CFG.vocab_size, n).tolist()
              for n in (1, 33, 64)]
    key = jax.random.key(42)
    routed = [text_generation.EXPERT_PAIRS, text_generation.ROUTED_TOKENS,
              text_generation.EXPERT_PAIRS_MAX,
              text_generation.EXPERT_ROW_TILES]
    alone = pipe.run_batched([{"prompt_ids": mine, "rng": key}],
                             max_new_tokens=6)
    among = pipe.run_batched(
        [{"prompt_ids": others, "rng": jax.random.key(7)},
         {"prompt_ids": mine, "rng": key}], max_new_tokens=6)
    # a row's ids do not depend on its batchmates
    assert np.array_equal(alone[0][0], among[1][0])
    envelope = among[1][1]
    # nothing was routed: every count reads 0, for the pass and for its
    # two programs, and the four counters stand at 0 for this model
    empty = {"pairs": 0, "routed": 0, "pairs_max": 0, "active": 0,
             "tiles": 0, "calls": 0}
    assert envelope["routing"] == {**empty, "prefill": empty,
                                   "decode": empty, "pairs_by_expert": []}
    assert all(counter.value(model=NAME) == 0 for counter in routed)
    assert envelope["decode_steps"] == 5 and envelope["pass_rows"] == 5
    assert envelope["prefill_chunk_widths"] == {"64": 1}
    rows, positions = 8, 64 + 6
    whole, rings, state = falcon_h1.cache_bytes(CFG, rows, positions, 4)
    assert (envelope["cache_bytes"], envelope["cache_bytes_window"],
            envelope["cache_bytes_state"]) == (whole, rings, state)
    # both kinds on both layers: a state and a tail a row, keys and values
    # a position
    assert state == rows * 2 * (4 * 4 * 16 * 8 + 4 * 3 * 96)
    assert whole - state == rows * positions * 2 * (2 * 2 * 16 * 4)
    assert text_generation.PASS_STATE_BYTES.value(model=NAME) == state
    assert text_generation.PASS_CACHE_BYTES.value(model=NAME) == whole
    assert text_generation.PASS_WINDOW_CACHE_BYTES.value(model=NAME) == 0
    # the decode on this platform took the recurrence in `jax.numpy`
    assert platform.KERNEL_TRACES.value(op="ssd_step", path="reference") > 0
    with pytest.raises(ValueError, match="denoising_steps"):
        pipe.run_batched([{"prompt_ids": mine, "rng": key}],
                         max_new_tokens=2, denoising_steps=2)


def test_no_familys_module_imports_anothers():
    import ast
    import pathlib

    from chiaswarm_tpu.text_families import TEXT_FAMILIES

    modules = {row["module"] for row in TEXT_FAMILIES.values()}
    root = pathlib.Path(falcon_h1.__file__).parent
    for module in modules:
        tree = ast.parse((root / f"{module}.py").read_text())
        imported = {node.module.rsplit(".", 1)[-1] for node in ast.walk(tree)
                    if isinstance(node, ast.ImportFrom) and node.module}
        assert not imported & (modules - {module}), module


def test_jobs_go_through_hive_worker_and_pipeline(sdaas_root, monkeypatch):
    """Two jobs of ragged rows by the family's wire name and by the
    model's name alone: one gang, one pass, the spans and the envelope of
    any text family, the state's bytes beside the cache's, a routing that
    reads 0."""
    import asyncio
    import json

    from chiaswarm_tpu import worker as worker_module
    from chiaswarm_tpu.hive_server.harness import LocalSwarm
    from chiaswarm_tpu.settings import Settings

    monkeypatch.setattr(worker_module, "POLL_SECONDS", 0.1)
    rng = np.random.default_rng(2)

    def job(number, **extra):
        return {"id": f"falcon-{number}", "workflow": "txt2txt",
                "model_name": NAME, "max_new_tokens": 5, "seed": number,
                "prompt_ids": [rng.integers(0, CFG.vocab_size, n).tolist()
                               for n in (1, 3, 20)], **extra}

    async def scenario():
        swarm = LocalSwarm(n_workers=0, settings=Settings(
            sdaas_token="t", worker_name="w", hive_port=0, metrics_port=0))
        await swarm.start()
        try:
            ids = [await swarm.submit(job(0)), await swarm.submit(job(
                1, parameters={"pipeline_type": "FalconH1ForCausalLM"}))]
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
        assert config["routing"]["pairs"] == config["routing"]["routed"] == 0
        rows = json.loads(blob)["token_ids"]
        assert len(rows) == 3 and all(len(row) == 5 for row in rows)
        assert all(0 <= i < CFG.vocab_size for row in rows for i in row)
