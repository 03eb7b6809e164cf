"""The text decode programs' sampler (ISSUE 47, ops/sampling.py): one
uniform number a position over a running sum of the position's own
`exp(logit - max)`, on the CPU:

(a) the draws follow the softmax (chi-square over 200,000 draws, a
    vocabulary of several blocks that is no multiple of 128, one logit in
    seven at `-inf`), and no id of probability 0 is drawn, whatever `u`:
    0, the largest float32 under 1, and trailing blocks all at `-inf`;
(b) the logarithm handed back is `log_softmax(logits / temperature)[id]`
    to float32;
(c) a row draws the same ids alone, among other rows and at another row
    count: its key and its logits decide, nothing else;
(d) temperature 0 is the arg-max, and in a batch of mixed temperatures
    each row goes its own way;
(e) the two decode programs of each of the five text families at its tiny
    preset draw `rows x positions` random words a step, counted from the
    program's jaxpr, and `jax.random.categorical` is in neither;
(f) the two Pallas kernels (interpreted) against their `jax.numpy` forms.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chiaswarm_tpu.ops import sampling
from chiaswarm_tpu.pipelines.text_generation import TextGenerationPipeline
from chiaswarm_tpu.text_families import TEXT_FAMILIES

# three blocks, the last one of 452 ids: no multiple of 128
VOCAB = 2 * sampling.BLOCK + 452


def _logits(seed, *lead, vocab=VOCAB, spread=2.0):
    rng = np.random.default_rng(seed)
    logits = (spread * rng.normal(size=lead + (vocab,))).astype(np.float32)
    logits[rng.random(lead + (vocab,)) < 1 / 7] = -np.inf
    return logits


def _softmax(scaled):
    return np.asarray(jax.nn.softmax(jnp.asarray(scaled), axis=-1))


# --- (a) the distribution ----------------------------------------------------


def test_the_draws_follow_the_softmax_and_no_id_of_probability_0_is_drawn():
    logits = _logits(0)
    draws, chunk = 200_000, 10_000
    drawn = jax.jit(lambda logits, u: sampling.draw_uniform(
        jnp.broadcast_to(logits, (chunk, VOCAB)), jnp.ones((chunk,)), u)[0])
    counts = np.zeros(VOCAB, np.int64)
    for number in range(draws // chunk):
        u = jax.random.uniform(jax.random.key(number), (chunk,))
        counts += np.bincount(np.asarray(drawn(logits, u)), minlength=VOCAB)
    p = _softmax(logits)
    assert counts[p == 0].sum() == 0
    drawable = p > 0
    expected = draws * p[drawable]
    chi_square = ((counts[drawable] - expected) ** 2 / expected).sum()
    freedom = drawable.sum() - 1
    # five standard deviations over the mean: a sampler off by a block's
    # tail or a lane reads thousands
    assert chi_square < freedom + 5 * np.sqrt(2 * freedom), (
        chi_square, freedom)


def _edge_rows():
    plain = _logits(1)
    empty_tail = plain.copy()  # the last block and a half hold nothing
    empty_tail[sampling.BLOCK + 300:] = -np.inf
    one = np.full(VOCAB, -np.inf, np.float32)  # one id can be drawn at all
    one[sampling.BLOCK + 7] = 3.0
    faint = plain.copy()  # ids too far under the first to raise a sum
    faint[0], faint[-3:] = 60.0, -np.inf
    faint[-4] = -20.0
    return {"plain": plain, "empty_tail": empty_tail, "one_id": one,
            "faint_last": faint}


@pytest.mark.parametrize("u", [0.0, float(np.nextafter(
    np.float32(1), np.float32(0)))], ids=["u_0", "u_under_1"])
@pytest.mark.parametrize("row", sorted(_edge_rows()))
def test_no_u_falls_on_an_id_of_probability_0(row, u):
    logits = _edge_rows()[row]
    drawn, log_p, greedy, _ = (
        np.asarray(x)[0] for x in sampling.draw_uniform(
            jnp.asarray(logits)[None], jnp.ones((1,)), jnp.full((1,), u)))
    p = _softmax(logits)
    assert p[drawn] > 0 and np.isfinite(log_p)
    assert greedy == logits.argmax()
    if row != "faint_last":
        first, last = np.flatnonzero(p > 0)[[0, -1]]
        assert drawn == (first if u == 0 else last)


# --- (b) the probability -----------------------------------------------------


@pytest.mark.parametrize("temperature", [1.0, 0.7, 2.5])
def test_the_logarithm_handed_back_is_the_drawn_ids_log_softmax(temperature):
    logits = _logits(2, 6, 4)
    keys = jax.random.split(jax.random.key(3), 6)
    ids, log_p = (np.asarray(x) for x in sampling.sample(
        keys, jnp.asarray(logits), temperature))
    assert ids.shape == log_p.shape == (6, 4) and ids.dtype == np.int32
    want = np.asarray(jax.nn.log_softmax(
        jnp.asarray(logits) / temperature, axis=-1))
    want = np.take_along_axis(want, ids[..., None], axis=-1)[..., 0]
    assert np.isfinite(want).all()
    np.testing.assert_allclose(log_p, want, rtol=0, atol=2e-5)


# --- (c) a row's draw is its own ---------------------------------------------


@pytest.mark.parametrize("lead", [(), (4,)], ids=["a_token", "a_block"])
def test_a_row_draws_the_same_alone_and_among_others(lead):
    logits = jnp.asarray(_logits(4, 8, *lead))
    keys = jax.random.split(jax.random.key(5), 8)
    together = np.asarray(sampling.sample(keys, logits, 1.0)[0])
    for row in (0, 3, 7):
        alone = sampling.sample(keys[row:row + 1], logits[row:row + 1], 1.0)
        assert np.asarray(alone[0])[0].tolist() == together[row].tolist()
    # another row count, the rows in another order
    order = np.array([6, 1, 3])
    some = np.asarray(sampling.sample(keys[order], logits[order], 1.0)[0])
    assert some.tolist() == together[order].tolist()
    # another key, other ids
    other = np.asarray(sampling.sample(
        jax.random.split(jax.random.key(6), 8), logits, 1.0)[0])
    assert (other != together).any()


# --- (d) temperature ---------------------------------------------------------


def test_temperature_0_is_the_arg_max_and_rows_of_a_batch_go_their_own_way():
    logits = _logits(7, 6, 4)
    keys = jax.random.split(jax.random.key(8), 6)
    greedy, log_p = (np.asarray(x) for x in sampling.sample(
        keys, jnp.asarray(logits), 0.0))
    assert (greedy == logits.argmax(-1)).all()
    np.testing.assert_allclose(
        log_p, np.log(_softmax(logits).max(-1)), rtol=0, atol=2e-5)
    warm = np.asarray(sampling.sample(keys, jnp.asarray(logits), 1.3)[0])
    mixed = np.asarray(sampling.sample(
        keys, jnp.asarray(logits),
        jnp.asarray([0.0, 1.3, 0.0, 1.3, 1.3, 0.0]))[0])
    cold = np.array([True, False, True, False, False, True])
    assert (mixed[cold] == greedy[cold]).all()
    assert (mixed[~cold] == warm[~cold]).all()
    assert (warm != greedy).any()


# --- (e) the random words a decode program draws -----------------------------


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for equation in jaxpr.eqns:
        yield equation
        for value in equation.params.values():
            for held in (value if isinstance(value, (tuple, list))
                         else (value,)):
                inner = getattr(held, "jaxpr", held)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


@pytest.mark.parametrize("family", sorted(TEXT_FAMILIES))
def test_a_decode_program_draws_a_random_word_a_position(family):
    """Not a word a logit: every `random_bits` of the program's jaxpr
    yields `rows x positions` words (positions: 1, or the block's length),
    there is one at each place the program samples, and nothing of the
    Gumbel draw (a `random_bits` as wide as the vocabulary) is left."""
    row = TEXT_FAMILIES[family]
    pipe = TextGenerationPipeline(f"test/tiny-{row['name']}",
                                  allow_random_init=True)
    cfg, model = pipe.config, pipe.model
    rows, slots, new = 4, 16, 9
    positions = pipe.cache_positions(slots, new)
    cache = jax.eval_shape(
        lambda: model.new_cache(cfg, rows, positions, pipe.dtype))
    integers = jax.ShapeDtypeStruct((rows,), jnp.int32)
    sampling_arguments = (
        integers, jax.ShapeDtypeStruct((2, 2), jnp.uint32), integers,
        integers, jax.ShapeDtypeStruct((), jnp.float32))
    load = jax.eval_shape(lambda: model.empty_load(cfg))
    if pipe.by_blocks:
        program = pipe.block_decode_program(rows, slots, new, 2, False)
        jaxpr = jax.make_jaxpr(program)(
            pipe.params, cache,
            jax.ShapeDtypeStruct((rows, slots), jnp.int32),
            *sampling_arguments, jax.ShapeDtypeStruct((), jnp.float32), load)
        # the opening block's loop, a later block's fused forward, its loop
        words, sites = rows * cfg.block_length, 3
    else:
        program = pipe.decode_program(rows, slots, new)
        jaxpr = jax.make_jaxpr(program)(
            pipe.params, cache,
            jax.ShapeDtypeStruct((rows, cfg.vocab_size), jnp.float32),
            *sampling_arguments, load)
        # prefill's logits, and the scan's step
        words, sites = rows, 2
    drawn = [int(np.prod(equation.outvars[0].aval.shape))
             for equation in _equations(jaxpr.jaxpr)
             if equation.primitive.name == "random_bits"]
    assert drawn == [words] * sites
    pipe.release()


def test_no_categorical_is_left_in_the_pipeline():
    import inspect

    from chiaswarm_tpu.pipelines import text_generation

    assert "categorical" not in inspect.getsource(text_generation)


# --- (f) the kernels ---------------------------------------------------------


@pytest.mark.parametrize("positions, vocab", [
    (16, VOCAB), (5, VOCAB), (8, 2 * sampling.BLOCK), (3, 200)],
    ids=["ragged_vocabulary", "ragged_rows", "whole_blocks", "one_block"])
def test_the_statistics_kernel_is_the_plain_pass(positions, vocab):
    logits = _logits(9, positions, vocab=vocab)
    logits[0, sampling.BLOCK:] = -np.inf  # whole blocks that hold nothing
    inverse = jnp.linspace(0.5, 2.0, positions)
    want = sampling.statistics_reference(jnp.asarray(logits), inverse)
    got = sampling.block_statistics(
        jnp.asarray(logits), inverse, interpret=True)
    for name, a, b in zip(("largest", "sums", "place"), got, want):
        if name == "place":
            # a block that holds nothing has no largest to stand anywhere
            held = np.isfinite(np.asarray(want[0]))
            assert (np.asarray(a)[held] == np.asarray(b)[held]).all()
        else:
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=0)


@pytest.mark.parametrize("positions, vocab", [
    (16, VOCAB), (5, VOCAB), (3, 200)],
    ids=["ragged_vocabulary", "ragged_rows", "one_block"])
def test_the_block_kernel_hands_each_position_its_block(positions, vocab):
    logits = jnp.asarray(_logits(10, positions, vocab=vocab))
    width, blocks = sampling._blocks(vocab)
    block = jnp.asarray(np.random.default_rng(11).integers(
        0, blocks, positions), jnp.int32).at[0].set(blocks - 1)
    got = np.asarray(sampling.block_logits(logits, block, interpret=True))
    want = np.asarray(sampling.block_reference(logits, block))
    inside = (np.asarray(block)[:, None] * width + np.arange(width)) < vocab
    assert (got[inside] == want[inside]).all()


def test_the_interpreted_kernels_draw_what_the_plain_path_draws():
    logits = jnp.asarray(_logits(12, 6, 4))
    keys = jax.random.split(jax.random.key(13), 6)
    plain = sampling.sample(keys, logits, 0.9)
    kernel = sampling.sample(keys, logits, 0.9, interpret=True)
    assert np.asarray(kernel[0]).tolist() == np.asarray(plain[0]).tolist()
    np.testing.assert_allclose(kernel[1], plain[1], rtol=0, atol=2e-5)
