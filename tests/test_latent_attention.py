"""The decode attention kernel over a latent cache (ISSUE 53;
`ops/latent_attention.py`), interpreted on the CPU:

(a) against the float32 reference (benchmark/reference/moe_kernels.py
    `latent_attention`) under the masks a decode hands it and the ones
    that try its skipping: a prefix, the pass's two intervals at its first
    and last step, a row that sees one position, a row whose first live
    block is not block 0, rows and positions that are not multiples of the
    blocks; bf16 and float32 operands, within what the plain form reads;
(b) a row's result bit-equal among different sets of batchmates and at
    different places in the row block, and what a block nobody sees holds
    reaching nobody;
(c) the table of live blocks (a grid step's rows together) against a count
    from the mask in `numpy`, and the host's account, which feeds
    `swarm_decode_cache_blocks_total`, equal to it at every step;
(d) the model's decode step through the kernel against the plain form, and
    which labels a traced call bumps.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import moe_kernels
from chiaswarm_tpu.models import kimi, text_model
from chiaswarm_tpu.ops import latent_attention, platform
from chiaswarm_tpu.ops.latent_attention import (
    COLUMN_BLOCK,
    decode_reference,
    latent_decode_attention,
    live_blocks,
    rows_a_step,
    walk,
)

HEADS, LATENT, ROPE, SCALE = 4, 128, 16, 0.11


def operands(rows, positions, dtype, seed=0):
    keys = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(keys[0], (rows, HEADS, LATENT), dtype),
            jax.random.normal(keys[1], (rows, HEADS, ROPE), dtype),
            jax.random.normal(keys[2], (rows, positions, LATENT + ROPE),
                              dtype))


def prefix(rows, positions):
    seen = np.random.default_rng(1).integers(1, positions + 1, rows)
    return np.arange(positions)[None, :] < seen[:, None]


def two_intervals(number):
    """The pass's mask at generated token `number`: 256 prompt slots, 256
    new tokens, lengths log-uniform over 16-256."""
    def make(rows, positions):
        lengths = np.exp(np.random.default_rng(2).uniform(
            np.log(16), np.log(positions // 2), rows)).astype(np.int32)
        return np.array(text_model.decode_mask(
            jnp.asarray(lengths), positions // 2, positions, number))
    return make


def one_position(rows, positions):
    mask = np.zeros((rows, positions), bool)
    mask[np.arange(rows), np.random.default_rng(3).integers(
        0, positions, rows)] = True
    return mask


def late_start(rows, positions):
    """No row sees block 0; some start in the last block."""
    start = np.random.default_rng(4).integers(
        COLUMN_BLOCK, positions, rows)
    return np.arange(positions)[None, :] >= start[:, None]


@pytest.mark.parametrize("dtype, limit", [
    pytest.param(jnp.float32, 2e-6, id="float32"),
    pytest.param(jnp.bfloat16, 0.025, id="bfloat16")])
@pytest.mark.parametrize("rows, positions, make", [
    pytest.param(8, 512, prefix, id="prefix"),
    pytest.param(8, 512, two_intervals(0), id="two-intervals-first-step"),
    pytest.param(8, 512, two_intervals(254), id="two-intervals-last-step"),
    pytest.param(4, 384, one_position, id="one-position-a-row"),
    pytest.param(6, 512, late_start, id="first-live-block-not-block-0"),
    pytest.param(7, 300, prefix, id="ragged-rows-and-positions"),
    pytest.param(40, 40, prefix, id="narrower-than-a-block"),
])
def test_the_kernel_gives_the_references_context(rows, positions, make,
                                                  dtype, limit):
    q_lat, q_rope, cache = operands(rows, positions, dtype)
    mask = jnp.asarray(make(rows, positions))
    got = latent_decode_attention(q_lat, q_rope, cache, mask, SCALE,
                                  interpret=True)
    assert got.shape == q_lat.shape and got.dtype == cache.dtype
    want = moe_kernels.latent_attention(q_lat, q_rope, cache, mask, SCALE)
    plain = decode_reference(q_lat, q_rope, cache, mask, SCALE)
    err, plain_err = (float(jnp.max(jnp.abs(x.astype(jnp.float32) - want)))
                      for x in (got, plain))
    # the op's present tolerance (the benchmark's `correct` 4 holds the
    # cell's shape to 0.025), and no further from the reference than the
    # plain form stands but for the order of the sums
    assert err <= limit, (err, plain_err)
    assert err <= 2 * plain_err + limit / 2, (err, plain_err)


def test_rows_part_into_steps_of_whole_sublanes_or_the_plain_form_runs():
    assert [rows_a_step(n) for n in (1, 6, 8, 16, 24, 40, 256)] == [
        1, 6, 8, 16, 8, 8, 16]
    # 34 rows part into no step: the call is the plain form's, interpreted
    # or not, and says so
    assert rows_a_step(34) == rows_a_step(20) == 0
    q_lat, q_rope, cache = operands(34, 40, jnp.float32)
    mask = jnp.asarray(prefix(34, 40))
    before = platform.KERNEL_TRACES.value(op="latent_attention",
                                          path="pallas")
    got = latent_decode_attention(q_lat, q_rope, cache, mask, SCALE,
                                  interpret=True)
    assert platform.KERNEL_TRACES.value(
        op="latent_attention", path="pallas") == before
    assert np.array_equal(np.asarray(got), np.asarray(
        decode_reference(q_lat, q_rope, cache, mask, SCALE)))


def test_a_rows_context_is_bit_equal_whoever_shares_its_step():
    """The probe's row: the same operands and mask at two places of a row
    block, among batchmates that see other blocks than it does (one set
    everything, the other one position each), give the same bits."""
    rows, positions = 2 * rows_a_step(32), 512
    assert rows == 32
    q_lat, q_rope, cache = operands(rows, positions, jnp.bfloat16, seed=5)
    probe = np.zeros((positions,), bool)
    probe[40:97] = probe[256:300] = True  # blocks 0 and 2, not 1 and 3
    results = []
    for place, mates in ((3, np.ones((rows, positions), bool)),
                         (21, one_position(rows, positions)),
                         (16, late_start(rows, positions))):
        order = np.arange(rows)
        order[[0, place]] = place, 0
        mask = mates.copy()
        mask[place] = probe
        got = latent_decode_attention(
            q_lat[order], q_rope[order], cache[order], jnp.asarray(mask),
            SCALE, interpret=True)
        results.append(np.asarray(got[place].astype(jnp.float32)))
    assert np.array_equal(results[0], results[1])
    assert np.array_equal(results[0], results[2])
    alone = latent_decode_attention(
        q_lat[:1], q_rope[:1], cache[:1], jnp.asarray(probe[None]), SCALE,
        interpret=True)
    assert np.array_equal(results[0], np.asarray(alone[0], np.float32))


def test_what_a_block_nobody_sees_holds_counts_for_nothing():
    """A block none of a step's rows sees is not the step's to read: it is
    neither fetched nor computed, so NaN there reaches nobody."""
    rows, positions = 2 * rows_a_step(64), 512
    q_lat, q_rope, cache = operands(rows, positions, jnp.float32, seed=6)
    mask = two_intervals(3)(rows, positions)
    mask[0, :] = True  # the first step's rows go over every block
    live = np.asarray(live_blocks(jnp.asarray(mask), COLUMN_BLOCK,
                                  rows_a_step(rows)))
    assert live[0].all() and not live[1].all()
    spoiled = np.where(np.repeat(np.repeat(
        live, rows_a_step(rows), axis=0), COLUMN_BLOCK, axis=1)[:, :, None],
        cache, np.nan)
    assert np.isnan(spoiled).any()
    clean, dirty = (latent_decode_attention(
        q_lat, q_rope, jnp.asarray(c), jnp.asarray(mask), SCALE,
        interpret=True) for c in (cache, spoiled))
    assert np.array_equal(np.asarray(clean), np.asarray(dirty))


@pytest.mark.parametrize("slots, new_tokens", [
    pytest.param(256, 256, id="the-cells-pass"),
    pytest.param(64, 200, id="a-generated-span-over-three-blocks"),
    pytest.param(8, 6, id="narrower-than-a-block"),
    pytest.param(192, 70, id="generated-columns-start-inside-a-block"),
])
def test_the_hosts_account_is_the_table_of_live_blocks(monkeypatch, slots,
                                                       new_tokens):
    """`decode_cache_blocks` against the table the kernel is handed,
    counted from the mask in numpy, at every step of a decode."""
    positions, steps = slots + new_tokens, new_tokens - 1
    lengths = np.sort(np.exp(np.random.default_rng(7).uniform(
        0, np.log(slots), 48)).astype(np.int32))[::-1].copy()
    lengths[0], lengths[-1] = slots, 0  # a full prompt, a row that pads
    block = latent_attention.column_block(positions)
    blocks, together = -(-positions // block), rows_a_step(len(lengths))
    assert together == 16
    cfg = kimi.KIMI_K2_EP32
    walked = 0
    for number in range(steps):
        mask = np.asarray(text_model.decode_mask(
            jnp.asarray(lengths), slots, positions, number))
        table = np.asarray(live_blocks(jnp.asarray(mask), block, together))
        want = np.zeros((len(lengths) // together, blocks), bool)
        for row, column in zip(*np.nonzero(mask)):
            want[row // together, column // block] = True
        assert np.array_equal(table, want), number
        walked += together * int(want.sum())
        # the walk: the live pairs in order, a grid step each, the first
        # and last of a step's rows marked, and behind them the last again
        step, at, kind = (np.asarray(x) for x in walk(jnp.asarray(table)))
        pairs = np.argwhere(want)
        count = len(pairs)
        assert np.array_equal(np.stack([step, at], 1)[:count], pairs)
        assert (kind[:count] >= 4).all() and (kind[count:] == 0).all()
        assert (step[count:] == step[count - 1]).all()
        assert (at[count:] == at[count - 1]).all()
        assert np.array_equal(
            np.nonzero(kind & 1)[0],
            np.nonzero(np.r_[True, np.diff(pairs[:, 0]) != 0])[0])
        assert np.array_equal(
            np.nonzero(kind & 2)[0],
            np.nonzero(np.r_[np.diff(pairs[:, 0]) != 0, True])[0])
    monkeypatch.setattr(platform, "trace_platform", lambda: "tpu")
    layers = cfg.num_hidden_layers
    assert kimi.decode_cache_blocks(
        cfg, lengths, slots, positions, steps) == (
        layers * walked, layers * len(lengths) * blocks * steps)
    # the plain form walks the width: off the chip, or a latent that is
    # not whole lanes
    monkeypatch.undo()
    whole = layers * len(lengths) * blocks * steps
    assert kimi.decode_cache_blocks(
        cfg, lengths, slots, positions, steps) == (whole, whole)


def test_the_pass_reports_the_blocks_its_decode_walked():
    """The envelope's `decode_cache_blocks` beside `decode_steps`, and the
    counter: off the chip the plain form ran, so walked is the bucket."""
    from chiaswarm_tpu.pipelines import text_generation

    name = "test/tiny-kimi"
    pipe = text_generation.TextGenerationPipeline(
        name, allow_random_init=True)
    assert pipe.bounds_decode

    def counted():
        return {extent: text_generation.DECODE_CACHE_BLOCKS.value(
            model=name, extent=extent) for extent in ("walked", "bucket")}

    before = counted()
    (_, config), = pipe.run_batched(
        [{"prompt_ids": [[5, 9, 2], [7, 1]], "rng": jax.random.key(1)}],
        max_new_tokens=6)
    assert config["decode_steps"] == 5
    rows, layers = config["padded_rows"], pipe.config.num_hidden_layers
    bucket = layers * rows * 5  # a cache narrower than a block is one
    assert config["decode_cache_blocks"] == {"walked": bucket,
                                             "bucket": bucket}
    after = counted()
    assert {k: after[k] - before[k] for k in after} == {
        "walked": bucket, "bucket": bucket}


def test_the_models_decode_step_through_the_kernel(monkeypatch):
    """`decode_step(interpret=True)` runs the kernel and gives the plain
    form's logits; a traced call bumps `absorbed` either way (what the
    benchmark's `expected_kernel_paths` looks for) and `pallas` beside it
    where the kernel was what was traced."""
    cfg = kimi.KIMI_TINY
    params = kimi.init_params(cfg, jax.random.key(0), jnp.float32)
    rows, slots, positions = 4, 8, 14
    lengths = jnp.asarray([8, 3, 5, 1], jnp.int32)
    rng = np.random.default_rng(0)
    cache = tuple(
        jnp.asarray(rng.normal(size=(rows, positions, cfg.cache_width)),
                    jnp.float32) for _ in range(cfg.num_hidden_layers))
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, rows), jnp.int32)
    column = slots + 2
    mask = text_model.decode_mask(lengths, slots, positions, 2, column)

    def traced(path):
        return platform.KERNEL_TRACES.value(op="latent_attention", path=path)

    logits = {}
    for interpret in (False, True):
        before = {path: traced(path) for path in ("absorbed", "pallas")}
        logits[interpret], _, _ = kimi.decode_step(
            params, cfg, tokens, lengths + 2, cache, column, mask,
            kimi.empty_load(cfg), interpret=interpret)
        layers = cfg.num_hidden_layers
        assert traced("absorbed") - before["absorbed"] == layers
        assert traced("pallas") - before["pallas"] == layers * interpret
    np.testing.assert_allclose(logits[True], logits[False], rtol=2e-4,
                               atol=2e-4)
