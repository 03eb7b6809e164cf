"""K-EXAONE's language model (ISSUE 36): grouped-query attention on window
and full layers side by side, a ring cache beside a whole one, prefill in
chunks of positions, on the CPU at the tiny preset (window 4, `LLLG` behind
a dense first layer, 8 of 32 experts held), against the plain references
(benchmark/reference/gqa_window_moe.py, banded_kernels.py):

(a) prefill in position chunks + cached decode against the reference's one
    full forward pass, by logits: prompts longer than the window, a chunk
    edge inside a window, decode steps that wrap the ring, and whole-row
    chunks of short rows;
(b) the banded kernel (interpreted) against the plain masked attention at
    `Sq = Skv` and `Sq < Skv`, with and without a window, a group of 1 and
    of 4, lengths that pad;
(c) no key block outside the band is visited: by the index map over the
    whole grid, and by keys that would poison whatever touched them;
(d) the shares add up: the tiny preset's 4 shares' routed parts, the
    shared expert counted once, are the reference's uncut layer;
(e) `ops.attention` under a window and grouped heads on the XLA path.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import banded_kernels, gqa_window_moe, mla_moe
from chiaswarm_tpu.models import (
    exaone,
    experts,
    prefill_chunks,
    text_model,
)
from chiaswarm_tpu.ops import dot_product_attention
from chiaswarm_tpu.ops.banded_attention import (
    band_blocks,
    banded_attention,
    banded_blocks,
)

CFG = exaone.EXAONE_TINY
SIZES = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "num_experts": 32, "num_experts_per_tok": 4,
    "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-5, "sliding_window": 4,
    "layer_types": list(CFG.layer_types),
    "rope_parameters": {"rope_theta": 1e6, "rope_type": "default"}}


@pytest.fixture(scope="module")
def params():
    return exaone.init_params(CFG, jax.random.key(0), jnp.float32)


def test_the_tiny_preset_is_the_cut_in_small():
    assert CFG.layer_types == (
        "sliding_attention", "sliding_attention", "sliding_attention",
        "full_attention", "sliding_attention")
    assert CFG.windows == (4, 4, 4, 0, 4)
    full = exaone.EXAONE_236B_EP8
    assert full.windows == (128, 128, 128, 0, 128)
    assert (full.hidden_size, full.num_attention_heads,
            full.num_key_value_heads, full.head_dim, full.intermediate_size,
            full.moe_intermediate_size, full.num_experts,
            full.num_experts_per_tok) == (6144, 64, 8, 128, 18432, 2048,
                                          128, 8)
    assert exaone.ExaoneConfig().layer_types.count("full_attention") == 12
    shapes = exaone.param_shapes(full, jnp.bfloat16)
    count = sum(int(np.prod(leaf.shape))
                for leaf in jax.tree_util.tree_leaves(shapes))
    assert round(count / 1e9, 2) == 3.71
    # a 16512-position row: one whole layer and four rings of 128
    assert exaone.cache_bytes(full, 4, 16512, 2) == (
        4 * 4096 * (16512 + 4 * 128), 4 * 4096 * 4 * 128, 0)


@pytest.mark.parametrize("slots, chunk_rows, chunk_slots, lengths", [
    # the same few lengths in every case: the reference runs op by op, and
    # an op compiles once a shape
    (16, 1, 8, [16, 11, 6, 1]),   # a chunk edge inside a window
    (16, 1, 4, [11, 16, 1, 6]),   # a chunk as long as the window
    (16, 1, 2, [6, 1, 16, 11]),   # a chunk shorter than the window
    (8, 2, 8, [8, 1, 6, 3]),      # short rows: whole rows a chunk
], ids=["chunk8", "chunk4", "chunk2", "whole_rows"])
def test_chunked_prefill_and_two_caches_give_the_references_logits(
        params, slots, chunk_rows, chunk_slots, lengths):
    rows, new = 4, 7  # seven steps: every row wraps its ring of 4
    rng = np.random.default_rng(slots + chunk_slots)
    lengths = np.array(lengths, np.int32)
    ids = np.zeros((rows, slots), np.int32)
    for row, length in enumerate(lengths):
        ids[row, :length] = rng.integers(0, CFG.vocab_size, length)
    given = rng.integers(0, CFG.vocab_size, (rows, new)).astype(np.int32)
    positions = slots + new
    logits, cache, load = jax.jit(lambda p, i, n: exaone.prefill(
        p, CFG, i, n, positions, chunk_rows, chunk_slots))(
            params, ids, lengths)
    assert [layer[0].shape[1] for layer in cache] == [
        4, 4, 4, positions, 4]
    got = [logits]
    step = jax.jit(lambda p, t, n, number, c, tally: exaone.step(
        p, CFG, t, n, number, slots, c, tally))
    for j in range(new):
        logits, cache, load = step(params, given[:, j], lengths, j, cache,
                                   load)
        got.append(logits)
    got = np.stack([np.asarray(g) for g in got], axis=1)
    for row, length in enumerate(lengths):
        want = np.asarray(gqa_window_moe.forward(
            params, SIZES, np.concatenate([ids[row, :length], given[row]]),
            held=CFG.experts_held))[length - 1:]
        assert np.linalg.norm(got[row] - want) / np.linalg.norm(want) < 1e-5
    pairs, (routed, _, active, tiles) = (np.asarray(x) for x in load)
    tokens = int(lengths.sum()) + rows * new
    assert routed == tokens * CFG.num_experts_per_tok * CFG.expert_layers
    assert 0 < pairs.sum() < routed
    # an expert with a pair has a row tile, 16 pairs fill one
    assert active <= tiles < active + pairs.sum() / 16


def test_prefill_writes_every_element_of_the_cache(params, monkeypatch):
    """Whatever the cache's buffer held: the TPU compiler drops a carried
    buffer's initial zeros where it takes the loop to write it whole, so
    the loop has to (`prefill_chunks.whole_rows`). Started from NaN, the
    columns past the prompt come back zero."""
    made = exaone.new_cache
    monkeypatch.setattr(exaone, "new_cache", lambda *a: jax.tree_util.tree_map(
        lambda x: jnp.full_like(x, jnp.nan), made(*a)))
    ids = np.ones((4, 16), np.int32)
    lengths = np.array([16, 11, 6, 1], np.int32)
    _, cache, _ = exaone.prefill(params, CFG, ids, lengths, 23, 1, 8)
    assert all(bool(jnp.isfinite(x).all()) for layer in cache for x in layer)
    assert not np.asarray(cache[3][1][:, 16:]).any()


def _prefill_from_nan(every_span: bool, positions: int, chunk_rows: int,
                      chunk_slots: int, p, ids, lengths):
    """`exaone.prefill` traced with the cache started from NaN and, for
    `every_span`, a rule that runs a span whatever the lengths (a device
    value all the same: the compiler is to keep the one program, and with
    it the order of every sum)."""
    rule, made = prefill_chunks.span_runs, exaone.new_cache
    exaone.new_cache = lambda *a: jax.tree_util.tree_map(
        lambda x: jnp.full_like(x, jnp.nan), made(*a))
    if every_span:
        prefill_chunks.span_runs = lambda lengths, start: (
            lengths > -1).any()
    try:
        return exaone.prefill(p, CFG, ids, lengths, positions, chunk_rows,
                              chunk_slots)
    finally:
        prefill_chunks.span_runs, exaone.new_cache = rule, made


_PREFILL = jax.jit(_prefill_from_nan, static_argnums=(0, 1, 2, 3))
_STEP = jax.jit(
    lambda p, t, n, number, slots, c, tally: exaone.step(
        p, CFG, t, n, number, slots, c, tally, valid=n > 0),
    static_argnums=4)


@pytest.mark.parametrize("slots, chunk_rows, lengths, skipped", [
    (24, 1, [8, 16, 8, 16], 6),   # a row ends where a span starts
    (24, 1, [9, 17, 9, 17], 2),   # ... and one id into it
    (24, 1, [1, 1, 1, 1], 8),
    (24, 1, [24, 24, 24, 24], 0),  # the whole bucket: every span runs
    (24, 1, [24, 5, 0, 0], 8),    # rows that only pad the pass: no span
    (8, 2, [8, 3, 0, 0], 1),      # whole rows a chunk: the padding rows'
], ids=["at_a_start", "one_past_a_start", "one_id", "whole_bucket",
        "rows_that_pad_the_pass", "whole_rows"])
def test_a_span_no_row_reaches_is_left_out_and_nothing_read_changes(
        params, slots, chunk_rows, lengths, skipped):
    """Spans of 8 positions: the conditional against every span run.
    Logits, rings, tally and six greedy decode steps (every ring wraps) are
    the same to the bit; of the full layer's cache the columns a mask
    shows are, and a span left out is zeros where the buffer held NaN."""
    rows, new, span = 4, 6, 8
    rng = np.random.default_rng(slots + sum(lengths))
    lengths = np.array(lengths, np.int32)
    # every slot an id, padding too: a span run on padding leaves keys
    ids = rng.integers(1, CFG.vocab_size, (rows, slots)).astype(np.int32)
    got, want = (
        _PREFILL(every, slots + new, chunk_rows, span, params, ids, lengths)
        for every in (False, True))
    left_out = np.zeros((rows, slots + new), bool)
    for at in range(0, rows, chunk_rows):
        for start in range(0, slots, span):
            if not prefill_chunks.span_runs(lengths[at:at + chunk_rows],
                                            start):
                left_out[at:at + chunk_rows, start:start + span] = True
    assert left_out.sum() == skipped * chunk_rows * span
    seen = text_model.decode_mask(lengths, slots, slots + new, new - 1)
    assert not (np.asarray(seen) & left_out).any()
    for step in range(new + 1):
        (logits, cache, load), (logits_, cache_, load_) = got, want
        assert np.array_equal(logits, logits_)
        assert np.isfinite(np.asarray(logits)).all()
        for index, window in enumerate(CFG.windows):
            for mine, theirs in zip(cache[index], cache_[index]):
                mine, theirs = np.asarray(mine), np.asarray(theirs)
                assert np.isfinite(mine).all()
                if window:
                    assert np.array_equal(mine, theirs)
                    continue
                assert np.array_equal(mine[~left_out], theirs[~left_out])
                assert not mine[left_out].any()
                assert theirs[left_out].all() or not left_out.any()
        for mine, theirs in zip(load, load_):
            assert np.array_equal(mine, theirs)
        if step < new:
            tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            got, want = (
                _STEP(params, tokens, lengths, step, slots, cache, load)
                for _, cache, load in (got, want))


def test_the_references_visibility_and_the_tokens_a_logit_needs(params):
    """The reference itself: who sees whom, which tokens each layer has to
    put out for the last logits (everything below the full layer, a window
    a layer behind it), and that computing only those gives the logits of
    the whole forward pass."""
    seen = np.asarray(gqa_window_moe.visibility(np.arange(9), np.arange(9), 4))
    assert seen[8].tolist() == [False] * 5 + [True] * 4
    assert seen[2].tolist() == [True] * 3 + [False] * 6
    assert np.asarray(gqa_window_moe.visibility(
        np.arange(9), np.arange(9), 0))[8].all()
    # 23 tokens: a 16-id prompt and 7 given tokens, the test's above
    need = gqa_window_moe.needed(SIZES, 5, 23, [21, 22])
    assert [len(rows) for rows in need] == [23, 23, 23, 23, 5, 2]
    assert need[4].tolist() == [18, 19, 20, 21, 22]
    ids = np.random.default_rng(4).integers(0, CFG.vocab_size, 23)
    whole = np.asarray(gqa_window_moe.forward(
        params, SIZES, ids, held=CFG.experts_held))
    margins = []
    (part,) = gqa_window_moe.forward_rows(
        params, SIZES, [ids], held=CFG.experts_held,
        positions=[np.array([21, 22])], margins=margins)
    np.testing.assert_allclose(np.asarray(part), whole[21:], atol=2e-6)
    assert np.isfinite(np.asarray(margins[0])[[21, 22]]).all()


@pytest.mark.parametrize("sq, skv, heads, kv_heads, dim, window, blocks", [
    (24, 24, 4, 1, 16, 0, (8, 8)),
    (24, 24, 4, 4, 16, 4, (8, 8)),
    (10, 37, 4, 2, 16, 0, (8, 8)),
    (10, 37, 8, 2, 16, 4, (8, 8)),
    (13, 29, 2, 2, 128, 5, (16, 128)),
    (40, 40, 2, 1, 16, 12, (8, 8)),
    (20, 20, 2, 1, 16, 3, None),
], ids=["full_group4", "window_group1", "chunk_full_pads",
        "chunk_window_pads", "lanes128_rule_sized", "window_over_a_block",
        "the_rules_blocks"])
def test_the_banded_kernel_is_the_plain_masked_attention(
        sq, skv, heads, kv_heads, dim, window, blocks):
    keys = jax.random.split(jax.random.key(sq + skv + window), 3)
    q = jax.random.normal(keys[0], (2, sq, heads, dim))
    k = jax.random.normal(keys[1], (2, skv, kv_heads, dim))
    v = jax.random.normal(keys[2], (2, skv, kv_heads, dim))
    got = banded_attention(q, k, v, scale=dim ** -0.5, window=window,
                           blocks=blocks, interpret=True)
    want = banded_kernels.banded_attention(q, k, v, dim ** -0.5, window)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6


@pytest.mark.parametrize("sq, skv, window, block_q, block_k", [
    (64, 64, 0, 8, 8), (64, 64, 12, 8, 8), (16, 80, 0, 8, 16),
    (24, 88, 20, 8, 16), (4096, 16384, 0, 512, 512),
    (4096, 4224, 128, 256, 256),
], ids=["causal", "window", "chunk", "chunk_window", "exaone_full_layer",
        "exaone_window_layer"])
def test_no_key_block_outside_the_band_is_visited(sq, skv, window, block_q,
                                                  block_k):
    """The grid and the index map: step `j` of query block `i` maps to key
    block `min(first + j, last)`; the blocks `first .. last` are exactly
    those with a visible pair."""
    seen = np.asarray(banded_kernels.visibility(sq, skv, window))
    n_q, n_k = -(-sq // block_q), -(-skv // block_k)
    longest = 0
    for i in range(n_q):
        first, last = (int(x) for x in band_blocks(
            i, sq, skv, window, block_q, block_k))
        rows = seen[i * block_q:(i + 1) * block_q]
        touched = [block for block in range(n_k)
                   if rows[:, block * block_k:(block + 1) * block_k].any()]
        assert touched == list(range(first, last + 1))
        longest = max(longest, last - first + 1)
    # the band is a small part of the blocks where there is a window
    if window:
        assert longest <= -(-(block_q + window) // block_k) + 1


def test_keys_outside_the_band_never_reach_the_kernel():
    """Keys and values in blocks no query block's band holds are NaN: one
    fetched and computed would poison the output."""
    sq = skv = 64
    window, blocks = 12, (8, 8)
    keys = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(keys[0], (1, 8, 2, 16))  # the last 8 queries
    k = jax.random.normal(keys[1], (1, skv, 1, 16))
    v = jax.random.normal(keys[2], (1, skv, 1, 16))
    first, last = band_blocks(0, 8, skv, window, *blocks)
    assert (int(first), int(last)) == (5, 7)
    poison = jnp.arange(skv)[None, :, None, None] < 5 * 8
    got = banded_attention(q, jnp.where(poison, jnp.nan, k),
                           jnp.where(poison, jnp.nan, v),
                           scale=0.25, window=window, blocks=blocks,
                           interpret=True)
    want = banded_kernels.banded_attention(q, k, v, 0.25, window)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6


def test_the_rule_sizes_blocks_by_the_window():
    assert banded_blocks(4096, 16384, 0, jnp.bfloat16) == (512, 512)
    assert banded_blocks(4096, 4224, 128, jnp.bfloat16) == (256, 256)
    assert banded_blocks(10, 37, 4, jnp.float32) == (16, 128)


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(params):
    """Four chips of eight experts each: their routed parts, the shared
    expert counted once, are the reference's layer with all 32 experts."""
    moe = params["layers"][1]["moe"]
    h = jax.random.normal(jax.random.key(3), (24, CFG.hidden_size))
    stacks = {name: jax.random.normal(
        jax.random.key(10 + n), (32, *moe["experts"][name].shape[1:]))
        / np.sqrt(moe["experts"][name].shape[1])
        for n, name in enumerate(("gate", "up", "down"))}
    shared = np.asarray(experts.swiglu(moe["shared"], h))
    total = np.zeros_like(shared)
    for share in range(4):
        cfg = dataclasses.replace(CFG, experts_held=(8 * share, 8))
        mine = dict(moe, experts={name: stack[8 * share:8 * share + 8]
                                  for name, stack in stacks.items()})
        out, _ = experts.expert_layer(mine, cfg, h)
        total += np.asarray(out) - shared
    want = mla_moe.experts(dict(moe, experts=stacks), SIZES, h, (0, 32))
    np.testing.assert_allclose(total + shared, np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("sq, skv, window, kv_heads", [
    (12, 12, 0, 2), (12, 12, 5, 1), (6, 20, 5, 4), (6, 20, 0, 2),
], ids=["causal_grouped", "window_grouped", "chunk_window", "chunk_grouped"])
def test_ops_attention_takes_a_window_and_grouped_heads(sq, skv, window,
                                                        kv_heads):
    keys = jax.random.split(jax.random.key(sq + window), 3)
    q = jax.random.normal(keys[0], (2, sq, 4, 16))
    k = jax.random.normal(keys[1], (2, skv, kv_heads, 16))
    v = jax.random.normal(keys[2], (2, skv, kv_heads, 16))
    got = dot_product_attention(q, k, v, scale=0.25, causal=True,
                                window=window)
    want = banded_kernels.banded_attention(q, k, v, 0.25, window)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6
    with pytest.raises(ValueError, match="causal"):
        dot_product_attention(q, k, v, window=4)
