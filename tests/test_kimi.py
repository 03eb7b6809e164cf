"""Kimi-K2's language model (ISSUE 32): latent attention with a latent
cache and sigmoid-routed experts of which this chip holds a share, on the
CPU at the tiny preset, against the plain reference
(benchmark/reference/mla_moe.py):

(a) prefill + cached decode against the reference's full forward pass,
    logits at every position, absorbed and unabsorbed decode alike;
(b) the shares add up: with 32 experts over 4 shares, the four partial
    expert-layer outputs, the shared expert counted once, sum to the uncut
    reference layer;
(c) the grouped matmul against a per-expert loop with empty and very
    uneven groups, through the kernel (interpreted) and the plain path, and
    a row's result bit-equal among two different sets of batchmates;
(d) causal attention with values narrower than keys through
    `ops.attention`, against the float32 reference.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mla_moe, moe_kernels
from chiaswarm_tpu.models import experts, kimi, text_model
from chiaswarm_tpu.ops import dot_product_attention
from chiaswarm_tpu.ops.expert_matmul import buffer_rows, plan

CFG = kimi.KIMI_TINY
SIZES = {
    "hidden_size": 64, "q_lora_rank": 32, "kv_lora_rank": 16,
    "num_attention_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "n_routed_experts": 32, "num_experts_per_tok": 4,
    "routed_scaling_factor": 2.827, "rms_norm_eps": 1e-5,
    "rope_theta": 50000.0,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16}}


@pytest.fixture(scope="module")
def params():
    return kimi.init_params(CFG, jax.random.key(0), jnp.float32)


@pytest.mark.parametrize("absorb", [True, False],
                         ids=["absorbed", "unabsorbed"])
def test_prefill_and_cached_decode_give_the_references_logits(params, absorb):
    rows, slots, new = 4, 8, 5
    rng = np.random.default_rng(0)
    lengths = np.array([8, 3, 5, 1], np.int32)
    ids = np.zeros((rows, slots), np.int32)
    for row, length in enumerate(lengths):
        ids[row, :length] = rng.integers(0, CFG.vocab_size, length)
    given = rng.integers(0, CFG.vocab_size, (rows, new)).astype(np.int32)
    positions = slots + new
    logits, cache, load = jax.jit(lambda p, i, n: kimi.prefill(
        p, CFG, i, n, positions, 2))(params, ids, lengths)
    got = [logits]
    step = jax.jit(lambda p, t, at, c, column, mask, tally: kimi.decode_step(
        p, CFG, t, at, c, column, mask, tally, absorb=absorb))
    for j in range(new):
        mask = text_model.decode_mask(jnp.asarray(lengths), slots,
                                      positions, j)
        logits, cache, load = step(params, given[:, j], lengths + j, cache,
                                   slots + j, mask, load)
        got.append(logits)
    got = np.stack([np.asarray(g) for g in got], axis=1)
    for row, length in enumerate(lengths):
        want = np.asarray(mla_moe.forward(
            params, SIZES, np.concatenate([ids[row, :length], given[row]]),
            held=CFG.experts_held))[length - 1:]
        assert np.linalg.norm(got[row] - want) / np.linalg.norm(want) < 1e-5
    # what the routing's tally counted: every real token, k pairs a layer
    pairs, (routed, _, active, tiles) = (np.asarray(x) for x in load)
    tokens = int(lengths.sum()) + rows * new
    assert routed == tokens * CFG.num_experts_per_tok * CFG.expert_layers
    assert 0 < pairs.sum() < routed
    # an expert with a pair has a row tile, 16 pairs fill one
    assert active <= tiles < active + pairs.sum() / 16


def _ragged(order, lengths, vocabulary, seed=5):
    """`lengths` longest first or shuffled (rows of no length among the
    others then), and ids behind them."""
    rng = np.random.default_rng(seed)
    lengths = np.array(lengths, np.int32)
    if order == "shuffled":
        lengths = lengths[rng.permutation(len(lengths))]
    ids = np.zeros((len(lengths), max(lengths)), np.int32)
    for row, length in enumerate(lengths):
        ids[row, :length] = rng.integers(0, vocabulary, length)
    return ids, lengths


@pytest.mark.parametrize("order", ["ordered", "shuffled"])
def test_chunks_as_wide_as_their_rows_leave_what_one_wide_chunk_leaves(
        params, monkeypatch, order):
    """ISSUE 43: two rows a chunk at most, each row at the narrowest of
    16, 8 or 4 slots that holds it, rows that only pad the pass not run:
    the logits, the cache (started from NaN: an element no chunk wrote
    would show) and the pairs of the same rows in one chunk of 16 slots."""
    ids, lengths = _ragged(order, [16, 13, 8, 7, 4, 2, 0, 0], CFG.vocab_size)
    (rows, slots), positions = ids.shape, 19
    made = kimi.new_cache
    monkeypatch.setattr(
        kimi, "new_cache", lambda *args: jax.tree_util.tree_map(
            lambda x: x + jnp.nan, made(*args)))
    logits, cache, load = jax.jit(lambda p, i, n: kimi.prefill(
        p, CFG, i, n, positions, 2))(params, ids, lengths)
    last, entries, told = jax.jit(lambda p, i, n: kimi.prefill_rows(
        p, CFG, i, n, kimi.empty_load(CFG)))(params, ids, lengths)
    real = lengths > 0
    np.testing.assert_allclose(
        np.asarray(logits)[real],
        np.asarray(kimi.logits_of(params, CFG, last))[real], atol=2e-5)
    seen = (np.arange(slots)[None, :] < lengths[:, None])[..., None]
    for mine, entry in zip(cache, entries):
        mine = np.asarray(mine)
        assert np.isfinite(mine).all() and not mine[:, slots:].any()
        np.testing.assert_allclose(np.where(seen, mine[:, :slots], 0),
                                   np.where(seen, entry, 0), atol=2e-5)
    assert np.array_equal(np.asarray(load[0]), np.asarray(told[0]))
    assert int(load[1][0]) == int(told[1][0]) == int(
        lengths.sum()) * CFG.num_experts_per_tok * CFG.expert_layers


def test_a_rows_bits_do_not_depend_on_its_batchmates(params):
    """ISSUE 43: a row's width is its own, so its logits and its cache
    entries are the same bits wherever it stands and whoever shares its
    chunk (a sampler turns the last bit of a logit into another id sooner
    or later, and a job's ids may not depend on its batchmates)."""
    ids, lengths = _ragged("ordered", [16, 13, 8, 7, 4, 2, 0, 0],
                           CFG.vocab_size)
    prefill = jax.jit(lambda p, i, n: kimi.prefill(p, CFG, i, n, 19, 2))
    logits, cache, _ = prefill(params, ids, lengths)
    order = np.array([5, 0, 7, 3, 1, 6, 2, 4])
    moved, moved_cache, _ = prefill(params, ids[order], lengths[order])
    real = lengths[order] > 0
    assert np.array_equal(np.asarray(moved)[real],
                          np.asarray(logits)[order][real])
    for mine, other in zip(moved_cache, cache):
        assert np.array_equal(np.asarray(mine)[real],
                              np.asarray(other)[order][real])


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(params):
    """Four chips of eight experts each: their parts, the shared expert
    counted once, are the reference's layer with all 32 experts."""
    moe = params["layers"][1]["moe"]
    h = jax.random.normal(jax.random.key(3), (24, CFG.hidden_size))
    whole = dataclasses.replace(CFG, experts_held=(0, 32))
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
    want = mla_moe.experts(dict(moe, experts=stacks), SIZES, h,
                           whole.experts_held)
    np.testing.assert_allclose(total + shared, np.asarray(want), atol=2e-5)


def _experts(held, hidden, width, key):
    keys = jax.random.split(key, 3)
    return {"gate": jax.random.normal(keys[0], (held, hidden, width)) / 8,
            "up": jax.random.normal(keys[1], (held, hidden, width)) / 8,
            "down": jax.random.normal(keys[2], (held, width, hidden)) / 6}


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["plain_path", "kernel_interpreted"])
def test_grouped_matmul_matches_a_loop_over_experts_on_uneven_groups(
        interpret):
    """Expert 0 takes most pairs, experts 2 and 5 none, many tokens hold
    no expert here; every pair's result is the loop's."""
    held, hidden, width, tokens, choices = 6, 64, 32, 40, 4
    experts = _experts(held, hidden, width, jax.random.key(1))
    h = jax.random.normal(jax.random.key(2), (tokens, hidden))
    rng = np.random.default_rng(5)
    local = rng.choice([0, 0, 0, 0, 1, 3, 4, 6, 7, 9, 11, 20],
                       (tokens, choices)).astype(np.int32)
    local[7] = 30  # a token with nothing here
    got, sizes = jax.jit(lambda e, x, l: kimi.held_experts(
        e, x, l, interpret=interpret))(experts, h, local)
    assert list(np.asarray(sizes)) == [int((local == e).sum())
                                       for e in range(held)]
    assert sizes[2] == 0 and sizes[5] == 0 and sizes[0] > 16 * 3
    want = moe_kernels.expert_ffn(h, local, experts["gate"], experts["up"],
                                  experts["down"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["plain_path", "kernel_interpreted"])
def test_a_rows_expert_output_is_bit_equal_among_other_batchmates(
        params, interpret):
    moe = params["layers"][2]["moe"]
    mine = jax.random.normal(jax.random.key(4), (3, CFG.hidden_size))
    outs = []
    for seed, others in ((5, 9), (6, 29)):
        mates = jax.random.normal(jax.random.key(seed),
                                  (others, CFG.hidden_size))
        batch = jnp.concatenate([mates[:others // 2], mine,
                                 mates[others // 2:]])
        out, _ = jax.jit(lambda p, x: experts.expert_layer(
            p, CFG, x, interpret=interpret))(moe, batch)
        outs.append(np.asarray(out)[others // 2:others // 2 + 3])
    assert np.array_equal(outs[0], outs[1])


def test_the_row_buffer_holds_the_worst_case_and_only_real_tiles_count():
    local = jnp.asarray([[0, 1], [0, 2], [3, 3]], jnp.int32)  # 3 = not held
    where = plan(local, groups=3, tm=4)
    assert where.row_token.shape[0] == buffer_rows(3, 2, 3, 4) == 20
    assert list(np.asarray(where.sizes)) == [2, 1, 1]
    assert int(where.n_tiles) == 3
    assert list(np.asarray(where.tile_expert[:3])) == [0, 1, 2]
    # each held pair has a row of its own expert's tile; the others none
    rows = np.asarray(where.pair_row)
    assert rows[0, 0] // 4 == 0 and rows[1, 0] // 4 == 0
    assert rows[0, 1] // 4 == 1 and rows[1, 1] // 4 == 2
    assert (rows[2] == 20).all()
    assert sorted(np.asarray(where.row_token)[[rows[0, 0], rows[1, 0]]]) \
        == [0, 1]


def test_causal_attention_with_values_narrower_than_keys():
    keys = jax.random.split(jax.random.key(8), 3)
    q = jax.random.normal(keys[0], (2, 16, 4, 24))
    k = jax.random.normal(keys[1], (2, 16, 4, 24))
    v = jax.random.normal(keys[2], (2, 16, 4, 16))
    got = dot_product_attention(q, k, v, scale=0.3, causal=True)
    assert got.shape == (2, 16, 4, 16)
    want = moe_kernels.causal_attention(q, k, v, 0.3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    # the first query sees one key: its output is that key's value
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(v[:, 0]),
                               atol=1e-6)
