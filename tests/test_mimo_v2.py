"""MiMo-V2 (ISSUE 57) at the tiny preset, seeded weights, on the CPU:

(a) prefill in spans + cached decode steps through `step` give the plain
    reference's ONE full forward pass (benchmark/reference/gqa_sink_moe.py)
    by logits, on prompts longer than the window and than a span, past the
    ring's wrap, on the plain path and with the kernel interpreted;
(b) three controls have to FAIL that comparison's limit: the sink left
    out, the values' scale left out, the two rotary bases swapped;
(c) `ops.wide_key_attention` against its reference path and under
    `interpret=True`: keys of 192 on values of 128, groups of 16 and 8,
    with and without the sink, with and without a window, a chunk at an
    offset, a row shorter than its span; a sink far above every score (the
    output near 0) and far below (equal to no sink);
(d) the 16 shares of one expert layer add up to the uncut layer;
(e) `cached_attention` without a sink lowers to the text it lowered to
    before it took one (a copy of that function is kept here), and with a
    sink and narrower values gives the explicit softmax;
(f) the share's parameters, cache and key extent are the issue's counts.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import gqa_sink_moe, mla_moe
from chiaswarm_tpu.models import experts, mimo_v2, text_model
from chiaswarm_tpu.ops import wide_key_attention as kernel

CFG = mimo_v2.MIMO_TINY
SIZES = {
    **{key: getattr(CFG, key) for key in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "swa_num_key_value_heads", "head_dim", "v_head_dim",
        "partial_rotary_factor", "rope_theta", "swa_rope_theta",
        "attention_value_scale", "sliding_window", "hybrid_layer_pattern",
        "n_routed_experts", "num_experts_per_tok")},
    "routed_scaling_factor": None, "layernorm_epsilon": 1e-5}
# float32 on both sides: what a sound program reads is rounding (1e-6);
# the controls read a thousand times the limit
LIMIT = 1e-4
PATHS = pytest.mark.parametrize("interpret", [False, True],
                                ids=["plain_path", "kernel_interpreted"])


@pytest.fixture(scope="module")
def params():
    return mimo_v2.init_params(CFG, jax.random.key(0), jnp.float32)


@functools.lru_cache(maxsize=None)
def _programs(slots, span, new, interpret):
    prefill = jax.jit(lambda p, ids, lengths: mimo_v2.prefill(
        p, CFG, ids, lengths, slots + new, 1, span, interpret=interpret))
    step = jax.jit(lambda p, cache, tokens, lengths, number: mimo_v2.step(
        p, CFG, tokens, lengths, number, slots, cache,
        mimo_v2.empty_load(CFG))[:2])
    return prefill, step


def _served(params, lengths, slots, span, new, interpret=False):
    """(prompts + given tokens a row, the served logits [rows, 1 + new,
    vocab]): the prefill in spans, then `new` steps with given tokens."""
    rng = np.random.default_rng(sum(lengths))
    lengths = np.array(lengths, np.int32)
    ids = np.zeros((len(lengths), slots), np.int32)
    for row, length in enumerate(lengths):
        ids[row, :length] = rng.integers(0, CFG.vocab_size, length)
    given = rng.integers(0, CFG.vocab_size, (len(lengths), new)).astype(
        np.int32)
    prefill, step = _programs(slots, span, new, interpret)
    logits, cache, _ = prefill(params, ids, lengths)
    got = [logits]
    for number in range(new):
        logits, cache = step(params, cache, given[:, number], lengths,
                             number)
        got.append(logits)
    rows = [np.concatenate([ids[row, :length], given[row]])
            for row, length in enumerate(lengths)]
    return rows, np.stack([np.asarray(x) for x in got], axis=1)


def _apart(params, rows, got, lengths, control=None):
    """Relative L2 of the served logits against the reference's, a row."""
    out = []
    for row, length, mine in zip(rows, lengths, got):
        want = np.asarray(gqa_sink_moe.forward(
            params, SIZES, row, held=CFG.experts_held,
            positions=np.arange(length - 1, len(row)), control=control))
        out.append(float(np.linalg.norm(mine - want)
                         / np.linalg.norm(want)))
    return out


# rows of 29 and 13 ids in spans of 8 (the window is 4: a span is two
# windows, the first row three spans and a half), then 7 given tokens, which
# wrap the ring of 4 nearly twice
LENGTHS = [29, 13]


@pytest.fixture(scope="module")
def served(params):
    return _served(params, LENGTHS, 32, 8, 7)


@PATHS
def test_spans_and_cached_decode_give_the_references_logits(params, served,
                                                            interpret):
    rows, got = _served(params, LENGTHS, 32, 8, 7, True) if interpret \
        else served
    assert max(_apart(params, rows, got, LENGTHS)) < LIMIT


@pytest.mark.parametrize("control", gqa_sink_moe.CONTROLS[1:])
def test_the_three_controls_fail_the_limit(params, served, control):
    assert min(_apart(params, *served, LENGTHS, control)) > 100 * LIMIT


# --- the kernel --------------------------------------------------------------


def _operands(sq, skv, heads, kv_heads, sink, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (1, sq, heads, 192))
    k = jax.random.normal(ks[1], (1, skv, kv_heads, 192))
    v = jax.random.normal(ks[2], (1, skv, kv_heads, 128))
    return q, k, v, (2.0 * jax.random.normal(ks[3], (heads,))
                     if sink else None)


def _explicit(q, k, v, window, sink, offset, floor):
    """The softmax written out in numpy, the sink as one more term of the
    denominator."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    b, sq, heads, d = q.shape
    group = heads // k.shape[2]
    out = np.zeros((b, sq, heads, v.shape[-1]))
    for head in range(heads):
        scores = np.einsum("bqd,bkd->bqk", q[:, :, head],
                           k[:, :, head // group]) * d ** -0.5
        row = offset + np.arange(sq)[:, None]
        col = np.arange(k.shape[1])[None, :]
        seen = (col <= row) & (col >= floor)
        if window:
            seen &= row - col < window
        scores = np.where(seen, scores, -np.inf)
        top = scores.max(-1, keepdims=True)
        if sink is not None:
            top = np.maximum(top, float(sink[head]))
        weights = np.exp(scores - top)
        total = weights.sum(-1, keepdims=True)
        if sink is not None:
            total = total + np.exp(float(sink[head]) - top)
        out[:, :, head] = np.einsum("bqk,bkd->bqd", weights / total,
                                    v[:, :, head // group])
    return out


@pytest.mark.parametrize(
    "sq, skv, heads, kv_heads, window, sink, offset, floor, blocks", [
        (24, 24, 16, 1, 0, False, None, None, None),
        (16, 300, 16, 1, 0, False, 140, None, (8, 128)),
        (16, 300, 16, 2, 0, True, 284, None, (8, 128)),
        (13, 300, 16, 1, 0, False, 100, None, (8, 128)),
        (40, 168, 16, 2, 128, True, None, 128, (16, 128)),
        (40, 168, 16, 2, 128, True, None, 0, (16, 128)),
        (200, 328, 8, 1, 128, False, None, None, (64, 128)),
    ], ids=["whole_row_group_16", "chunk_at_an_offset", "last_span_sink",
            "row_shorter_than_its_span", "first_span_behind_an_empty_tail",
            "later_span_behind_its_tail", "window_no_sink"])
def test_the_kernel_gives_the_explicit_softmax(sq, skv, heads, kv_heads,
                                               window, sink, offset, floor,
                                               blocks):
    """Keys of 192 on values of 128, the kernel interpreted (with blocks
    small enough that a call has several) and its reference path, both
    against the softmax written out."""
    q, k, v, sinks = _operands(sq, skv, heads, kv_heads, sink)
    traced = (None if offset is None else jnp.int32(offset),
              None if floor is None else jnp.int32(floor))
    got = kernel._wide_key_pallas(q, k, v, sinks, *traced, window=window,
                                  blocks=blocks, interpret=True)
    plain = kernel.wide_key_reference(q, k, v, None, window, sinks, *traced)
    want = _explicit(q, k, v, window, sinks,
                     skv - sq if offset is None else offset, floor or 0)
    assert got.shape == (1, sq, heads, 128)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(plain), want, atol=2e-5)


@PATHS
def test_a_sink_far_above_takes_all_and_far_below_takes_nothing(interpret):
    q, k, v, _ = _operands(40, 168, 16, 2, False, seed=1)
    call = functools.partial(kernel.wide_key_attention, q, k, v, window=128,
                             interpret=interpret)
    none = np.asarray(call())
    high = np.asarray(call(sink=jnp.full((16,), 60.0)))
    low = np.asarray(call(sink=jnp.full((16,), -60.0)))
    assert np.abs(high).max() < 1e-12 < np.abs(none).max()
    np.testing.assert_allclose(low, none, atol=1e-6)


def test_what_lies_past_a_spans_end_is_not_read():
    """A full layer's call at `offset` walks the keys up to `offset + Sq`:
    whatever the cache holds past it changes nothing."""
    q, k, v, _ = _operands(16, 512, 16, 1, False, seed=2)
    call = functools.partial(kernel._wide_key_pallas, q, offset=jnp.int32(112),
                             blocks=(8, 128), interpret=True)
    junk = jnp.arange(512)[None, :, None, None] >= 128
    np.testing.assert_array_equal(
        np.asarray(call(k, v)),
        np.asarray(call(jnp.where(junk, jnp.nan, k),
                        jnp.where(junk, jnp.nan, v))))


def test_the_blocks_hold_a_group_of_sixteen_inside_fast_memory():
    """The cell's two calls: a full layer's group of 16 takes 256 queries a
    step, a window layer's group of 8 the window's 256."""
    from chiaswarm_tpu.ops.flash_attention import _VMEM_CAP

    for group, window, want in ((16, 0, (256, 512)), (8, 128, (256, 256))):
        blocks = kernel.wide_key_blocks(4096, 32768, window, group,
                                        jnp.bfloat16)
        assert blocks == want
        assert kernel.step_vmem_bytes(*blocks, group, 256, 128,
                                      2) < _VMEM_CAP // 2


# --- a decode step's attention -----------------------------------------------


def _cached_attention_before(q, keys, values, mask, scale: float):
    """`models/text_model.py` `cached_attention` as it was before ISSUE 57
    (PR 56's tree), kept to hold the lowered text of every present caller
    to what it was."""
    rows, heads, d = q.shape
    kv_heads = keys.shape[2]
    q = q.reshape(rows, kv_heads, heads // kv_heads, d)
    scores = jnp.einsum("rhgd,rshd->rhgs", q, keys,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1).astype(values.dtype)
    out = jnp.einsum("rhgs,rshd->rhgd", weights, values,
                     preferred_element_type=jnp.float32)
    return out.astype(values.dtype).reshape(rows, heads * d)


def test_cached_attention_without_a_sink_lowers_as_it_did():
    q = jax.ShapeDtypeStruct((4, 8, 16), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((4, 40, 2, 16), jnp.bfloat16)
    mask = jax.ShapeDtypeStruct((4, 40), jnp.bool_)

    def lowered(function):
        def cached_attention(q, keys, values, mask):
            return function(q, keys, values, mask, 0.25)

        return jax.jit(cached_attention).lower(q, kv, kv, mask).as_text()

    assert lowered(text_model.cached_attention) == lowered(
        _cached_attention_before)


def test_cached_attention_with_a_sink_and_narrower_values():
    ks = jax.random.split(jax.random.key(4), 4)
    q = jax.random.normal(ks[0], (3, 8, 24))
    keys = jax.random.normal(ks[1], (3, 10, 2, 24))
    values = jax.random.normal(ks[2], (3, 10, 2, 16))
    sink = jax.random.normal(ks[3], (8,))
    mask = jnp.arange(10)[None, :] < jnp.array([10, 4, 7])[:, None]
    got = text_model.cached_attention(q, keys, values, mask, 24 ** -0.5,
                                      sink=sink)
    assert got.shape == (3, 8 * 16)
    for row, seen in enumerate((10, 4, 7)):
        want = _explicit(q[row][None, None], keys[row][None, :seen],
                         values[row][None, :seen], 0, sink, seen - 1, 0)
        np.testing.assert_allclose(np.asarray(got[row]).reshape(8, 16),
                                   want[0, 0], atol=2e-6)


# --- the share ---------------------------------------------------------------


def test_the_sixteen_shares_of_an_expert_layer_add_up_to_the_uncut_layer(
        params):
    """Sixteen chips of two experts each: their parts (nothing is shared
    here, so nothing is counted once) are the layer with all 32 experts."""
    moe = params["layers"][1]["moe"]
    assert "shared" not in moe
    h = jax.random.normal(jax.random.key(3), (24, CFG.hidden_size))
    stacks = {name: jax.random.normal(
        jax.random.key(10 + n), (32, *moe["experts"][name].shape[1:]))
        / np.sqrt(moe["experts"][name].shape[1])
        for n, name in enumerate(("gate", "up", "down"))}
    total = np.zeros((24, CFG.hidden_size), np.float32)
    for share in range(16):
        cfg = dataclasses.replace(CFG, experts_held=(2 * share, 2))
        mine = dict(moe, experts={name: stack[2 * share:2 * share + 2]
                                  for name, stack in stacks.items()})
        total += np.asarray(experts.expert_layer(mine, cfg, h)[0])
    sizes = {**SIZES, "routed_scaling_factor": 1.0}
    want = gqa_sink_moe.experts(dict(moe, experts=stacks), sizes, h, (0, 32))
    np.testing.assert_allclose(total, np.asarray(want), atol=2e-5)
    # ... which is `mla_moe.py`'s layer less its shared expert
    zero = jax.tree_util.tree_map(
        jnp.zeros_like, {name: stack[0] for name, stack in stacks.items()})
    np.testing.assert_allclose(np.asarray(want), np.asarray(mla_moe.experts(
        dict(moe, experts=stacks, shared=zero), sizes, h, (0, 32))),
        atol=2e-5)


def test_the_share_counts_the_issues_parameters():
    whole = mimo_v2.MIMO_V25_EP16
    shapes = mimo_v2.param_shapes(whole, jnp.bfloat16)

    def count(tree):
        return sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree_util.tree_leaves(tree))

    layers = shapes["layers"]
    assert [count(layer) for layer in layers] == [
        290463744, *[498082112] * 5, 492839168]
    assert count(shapes) == 3429955392
    assert ["sink" in layer["attn"] for layer in layers] == [
        False, True, True, True, True, True, False]
    assert (whole.rotary_dim, mimo_v2.MIMO_TINY.rotary_dim) == (64, 8)
    assert whole.windows == (0, 128, 128, 128, 128, 128, 0)
    # the published 48: 9 full layers, 39 window layers, one dense
    published = mimo_v2.MimoV2Config()
    assert (published.windows.count(0), published.expert_layers) == (9, 47)
    # two geometries: 2560 B a position on the two full layers, rings of
    # 128 columns of 5120 B on the five window layers
    assert mimo_v2.cache_bytes(whole, 2, 32896, 2) == (
        2 * 32896 * 5120 + 2 * 128 * 25600, 2 * 128 * 25600, 0)
    # a row of eight spans walks 36 of its 64 span-widths, a full layer
    assert mimo_v2.prefill_key_extent(whole, [30000, 29000], 32768, 1,
                                      4096) == (2 * 2 * 36 * 4096,
                                                2 * 2 * 64 * 4096)
