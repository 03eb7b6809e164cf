"""GLM-5's language model (ISSUE 49): latent attention over the keys a
lightning indexer selects, two caches a layer, sigmoid-routed experts of
which this chip holds a share, on the CPU at the tiny preset, against the
plain reference (benchmark/reference/dsa_mla_moe.py):

(a) prefill in spans + cached decode steps against the reference's ONE
    full forward pass, by logits, with more cached positions than the tiny
    `index_topk` (the selection bites) and with fewer (it is dense), the
    operations on their plain path and as kernels (interpreted);
(b) two controls that FAIL the same limit: attention over every visible
    key, and a selection from index scores rounded to 8 bits;
(c) each operation against the benchmark's plain reference on either
    path, the selection on ties, on fewer visible positions than
    `index_topk` and on a row shorter than its span;
(d) the 16 shares of one sparse layer add up to the uncut layer;
(e) the selection's tally: what the queries saw and what attention read,
    by phase, past 32 bits;
(f) a span's key side bounded by the span's end (ISSUE 50): each of the
    four operations against its plain reference on the columns below the
    end, at a first, a middle and the last span of a bucket of several
    blocks; what the caches hold past the end changes nothing; called
    without a bound the indexer and the selection give whole results; and
    the key positions walked of the bucket's, through the pipeline.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import dsa_kernels, dsa_mla_moe, mla_moe
from chiaswarm_tpu.models import experts, glm_moe_dsa
from chiaswarm_tpu.ops import lightning_indexer as indexer
from chiaswarm_tpu.ops import sparse_latent_attention as attention

CFG = glm_moe_dsa.GLM5_TINY
SIZES = {
    "hidden_size": 64, "q_lora_rank": 32, "kv_lora_rank": 16,
    "num_attention_heads": 4, "qk_nope_head_dim": 24, "qk_rope_head_dim": 8,
    "v_head_dim": 32, "index_n_heads": 4, "index_head_dim": 16,
    "index_topk": 8, "n_routed_experts": 32, "num_experts_per_tok": 4,
    "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}}
# float32 on both sides: what a sound program reads is rounding (1e-6);
# the controls read a thousand times the limit
LIMIT = 1e-4
PATHS = pytest.mark.parametrize("interpret", [False, True],
                                ids=["plain_path", "kernels_interpreted"])


@pytest.fixture(scope="module")
def params():
    return glm_moe_dsa.init_params(CFG, jax.random.key(0), jnp.float32)


@functools.lru_cache(maxsize=None)
def _programs(slots, span, new, interpret):
    """(prefill in spans of `span`, a decode step), compiled once a shape
    for every test here."""
    return (jax.jit(lambda p, i, n: glm_moe_dsa.prefill(
        p, CFG, i, n, slots + new, 1, span, interpret=interpret)),
        jax.jit(lambda p, t, n, number, c, tally: glm_moe_dsa.step(
            p, CFG, t, n, number, slots, c, tally, interpret=interpret)))


def _served(params, lengths, interpret, slots=16, span=8, new=3, seed=0):
    """Logits [rows, 1 + new, vocab] of prefill in spans of `span` + `new`
    decode steps with given tokens, the sequences, and the tally after
    either program."""
    rng = np.random.default_rng(seed)
    lengths = np.array(lengths, np.int32)
    rows = len(lengths)
    ids = np.zeros((rows, slots), np.int32)
    for row, length in enumerate(lengths):
        ids[row, :length] = rng.integers(0, CFG.vocab_size, length)
    given = rng.integers(0, CFG.vocab_size, (rows, new)).astype(np.int32)
    prefill, step = _programs(slots, span, new, interpret)
    logits, cache, load = prefill(params, ids, lengths)
    filled = load
    got = [logits]
    for number in range(new):
        logits, cache, load = step(params, given[:, number], lengths, number,
                                   cache, load)
        got.append(logits)
    sequences = [np.concatenate([ids[row, :length], given[row]])
                 for row, length in enumerate(lengths)]
    return (np.stack([np.asarray(g) for g in got], axis=1), sequences,
            filled, load)


def _rel(got, want):
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@PATHS
@pytest.mark.parametrize("lengths, bites", [
    ([16, 13], True), ([4, 2], False)], ids=["selected", "dense"])
def test_spans_and_cached_decode_give_the_references_logits(
        params, lengths, bites, interpret):
    new = 3
    got, sequences, filled, load = _served(params, lengths, interpret)
    for row, ids in enumerate(sequences):
        at = np.arange(lengths[row] - 1, len(ids))
        want = np.asarray(dsa_mla_moe.forward(
            params, SIZES, ids, held=CFG.experts_held, positions=at))
        assert _rel(got[row], want) < LIMIT
    # the selection's tally: every real query, every layer
    visible, selected, *extent = glm_moe_dsa.selection_counts(
        np.asarray(load[2]))
    before = glm_moe_dsa.selection_counts(np.asarray(filled[2]))
    layers, topk = CFG.num_hidden_layers, CFG.index_topk
    seen = [np.arange(1, length + 1) for length in lengths]
    assert before[:2] == (
        layers * sum(int(s.sum()) for s in seen),
        layers * sum(int(np.minimum(s, topk).sum()) for s in seen))
    # the key positions the prefill's spans walked, of spans x the bucket
    # (spans of 8 in 16 slots: a row over 8 ids runs both, 8 + 16 of 2 x
    # 16; a shorter one the first alone); a decode step adds none
    ends = [[8, 16] if length > 8 else [8] for length in lengths]
    assert list(before[2:]) == extent == [
        layers * sum(map(sum, ends)), layers * 16 * sum(map(len, ends))]
    steps = [length + 1 + np.arange(new) for length in lengths]
    assert (visible - before[0], selected - before[1]) == (
        layers * sum(int(s.sum()) for s in steps),
        layers * sum(int(np.minimum(s, topk).sum()) for s in steps))
    assert (selected < visible) == bites


def test_the_two_controls_fail_the_limit(params):
    """Attention over every visible key, and a selection from index scores
    rounded to 8 bits, are other functions: the comparison that passes the sound
    program by a factor of a hundred refuses both."""
    got, sequences, _, _ = _served(params, [16, 16], False)
    for row, ids in enumerate(sequences):
        at = np.arange(len(ids) - 4, len(ids))
        sound = _rel(got[row], np.asarray(dsa_mla_moe.forward(
            params, SIZES, ids, held=CFG.experts_held, positions=at)))
        assert sound < LIMIT
        for control in ("none", "int8"):
            want = np.asarray(dsa_mla_moe.forward(
                params, SIZES, ids, held=CFG.experts_held, positions=at,
                selection=control))
            assert _rel(got[row], want) > 100 * LIMIT, control


def _operands(sq, skv, heads=4, dim=16, seed=1):
    keys = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(keys[0], (2, sq, heads, dim)),
            jax.random.normal(keys[1], (2, sq, heads)) / 8,
            jax.random.normal(keys[2], (2, skv, dim)))


@PATHS
@pytest.mark.parametrize("sq, skv", [(24, 40), (16, 16), (5, 300)])
def test_the_indexer_gives_the_references_scores(sq, skv, interpret):
    q, w, k = _operands(sq, skv)
    got = indexer.lightning_indexer(q, w, k, interpret=interpret)
    for row in range(2):
        want = dsa_kernels.index_scores(q[row], w[row], k[row])
        seen = np.isfinite(np.asarray(want))
        assert (np.isfinite(np.asarray(got[row])) == seen).all()
        np.testing.assert_allclose(np.asarray(got[row])[seen],
                                   np.asarray(want)[seen], atol=2e-5)
    # one decode position a row, seeing what a mask says
    visible = jax.random.bernoulli(jax.random.key(2), 0.7, (2, skv))
    one = indexer.lightning_indexer(q[:, :1], w[:, :1], k, visible)
    whole = indexer.indexer_reference(
        q[:, :1], w[:, :1], k, jnp.ones((2, skv), bool))
    assert (np.isfinite(np.asarray(one[:, 0])) == np.asarray(visible)).all()
    np.testing.assert_allclose(
        np.asarray(one)[np.isfinite(np.asarray(one))],
        np.asarray(whole)[np.isfinite(np.asarray(one))], atol=2e-5)


@PATHS
@pytest.mark.parametrize("case", ["ties", "few_visible", "short_row"])
def test_the_selection_is_top_ks_on_ties_and_short_rows(case, interpret):
    """Exactly `jax.lax.top_k`'s choice as a mask: ties to the lower
    position, every visible position where there are `topk` at most, and
    a row shorter than its span (queries past its end see what is there)."""
    sq, skv, topk = 24, 40, 8
    q, w, k = _operands(sq, skv, seed=3)
    scores = indexer.indexer_reference(q, w, k)
    if case == "ties":
        # a few distinct values: most of the choice is the tie's
        scores = jnp.where(jnp.isfinite(scores), jnp.round(scores), scores)
    elif case == "few_visible":
        sq = skv = 10
        scores, topk = scores[:, :sq, :skv] + jnp.where(
            jnp.tril(jnp.ones((sq, skv), bool)), 0.0, -jnp.inf), 16
    else:
        # the row's prompt ends at position 20: later keys hold padding,
        # which only padding's queries see; a real query's choice is among
        # its own
        topk = 12
    mask, count = indexer.index_select(scores, topk, interpret=interpret)
    for row in range(2):
        want = np.asarray(dsa_kernels.selection(scores[row], topk))
        assert (np.asarray(mask[row] != 0) == want).all()
        assert (np.asarray(count[row]) == want.sum(-1)).all()
    visible = np.isfinite(np.asarray(scores)).sum(-1)
    assert (np.asarray(count) == np.minimum(visible, topk)).all()
    if case == "short_row":
        real = np.asarray(mask)[:, :20 - (skv - sq)]
        assert not real[:, :, 20:].any()
    # the decode's form: columns, and which of them are visible
    columns, chosen = indexer.index_select(scores[:, -1:], topk, "indices")
    picked = np.zeros((2, scores.shape[-1]), bool)
    for row in range(2):
        picked[row, np.asarray(columns[row, 0])[np.asarray(chosen[row, 0])]] \
            = True
    assert (picked == np.asarray(mask[:, -1] != 0)).all()


@PATHS
def test_masked_attention_gives_the_references_output(interpret):
    sq, skv, heads, dim, topk = 24, 40, 4, 32, 8
    keys = jax.random.split(jax.random.key(4), 4)
    q = jax.random.normal(keys[0], (2, sq, heads * dim))
    k = jax.random.normal(keys[1], (2, skv, heads * dim))
    v = jax.random.normal(keys[2], (2, skv, heads * dim))
    scores = indexer.indexer_reference(*_operands(sq, skv, seed=5))
    mask, _ = indexer.index_select(scores, topk)
    got = attention.sparse_prefill_attention(
        q, k, v, mask, dim ** -0.5, heads, interpret=interpret)
    for row in range(2):
        want = dsa_kernels.masked_attention(
            q[row], k[row], v[row], mask[row], dim ** -0.5, heads)
        np.testing.assert_allclose(np.asarray(got[row]), np.asarray(want),
                                   atol=2e-5)
        # the selection left out is another function
        dense = dsa_kernels.masked_attention(
            q[row], k[row], v[row], jnp.isfinite(scores[row]), dim ** -0.5,
            heads)
        assert float(jnp.max(jnp.abs(got[row] - dense))) > 0.05


# a bucket of four spans of 96 positions over blocks of 128 (the kernels'
# own blocks shrunk: 1024 keys, 2048 columns and 512 rows a step on the
# chip): the first span's end cuts block 0 and walks one of three, the
# second's cuts block 1, the last's falls on the bucket's
BUCKET, SPAN = 384, 96


@pytest.fixture
def small_blocks(monkeypatch):
    """The four kernels at blocks of 128, traced anew (a jitted function
    keeps what it traced under the module's constants of the time)."""
    kernels = (indexer._indexer_pallas, indexer._select_pallas,
               attention._expand_pallas, attention._prefill_pallas)
    monkeypatch.setattr(indexer, "_BLOCK_K", 128)
    monkeypatch.setattr(indexer, "_SELECT_COLUMNS", 128)
    monkeypatch.setattr(attention, "_BLOCK_K", 128)
    monkeypatch.setattr(attention, "_EXPAND_ROWS", 128)
    for kernel in kernels:
        kernel.clear_cache()
    yield
    for kernel in kernels:
        kernel.clear_cache()


@pytest.mark.parametrize("number", [0, 1, 3], ids=["first", "middle", "last"])
def test_a_span_bounded_by_its_end_gives_the_references(small_blocks,
                                                        number):
    """Interpreted, where a result's blocks no grid step wrote hold NaN
    (int8: -128) and an operation that read one would show it: the scores
    past the walked key blocks, the expanded rows past the walked row
    blocks."""
    heads, dim, latent, rope, topk = 2, 32, 48, 16, 24
    start, end = number * SPAN, (number + 1) * SPAN
    q_i, w_i, k_i = _operands(SPAN, BUCKET, seed=7)
    scores = indexer.lightning_indexer(q_i, w_i, k_i, offset=start, end=end,
                                       interpret=True)
    walked = -(-end // 128) * 128
    assert np.isnan(np.asarray(scores[..., walked:])).all()
    mask, count = indexer.index_select(scores, topk, end=end, interpret=True)
    keys = jax.random.split(jax.random.key(8), 4)
    latents = jax.random.normal(keys[0], (2, BUCKET, latent + rope))
    key_up = jax.random.normal(keys[1], (latent + rope, heads * dim)) / 8
    value_up = jax.random.normal(keys[2], (latent, heads * dim)) / 8
    k, v = attention.expand_latents(latents, key_up, value_up, end,
                                    interpret=True)
    assert np.isnan(np.asarray(k[:, walked:])).all()
    q = jax.random.normal(keys[3], (2, SPAN, heads * dim))
    got = attention.sparse_prefill_attention(
        q, k, v, mask, dim ** -0.5, heads, offset=start, interpret=True)
    for row in range(2):
        want = dsa_kernels.index_scores(q_i[row], w_i[row], k_i[row, :end])
        seen = np.isfinite(np.asarray(want))
        mine = np.asarray(scores[row, :, :end])
        assert ((mine > -np.inf) == seen).all()
        np.testing.assert_allclose(mine[seen], np.asarray(want)[seen],
                                   atol=2e-5)
        chosen = np.asarray(dsa_kernels.selection(scores[row, :, :end], topk))
        assert (np.asarray(mask[row, :, :end] != 0) == chosen).all()
        assert not np.asarray(mask[row, :, end:]).any()
        assert (np.asarray(count[row]) == chosen.sum(-1)).all()
        np.testing.assert_allclose(
            np.asarray(k[row, :end]), np.asarray(latents[row, :end] @ key_up),
            atol=2e-5)
        np.testing.assert_allclose(
            np.asarray(v[row, :end]),
            np.asarray(latents[row, :end, :latent] @ value_up), atol=2e-5)
        np.testing.assert_allclose(
            np.asarray(got[row]), np.asarray(dsa_kernels.masked_attention(
                q[row], latents[row, :end] @ key_up,
                latents[row, :end, :latent] @ value_up, chosen, dim ** -0.5,
                heads)), atol=2e-5)


@PATHS
@pytest.mark.parametrize("number", [0, 1], ids=["first", "middle"])
def test_what_lies_past_a_spans_end_is_not_read(small_blocks, params,
                                                number, interpret):
    """NaN in both caches' columns past the span's end (a later span will
    write there) leaves the output, the columns the span wrote and the
    tally's part bit for bit what they are over zeros."""
    layer = params["layers"][1]["attn"]
    start, end = number * SPAN, (number + 1) * SPAN
    keys = jax.random.split(jax.random.key(9), 3)
    h = jax.random.normal(keys[0], (2, SPAN, CFG.hidden_size))
    past = (jnp.arange(BUCKET) >= end)[None, :, None]
    before = (jnp.arange(BUCKET) < start)[None, :, None]
    cache = tuple(
        jnp.where(before, jax.random.normal(key, (2, BUCKET, width)), 0.0)
        for key, width in zip(keys[1:], (CFG.cache_width,
                                         CFG.index_head_dim)))
    real = jnp.arange(SPAN)[None, :] < jnp.array([[SPAN], [SPAN - 5]])
    run = jax.jit(lambda cache: glm_moe_dsa.attention_prefill(
        layer, CFG, h, jnp.int32(start), cache, real, interpret))
    out, written, seen = run(cache)
    again, poisoned, seen_again = run(
        tuple(jnp.where(past, jnp.nan, whole) for whole in cache))
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_array_equal(np.asarray(again), np.asarray(out))
    np.testing.assert_array_equal(np.asarray(seen_again), np.asarray(seen))
    for mine, clean in zip(poisoned, written):
        np.testing.assert_array_equal(np.asarray(mine[:, :end]),
                                      np.asarray(clean[:, :end]))
    assert np.asarray(seen)[2:].tolist() == [2 * end, 2 * BUCKET]


def test_without_a_bound_the_indexer_and_the_selection_are_whole(
        small_blocks):
    """As the benchmark's operation-level comparison calls them (no
    offset, no end): `-inf` wherever a query does not see, a whole mask,
    bit for bit `jax.lax.top_k`'s, over every block."""
    q, w, k = _operands(SPAN, BUCKET, seed=11)
    scores = indexer.lightning_indexer(q, w, k, interpret=True)
    want = indexer.indexer_reference(q, w, k)
    assert not np.isnan(np.asarray(scores)).any()
    assert (np.isfinite(np.asarray(scores))
            == np.isfinite(np.asarray(want))).all()
    mask, count = indexer.index_select(scores, 24, interpret=True)
    plain, plain_count = indexer.select_reference(scores, 24)
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(plain))
    np.testing.assert_array_equal(np.asarray(count), np.asarray(plain_count))
    columns, chosen = indexer.index_select(scores[:, -1:], 24, "indices",
                                           interpret=True)
    assert (np.asarray(columns[:, 0])[np.asarray(chosen[:, 0])]
            == np.nonzero(np.asarray(mask[:, -1]))[1]).all()


@pytest.mark.parametrize("length, tokens, share", [
    (60, 8, (36, 64)), (7, 4096, (1, 1))], ids=["eight_spans", "one_span"])
def test_the_pipeline_reads_back_the_key_positions_walked(
        monkeypatch, length, tokens, share):
    """A row of eight spans walks 1 + 2 + ... + 8 = 36 span widths of its
    8 x 8, a row of one span its bucket: the envelope and the counter, a
    layer each."""
    from chiaswarm_tpu.pipelines import text_generation

    monkeypatch.setattr(text_generation, "PREFILL_CHUNK_TOKENS", tokens)
    pipe = text_generation.TextGenerationPipeline(
        "test/tiny-glm-5", allow_random_init=True)
    label = {"model": "test/tiny-glm-5"}
    counter = text_generation.PREFILL_KEY_EXTENT
    before = [counter.value(extent=extent, **label)
              for extent in ("walked", "bucket")]
    rows = [np.random.default_rng(5).integers(0, 128, length).tolist()]
    ((_, config),) = pipe.run_batched(
        [{"prompt_ids": rows, "rng": jax.random.key(1)}], max_new_tokens=2)
    extent = config["selection"]["prefill_key_extent"]
    spans = config["prefill_chunks"]
    assert spans == (8 if length > tokens else 1)
    slots, layers = config["prompt_slots"], CFG.num_hidden_layers
    assert (extent["walked"] * share[1], extent["bucket"]) == (
        extent["bucket"] * share[0], layers * spans * slots)
    assert [counter.value(extent=name, **label) - was for name, was in zip(
        ("walked", "bucket"), before)] == [extent["walked"],
                                          extent["bucket"]]


def test_a_decode_step_reads_the_selected_rows_alone():
    rows, positions, heads, latent, rope, topk = 3, 50, 4, 16, 8, 8
    keys = jax.random.split(jax.random.key(6), 4)
    q_lat = jax.random.normal(keys[0], (rows, heads, latent))
    q_rope = jax.random.normal(keys[1], (rows, heads, rope))
    cache = jax.random.normal(keys[2], (rows, positions, latent + rope))
    seen = jnp.array([50, 23, 5])  # the last row sees fewer than `topk`
    scores = jnp.where(jnp.arange(positions)[None, :] < seen[:, None],
                       jax.random.normal(keys[3], (rows, positions)),
                       -jnp.inf)
    columns, chosen = indexer.index_select(scores[:, None], topk, "indices")
    got, read = attention.sparse_decode_attention(
        q_lat, q_rope, cache, columns[:, 0], chosen[:, 0], 0.2)
    mask = jnp.stack([dsa_kernels.selection(row[None], topk)[0]
                      for row in scores])
    want = dsa_kernels.latent_attention(q_lat, q_rope, cache, mask, 0.2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert np.asarray(read).tolist() == [topk] * rows
    assert np.asarray(chosen[:, 0]).sum(-1).tolist() == [8, 8, 5]
    # what the other positions hold does not matter: they are not read
    other = jnp.where(mask[..., None], cache, jnp.nan)
    again, _ = attention.sparse_decode_attention(
        q_lat, q_rope, other, columns[:, 0], chosen[:, 0], 0.2)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(got))


def test_the_sixteen_shares_of_a_sparse_layer_add_up_to_the_uncut_layer(
        params):
    """Sixteen chips of two experts each: their parts, the shared expert
    counted once, are the reference's layer with all 32 experts."""
    moe = params["layers"][1]["moe"]
    h = jax.random.normal(jax.random.key(3), (24, CFG.hidden_size))
    stacks = {name: jax.random.normal(
        jax.random.key(10 + n), (32, *moe["experts"][name].shape[1:]))
        / np.sqrt(moe["experts"][name].shape[1])
        for n, name in enumerate(("gate", "up", "down"))}
    shared = np.asarray(experts.swiglu(moe["shared"], h))
    total = np.zeros_like(shared)
    for share in range(16):
        cfg = dataclasses.replace(CFG, experts_held=(2 * share, 2))
        mine = dict(moe, experts={name: stack[2 * share:2 * share + 2]
                                  for name, stack in stacks.items()})
        out, _ = experts.expert_layer(mine, cfg, h)
        total += np.asarray(out) - shared
    want = mla_moe.experts(dict(moe, experts=stacks), SIZES, h, (0, 32))
    np.testing.assert_allclose(total + shared, np.asarray(want), atol=2e-5)


def test_the_selections_tally_counts_past_32_bits():
    load = glm_moe_dsa.empty_load(CFG)
    assert [leaf.shape for leaf in load] == [
        (CFG.expert_layers, CFG.experts_held[1]), (4,), (4, 2)]
    add = jax.jit(lambda load, seen: glm_moe_dsa.tally(
        load, 0, CFG, None, seen))
    seen = jnp.array([2 ** 30 - 1, 2 ** 29 + 7, 36, 64], jnp.int32)
    for _ in range(9):
        load = add(load, seen)
    assert glm_moe_dsa.selection_counts(np.asarray(load[2])) == (
        9 * (2 ** 30 - 1), 9 * (2 ** 29 + 7), 9 * 36, 9 * 64)
    # both caches are counted, and the index keys' part is said apart
    whole = glm_moe_dsa.GLM5_EP16
    assert glm_moe_dsa.cache_bytes(whole, 2, 32896, 2) == (
        2 * 32896 * (576 + 128) * 2 * 5, 0, 0)
    assert glm_moe_dsa.index_cache_bytes(whole, 2, 32896, 2) == (
        2 * 32896 * 128 * 2 * 5)
    cache = glm_moe_dsa.new_cache(CFG, 3, 10, jnp.float32)
    assert [tuple(x.shape for x in layer) for layer in cache] == [
        ((3, 10, 24), (3, 10, 16))] * CFG.num_hidden_layers


def test_the_share_counts_the_issues_parameters():
    """One dense and four sparse layers of the 16-chip share: 3,909,632,768
    parameters, 7.82 GB in bfloat16."""
    shapes = glm_moe_dsa.param_shapes(glm_moe_dsa.GLM5_EP16, jnp.bfloat16)
    count = sum(int(np.prod(leaf.shape))
                for leaf in jax.tree_util.tree_leaves(shapes))
    assert count == 3_909_632_768
    attn = sum(int(np.prod(leaf.shape)) for leaf in
               jax.tree_util.tree_leaves(shapes["layers"][1]["attn"]))
    assert attn == 165_022_208 + 9_371_904
    assert glm_moe_dsa.config_for("test/GLM-5") is glm_moe_dsa.GLM5_EP16
    assert glm_moe_dsa.config_for("test/tiny-glm-5") is CFG
