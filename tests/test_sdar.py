"""SDAR's language model (ISSUE 40): a mask that is causal between blocks
and bidirectional inside one, softmax-routed experts with every expert
held, and a decode that yields a block of tokens over several forwards, on
the CPU at the tiny preset (block length 4, 16 experts, 4 a token, 3
layers), against the plain reference
(benchmark/reference/block_diffusion_moe.py):

(a) prefill + given blocks through the pipeline's `block_program` against
    the reference's one full forward pass, by logits: lengths with every `L
    mod B`, whole rows a chunk and position chunks;
(b) a forward without `commit` leaves the cache, and the next block's
    logits, as they were; a fused forward (ISSUE 41: the finished block
    beside the new one, eight positions a row) writes what a commit of
    the finished block writes and yields the new block's logits;
(c) the un-masking rule against the plain one: static at 1, 2 and 4
    forwards a block, under a threshold with and without enough positions
    over it, a first block with a given tail;
(d) the served ids are a plain loop over `block_program` with the same
    keys, and a row's ids do not change with its batchmates;
(e) `span=` on `ops.attention` and on the banded kernel (interpreted)
    against a masked softmax, `span=0` the graph it was;
(f) the expert layer under the softmax rule with every expert held is the
    reference's uncut layer, and two halves of the experts add up to it.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import block_diffusion_moe as reference
from chiaswarm_tpu.models import experts, sdar
from chiaswarm_tpu.ops import dot_product_attention, sampling
from chiaswarm_tpu.ops.attention import reference_attention
from chiaswarm_tpu.ops.banded_attention import band_blocks, banded_attention
from chiaswarm_tpu.pipelines.text_generation import TextGenerationPipeline

CFG = sdar.SDAR_TINY
B = CFG.block_length
SIZES = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "num_experts": 16, "num_experts_per_tok": 4,
    "rms_norm_eps": 1e-6, "rope_theta": 1e6, "block_length": B}


@pytest.fixture(scope="module")
def pipe():
    return TextGenerationPipeline("test/tiny-sdar", allow_random_init=True)


def test_the_tiny_preset_is_the_cut_in_small():
    full = sdar.SDAR_30B_PP8
    assert (full.hidden_size, full.num_attention_heads,
            full.num_key_value_heads, full.head_dim,
            full.moe_intermediate_size, full.num_experts,
            full.num_experts_per_tok, full.vocab_size,
            full.experts_held) == (2048, 32, 4, 128, 768, 128, 8, 151936,
                                   (0, 128))
    assert full.num_hidden_layers == full.expert_layers == 6
    assert sdar.SdarConfig().num_hidden_layers == 48
    shapes = sdar.param_shapes(full, jnp.bfloat16)
    count = sum(int(np.prod(leaf.shape))
                for leaf in jax.tree_util.tree_leaves(shapes))
    assert round(count / 1e9, 2) == 4.36
    moe = shapes["layers"][0]["moe"]
    assert set(moe) == {"router", "experts"}  # no bias, no shared expert
    # 256 new tokens behind a tail of up to 3: 65 blocks, 64 committed
    assert sdar.blocks_of(full, 256) == 65
    assert sdar.cache_positions(full, 256, 256) == 512
    assert sdar.cache_bytes(full, 256, 512, 2) == (
        6 * 256 * 512 * 2048, 0, 0)
    assert (CFG.block_length, CFG.scoring_func) == (4, "softmax")
    assert sdar.blocks_of(CFG, 6) == 3 and sdar.cache_positions(
        CFG, 16, 6) == 24


@pytest.mark.parametrize("order", ["ordered", "shuffled"])
def test_chunks_as_wide_as_their_rows_leave_what_one_wide_chunk_leaves(
        pipe, monkeypatch, order):
    """ISSUE 43: two rows a chunk at most, taken by `prefill_by_length`
    (this model offers the bucket's width alone), rows that only pad the
    pass not run: every layer's keys and values of each row's whole blocks
    (the cache started from NaN: an element no chunk wrote would show) and
    the pairs of the same rows in one chunk of all eight."""
    rng = np.random.default_rng(5)
    lengths = np.array([16, 13, 8, 7, 4, 2, 0, 0], np.int32)
    if order == "shuffled":
        lengths = lengths[rng.permutation(len(lengths))]
    rows, slots, positions = len(lengths), 16, 24
    ids = rng.integers(0, CFG.vocab_size, (rows, slots)).astype(np.int32)
    assert sdar.prefill_widths(slots) == (16,)
    made = sdar.new_cache
    monkeypatch.setattr(
        sdar, "new_cache", lambda *args: jax.tree_util.tree_map(
            lambda x: x + jnp.nan, made(*args)))
    cache, load = jax.jit(lambda p, i, n: sdar.prefill(
        p, CFG, i, n, positions, 2))(pipe.params, ids, lengths)
    entries, told = jax.jit(lambda p, i, n: sdar.prefill_rows(
        p, CFG, i, n, slots, sdar.empty_load(CFG)))(pipe.params, ids, lengths)
    seen = (np.arange(slots)[None, :] < (lengths // B * B)[:, None])[
        ..., None, None]
    for layer, written in zip(cache, entries):
        for mine, entry in zip(layer, written):
            mine = np.asarray(mine)
            assert np.isfinite(mine).all() and not mine[:, slots:].any()
            np.testing.assert_allclose(np.where(seen, mine[:, :slots], 0),
                                       np.where(seen, entry, 0), atol=2e-5)
    assert np.array_equal(np.asarray(load[0]), np.asarray(told[0]))
    assert int(load[1][0]) == int(told[1][0]) > 0


def _given(rng, ids, lengths, blocks):
    """Given ids for `blocks` blocks a row, the first behind the prompt's
    tail, and each row's whole sequence for the reference."""
    rows = len(lengths)
    given = rng.integers(0, CFG.vocab_size, (rows, blocks, B)).astype(np.int32)
    # the mask id among them, as a block half denoised holds it
    given[rng.random(given.shape) < 0.4] = CFG.mask_token_id
    sequences = []
    for row, length in enumerate(lengths):
        whole, tail = length // B * B, length % B
        given[row, 0, :tail] = ids[row, whole:length]
        sequences.append(np.concatenate(
            [ids[row, :whole], given[row].reshape(-1)]))
    return given, sequences


@pytest.mark.parametrize("lengths, chunk", [
    # L mod B = 0, 1, 2, 3 behind the same whole blocks: the reference
    # runs op by op, and an op compiles once a shape
    ([12, 13, 14, 15], None),    # the pipeline's chunks
    ([12, 13, 14, 15], (1, 8)),  # a row's positions in chunks of 8
    ([15, 14, 13, 12], (1, 4)),  # ... of a block
    ([4, 3, 2, 0], None),  # shorter than a block: nothing cached; padding
], ids=["every_tail", "position_chunks", "a_block_a_chunk", "under_a_block"])
def test_prefill_and_given_blocks_give_the_references_logits(
        pipe, lengths, chunk):
    """The pipeline's own `prefill_program` (whole rows a chunk at this
    size), or the model's prefill in chunks of a row's positions, each
    attending to what the chunks before it cached; then three given blocks
    through `block_program`, each first without commit and then with."""
    rows, blocks, slots = 4, 3, 16
    rng = np.random.default_rng(7)  # the same ids: an op compiles a shape
    lengths = np.array(lengths, np.int32)
    ids = rng.integers(0, CFG.vocab_size, (rows, slots)).astype(np.int32)
    given, sequences = _given(rng, ids, lengths, blocks)
    positions = slots + blocks * B
    prefill = pipe.prefill_program(rows, slots, positions) if chunk is None \
        else jax.jit(lambda p, i, n: sdar.prefill(
            p, CFG, i, n, positions, *chunk))
    cache, load = prefill(pipe.params, ids, lengths)
    # whole blocks only are routed: a prompt's tail waits for its block
    # (a prefill stops before its last layer's experts)
    assert int(np.asarray(load[1])[0]) == int(
        (lengths // B * B).sum()) * CFG.num_experts_per_tok * (
            CFG.expert_layers - 1)
    peek, commit = (pipe.block_program(rows, slots, positions, flag)
                    for flag in (False, True))
    # ONE full forward a row over prompt + given blocks: a block sees no
    # later one, so its logits are the whole sequence's at its positions
    wanted = [None if not length else np.asarray(reference.forward(
        pipe.params, SIZES, sequences[row])) for row, length in
        enumerate(lengths)]
    for block in range(blocks):
        before = jax.tree_util.tree_map(np.asarray, cache)
        unwritten, cache = peek(pipe.params, cache, given[:, block], lengths,
                                block)
        for old, new in zip(jax.tree_util.tree_leaves(before),
                            jax.tree_util.tree_leaves(cache)):
            assert np.array_equal(old, np.asarray(new))
        logits, cache = commit(pipe.params, cache, given[:, block], lengths,
                               block)
        assert np.array_equal(np.asarray(unwritten), np.asarray(logits))
        for row, length in enumerate(lengths):
            if not length:
                continue
            at = length // B * B + block * B
            want = wanted[row][at:at + B]
            got = np.asarray(logits[row])
            assert np.linalg.norm(got - want) / np.linalg.norm(want) < 2e-5


def test_a_forward_without_commit_changes_nothing_a_later_block_reads(pipe):
    """Block 1's logits after block 0 was run twice uncommitted with other
    ids and then committed are those after the commit alone."""
    rows, slots, positions = 4, 16, 28
    rng = np.random.default_rng(3)
    lengths = np.array([16, 13, 10, 7], np.int32)
    ids = rng.integers(0, CFG.vocab_size, (rows, slots)).astype(np.int32)
    given, _ = _given(rng, ids, lengths, 2)
    other = rng.integers(0, CFG.vocab_size, (rows, B)).astype(np.int32)
    peek, commit = (pipe.block_program(rows, slots, positions, flag)
                    for flag in (False, True))

    def run(noise: bool):
        cache, _ = pipe.prefill_program(rows, slots, positions)(
            pipe.params, ids, lengths)
        if noise:
            for _ in range(2):
                _, cache = peek(pipe.params, cache, other, lengths, 0)
        _, cache = commit(pipe.params, cache, given[:, 0], lengths, 0)
        return np.asarray(peek(pipe.params, cache, given[:, 1], lengths,
                               1)[0])

    assert np.array_equal(run(noise=True), run(noise=False))


@pytest.mark.parametrize("block", [1, 3], ids=["first_fused", "a_later_one"])
def test_a_fused_forward_is_a_commit_and_a_forward_apart(pipe, block):
    """`block_step(finished=)` on block `block`: the cache columns of
    block `block - 1` of every layer are what `block_program(commit=True)`
    writes for it, the logits those of `block_program(commit=False)` run
    after that commit; rows of every tail, one shorter than a block and a
    padding row; no other column is written, and the tally is the two
    forwards' summed."""
    rows, slots, blocks = 6, 16, 4
    positions = slots + blocks * B
    rng = np.random.default_rng(40 + block)
    lengths = np.array([16, 13, 10, 7, 2, 0], np.int32)
    ids = rng.integers(0, CFG.vocab_size, (rows, slots)).astype(np.int32)
    given, _ = _given(rng, ids, lengths, blocks)
    peek, commit = (pipe.block_program(rows, slots, positions, flag)
                    for flag in (False, True))
    cache, _ = pipe.prefill_program(rows, slots, positions)(
        pipe.params, ids, lengths)
    for earlier in range(block - 1):
        _, cache = commit(pipe.params, cache, given[:, earlier], lengths,
                          earlier)
    # columns nobody wrote hold a number a write would change
    cache = jax.tree_util.tree_map(
        lambda x: x.at[:, slots + (block - 1) * B:].set(7.0), cache)
    before = jax.tree_util.tree_map(np.asarray, cache)
    valid = jnp.asarray(lengths > 0)

    fused = jax.jit(lambda cache, finished, tokens: sdar.block_step(
        pipe.params, CFG, tokens, jnp.asarray(lengths), block, slots, cache,
        sdar.empty_load(CFG), valid=valid, finished=finished))
    got_logits, got_cache, got_load = fused(
        cache, given[:, block - 1], given[:, block])
    _, want_cache = commit(pipe.params, cache, given[:, block - 1], lengths,
                           block - 1)
    want_logits, _ = peek(pipe.params, want_cache, given[:, block], lengths,
                          block)
    real = lengths > 0
    got, want = np.asarray(got_logits)[real], np.asarray(want_logits)[real]
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 2e-5
    written = slice(slots + (block - 1) * B, slots + block * B)
    for layer in range(CFG.num_hidden_layers):
        for old, new, wanted in zip(before[layer], got_cache[layer],
                                    want_cache[layer]):
            new, wanted = np.asarray(new), np.asarray(wanted)
            np.testing.assert_allclose(new[:, written], wanted[:, written],
                                       atol=2e-5)
            assert not np.array_equal(new[:, written], old[:, written])
            # what the rows see already, and what no row may see yet
            assert np.array_equal(new[:, :written.start],
                                  old[:, :written.start])
            assert np.array_equal(new[:, written.stop:],
                                  old[:, written.stop:])
    # the tally: the finished block's positions are routed on every layer
    # but the last, the new block's on every layer
    pairs, sums = (np.asarray(x) for x in got_load)
    routed = int(real.sum()) * B * CFG.num_experts_per_tok
    layers = CFG.expert_layers
    assert int(sums[0]) == routed * (2 * layers - 1) == int(pairs.sum())
    assert int(pairs[-1].sum()) == routed


# --- the un-masking rule -----------------------------------------------------


@pytest.mark.parametrize("count, threshold", [
    (4, None), (2, None), (1, None), (1, 0.5), (2, 0.5), (1, 2.0),
], ids=["one_forward", "two_forwards", "four_forwards",
        "threshold_passed_by_some", "threshold_passed_by_too_few",
        "threshold_passed_by_none"])
def test_the_unmasking_rule_is_the_plain_one(count, threshold):
    """Rows of every kind: all masked, a given tail (fixed positions in
    front), fewer masked than `count`, none masked, confidences on either
    side of the threshold, a tie."""
    rng = np.random.default_rng(count)
    rows = 64
    ids = rng.integers(0, 100, (rows, B))
    drawn = rng.integers(100, 200, (rows, B))
    confidence = rng.random((rows, B)).astype(np.float32)
    confidence[5, 1] = confidence[5, 2]  # a tie: the earlier position
    masked = rng.random((rows, B)) < 0.6
    masked[0], masked[1] = True, False
    masked[2] = [False, False, False, True]  # a tail of three given
    masked[3] = [False, True, True, True]
    got_ids, got_masked = sdar.unmask(
        jnp.asarray(ids), jnp.asarray(masked), jnp.asarray(drawn),
        jnp.asarray(confidence), count, threshold)
    for row in range(rows):
        want_ids, want_masked = reference.unmask(
            ids[row].tolist(), masked[row].tolist(), drawn[row].tolist(),
            confidence[row].tolist(), count, threshold)
        assert np.asarray(got_ids[row]).tolist() == want_ids
        assert np.asarray(got_masked[row]).tolist() == want_masked
        # a fixed position never moves, whatever the rule takes
        assert (np.asarray(got_ids[row])[~masked[row]]
                == ids[row][~masked[row]]).all()
    took = masked & ~np.asarray(got_masked)
    if threshold is None:
        assert (took.sum(-1) == np.minimum(masked.sum(-1), count)).all()
    else:
        assert (took.sum(-1) >= np.minimum(masked.sum(-1), count)).all()


def test_the_first_block_opens_with_the_prompts_tail():
    ids = np.arange(1, 33, dtype=np.int32).reshape(2, 16)
    lengths = np.array([16, 7], np.int32)
    block, masked = sdar.first_block(CFG, jnp.asarray(ids), jnp.asarray(
        lengths))
    mask = CFG.mask_token_id
    assert np.asarray(block).tolist() == [[mask] * 4, [21, 22, 23, mask]]
    assert np.asarray(masked).tolist() == [[True] * 4,
                                           [False, False, False, True]]


# --- the served decode -------------------------------------------------------


def _plain_decode(pipe, requests, new_tokens, steps, temperature,
                  threshold=None):
    """The decode as a loop on the host over `block_program`: a row at a
    time the plain rule, the keys folded as the program folds them."""
    rows = [(job, number, row) for job, request in enumerate(requests)
            for number, row in enumerate(request["prompt_ids"])]
    slots = 16
    count = B // steps
    lengths = np.array([len(row) for _, _, row in rows], np.int32)
    ids = np.zeros((len(rows), slots), np.int32)
    for at, (_, _, row) in enumerate(rows):
        ids[at, :len(row)] = row
    positions = pipe.cache_positions(slots, new_tokens)
    blocks = sdar.blocks_of(CFG, new_tokens)
    cache, _ = pipe.prefill_program(len(rows), slots, positions)(
        pipe.params, ids, lengths)
    peek, commit = (pipe.block_program(len(rows), slots, positions, flag)
                    for flag in (False, True))
    first, first_masked = (np.asarray(x) for x in sdar.first_block(
        CFG, jnp.asarray(ids), jnp.asarray(lengths)))
    out = [[] for _ in rows]
    forwards = 0
    for block in range(blocks):
        tokens = first.copy() if block == 0 else np.full_like(
            first, CFG.mask_token_id)
        masked = first_masked.copy() if block == 0 else np.ones_like(
            first_masked)
        for forward in range(steps):
            if not masked.any():
                break
            forwards += 1
            logits, _ = peek(pipe.params, cache, tokens, lengths, block)
            scaled = np.asarray(logits) / (temperature or 1.0)
            for at, (job, number, _) in enumerate(rows):
                key = jax.random.fold_in(jax.random.fold_in(
                    jax.random.fold_in(requests[job]["rng"], number), block),
                    forward)
                # the program's own sampler on the row's own key (ISSUE 47)
                drawn = np.asarray(sampling.sample(
                    key[None], logits[at][None], temperature)[0][0])
                probs = np.asarray(jax.nn.softmax(scaled[at], axis=-1))
                confidence = probs[np.arange(B), drawn]
                new_ids, new_masked = reference.unmask(
                    tokens[at].tolist(), masked[at].tolist(), drawn.tolist(),
                    confidence.tolist(), count, threshold)
                tokens[at], masked[at] = new_ids, new_masked
        assert not masked.any()
        if block < blocks - 1:
            _, cache = commit(pipe.params, cache, tokens, lengths, block)
        for at in range(len(rows)):
            out[at].extend(tokens[at].tolist())
    return [row[length % B:length % B + new_tokens]
            for row, length in zip(out, lengths)], forwards


@pytest.mark.parametrize("steps, temperature, threshold", [
    (2, 1.0, None), (4, 0.0, None), (4, 1.0, 0.004),
], ids=["two_forwards_sampled", "four_forwards_greedy", "threshold"])
def test_the_served_ids_are_a_plain_loop_over_the_block_program(
        pipe, steps, temperature, threshold):
    rng = np.random.default_rng(steps)
    requests = [
        {"prompt_ids": [rng.integers(0, 127, n).tolist() for n in (16, 5, 7)],
         "rng": jax.random.key(11)},
        {"prompt_ids": [rng.integers(0, 127, n).tolist() for n in (2, 9)],
         "rng": jax.random.key(12)}]
    new_tokens = 6
    served = pipe.run_batched(
        requests, max_new_tokens=new_tokens, temperature=temperature,
        denoising_steps=steps, confidence_threshold=threshold)
    want, forwards = _plain_decode(pipe, requests, new_tokens, steps,
                                   temperature, threshold)
    got = [row for ids, _ in served for row in ids.tolist()]
    assert got == want
    config = served[0][1]
    blocks = sdar.blocks_of(CFG, new_tokens)
    # no commit is a forward of its own: every block behind the opening
    # one commits the block before it inside its first forward
    assert config["forwards"] == {"denoise": forwards, "commit": 0,
                                  "fused": blocks - 1}
    assert config["decode_steps"] == forwards
    assert (config["block_length"], config["denoising_steps"],
            config["blocks"]) == (B, steps, blocks)
    if threshold is None:
        assert forwards == blocks * steps
    else:
        assert forwards < blocks * steps  # the threshold saved forwards
    # a fused forward's last layer runs its experts for the new block
    layers = CFG.expert_layers
    assert config["routing"]["decode"]["calls"] == layers * forwards
    # every expert is held: every routed pair is computed; the finished
    # blocks' positions are routed on every layer but the last, as a
    # commit's were
    routing = config["routing"]
    assert routing["pairs"] == routing["routed"] > 0
    real = sum(len(request["prompt_ids"]) for request in requests)
    assert routing["decode"]["routed"] == real * B * (
        layers * forwards + (layers - 1) * (blocks - 1)
    ) * CFG.num_experts_per_tok
    # alone, among other batchmates, the same ids for the same key
    alone = pipe.run_batched(
        requests[1:], max_new_tokens=new_tokens, temperature=temperature,
        denoising_steps=steps, confidence_threshold=threshold)
    assert alone[0][0].tolist() == served[1][0].tolist()


def test_a_job_of_another_family_takes_no_denoising_steps():
    other = TextGenerationPipeline("test/tiny-kimi", allow_random_init=True)
    request = [{"prompt_ids": [[1, 2, 3]], "rng": jax.random.key(0)}]
    with pytest.raises(ValueError, match="token a step"):
        other.run_batched(request, max_new_tokens=2, denoising_steps=2)
    mine = TextGenerationPipeline("test/tiny-sdar", allow_random_init=True)
    with pytest.raises(ValueError, match="divisor of the block length"):
        mine.run_batched(request, max_new_tokens=2, denoising_steps=3)


# --- the mask in ops.attention and the banded kernel -------------------------


@pytest.mark.parametrize("sq, skv, kv_heads, span, blocks", [
    (24, 24, 2, 4, (8, 8)), (12, 36, 1, 4, (8, 8)), (20, 20, 4, 4, None),
], ids=["whole", "chunk", "the_rules_blocks"])
def test_a_span_in_the_banded_kernel_and_on_the_xla_path(sq, skv, kv_heads,
                                                         span, blocks):
    keys = jax.random.split(jax.random.key(sq + skv + span), 3)
    q = jax.random.normal(keys[0], (2, sq, 4, 16))
    k = jax.random.normal(keys[1], (2, skv, kv_heads, 16))
    v = jax.random.normal(keys[2], (2, skv, kv_heads, 16))
    want = np.asarray(reference.span_attention(q, k, v, 0.25, span))
    kernel = banded_attention(q, k, v, scale=0.25, span=span, blocks=blocks,
                              interpret=True)
    assert float(np.max(np.abs(np.asarray(kernel) - want))) < 2e-6
    plain = dot_product_attention(q, k, v, scale=0.25, causal=True, span=span)
    assert float(np.max(np.abs(np.asarray(plain) - want))) < 2e-6
    # the blocks a query block visits are those with a visible pair
    if blocks:
        block_q, block_k = blocks
        t = np.arange(sq)[:, None] + (skv - sq)
        seen = np.arange(skv)[None, :] // span <= t // span
        for i in range(-(-sq // block_q)):
            first, last = (int(x) for x in band_blocks(
                i, sq, skv, 0, block_q, block_k, span))
            rows = seen[i * block_q:(i + 1) * block_q]
            touched = [n for n in range(-(-skv // block_k))
                       if rows[:, n * block_k:(n + 1) * block_k].any()]
            assert touched == list(range(first, last + 1))


def test_no_span_is_the_graph_it_was():
    """`span=0` is the causal call, to the bit and in its text; a span
    takes `causal=True` and no window."""
    keys = jax.random.split(jax.random.key(1), 3)
    q, k, v = (jax.random.normal(key, (1, 8, 2, 16)) for key in keys)

    def text(fn):
        return jax.jit(fn).lower(q, k, v).as_text()

    assert text(lambda q, k, v: reference_attention(
        q, k, v, causal=True, span=0)) == text(
            lambda q, k, v: reference_attention(q, k, v, causal=True))
    assert text(lambda q, k, v: banded_attention(
        q, k, v, span=0, blocks=(8, 8), interpret=True)) == text(
            lambda q, k, v: banded_attention(
                q, k, v, blocks=(8, 8), interpret=True))
    assert not np.array_equal(
        np.asarray(reference_attention(q, k, v, causal=True, span=4)),
        np.asarray(reference_attention(q, k, v, causal=True)))
    with pytest.raises(ValueError, match="causal"):
        dot_product_attention(q, k, v, span=4)
    with pytest.raises(ValueError, match="no window"):
        dot_product_attention(q, k, v, causal=True, span=4, window=4)


# --- the experts under the softmax rule --------------------------------------


def test_every_expert_held_is_the_uncut_layer_and_two_halves_add_up(pipe):
    """The layer as served (16 of 16 held) is the reference's; two chips
    of eight experts each give parts that add up to it: nothing is
    computed alike on both (no shared expert to count once)."""
    moe = pipe.params["layers"][1]["moe"]
    assert "shared" not in moe and "router_bias" not in moe
    h = jax.random.normal(jax.random.key(3), (24, CFG.hidden_size))
    (want,) = (np.asarray(x) for x in reference.experts(moe, SIZES, [h]))
    whole, (sizes, stats) = experts.expert_layer(moe, CFG, h)
    np.testing.assert_allclose(np.asarray(whole), want, atol=2e-5)
    assert int(sizes.sum()) == int(stats[0]) == 24 * CFG.num_experts_per_tok
    total = np.zeros_like(want)
    for share in range(2):
        cfg = dataclasses.replace(CFG, experts_held=(8 * share, 8))
        mine = dict(moe, experts={
            name: stack[8 * share:8 * share + 8]
            for name, stack in moe["experts"].items()})
        out, _ = experts.expert_layer(mine, cfg, h)
        total += np.asarray(out)
    np.testing.assert_allclose(total, want, atol=2e-5)
    # the weights of a token's chosen experts sum to one: no scale
    chosen, weights = experts.route(moe, CFG, h)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, atol=1e-6)
    want_chosen, want_weights = reference.routing(moe, SIZES, h)
    assert np.array_equal(np.sort(np.asarray(chosen), -1),
                          np.sort(np.asarray(want_chosen), -1))
