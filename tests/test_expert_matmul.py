"""The grouped expert matmul's blocks (ISSUE 44), on the CPU:

(a) the block rule as a function of shapes: the four language models'
    gated and `down` products at both row tiles. SDAR's and Qwen3-Next's
    matrices are one block (K whole: the weight block's index stays put
    between an expert's row tiles), Kimi's and K-EXAONE's keep the
    streamed extents they had;
(b) the kernel, interpreted, against the plain path at a small shape of
    each kind (one K block; several K blocks under one N block; several
    of both), with a group of three row tiles, an empty group and tiles
    past the last that holds rows; and a pair's result bit-equal wherever
    `plan` puts its row (two batches that share one token).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chiaswarm_tpu.models import experts
from chiaswarm_tpu.ops import expert_matmul as op

# (hidden, expert width): the published widths of the four configurations
WIDTHS = {"kimi": (7168, 2048), "exaone": (6144, 2048),
          "sdar": (2048, 768), "qwen3next": (2048, 512)}
# what the streamed shapes had before the rule (K and N cut at 1024, 2048)
STREAMED = {("kimi", "gated"): (1024, 2048), ("kimi", "down"): (1024, 1792),
            ("exaone", "gated"): (1024, 2048),
            ("exaone", "down"): (1024, 2048)}


@pytest.mark.parametrize("tm", [16, 128])
@pytest.mark.parametrize("product", ["gated", "down"])
@pytest.mark.parametrize("model", list(WIDTHS))
def test_the_block_rule_by_shape(model, product, tm):
    hidden, inner = WIDTHS[model]
    width, n = (hidden, inner) if product == "gated" else (inner, hidden)
    n_weights = 2 if product == "gated" else 1
    got = op.blocks(width, n, n_weights, tm, 2)
    # one block: the index (expert, 0, 0) whatever the row tile
    assert got == STREAMED.get((model, product), (width, n))


def test_k_is_whole_only_where_the_weights_index_would_stay_put():
    """Several N blocks move the weights between an expert's tiles whatever
    K is, so K is streamed there; and a float32 operand counts double."""
    assert op.blocks(2048, 4096, 1, 128, 2) == (1024, 2048)
    assert op.blocks(2048, 2048, 1, 128, 2) == (2048, 2048)
    assert op.blocks(2048, 2048, 2, 128, 4) == (1024, 2048)
    assert op.blocks(2048, 512, 2, 128, 4) == (2048, 512)


def _case(width, n, held, sizes, tm, key):
    """Rows grouped by expert as `plan` lays them, `sizes` pairs each."""
    keys = jax.random.split(key, 3)
    tokens = sum(sizes)
    local = np.concatenate([np.full(count, e, np.int32)
                            for e, count in enumerate(sizes)])
    local = jax.random.permutation(keys[0], jnp.asarray(local))[:, None]
    where = op.plan(local, held, tm)
    h = jax.random.normal(keys[1], (tokens, width), jnp.float32)
    rows = jnp.concatenate([h, jnp.zeros((1, width))])[where.row_token]
    weights = jax.random.normal(keys[2], (2, held, width, n)) / np.sqrt(width)
    return where, rows, weights


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "down"])
@pytest.mark.parametrize("max_tk, max_tn, vmem, k_blocks, n_blocks", [
    (1024, 2048, op._VMEM_LIMIT, 1, 1),
    (128, 2048, 64 * 1024, 2, 1),
    (128, 128, op._VMEM_LIMIT, 2, 2),
], ids=["one_k_block", "several_k_blocks", "several_of_both"])
def test_the_interpreted_kernel_gives_the_plain_paths_rows(
        monkeypatch, max_tk, max_tn, vmem, k_blocks, n_blocks, gated):
    """Expert 1 has three row tiles, expert 2 none, and the buffer ends in
    tiles no pair reached: every row of a tile that holds rows is the plain
    path's, the grid's tiles past `n_tiles` compute nothing."""
    monkeypatch.setattr(op, "_MAX_TK", max_tk)
    monkeypatch.setattr(op, "_MAX_TN", max_tn)
    monkeypatch.setattr(op, "_VMEM_LIMIT", vmem)
    width, n, held, tm = 256, 256, 4, 16
    assert op.blocks(width, n, 1 + gated, tm, 4) == (
        width // k_blocks, n // n_blocks)
    sizes = [5, 2 * tm + 3, 0, tm]
    where, rows, weights = _case(width, n, held, sizes, tm,
                                 jax.random.key(k_blocks + 2 * n_blocks))
    assert int(where.n_tiles) == 1 + 3 + 0 + 1 < rows.shape[0] // tm
    weights = tuple(weights[:1 + gated])
    # the extents are read when the call is traced
    op._grouped.clear_cache()
    got = op.expert_matmul(rows, weights, where.tile_expert, where.n_tiles,
                           tm=tm, interpret=True)
    op._grouped.clear_cache()
    want = op._reference(rows, weights, where.tile_expert, tm)
    real = int(where.n_tiles) * tm
    np.testing.assert_allclose(np.asarray(got)[:real],
                               np.asarray(want)[:real], rtol=2e-5, atol=2e-5)
    assert np.abs(np.asarray(want)[:real]).max() > 0.1


@pytest.mark.parametrize("max_tk, vmem", [
    (1024, op._VMEM_LIMIT), (128, 64 * 1024)],
    ids=["one_k_block", "several_k_blocks"])
def test_a_pairs_result_is_bit_equal_wherever_plan_puts_its_row(
        monkeypatch, max_tk, vmem):
    """One token among two different sets of batchmates: its pairs land in
    other rows of other tiles (its expert's first tile among few mates, its
    third among many), and come back with the same bits."""
    monkeypatch.setattr(op, "_MAX_TK", max_tk)
    monkeypatch.setattr(op, "_VMEM_LIMIT", vmem)
    hidden, width, held, choices = 256, 128, 3, 2
    keys = jax.random.split(jax.random.key(44), 4)
    stack = {"gate": jax.random.normal(keys[0], (held, hidden, width)) / 16,
             "up": jax.random.normal(keys[1], (held, hidden, width)) / 16,
             "down": jax.random.normal(keys[2], (held, width, hidden)) / 11}
    mine = jax.random.normal(keys[3], (1, hidden))
    outs, rows = [], []
    for seed, mates in ((1, 6), (2, 45)):
        others = jax.random.normal(jax.random.key(seed), (mates, hidden))
        h = jnp.concatenate([others, mine])
        # every mate on experts 0 and 2, as the shared token is
        local = jnp.tile(jnp.asarray([[0, 2]], jnp.int32), (mates + 1, 1))
        where = op.plan(local, held, op.row_tile(mates + 1))
        rows.append(np.asarray(where.pair_row)[-1])
        op._grouped.clear_cache()
        got, sizes = experts.held_experts(stack, h, local, interpret=True)
        op._grouped.clear_cache()
        assert list(np.asarray(sizes)) == [mates + 1, 0, mates + 1]
        outs.append(np.asarray(got)[-1])
    assert outs[0].shape == (choices, hidden)
    assert (rows[0] != rows[1]).all() and rows[1][0] // 16 == 2
    assert np.array_equal(outs[0], outs[1]) and np.abs(outs[0]).max() > 0.01
