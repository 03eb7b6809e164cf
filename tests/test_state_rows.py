"""The rows of recurrent state a grid step of a step kernel moves
(ops/state_rows.py; ISSUE 48), for both kernels that stream a state
(`ssd_step`, `gated_delta_step`), interpreted at small state widths:

(a) whatever the rows a step, every row's output and state are bit for bit
    what one row a step gives, over three successive steps, and within the
    kernels' limits of `step_reference`;
(b) a row alone and the same row among batchmates give the same bits;
(c) at the cells' shapes the rule's count divides the rows and its buffers
    fit the limit the kernels compile under.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chiaswarm_tpu.ops import gated_delta_rule as rule
from chiaswarm_tpu.ops import ssd, state_rows

STEPS = 3


def _ssd_operands(key, rows, heads=4, size=16, dim=128, groups=2):
    """(a step's operands a step, the operands every step shares, the
    state the rows start from)."""
    ks = jax.random.split(key, 7)
    moving = (jax.random.normal(ks[0], (STEPS, rows, heads, dim)),
              jax.random.uniform(ks[1], (STEPS, rows, heads), minval=0.1,
                                 maxval=1.0),
              jax.random.normal(ks[2], (STEPS, rows, groups, size)),
              jax.random.normal(ks[3], (STEPS, rows, groups, size)))
    fixed = (-jax.random.uniform(ks[4], (heads,), minval=0.01, maxval=0.3),
             1.0 + 0.1 * jax.random.normal(ks[5], (heads,)))
    return moving, fixed, jax.random.normal(ks[6], (rows, heads, size, dim))


def _ssd_step(step, moving, fixed, state, **how):
    x, dt, b, c = moving
    return step(x, dt, fixed[0], b, c, fixed[1], state, **how)


def _rule_operands(key, rows, heads=4, keys=8, values=128):
    ks = jax.random.split(key, 6)

    def unit(key):
        x = jax.random.normal(key, (STEPS, rows, heads, keys))
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    moving = (unit(ks[0]) * keys ** -0.5, unit(ks[1]),
              jax.random.normal(ks[2], (STEPS, rows, heads, values)),
              -jax.random.uniform(ks[3], (STEPS, rows, heads), maxval=0.3),
              jax.random.uniform(ks[4], (STEPS, rows, heads)))
    return moving, (), jax.random.normal(ks[5], (rows, heads, keys, values))


def _rule_step(step, moving, fixed, state, **how):
    return step(*moving, state, **how)


# name: (operands, one step, the kernel, the recurrence in `jax.numpy`, the
# limit the kernel's own test holds it to against that recurrence)
KERNELS = {
    "ssd_step": (_ssd_operands, _ssd_step, ssd._step_pallas,
                 ssd.step_reference, 2e-5),
    "gated_delta_step": (_rule_operands, _rule_step, rule._step_pallas,
                         rule.step_reference, 1e-5),
}


def _run(name, moving, fixed, state, rows=None, **how):
    """Three steps one after another, the state handed on, over the first
    `rows` rows: (the outputs [STEPS, rows, ...], the state after them)."""
    _, one, kernel, *_ = KERNELS[name]
    step = how.pop("step", kernel)
    rows = state.shape[0] if rows is None else rows
    state, out = state[:rows], []
    for t in range(STEPS):
        y, state = one(step, tuple(v[t, :rows] for v in moving), fixed,
                       state, **how)
        out.append(y)
    return np.asarray(jnp.stack(out)), np.asarray(state)


@pytest.mark.parametrize("rows", [1, 2, 3, 8, 16])
@pytest.mark.parametrize("name", list(KERNELS))
def test_rows_of_a_step_change_no_bit_of_a_row(name, rows):
    operands, _, _, reference, limit = KERNELS[name]
    moving, fixed, state = operands(jax.random.key(rows), rows)
    row_bytes = 4 * state[0].size
    want, want_state = _run(name, moving, fixed, state, block_rows=1,
                            interpret=True)
    # what the rule gives where four rows' buffers fit and where every
    # row's do (the default at these widths), and each divisor besides
    four = state_rows.rows_a_step(rows, row_bytes, budget=8 * row_bytes)
    assert rows % four == 0 and four <= 4
    assert four == {1: 1, 2: 2, 3: 3, 8: 4, 16: 4}[rows]
    assert state_rows.rows_a_step(rows, row_bytes) == rows
    for block in sorted({four, None, *(n for n in (2, 8) if rows % n == 0)},
                        key=str):
        got, got_state = _run(name, moving, fixed, state, block_rows=block,
                              interpret=True)
        assert np.array_equal(got, want), block
        assert np.array_equal(got_state, want_state), block
    ref, ref_state = _run(name, moving, fixed, state, step=reference)
    assert np.max(np.abs(want - ref)) < limit
    assert np.max(np.abs(want_state - ref_state)) < limit


@pytest.mark.parametrize("name", list(KERNELS))
def test_a_row_alone_and_among_batchmates_is_the_same_bits(name):
    operands = KERNELS[name][0]
    moving, fixed, state = operands(jax.random.key(48), 8)
    among, among_state = _run(name, moving, fixed, state, interpret=True)
    # beside one batchmate, a row a step and both in one
    for block in (1, 2):
        pair, pair_state = _run(name, moving, fixed, state, rows=2,
                                block_rows=block, interpret=True)
        assert np.array_equal(pair[:, 0], among[:, 0])
        assert np.array_equal(pair_state[0], among_state[0])
    # a pass of one row is a program of its own to the CPU's compiler,
    # which the interpreted kernel goes through: its loops of one turn are
    # unrolled and a product and a sum contracted that are not elsewhere
    # (the kernel before ISSUE 48 read the same; on the chip a lone row's
    # bits are its bits among 255, PERF.md, PR 48), so here the lone row is
    # held to its last places only
    alone, alone_state = _run(name, moving, fixed, state, rows=1,
                              interpret=True)
    np.testing.assert_allclose(alone_state[0], among_state[0], rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(alone[:, 0], among[:, 0], rtol=1e-6,
                               atol=1e-6)
    # and among other batchmates, in another place of its block
    other = tuple(v[:, ::-1] for v in moving)
    turned, turned_state = _run(name, other, fixed, state[::-1],
                                block_rows=4, interpret=True)
    assert np.array_equal(turned[:, -1], among[:, 0])
    assert np.array_equal(turned_state[-1], among_state[0])


@pytest.mark.parametrize("rows", [1, 2, 6, 8, 64, 255, 256])
@pytest.mark.parametrize("row, beside, most", [
    # falconh1-batch-decode: a row's 32 matrices of [256, 128]; beside
    # them each group's `B | C` as two lanes of 128 and three head vectors
    pytest.param((32, 256, 128), 2 * 256 * 128 + 3 * 32 * 128, 4,
                 id="ssd_step"),
    # qwen3next-batch-decode: a row's 32 matrices of [128, 128]; beside
    # them `q | k` as 64 lanes of 128 and four head vectors
    pytest.param((32, 128, 128), 128 * 128 + 4 * 32 * 128, 8,
                 id="gated_delta_step"),
])
def test_the_rule_at_the_cells_shapes_divides_the_rows_and_fits(
        row, beside, most, rows):
    row_bytes = 4 * int(np.prod(row))
    count = state_rows.rows_a_step(rows, row_bytes)
    assert rows % count == 0 and count <= most
    assert count == max(n for n in range(1, most + 1) if rows % n == 0)
    # a whole pass of the cell streams in phases of 16 MB
    assert state_rows.rows_a_step(256, row_bytes) * row_bytes == 16 * 2 ** 20
    # the state's two sets and the vectors beside them, two buffers each,
    # under the limit the kernels compile with
    assert 2 * count * row_bytes <= state_rows.STATE_BUFFERS
    assert (2 * count * row_bytes + 2 * count * 4 * beside
            < state_rows.VMEM_LIMIT - 8 * 2 ** 20)
    # a row that no two sets hold still goes, a row a step
    assert state_rows.rows_a_step(rows, state_rows.STATE_BUFFERS) == 1
