"""How wide a prefill chunk is (models/prefill_chunks.py, ISSUE 43): the
widths a pass may use, the plan the host counts by, and the same plan as
the device walks it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chiaswarm_tpu.models.prefill_chunks import (
    chunk_plan,
    chunk_widths,
    prefill_by_length,
)


@pytest.mark.parametrize("args, widths", [
    ((256,), (256, 128, 64)),  # the three batch-decode cells' bucket
    ((16,), (16, 8, 4)),
    ((16, 16), (16, 8, 4)),
    ((4,), (4, 2, 1)),
    ((2,), (2, 1)),
    ((16384, 4096), (16384,)),  # spans of a row's positions: the one width
], ids=["cell", "tiny", "whole_rows", "four_slots", "two_slots", "spans"])
def test_a_chunks_widths_are_the_bucket_and_its_halvings(args, widths):
    assert chunk_widths(*args) == widths


def _lengths(rng, rows, slots, kind):
    lengths = np.exp(rng.uniform(0, np.log(slots), rows)).astype(np.int32)
    lengths = np.clip(lengths, 1, slots)
    if kind != "shuffled":
        lengths = -np.sort(-lengths)
    if kind == "padded":  # rows that only pad the pass to its bucket
        lengths[rows - rows // 3:] = 0
    if kind == "holes":  # rows of no length anywhere: any order is served
        lengths[rng.random(rows) < 0.6] = 0
    return lengths


def _device_plan(lengths, slots, chunk_rows, widths):
    """The width every row was run at (0: not run), the number of its
    chunk among those run, and how many ran, as `prefill_by_length` walked
    the rows; a chunk's `run` sees the rows it does not take as rows of
    length 0."""
    rows = len(lengths)

    def run(ids, lengths, ran):
        take, width = ids.shape
        return (jnp.full((take,), width), jnp.full((take,), ran + 1),
                lengths), ran + 1

    (width, chunk, seen), ran = jax.jit(lambda n: prefill_by_length(
        jnp.zeros((rows, slots), jnp.int32), n, chunk_rows, widths, run,
        (jnp.full((rows,), -1), jnp.full((rows,), -1),
         jnp.full((rows,), -1)), jnp.int32(0)))(lengths)
    assert (np.asarray(seen) == lengths).all()
    return np.asarray(width), np.asarray(chunk), int(ran)


@pytest.mark.parametrize("kind", ["ordered", "shuffled", "padded", "holes"])
@pytest.mark.parametrize("rows, slots, chunk_rows", [
    (256, 256, 16), (64, 128, 4), (8, 16, 2), (32, 16, 32)],
    ids=["cell", "mid", "tiny", "one_chunk"])
def test_the_devices_plan_is_the_hosts(rows, slots, chunk_rows, kind):
    """Every row lies in exactly one chunk, a row's width is the narrowest
    that holds it whoever its batchmates are, no chunk holds more than a
    chunk's tokens, rows of no length are not run, and the device walks
    the rows as the host reckons."""
    rng = np.random.default_rng(rows + len(kind))
    widths = chunk_widths(slots)
    lengths = _lengths(rng, rows, slots, kind)
    plan = chunk_plan(lengths, chunk_rows, widths)
    assert [at for at, _, _ in plan] == [
        sum(take for _, take, _ in plan[:n]) for n in range(len(plan))]
    assert sum(take for _, take, _ in plan) == rows
    for at, take, wide in plan:
        mine = lengths[at:at + take]
        assert 0 < take <= chunk_rows and take * wide <= chunk_rows * slots
        if not wide:
            assert not mine.any()
            continue
        # the narrowest width that holds each row of the chunk
        assert wide in widths and mine.min() > 0 and mine.max() <= wide
        assert not [width for width in widths if mine.min() <= width < wide]
    width, chunk, ran = _device_plan(lengths, slots, chunk_rows, widths)
    assert ran == sum(wide > 0 for _, _, wide in plan)
    number = 0
    for at, take, wide in plan:
        number += wide > 0
        assert (width[at:at + take] == wide).all(), (at, plan)
        assert (chunk[at:at + take] == (number if wide else 0)).all()
    if kind == "ordered" and rows == 256:
        # rows of a width stand together: half the bucket's slots, and at
        # most one chunk a width that is not full
        assert len(plan) <= rows // chunk_rows + len(widths) - 1
        assert sum(take * wide for _, take, wide in plan) < 0.6 * rows * slots
