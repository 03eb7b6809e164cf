"""How a step kernel streams a recurrent state through VMEM (ops/ssd.py
`ssd_step`, ops/gated_delta_rule.py `gated_delta_step`): which transfers
are in flight together, and how many rows a grid step moves.

Both kernels read a float32 state once and write it once, in place, and
are bound by that stream. On a v5e the memory gives a stream of reads
751 GB/s and a stream of writes 657, and a stream that mixes them (a block
in and a block out in flight together, as `pallas_call`'s own pipeline has
them, whatever the block or the number of buffers) 658 for both: a copy
takes 3.26 ms where the two streams apart take 3.06 (PERF.md, PR 48). So
the state stays in HBM and the kernel moves it itself, the reads and the
writes in phases of their own: a grid step's rows are all read (a row's
chunk computed as soon as it is there, in place), and all written only when
the next step's rows have been read, from the other of two sets of buffers.
A phase wants to be long (16 MB reaches what 64 MB do, 4 MB half of it), so
a step takes as many rows as the two sets may hold.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# what a kernel may take of a v5e core's 128 MiB of VMEM: the state's two
# sets of a step's rows under STATE_BUFFERS (at the cells' shapes 16 MB
# each), the vectors beside them (up to 0.3 MB a row, two buffers each)
# and the compiler's own scratch in what is left
VMEM_LIMIT = 48 * 1024 * 1024
STATE_BUFFERS = 32 * 1024 * 1024


def rows_a_step(rows: int, row_bytes: int,
                budget: int = STATE_BUFFERS) -> int:
    """The rows a grid step moves: the largest count that divides `rows`
    and whose two sets of buffers, `row_bytes` a row, fit `budget`; 1
    where no other does."""
    most = max(1, budget // (2 * row_bytes))
    return max(n for n in range(1, min(rows, most) + 1) if rows % n == 0)


def buffers(rows: int, heads: int, chunks: int, matrix: tuple[int, int]):
    """The scratch `stream` wants of a `pallas_call` whose grid step moves
    `rows` rows of `heads` matrices, a row in `chunks` transfers: the two
    sets, and a semaphore a transfer for the reads and for the writes."""
    return [pltpu.VMEM((2, rows, heads, *matrix), jnp.float32),
            pltpu.SemaphoreType.DMA((rows, chunks)),
            pltpu.SemaphoreType.DMA((rows, chunks))]


def stream(state_hbm, out_hbm, held, reads, writes, update):
    """A grid step's part of the stream, under a one-dimensional grid that
    runs in order: `state_hbm` / `out_hbm` [steps * rows, heads, ., .] stay
    in HBM (one buffer, under `input_output_aliases`), `held`, `reads`,
    `writes` are `buffers`'. `update(set, row, chunk)` computes chunk
    `chunk` (a Python int) of row `row` (traced) of the step's rows in
    place in `set` [rows, heads, ., .].

    Step `p` waits for its rows' reads a chunk at a time and computes each
    as it lands; before the last chunk's arithmetic it starts the writes of
    step `p - 1`, waits for them, and starts the reads of step `p + 1` into
    the set they leave free. Reads and writes are never in flight together,
    and the arithmetic lies under both."""
    step, steps = pl.program_id(0), pl.num_programs(0)
    _, rows, heads = held.shape[:3]
    chunks = reads.shape[1]
    per = heads // chunks

    def transfer(phase, row, chunk, out: bool):
        part = pl.ds(chunk * per, per)
        here, there = held.at[phase % 2, row, part], phase * rows + row
        if out:
            return pltpu.make_async_copy(here, out_hbm.at[there, part],
                                         writes.at[row, chunk])
        return pltpu.make_async_copy(state_hbm.at[there, part], here,
                                     reads.at[row, chunk])

    def every(phase, out: bool, wait: bool):
        """Start, or wait for, every transfer of a step's rows."""
        def one(row, carry):
            for chunk in range(chunks):
                copy = transfer(phase, row, chunk, out)
                copy.wait() if wait else copy.start()
            return carry

        jax.lax.fori_loop(0, rows, one, None)

    @pl.when(step == 0)
    def _():
        every(0, out=False, wait=False)

    def one(row, carry):
        for chunk in range(chunks):
            transfer(step, row, chunk, out=False).wait()
            if chunk == chunks - 1:
                @pl.when(jnp.logical_and(row == rows - 1, step > 0))
                def _():
                    every(step - 1, out=True, wait=False)
            update(held.at[step % 2], row, chunk)
        return carry

    jax.lax.fori_loop(0, rows, one, None)

    @pl.when(step > 0)
    def _():
        every(step - 1, out=True, wait=True)

    @pl.when(step + 1 < steps)
    def _():
        every(step + 1, out=False, wait=False)

    @pl.when(step == steps - 1)
    def _():
        every(step, out=True, wait=False)
        every(step, out=True, wait=True)
