"""How a pass's rows go through a prefill (models/kimi.py, models/sdar.py,
models/qwen3_next.py): in chunks of whole rows, each chunk as wide as its
rows need (models/exaone.py has fixed chunks of its own, and shares the
rule for a span and the host's account).

A chunk is up to `chunk_rows` rows x `W` slots: `W` one of the widths the
model's module offers (`prefill_widths`: Kimi's the pass's slot bucket and
its halvings, `chunk_widths`; SDAR's and Qwen3-Next's the bucket alone,
where a width's traced copy of the layers costs a worker's start more than
the passes gain). A row's width is its own: the narrowest that holds it,
whoever its batchmates are, so a row's keys, values and logits are the
same bits in any pass (a row that ran at its batchmates' width would sum
its attention over another number of keys, round otherwise, and sooner or
later draw another id). Which rows a chunk takes is data: a cursor walks the rows, and a chunk takes the rows from the
cursor on that have the cursor's row's width, up to `chunk_rows` of them,
read from `lengths` on the device (`prefill_by_length`: a `switch` over
the widths inside the loop over the chunks, one program whatever lengths a
pass brings). It is right for rows in any order and fastest for rows
ordered by length, which is how pipelines/text_generation.py hands them
over: rows of a width then stand together, and a pass has one chunk that
is not full a width at most. Rows of length 0 are not run. `chunk_plan`
is the same rule without jax, `span_runs` the rule by which a chunk that is
spans of a row's positions leaves out the spans no row reaches (one
function for the device and the host), and `chunk_account` what the host
counts a pass's chunks, widths and slots by, given a module's plan and its
rule for a span (a family's `prefill_account`, models/text_model.py: the
rule the device applies and the rule the host counts by sit in one module).

Every chunk is traced at `chunk_rows` rows (the rows it does not take go
through as rows of length 0, and what they leave is not written), so what
a chunk's rows leave (whole rows of the cache, a state a row) has one
shape whatever the width: a branch hands it out and the loop writes it.
Chunks of one token count (more rows where they are narrower) were
compiled for a v5e and given up: a branch that writes rows of its own
count into the cache has the chip's compiler lay the whole cache out a
width and copy it from branch to branch, and four times the rows hold
four times the recurrent states while the chunk runs (Qwen3-Next's cut:
1.35 GB more of temporaries; PERF.md section 6, PR 43).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# widths a chunk may have: the pass's bucket and this many halvings of it
# (256 rows drawn log-uniform over 16-256, longest first, 16 a chunk: half
# the bucket's slots are computed with two halvings; a third saves 3 % more).
# A width is a traced copy of the model's layers in the prefill program:
# its generated code is read back at every start of a worker (0.2-0.3 s a
# MB on a v5e's host: 7 s for Kimi's two halvings, 8 for SDAR's, 13 for
# Qwen3-Next's; PERF.md section 6, PR 43), so a model's module says whether
# it offers them (`prefill_widths`)
HALVINGS = 2


def chunk_widths(slots: int, chunk_slots: int | None = None
                 ) -> tuple[int, ...]:
    """The widths a chunk of whole rows of a `slots` bucket may have,
    widest first: the bucket and its halvings. The one width where a chunk
    is a span of a row's positions (`chunk_slots` under `slots`)."""
    if chunk_slots not in (None, slots):
        return (slots,)
    return (slots,) + tuple(slots >> halving
                            for halving in range(1, HALVINGS + 1)
                            if slots >> halving)


def chunk_plan(lengths, chunk_rows: int, widths
               ) -> list[tuple[int, int, int]]:
    """(first row, rows, width) of every chunk of a pass whose rows have
    `lengths`, in order: a chunk takes the rows from its first on that
    have its first row's width (the narrowest of `widths` that holds the
    row; 0 for a row of length 0: the chunk is not run), `chunk_rows` at
    most. The rule `prefill_by_length` applies on the device, without jax:
    what the host counts a pass's chunks by."""
    kinds = [min((width for width in widths if width >= length), default=0)
             if length else 0 for length in np.asarray(lengths).tolist()]
    plan, at = [], 0
    while at < len(kinds):
        take = 1
        while (take < chunk_rows and at + take < len(kinds)
               and kinds[at + take] == kinds[at]):
            take += 1
        plan.append((at, take, kinds[at]))
        at += take
    return plan


def span_runs(lengths, start: int):
    """Whether a chunk's span of positions from `start` is run: some row
    of the chunk has a prompt token at `start` or past it. `lengths` are
    the chunk's own rows' (the device's in a module's `prefill_rows`, the
    host's where a pass counts what it left out). A row's tokens are its
    first `lengths` positions, so once a span is not run no later one is."""
    return (lengths > start).any()


def chunk_account(lengths, slots: int, chunk_rows: int, chunk_slots: int,
                  widths=None, runs=None) -> tuple[dict[str, int], int, int]:
    """What a prefill ran of a pass of `slots` prompt slots whose rows have
    `lengths`, reckoned on the host: ({width: the chunks run at it}, the
    chunks not run, the slots computed). The chunks are `chunk_plan`'s over
    the module's `widths` (none: `chunk_rows` rows a chunk whatever their
    lengths, at the bucket's width), a chunk going through in spans of
    `chunk_slots` positions where it is wider; a chunk of width 0 is not
    run, and of the others a span that `runs(the chunk's lengths, start)`
    refuses (`span_runs`; None: every span runs)."""
    lengths = np.asarray(lengths)
    plan = (chunk_plan(lengths, chunk_rows, widths) if widths else
            [(at, chunk_rows, slots)
             for at in range(0, len(lengths), chunk_rows)])
    ran, skipped, computed = {}, 0, 0
    for at, take, width in plan:
        span = min(chunk_slots, width or slots)
        for start in range(0, width or slots, span):
            if width and (runs is None or runs(lengths[at:at + take], start)):
                ran[str(span)] = ran.get(str(span), 0) + 1
                computed += take * span
            else:
                skipped += 1
    return ran, skipped, computed


def whole_rows(entry, columns: int):
    """`entry` [R, S, ...] with zeros behind it up to `columns` columns: a
    row of a full layer's cache as it is written, the prompt's slots and
    the generated tokens' columns still empty. Written so and not left to
    the cache's initial zeros: where a loop's counter is the row it
    writes, the TPU compiler (libtpu 0.0.34) takes the loop to write the
    whole buffer and drops the zeros it started from, and the columns
    past the prompt are then whatever the memory held. A decode step gives
    them a weight of zero, and zero times a NaN is a NaN."""
    return jnp.pad(entry, ((0, 0), (0, columns - entry.shape[1]))
                   + ((0, 0),) * (entry.ndim - 2))


def prefill_by_length(ids, lengths, chunk_rows: int, widths, run, whole,
                      load):
    """Every row of `ids` [rows, slots] through `run` in chunks of at most
    `chunk_rows` rows of one of `widths` (`chunk_widths`), each row at the
    narrowest width that holds it. `run(ids [R, W], lengths [R], load)`
    returns what the chunk's rows leave, a tree of `[R, ...]` shaped as
    `whole` (a tree of `[rows, ...]` buffers: a leaf whose entry is `[R,
    W, ...]` takes it as whole rows, zeros behind), and the tally; the
    rows of its `R` that the chunk does not take come with length 0. The
    loop writes every row of every buffer once (zeros for a row of length
    0), so what the buffers held before does not matter. Returns `whole`
    and the tally."""
    rows = ids.shape[0]
    assert rows % chunk_rows == 0, (rows, chunk_rows)
    wide = jnp.array(widths)

    def rows_of(buffer, entry):
        entry = entry.astype(buffer.dtype)
        return entry if entry.shape[1:] == buffer.shape[1:] else whole_rows(
            entry, buffer.shape[1])

    def ran(width, ids, lengths, load):
        entries, load = run(ids[:, :width], lengths, load)
        return jax.tree_util.tree_map(rows_of, whole, entries), load

    def left_out(ids, lengths, load):
        return jax.tree_util.tree_map(
            lambda buffer: jnp.zeros((chunk_rows, *buffer.shape[1:]),
                                     buffer.dtype), whole), load

    # a row's branch: 0 for a row of length 0, else the narrowest width
    # that holds it (`widths` fall)
    branches = [left_out] + [functools.partial(ran, width)
                             for width in widths]

    def chunk(carry):
        at, buffers, load = carry
        # the last rows' window starts early enough to end with the pass
        first = jnp.minimum(at, rows - chunk_rows)
        window = jax.lax.dynamic_slice(lengths, (first,), (chunk_rows,))
        kind = jnp.where(window > 0, jnp.sum(
            wide[None, :] >= window[:, None], axis=1), 0)
        behind = first + jnp.arange(chunk_rows) >= at
        other = behind & (kind != kind[at - first])
        mine = behind & (jnp.cumsum(other) == 0)
        entries, load = jax.lax.switch(
            kind[at - first], branches,
            jax.lax.dynamic_slice(ids, (first, 0),
                                  (chunk_rows, ids.shape[1])),
            jnp.where(mine, window, 0), load)

        def written(buffer, entry):
            start = (first,) + (0,) * (buffer.ndim - 1)
            keep = mine.reshape((-1,) + (1,) * (buffer.ndim - 1))
            return jax.lax.dynamic_update_slice(buffer, jnp.where(
                keep, entry, jax.lax.dynamic_slice(
                    buffer, start, entry.shape)), start)

        return (at + jnp.sum(mine), jax.tree_util.tree_map(
            written, buffers, entries), load)

    _, whole, load = jax.lax.while_loop(
        lambda carry: carry[0] < rows, chunk, (jnp.int32(0), whole, load))
    return whole, load
