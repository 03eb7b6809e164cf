"""The two halves of a learned key selection (models/glm_moe_dsa.py):
`lightning_indexer` scores every cached position for every query, and
`index_select` picks, exactly, the `topk` largest visible scores a query.

**`lightning_indexer`**: `I[t, s] = sum_j w[t, j] * ReLU(q[t, j] . k[s])`
over `heads` index heads that share ONE key a position: `q` [R, Sq, heads,
D], `w` [R, Sq, heads] float32, `k` [R, Skv, D] -> `I` [R, Sq, Skv]
float32, `-inf` where the query does not see the key. Query `i` stands at
position `offset + i` and sees every key up to its own: a prefill span
against a row's cache, in which the spans before it and its own are
written (`offset` is data, a scalar the kernel is handed before its grid
runs, so ONE compiled kernel serves every span of a row; left out, the
queries are the last `Sq` positions of the keys); or, with `visible` [R,
Skv], one decode position a row that sees what `visible` says. The Pallas
kernel (grid: row, query block, key block) keeps the heads' products on the
chip: a step makes `heads` matmuls `[block_q, D] x [D, block_k]`, takes
their ReLU, weighs and adds them in float32 and writes the `[block_q,
block_k]` block of `I` once; no `[heads, queries, keys]` array ever reaches
HBM. A key block wholly in the future of its query block is neither fetched
(its block index is the last needed one's: no new DMA) nor computed (the
step writes `-inf`). With `end` (the span's end: the keys at or past it do
not exist yet; data, as `offset`) the key-block axis of the grid is
`cdiv(end, block_k)`, a grid bound the kernel is handed as it runs: a key
block at or past `end` is no step at all and its scores are LEFT UNWRITTEN,
so only `index_select` under the same `end` may read the result; without
`end` every block is written and the result is whole. A decode step's one
query a row is a matrix-vector product the cache's read bounds: plain XLA
(`einsum`).

**`index_select`**: the `topk` largest of a query's visible scores, ties
to the lower position (as `jax.lax.top_k`), every visible position where
there are `topk` at most. Exact: no approximate selection is this model.
Two forms, as their readers need them:

- `form="mask"` (prefill): `(mask int8 [R, Sq, Skv], selected int32 [R,
  Sq])`, what the masked attention kernel reads. On the chip a radix
  select: a step holds `block_q` whole rows of scores in VMEM, maps each
  float to the int32 that orders as it does, and walks the 32 bits from
  the top, keeping a bit when at least `topk` keys are no smaller than the
  prefix with it: 32 counts over the row give the `topk`-th largest score
  `T`; `ceil(log2(Skv + 1))` more counts give the column `P` before which
  the ties at `T` that still fit lie. Selected: `s > -inf and (s > T or (s
  == T and column < P))`. No sort, nothing leaves VMEM but the mask. Every
  count is a loop over chunks of `_SELECT_COLUMNS` columns whose trip count
  comes from `end` (the columns that exist; data, a scalar the kernel is
  handed before its grid runs; None: all of them): a span's 48 counts go
  up to the span's end and not over the bucket, a column at or past `end`
  counts as one no query sees whatever its score holds, and its mask is 0,
  so the mask is whole and well defined under any `end`.
- `form="indices"` (decode): `(columns int32 [R, Sq, k], chosen bool [R,
  Sq, k])`, `k = min(topk, Skv)`: the same mask, then the selected
  columns in rising order (`columns_of`: the rank of every selected
  position by two running counts, a row of 128 lanes and the chunks before
  it, and the `j`-th column read off them with one gather of rows; no
  sort: a `jax.lax.top_k` of 2048 in 32,896 takes the chip's compiler 17 s
  in every program that holds one, and 0.66 ms a call): what a gather of
  the selected cache rows reads.

`swarm_kernel_traces_total{op="lightning_indexer" | "index_select"}` says
which path a program traced: `pallas`, `reference` (plain `jax.numpy`,
off the chip), and for the decode's index scores `einsum`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import platform
from .flash_attention import _LANES, _VMEM_SLACK, _pad_to, _round_up

_BLOCK_Q = 256    # queries a step of the indexer
_BLOCK_K = 1024   # keys a step of the indexer
_SELECT_ROWS = 32  # whole rows of scores a step of the selection (int8 tile)
_SELECT_COLUMNS = 2048  # columns of them a count takes at a time
_INT_MIN = -2 ** 31
_UNSEEN = -0x800000 ^ 0x7FFFFFFF  # the key of -inf (`_select_kernel`)


# --- lightning_indexer --------------------------------------------------------


def indexer_reference(q, w, k, visible=None, offset=None):
    """Plain `jax.numpy`: the heads' products laid out a head (tiny sizes
    and the decode's one query only)."""
    products = jnp.einsum("rqhd,rkd->rhqk", q, k,
                          preferred_element_type=jnp.float32)
    scores = jnp.einsum("rhqk,rqh->rqk", jnp.maximum(products, 0.0),
                        w.astype(jnp.float32))
    if visible is None:
        sq, skv = q.shape[1], k.shape[1]
        offset = skv - sq if offset is None else offset
        seen = (jnp.arange(skv)[None, :]
                <= (offset + jnp.arange(sq))[:, None])[None]
    else:
        seen = visible[:, None, :]
    return jnp.where(seen, scores, -jnp.inf)


def _last_block(i, offset, block_q: int, block_k: int):
    """The last key block the queries of block `i` see."""
    return (offset + (i + 1) * block_q - 1) // block_k


def _walked(end, block: int, blocks: int):
    """The blocks of `block` an axis of `blocks` of them is walked over up
    to `end`: all of them where there is no end (None), else a grid bound
    that is data, one at the least."""
    if end is None:
        return blocks
    return jnp.clip(pl.cdiv(jnp.asarray(end, jnp.int32), block), 1, blocks)


def _indexer_kernel(offset_ref, q_ref, w_ref, k_ref, o_ref, *, heads: int,
                    dim: int, block_k: int):
    """One (row, query block, key block) step: offset_ref [1] the first
    query's position; q_ref [BQ, heads * D], w_ref [BQ, heads] float32,
    k_ref [BK, D], o_ref [BQ, BK] float32."""
    block_q = q_ref.shape[0]
    i, j = pl.program_id(1), pl.program_id(2)
    offset = offset_ref[0]
    start = offset + i * block_q

    @pl.when(j > _last_block(i, offset, block_q, block_k))
    def _():
        o_ref[...] = jnp.full_like(o_ref, -jnp.inf)

    @pl.when(j <= _last_block(i, offset, block_q, block_k))
    def _():
        k = k_ref[...]
        total = jnp.zeros(o_ref.shape, jnp.float32)
        for head in range(heads):
            products = jax.lax.dot_general(
                q_ref[:, head * dim:(head + 1) * dim], k,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            total = total + jnp.maximum(products, 0.0) * w_ref[
                :, head:head + 1]
        column = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, total.shape, 1)
        row = start + jax.lax.broadcasted_iota(jnp.int32, total.shape, 0)
        o_ref[...] = jnp.where(column <= row, total, -jnp.inf)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _indexer_pallas(q, w, k, offset=None, end=None,
                    interpret: bool = False):
    rows, sq, heads, dim = q.shape
    skv = k.shape[1]
    assert sq <= skv and (interpret or dim % _LANES == 0), (q.shape, k.shape)
    block_q = min(_BLOCK_Q, _round_up(sq, 8))
    block_k = min(_BLOCK_K, _round_up(skv, _LANES))
    sq_pad, skv_pad = _round_up(sq, block_q), _round_up(skv, block_k)
    n_k = skv_pad // block_k
    offset = jnp.asarray(skv - sq if offset is None else offset,
                         jnp.int32).reshape(1)
    # padding lies behind every real position: no real query sees a padded
    # key (a padded query's row, which may, is cut off below)
    q = _pad_to(q, sq_pad, 1).reshape(rows, sq_pad, heads * dim)
    w = _pad_to(w.astype(jnp.float32), sq_pad, 1)
    k = _pad_to(k, skv_pad, 1)
    itemsize = jnp.dtype(q.dtype).itemsize
    vmem = (2 * block_q * heads * dim * itemsize + 2 * block_q * _LANES * 4
            + 2 * block_k * dim * itemsize + 5 * block_q * block_k * 4)
    out = pl.pallas_call(
        functools.partial(_indexer_kernel, heads=heads, dim=dim,
                          block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            # a key block at or past `end` is no step
            grid=(rows, sq_pad // block_q, _walked(end, block_k, n_k)),
            in_specs=[
                pl.BlockSpec((None, block_q, heads * dim),
                             lambda r, i, j, at: (r, i, 0)),
                pl.BlockSpec((None, block_q, heads),
                             lambda r, i, j, at: (r, i, 0)),
                pl.BlockSpec((None, block_k, dim), lambda r, i, j, at: (
                    r, jnp.minimum(jnp.minimum(j, _last_block(
                        i, at[0], block_q, block_k)), n_k - 1), 0)),
            ],
            out_specs=pl.BlockSpec((None, block_q, block_k),
                                   lambda r, i, j, at: (r, i, j))),
        out_shape=jax.ShapeDtypeStruct((rows, sq_pad, skv_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem + _VMEM_SLACK),
        name="lightning_indexer",
        # what the call is, beside its padded operands (the benchmark's
        # roofline counts the visible pairs from it)
        metadata={"span": f"queries[{sq}] keys[{skv}] heads[{heads}]"},
        interpret=interpret,
    )(offset, q, w, k)
    return out[:, :sq, :skv]


@functools.partial(jax.named_call, name="lightning_indexer")
def lightning_indexer(q, w, k, visible=None, offset=None, end=None, *,
                      interpret: bool = False):
    """`q` [R, Sq, heads, D], `w` [R, Sq, heads], `k` [R, Skv, D] -> the
    index scores [R, Sq, Skv] float32, `-inf` where the query does not see
    the key: query `i` at position `offset + i` (a number or a traced
    scalar; None: the queries are the last `Sq` positions of the keys), or
    with `visible` [R, Skv] one position a row that sees what it says.
    `end` (a number or a traced scalar, no less than the last query's
    position + 1): the keys at or past it do not exist yet; their scores
    are NOT DEFINED (on the chip a key block at or past it is never
    written), which `index_select` takes under the same `end`."""
    if visible is not None:
        assert q.shape[1] == 1, q.shape
        platform.KERNEL_TRACES.inc(
            op="lightning_indexer",
            path="einsum" if platform.trace_platform() == "tpu"
            else "reference")
        return indexer_reference(q, w, k, visible)
    if interpret or platform.trace_platform() == "tpu":
        platform.KERNEL_TRACES.inc(op="lightning_indexer", path="pallas")
        return _indexer_pallas(q, w, k, offset, end, interpret=interpret)
    platform.KERNEL_TRACES.inc(op="lightning_indexer", path="reference")
    return indexer_reference(q, w, k, offset=offset)


# --- index_select -------------------------------------------------------------


def select_reference(scores, topk: int):
    """Plain `jax.numpy`: `jax.lax.top_k` (ties to the lower column), then
    the mask by comparison; a query with fewer visible positions than
    `topk` takes them all."""
    values, columns = jax.lax.top_k(scores, min(topk, scores.shape[-1]))
    chosen = values > -jnp.inf
    hit = (columns[..., None] == jnp.arange(scores.shape[-1])) \
        & chosen[..., None]
    mask = jnp.any(hit, axis=-2)
    return mask.astype(jnp.int8), jnp.sum(chosen.astype(jnp.int32), -1)


def columns_of(mask, k: int):
    """(columns int32 [..., k], chosen bool [..., k]): the columns `mask`
    [..., S] selects, in rising order, and which of the `k` there are (a
    query that selects fewer fills up with column 0, not chosen). Without
    a sort: the selected positions are ranked by a running count inside
    each chunk of 128 lanes and the chunks' counts before it; rank `j`
    lies in the last chunk that starts at `j` or under, and at the lane
    where the chunk's running count passes it."""
    lead, size = mask.shape[:-1], mask.shape[-1]
    chunks = -(-size // _LANES)
    bits = jnp.pad(mask.reshape(-1, size) != 0,
                   ((0, 0), (0, chunks * _LANES - size)))
    within = jnp.cumsum(bits.reshape(-1, chunks, _LANES).astype(jnp.int32),
                        axis=-1)
    per = within[..., -1]
    before = jnp.cumsum(per, axis=-1) - per  # a chunk's first rank
    rank = jnp.arange(k)
    chunk = jnp.sum(before[:, None, :] <= rank[None, :, None], -1) - 1
    local = rank[None, :] - jnp.take_along_axis(before, chunk, axis=-1)
    lanes = jnp.take_along_axis(within, chunk[..., None], axis=1)
    lane = jnp.sum(lanes <= local[..., None], axis=-1)
    chosen = rank[None, :] < (before[:, -1:] + per[:, -1:])
    columns = jnp.where(chosen, chunk * _LANES + lane, 0)
    return (columns.astype(jnp.int32).reshape(*lead, k),
            chosen.reshape(*lead, k))


def _select_kernel(end_ref, s_ref, m_ref, n_ref, key_ref, *, topk: int,
                   chunk: int):
    """One (row, block of queries) step: end_ref [1] the columns that
    exist; s_ref [BQ, Skv] float32 whole rows of scores; m_ref [BQ, Skv]
    int8 the mask, n_ref [BQ, 128] int32 the selected positions a query
    (lane-replicated); key_ref [BQ, Skv] int32 the scores' keys. Every
    pass over the rows goes `chunk` columns at a time up to `end` and no
    further: a column at or past it counts as one no query sees, whatever
    its score holds, and its mask is 0."""
    rows, columns = s_ref.shape
    end = end_ref[0]
    walked = pl.cdiv(end, chunk)
    want = jnp.float32(topk)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, chunk), 1)

    def chunk_of(c):
        """(chunk `c`'s columns [rows, chunk], where they lie in a ref)."""
        at = pl.multiple_of(c * chunk, chunk)
        return at + lane, (slice(None), pl.ds(at, chunk))

    def to_key(c, _):
        column, here = chunk_of(c)
        bits = jax.lax.bitcast_convert_type(s_ref[here], jnp.int32)
        # an int32 that orders as the float does (no NaN comes here)
        key_ref[here] = jnp.where(
            column < end, jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits),
            _UNSEEN)

    jax.lax.fori_loop(0, walked, to_key, None)

    def count(test):
        """[rows, 1] float32: how many of a row's columns below the end
        `test(keys, column)` holds for (exact: a row has far fewer than
        2^24 columns); a lane's sum first, the lanes' once."""
        def add(c, lanes):
            column, here = chunk_of(c)
            hit = jnp.where(test(key_ref[here], column), 1.0, 0.0)
            for part in range(chunk // _LANES):
                lanes = lanes + hit[:, part * _LANES:(part + 1) * _LANES]
            return lanes

        return jnp.sum(jax.lax.fori_loop(
            0, walked, add, jnp.zeros((rows, _LANES), jnp.float32)),
            axis=-1, keepdims=True)

    def value_bit(number, prefix):
        # `prefix` walks the keys' order as unsigned numbers do: flipping
        # the top bit makes it the signed threshold
        with_bit = prefix | jnp.left_shift(jnp.int32(1), 31 - number)
        enough = count(
            lambda key, _: key >= (with_bit ^ _INT_MIN)) >= want
        return jnp.where(enough, with_bit, prefix)

    prefix = jax.lax.fori_loop(0, 32, value_bit,
                               jnp.zeros((rows, 1), jnp.int32))
    threshold = prefix ^ _INT_MIN  # the topk-th largest key (or the least)
    room = want - count(lambda key, _: key > threshold)  # ties that fit
    width = columns.bit_length()

    def column_bit(number, edge):
        with_bit = edge | jnp.left_shift(jnp.int32(1), width - 1 - number)
        fits = count(lambda key, column: (key == threshold)
                     & (column < with_bit)) <= room
        return jnp.where(fits, with_bit, edge)

    edge = jax.lax.fori_loop(0, width, column_bit,
                             jnp.zeros((rows, 1), jnp.int32))

    def chosen(key, column):
        return (key > _UNSEEN) & ((key > threshold) | (
            (key == threshold) & (column < edge)))

    def mark(c, _):
        column, here = chunk_of(c)
        m_ref[here] = chosen(key_ref[here], column).astype(jnp.int8)

    def clear(c, _):
        m_ref[chunk_of(c)[1]] = jnp.zeros((rows, chunk), jnp.int8)

    jax.lax.fori_loop(0, walked, mark, None)
    jax.lax.fori_loop(walked, columns // chunk, clear, None)
    n_ref[...] = jnp.broadcast_to(count(chosen).astype(jnp.int32),
                                  n_ref.shape)


@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def _select_pallas(scores, topk: int, end=None, interpret: bool = False):
    rows, sq, skv = scores.shape
    block_q = _SELECT_ROWS
    chunk = min(_SELECT_COLUMNS, _round_up(skv, _LANES))
    sq_pad, skv_pad = _round_up(sq, block_q), _round_up(skv, chunk)
    # a padded column is one no query sees; a padded query's row is cut
    scores = jnp.pad(scores, ((0, 0), (0, sq_pad - sq), (0, skv_pad - skv)),
                     constant_values=-jnp.inf)
    end = jnp.clip(jnp.asarray(skv if end is None else end, jnp.int32),
                   0, skv).reshape(1)
    vmem = block_q * skv_pad * (2 * 4 + 2 * 1 + 4) + 12 * block_q * chunk * 4
    mask, count = pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows, sq_pad // block_q),
            in_specs=[pl.BlockSpec((None, block_q, skv_pad),
                                   lambda r, i, end: (r, i, 0))],
            out_specs=[pl.BlockSpec((None, block_q, skv_pad),
                                    lambda r, i, end: (r, i, 0)),
                       pl.BlockSpec((None, block_q, _LANES),
                                    lambda r, i, end: (r, i, 0))],
            scratch_shapes=[pltpu.VMEM((block_q, skv_pad), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((rows, sq_pad, skv_pad), jnp.int8),
                   jax.ShapeDtypeStruct((rows, sq_pad, _LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=vmem + _VMEM_SLACK),
        name="index_select",
        interpret=interpret,
    )(end, scores)
    return mask[:, :sq, :skv], count[:, :sq, 0]


@functools.partial(jax.named_call, name="index_select")
def index_select(scores, topk: int, form: str = "mask", end=None, *,
                 interpret: bool = False):
    """The `topk` largest visible of `scores` [R, Sq, Skv] (`-inf`: not
    visible) a query, ties to the lower position; `form` `mask`: (int8
    [R, Sq, Skv], the selected a query [R, Sq]); `indices`: (columns [R,
    Sq, k], which of them are visible [R, Sq, k]). `end` (a number or a
    traced scalar; None: `Skv`): the columns at or past it do not exist
    yet, so what the scores hold there is not read and the mask is 0."""
    assert form in ("mask", "indices"), form
    if interpret or platform.trace_platform() == "tpu":
        platform.KERNEL_TRACES.inc(op="index_select", path="pallas")
        mask, count = _select_pallas(scores, topk, end, interpret=interpret)
    else:
        platform.KERNEL_TRACES.inc(op="index_select", path="reference")
        if end is not None:
            scores = jnp.where(jnp.arange(scores.shape[-1]) < end, scores,
                               -jnp.inf)
        mask, count = select_reference(scores, topk)
    if form == "indices":
        return columns_of(mask, min(topk, scores.shape[-1]))
    return mask, count
