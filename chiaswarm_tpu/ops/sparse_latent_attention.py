"""Latent attention over the keys a selection picked
(models/glm_moe_dsa.py; ops/lightning_indexer.py makes the selection): the
softmax runs over a query's selected positions alone.

**The expansion** (`expand_latents`): every head's keys and values of the
cached positions BELOW `end`, the span's end, from the latents: `k =
latents @ key_up`, `v = latents[..., :C] @ value_up`, both `[R, S, H * D]`,
the heads as columns. A Pallas matmul whose row-block axis is a grid bound
that is data (`cdiv(end, block)`): a row block at or past the span's end is
no grid step, and its rows of the two results are left as the buffers were
handed out (a `pallas_call`'s result is not zeroed), so a row's first span
expands an eighth of what its last one does and nothing is initialised
twice. Nothing may read those rows: the attention kernel below does not.

**Prefill** (`sparse_prefill_attention`): a span's queries `q` [R, Sq, H *
D] against those keys and values (`k`, `v` [R,
Skv, H * D], the heads as columns, which is how one matmul from the
latents leaves them: D 256 here, where `ops.attention` sends heads over 128 to XLA's
path and `ops.banded_attention` builds its mask from the grid's indices),
under a mask that is DATA: `mask` int8 [R, Sq, Skv], `index_select`'s. A
flash kernel in `ops.banded_attention`'s layout (heads are columns of `[R,
S, H * D]`, no operand is moved; grid: row, head, query block, visited key
block): a step reads its `[block_q, block_k]` block of the mask beside the
keys, and the softmax state stays in VMEM: no `[heads, Sq, Skv]` score
array reaches HBM. Query `i` stands at position `offset + i` (data, a
scalar the kernel is handed before its grid runs, so one compiled kernel
serves every span of a row; left out, the queries are the last `Sq`
positions of the keys), so a key block wholly in a query block's future
holds no selected key and is neither fetched nor computed, and a key block
at or past the span's end (`offset + Sq`) is no grid step at all: the key
axis of the grid is `cdiv(offset + Sq, block)`, data too, and in the one
block the span's end may cut the columns past it count as not selected and
their values as zeros, so what keys, values and mask hold at or past the
span's end is worth nothing to the result. It computes every visible pair
under the mask and does not gather the selected keys: expanded, a visible
pair costs `4 * D` operations a head where a query's gathered 2048 latents would cost
a DMA a row and matmuls of 64 rows each (PERF.md section 7 has the count).
Every query has a selected key (its own position while it sees `topk` at
most, `topk` of them after).

**Decode** (`sparse_decode_attention`): one new token a row, the absorbed
form of `ops.latent_attention`, over ONLY the selected rows of the latent
cache: `columns` [R, k] are gathered (`k * (C + P)` values a row a layer,
whatever the cache holds), then every head of the row meets the gathered
latents in two batched matmuls. Returns the context and the rows it read.

`swarm_kernel_traces_total{op="sparse_latent_attention"}`: `pallas` (the
prefill kernel), `gathered` (the decode), `reference` (plain `jax.numpy`,
off the chip); `{op="latent_expansion"}`: `pallas` or `reference`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import platform
from .flash_attention import (
    _LANES,
    _NEG_INF,
    _VMEM_SLACK,
    _lanes,
    _pad_to,
    _round_up,
)
from .lightning_indexer import _last_block, _walked

# v5e, a 4096-query span of 64 heads of 256 against 32768 keys, kernel
# alone (my chip run, PR 49): 55.7 ms at 512 x 512, 52.1 at 1024 x 512,
# 52.9 at 512 x 1024, 50.8 at 1024 x 1024 (173 TFLOP/s of the chip's 197)
_BLOCK_Q = 1024
_BLOCK_K = 1024


# rows and columns a step of the expansion (v5e, [32768, 576] -> two
# [32768, 16384]: section 6 of PERF.md, PR 50, has the timings)
_EXPAND_ROWS = 512
_EXPAND_COLUMNS = 2048


def expand_reference(latents, key_up, value_up, end=None):
    """Plain `jax.numpy`: the whole width, the rows at or past `end` from
    zeros (whatever the latents hold there is not read)."""
    if end is not None:
        latents = jnp.where(
            (jnp.arange(latents.shape[1]) < end)[None, :, None], latents, 0)
    return tuple(
        jnp.dot(x, up, preferred_element_type=jnp.float32).astype(x.dtype)
        for x, up in ((latents, key_up),
                      (latents[..., :value_up.shape[0]], value_up)))


def _expand_kernel(x_ref, key_up_ref, value_up_ref, k_ref, v_ref):
    """One (row, block of columns, block of positions) step: x_ref [BM,
    C + P] latents, key_up_ref [C + P, BN], value_up_ref [C, BN]; k_ref /
    v_ref [BM, BN]."""
    x = x_ref[...]
    for up_ref, o_ref in ((key_up_ref, k_ref), (value_up_ref, v_ref)):
        o_ref[...] = jnp.dot(
            x[:, :up_ref.shape[0]], up_ref[...],
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _expand_pallas(latents, key_up, value_up, end=None,
                   interpret: bool = False):
    rows, positions, width = latents.shape
    columns = key_up.shape[1]
    assert key_up.shape[0] == width and value_up.shape[1] == columns and (
        value_up.shape[0] <= width), (latents.shape, key_up.shape,
                                      value_up.shape)
    block_m = min(_EXPAND_ROWS, _round_up(positions, 8))
    block_n = min(_EXPAND_COLUMNS, columns)
    assert columns % block_n == 0 and (
        interpret or block_n % _LANES == 0), (columns, block_n)
    itemsize = jnp.dtype(latents.dtype).itemsize
    vmem = (2 * block_m * width * itemsize
            + 2 * (width + value_up.shape[0]) * block_n * itemsize
            + 2 * 2 * block_m * block_n * itemsize
            + 2 * block_m * block_n * 4)
    out_spec = pl.BlockSpec((None, block_m, block_n),
                            lambda r, c, i: (r, i, c))
    return pl.pallas_call(
        _expand_kernel,
        # positions innermost, up to `end` and no further: a block of the
        # two matrices is fetched once a row, the latents (a thirtieth of
        # what is written) once a block of columns
        grid=(rows, columns // block_n,
              _walked(end, block_m, pl.cdiv(positions, block_m))),
        in_specs=[
            pl.BlockSpec((None, block_m, width), lambda r, c, i: (r, i, 0)),
            pl.BlockSpec((width, block_n), lambda r, c, i: (0, c)),
            pl.BlockSpec((value_up.shape[0], block_n),
                         lambda r, c, i: (0, c))],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((rows, positions, columns),
                                        latents.dtype)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem + _VMEM_SLACK),
        name="latent_expansion",
        interpret=interpret,
    )(latents, key_up, value_up)


@functools.partial(jax.named_call, name="latent_expansion")
def expand_latents(latents, key_up, value_up, end=None, *,
                   interpret: bool = False):
    """`latents` [R, S, C + P] (`c_kv | k_rope` a position), `key_up` [C +
    P, H * D], `value_up` [C, H * D] -> (keys, values), [R, S, H * D] each:
    `latents @ key_up` and `latents[..., :C] @ value_up` for the positions
    below `end` (a number or a traced scalar; None: all of them). What the
    two hold at or past `end` is not defined (on the chip those rows are
    never written) and what the latents hold there is not read."""
    if interpret or platform.trace_platform() == "tpu":
        platform.KERNEL_TRACES.inc(op="latent_expansion", path="pallas")
        return _expand_pallas(latents, key_up, value_up, end,
                              interpret=interpret)
    platform.KERNEL_TRACES.inc(op="latent_expansion", path="reference")
    return expand_reference(latents, key_up, value_up, end)


def prefill_reference(q, k, v, mask, scale: float, heads: int):
    """Plain `jax.numpy`: every head's scores laid out (tiny sizes)."""
    shape = q.shape
    q, k, v = (x.reshape(*x.shape[:2], heads, -1) for x in (q, k, v))
    scores = jnp.einsum("rqhd,rkhd->rhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask[:, None] != 0, scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("rhqk,rkhd->rqhd", weights, v.astype(jnp.float32)
                      ).astype(q.dtype).reshape(shape)


def _prefill_kernel(offset_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, m_ref,
                    l_ref, acc_ref, *, span: int, block_k: int, scale: float,
                    fold_scale: bool):
    """One (row, head, query block, key block) step: offset_ref [1] the
    first of the `span` queries' positions; q_ref / o_ref [BQ, D], k_ref /
    v_ref [BK, D], mask_ref [BQ, BK] int8; the state [BQ, .] float32."""
    block_q, head_dim = q_ref.shape
    i, j = pl.program_id(2), pl.program_id(3)
    offset = offset_ref[0]
    # the block's keys below the span's end: what lies past it (a later
    # span writes it) may hold anything and counts for nothing
    live = offset + span - j * block_k
    seen = j <= _last_block(i, offset, block_q, block_k)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(whole: bool):
        q, v = q_ref[...], v_ref[...]
        chosen = mask_ref[...].astype(jnp.float32) > 0.0
        if not whole:
            chosen &= jax.lax.broadcasted_iota(
                jnp.int32, chosen.shape, 1) < live
            v = jnp.where(jax.lax.broadcasted_iota(
                jnp.int32, v.shape, 0) < live, v, jnp.zeros_like(v))
        if fold_scale:
            q = q * scale  # a power of two: exact
        s = jax.lax.dot_general(
            q, k_ref[...], dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if not fold_scale:
            s = s * scale
        s = jnp.where(chosen, s, _NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_next = jnp.maximum(m_prev, jnp.broadcast_to(
            jnp.max(s, axis=-1, keepdims=True), (block_q, _LANES)))
        # a row that has met no selected key yet weighs its masked scores
        # by exp(0): the first real one's `alpha` wipes that out
        p = jnp.exp(s - _lanes(m_next, block_k))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_prev + jnp.broadcast_to(
            jnp.sum(p, axis=-1, keepdims=True), (block_q, _LANES))
        m_ref[...] = m_next
        acc_ref[...] = acc_ref[...] * _lanes(alpha, head_dim) + (
            jax.lax.dot_general(
                p.astype(v.dtype), v,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))

    # the block the span's end cuts (none where the span is whole blocks)
    # is the one that pays for a second mask
    pl.when(seen & (live >= block_k))(functools.partial(step, True))
    pl.when(seen & (live < block_k))(functools.partial(step, False))

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        o_ref[...] = (acc_ref[...] / _lanes(l_ref[...], head_dim)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "heads", "interpret"))
def _prefill_pallas(q, k, v, mask, scale: float, heads: int, offset=None,
                    interpret: bool = False):
    rows, sq, width = q.shape
    skv, dim = k.shape[1], width // heads
    assert sq <= skv and k.shape == v.shape and mask.shape == (
        rows, sq, skv), (q.shape, k.shape, v.shape, mask.shape)
    # a head is a block of lanes (the interpreter takes any width)
    assert interpret or dim % _LANES == 0, dim
    block_q = min(_BLOCK_Q, _round_up(sq, 32))
    block_k = min(_BLOCK_K, _round_up(skv, _LANES))
    sq_pad, skv_pad = _round_up(sq, block_q), _round_up(skv, block_k)
    n_k = skv_pad // block_k
    # the key blocks the grid walks: up to the span's end and no further
    walked = _walked(None if offset is None else offset + sq, block_k, n_k)
    offset = jnp.asarray(skv - sq if offset is None else offset,
                         jnp.int32).reshape(1)
    # padding is masked out: a padded key is selected by nobody, a padded
    # query's row is cut off below
    q, k, v = _pad_to(q, sq_pad, 1), _pad_to(k, skv_pad, 1), _pad_to(
        v, skv_pad, 1)
    mask = _pad_to(_pad_to(mask, sq_pad, 1), skv_pad, 2)
    itemsize = jnp.dtype(q.dtype).itemsize

    def key_block(i, j, at):
        return jnp.minimum(jnp.minimum(
            j, _last_block(i, at[0], block_q, block_k)), n_k - 1)

    q_spec = pl.BlockSpec((None, block_q, dim),
                          lambda r, h, i, j, at: (r, i, h))
    kv_spec = pl.BlockSpec((None, block_k, dim),
                           lambda r, h, i, j, at: (r, key_block(i, j, at), h))
    vmem = (2 * 2 * block_q * dim * itemsize + 2 * 2 * block_k * dim
            * itemsize + 2 * block_q * block_k + 4 * block_q * block_k * 4
            + block_q * (2 * _LANES + dim) * 4)
    out = pl.pallas_call(
        functools.partial(
            _prefill_kernel, span=sq, block_k=block_k, scale=scale,
            fold_scale=math.frexp(scale)[0] == 0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows, heads, sq_pad // block_q, walked),
            in_specs=[q_spec, kv_spec, kv_spec,
                      pl.BlockSpec((None, block_q, block_k),
                                   lambda r, h, i, j, at: (
                                       r, i, key_block(i, j, at)))],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, dim), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((rows, sq_pad, heads * dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem + _VMEM_SLACK),
        name="sparse_latent_attention",
        # what the call is, beside its padded operands (the benchmark's
        # roofline counts the visible pairs from it)
        metadata={"span": f"queries[{sq}] keys[{skv}] heads[{heads}]"},
        interpret=interpret,
    )(offset, q, k, v, mask)
    return out[:, :sq]


@functools.partial(jax.named_call, name="sparse_latent_attention")
def sparse_prefill_attention(q, k, v, mask, scale: float, heads: int,
                             offset=None, *, interpret: bool = False):
    """`q` [R, Sq, H * D] (query `i` at position `offset + i`, a number or
    a traced scalar; None: the last `Sq` positions of the keys), `k`, `v`
    [R, Skv, H * D], `mask` int8 [R, Sq, Skv] the selected keys a query
    (none of them behind the query's own position) -> [R, Sq, H * D]: the
    softmax over the selected keys alone."""
    if interpret or platform.trace_platform() == "tpu":
        platform.KERNEL_TRACES.inc(op="sparse_latent_attention",
                                   path="pallas")
        return _prefill_pallas(q, k, v, mask, scale, heads, offset,
                               interpret=interpret)
    platform.KERNEL_TRACES.inc(op="sparse_latent_attention",
                               path="reference")
    return prefill_reference(q, k, v, mask, scale, heads)


@functools.partial(jax.named_call, name="sparse_latent_attention")
def sparse_decode_attention(q_lat, q_rope, cache, columns, chosen,
                            scale: float):
    """`q_lat` [R, H, C] and `q_rope` [R, H, P] against the rows `columns`
    [R, k] of `cache` [R, S, C + P] (`c_kv | k_rope` a position), of which
    `chosen` [R, k] are selected. Returns (the context [R, H, C] in the
    cache's dtype: the caller applies the value up-projection; the cache
    rows read a row [R] int32)."""
    latent = q_lat.shape[-1]
    platform.KERNEL_TRACES.inc(
        op="sparse_latent_attention",
        path="gathered" if platform.trace_platform() == "tpu"
        else "reference")
    # a row that sees fewer than `k` positions fills its columns up with
    # some it does not see: whatever those hold counts for nothing
    picked = jnp.where(chosen[..., None], jnp.take_along_axis(
        cache, columns[..., None], axis=1), 0)
    query = jnp.concatenate([q_lat, q_rope], axis=-1).astype(cache.dtype)
    scores = jnp.einsum("rhc,rkc->rhk", query, picked,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(chosen[:, None, :], scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1).astype(cache.dtype)
    context = jnp.einsum("rhk,rkc->rhc", weights, picked[..., :latent],
                         preferred_element_type=jnp.float32)
    return (context.astype(cache.dtype),
            jnp.full((cache.shape[0],), columns.shape[-1], jnp.int32))
