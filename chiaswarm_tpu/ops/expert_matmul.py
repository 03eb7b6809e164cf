"""Grouped matmul over the experts a chip holds: `expert_matmul`.

An expert layer routes every token over all of the model's experts and
computes the part of the result that the experts held here give
(models/kimi.py `expert_layer`). The (token, choice) pairs that fell on a
held expert are sorted by expert into one row buffer, each expert's group
padded to whole row tiles (`plan`), and this op multiplies every row tile
by its own expert's matrix: no token is dropped and there is no capacity
factor, the buffer is sized for the worst case and only the tiles that hold
rows are visited (the grid's first extent is a traced number, as in
`jax.experimental.pallas.ops.tpu.megablox`). A tile belongs to one expert,
so no tile is masked and a row's result does not depend on where the sort
put it or on who its batchmates are: the same K-order of the same products.

Blocks (`blocks`): the grid runs row tile -> N -> K and Pallas moves a block
again only when its index changed from the step before. A weight block's
index is `(expert, k, j)`, so it stays put between an expert's consecutive
row tiles exactly when both K and N are one block: then an expert's
matrices cross from HBM once a call, however many row tiles the expert
has, and a later tile's step moves only its rows in and its result out.
N is one block up to `_MAX_TN`; K is taken whole wherever N is one block
and a step's buffers (two of the row block, of each weight's block and of
the output, and the float32 products) fit `_VMEM_SHARE` of the VMEM the
kernel declares, else cut to `_MAX_TK` as a stream of blocks that fit
(matrices of tens of MB: each row tile fetches its expert's again). The
rule reads the shapes alone.

Routing (ops/platform.py): on a TPU the Pallas kernel (`grouped`), else a
gather of each tile's matrix and one einsum (`reference`, the CPU path of
the tiny presets). `interpret=True` runs the kernel interpreted, for tests.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import platform

# rows of a tile: a decode step hands an expert a handful of tokens (the
# bf16 sublane tile is 16 rows, and the step waits on the weights, not the
# MXU); a prefill chunk hands it a hundred and more
_TILE_SMALL, _TILE_LARGE = 16, 128
_LARGE_FROM_TOKENS = 1024
# block extents over K and N where a matrix is streamed: the largest
# divisor that is a multiple of a lane tile and at most this (a
# [1024, 2048] bf16 block is 4 MB, two in flight). N up to `_MAX_TN` is one
# block, and K is then whole if the step fits (`blocks`): the weight
# block's index `(expert, 0, 0)` stays put between an expert's row tiles,
# where with two K blocks it alternates and every tile fetches them again
_MAX_TK, _MAX_TN = 1024, 2048
_VMEM_LIMIT = 48 * 1024 * 1024
# what a step's blocks may take of it: the rest is the compiler's own
_VMEM_SHARE = 0.5


def row_tile(tokens: int) -> int:
    return _TILE_LARGE if tokens >= _LARGE_FROM_TOKENS else _TILE_SMALL


class Plan(NamedTuple):
    """Where every held (token, choice) pair lies in the row buffer."""

    row_token: jax.Array  # [M] the token of each buffer row; `tokens` = none
    pair_row: jax.Array  # [T, k] the buffer row of each pair; M = not held
    tile_expert: jax.Array  # [M // tm] the expert of each row tile
    n_tiles: jax.Array  # [] row tiles that hold rows
    sizes: jax.Array  # [G] pairs of each held expert


def buffer_rows(tokens: int, choices: int, groups: int, tm: int) -> int:
    """Rows of the buffer: every token on `min(choices, groups)` held
    experts, and a partly filled last tile a group."""
    worst = tokens * min(choices, groups)
    return -(-worst // tm) * tm + groups * tm


def plan(local, groups: int, tm: int) -> Plan:
    """`local` [T, k]: each pair's expert as an index into the held ones,
    or `groups` for an expert that is not here (or a token that is
    padding). Pairs are sorted by expert, stably, so inside a group they
    keep the order of their tokens."""
    tokens, choices = local.shape
    rows = buffer_rows(tokens, choices, groups, tm)
    key = local.reshape(-1).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=groups + 1)[:groups].astype(jnp.int32)
    padded = (sizes + tm - 1) // tm * tm
    padded_end = jnp.cumsum(padded)
    # one more entry for the pairs that are not held: they land past the
    # buffer and are dropped by the scatter
    padded_start = jnp.concatenate(
        [padded_end - padded, jnp.full((1,), rows, jnp.int32)])
    start = jnp.concatenate(
        [jnp.cumsum(sizes) - sizes, jnp.zeros((1,), jnp.int32)])
    sorted_key = key[order]
    rank = jnp.arange(key.shape[0], dtype=jnp.int32) - start[sorted_key]
    dest = jnp.where(sorted_key < groups,
                     padded_start[sorted_key] + rank, rows)
    row_token = jnp.full((rows,), tokens, jnp.int32).at[dest].set(
        (order // choices).astype(jnp.int32), mode="drop")
    pair_row = jnp.zeros_like(key).at[order].set(dest).reshape(local.shape)
    first_row = jnp.arange(rows // tm, dtype=jnp.int32) * tm
    tile_expert = jnp.minimum(
        jnp.searchsorted(padded_end, first_row, side="right"),
        groups - 1).astype(jnp.int32)
    return Plan(row_token, pair_row, tile_expert,
                (padded_end[-1] // tm).astype(jnp.int32), sizes)


def _block(extent: int, most: int) -> int:
    if extent <= most:
        return extent
    for size in range(most, 0, -128):
        if extent % size == 0:
            return size
    return extent


def blocks(width: int, n: int, n_weights: int, tm: int,
           itemsize: int) -> tuple[int, int]:
    """Block extents (tk, tn) of a `[tm, width] x [width, n]` step with
    `n_weights` matrices at once. K whole where N is one block (only then
    does the weight block's index stay put between an expert's row tiles)
    and the step's buffers fit: two each of the row block, every weight's
    block and the output, and each product in float32."""
    tn = _block(n, _MAX_TN)
    step = (2 * itemsize * (tm * width + n_weights * width * tn + tm * tn)
            + 4 * n_weights * tm * tn)
    if tn == n and step <= _VMEM_SHARE * _VMEM_LIMIT:
        return width, tn
    return _block(width, _MAX_TK), tn


def _kernel(tile_expert_ref, n_tiles_ref, x_ref, *refs, gated: bool,
            k_blocks: int):
    """One (row tile, N block, K block) step: the tile's rows times its
    expert's block, summed in float32 over K; with `gated` two matrices at
    once and SiLU(gate) * up on the way out. With one K block the product
    is the sum and goes straight out; with several it is added up in
    scratch accumulators (`refs` past the output)."""
    del tile_expert_ref
    weights = refs[:2] if gated else refs[:1]
    out_ref = refs[len(weights)]
    accs = refs[len(weights) + 1:]
    k = pl.program_id(2)

    def product(x, w_ref):
        return jax.lax.dot_general(
            x, w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def write(sums):
        if gated:
            gate, up = sums
            out = gate * jax.nn.sigmoid(gate) * up
        else:
            out, = sums
        out_ref[...] = out.astype(out_ref.dtype)

    # a static grid (the interpreter's) also walks the tiles past the last
    # that holds rows: nothing is computed there
    @pl.when(pl.program_id(0) < n_tiles_ref[0])
    def _():
        x = x_ref[...]
        if k_blocks == 1:
            write([product(x, w_ref) for w_ref in weights])
            return

        @pl.when(k == 0)
        def _():
            for acc in accs:
                acc[...] = jnp.zeros_like(acc)

        for w_ref, acc in zip(weights, accs):
            acc[...] += product(x, w_ref)

        @pl.when(k == k_blocks - 1)
        def _():
            write([acc[...] for acc in accs])


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _grouped(x, weights, tile_expert, n_tiles, *, tm: int,
             interpret: bool = False):
    rows, width = x.shape
    n = weights[0].shape[-1]
    itemsize = jnp.dtype(x.dtype).itemsize
    tk, tn = blocks(width, n, len(weights), tm, itemsize)
    k_blocks = width // tk
    tiles = rows // tm
    n_tiles = jnp.reshape(n_tiles, (1,)).astype(jnp.int32)

    def tile(i, n_ref):
        # past the last tile that holds rows, stay on it: no new block moves
        return jnp.minimum(i, jnp.maximum(n_ref[0] - 1, 0))

    x_spec = pl.BlockSpec((tm, tk), lambda i, j, k, te, nt: (tile(i, nt), k))
    w_spec = pl.BlockSpec(
        (None, tk, tn), lambda i, j, k, te, nt: (te[tile(i, nt)], k, j))
    out_spec = pl.BlockSpec((tm, tn),
                            lambda i, j, k, te, nt: (tile(i, nt), j))
    gated = len(weights) == 2
    return pl.pallas_call(
        functools.partial(_kernel, gated=gated, k_blocks=k_blocks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # only the tiles that hold rows; the interpreter wants a number
            grid=(tiles if interpret else n_tiles[0], n // tn, k_blocks),
            in_specs=[x_spec] + [w_spec] * len(weights),
            out_specs=out_spec,
            # accumulators only where K is summed over several blocks
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)
                            for _ in weights] if k_blocks > 1 else []),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        # what a call moves when every group has rows; the scheduler's hint
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * width * n * len(weights),
            bytes_accessed=itemsize * (
                sum(w.size for w in weights) + rows * (width + n)),
            transcendentals=rows * n if gated else 0),
        name="expert_matmul",
        interpret=interpret,
    )(tile_expert, n_tiles, x, *weights)


def _reference(x, weights, tile_expert, tm: int):
    """The plain path: each row tile times its expert's matrix."""
    tiles = x.reshape(-1, tm, x.shape[-1])
    outs = [jnp.einsum("tmk,tkn->tmn", tiles, w[tile_expert],
                       preferred_element_type=jnp.float32)
            for w in weights]
    out = outs[0] if len(outs) == 1 else jax.nn.silu(outs[0]) * outs[1]
    return out.reshape(x.shape[0], -1).astype(x.dtype)


def expert_matmul(x, weights, tile_expert, n_tiles, *, tm: int,
                  interpret: bool = False):
    """`x` [M, K] (rows grouped by expert, groups padded to `tm`) times
    `weights[e]` for the expert `tile_expert` names for each row tile:
    `(w,)` [G, K, N] gives `x @ w[e]`, `(gate, up)` gives
    `silu(x @ gate[e]) * (x @ up[e])`. Rows of tiles past `n_tiles` are
    not written on the kernel's path: whoever reads the result reads the
    rows of `plan`'s pairs and no others."""
    weights = tuple(weights)
    if interpret or platform.trace_platform() == "tpu":
        platform.KERNEL_TRACES.inc(op="expert_matmul", path="grouped")
        return _grouped(x, weights, tile_expert, n_tiles, tm=tm,
                        interpret=interpret)
    platform.KERNEL_TRACES.inc(op="expert_matmul", path="reference")
    return _reference(x, weights, tile_expert, tm)
