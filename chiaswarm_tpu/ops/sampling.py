"""An id a position from the softmax of its logits: one uniform number a
position over a running sum of the position's own `exp(logit - max)`, in
float32 (the text decode programs' sampler, pipelines/text_generation.py).

`jax.random.categorical` is `argmax(logits + gumbel)`: a threefry word, two
logarithms and a compare for EVERY logit, which on a chip is bound by the
vector unit's integer work and not by the memory (151,936 logits a position:
4.5 ms a forward of 1024 positions where the logits cross in 0.76). A
position needs one random number. The rule, for a position's logits `[V]`
over its temperature:

1. the vocabulary in fixed blocks of `BLOCK` ids (the last one shorter
   where `V` is no multiple), and of each block its largest logit, the
   first place that stands at, and the sum of its `exp(logit - largest)`
   (`block_statistics`: ONE pass over the logits, and what it writes is
   `[positions, V / BLOCK]`; the largest of the blocks' largest is the
   position's maximum, the place of that block's the greedy id);
2. a block's weight is its sum times `exp(its largest - the maximum)`; the
   weights' running sum is small and ends in the total;
3. `target = u * total` with one `u` in [0, 1) a position: the block is the
   count of running sums `<= target`, and the id inside it the same count
   over the running sum of the block's own `exp(logit - maximum)`, computed
   again from the block's logits (a slice a position taken from the
   logits: no array of `exp` as large as the logits is ever written);
4. the drawn id's probability is `exp(picked - maximum) / total`, from the
   same pass.

A count never runs past the place where the running sum reaches its last
value, so the id drawn raised the sum: its `exp` is positive whatever the
rounding at `u -> 1`, and a logit at `-inf` (or so far under the maximum
that its `exp` is 0 in float32) is never drawn. The distribution is the
float32 softmax, nothing truncated: a block is chosen with the share of the
total its weight has, and an id inside it with its share of the block.

On a TPU the pass is a Pallas kernel (`pallas`: XLA, left to itself, writes
the `exp` out transposed before it sums a block), elsewhere `jax.numpy`
(`reference`); `interpret=True` runs the kernel interpreted, for tests.
Routed by ops/platform.py and counted by
`swarm_kernel_traces_total{op="sampler", path}`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import platform

# ids a block covers: whole lanes of the chip's vector registers, whatever
# the model (151,936 = 148 blocks and 384 ids, 261,120 = 255 blocks,
# 20,480 = 20; a vocabulary under a block is one block)
BLOCK = 1024
# positions a kernel step takes: [256, 1024] float32 are 1 MB, two in flight
_ROWS = 256
_VMEM_LIMIT = 48 * 1024 * 1024


def _blocks(vocab: int) -> tuple[int, int]:
    """(ids a block, blocks) of a vocabulary."""
    width = min(BLOCK, vocab)
    return width, -(-vocab // width)


def statistics_reference(logits, inverse):
    """`block_statistics` in `jax.numpy`."""
    positions, vocab = logits.shape
    width, blocks = _blocks(vocab)
    scaled = jnp.pad(logits.astype(jnp.float32) * inverse[:, None],
                     ((0, 0), (0, blocks * width - vocab)),
                     constant_values=-jnp.inf).reshape(
        positions, blocks, width)
    largest = jnp.max(scaled, axis=-1)
    place = jnp.argmax(scaled, axis=-1).astype(jnp.int32)
    sums = jnp.sum(jnp.exp(scaled - jnp.where(
        largest == -jnp.inf, 0.0, largest)[..., None]), axis=-1)
    return largest, sums, place + width * jnp.arange(blocks, dtype=jnp.int32)


def _statistics_kernel(inverse_ref, logits_ref, largest_ref, sums_ref,
                       place_ref, *, vocab: int, width: int):
    """One block of `width` ids of `rows` positions: `logits_ref` [rows,
    width], `inverse_ref` [rows, 1]; the three outputs [rows, blocks in
    whole lanes] stay where they are over a position's blocks and take the
    block's column."""
    block = pl.program_id(1)
    scaled = logits_ref[...].astype(jnp.float32) * inverse_ref[...]
    ids = block * width + jax.lax.broadcasted_iota(
        jnp.int32, scaled.shape, 1)
    if vocab % width:  # the last block reads past the vocabulary
        scaled = jnp.where(ids < vocab, scaled, -jnp.inf)
    largest = jnp.max(scaled, axis=1, keepdims=True)
    sums = jnp.sum(jnp.exp(scaled - jnp.where(
        largest == -jnp.inf, 0.0, largest)), axis=1, keepdims=True)
    # the first place of the largest, as a float32 (exact under 2 ** 24):
    # a reduction over lanes of whole numbers is not the kernel's to make
    place = jnp.min(jnp.where(scaled == largest, ids.astype(jnp.float32),
                              jnp.float32(2 ** 24)), axis=1, keepdims=True)
    column = jax.lax.broadcasted_iota(
        jnp.int32, largest_ref.shape, 1) == block
    for ref, value in ((largest_ref, largest), (sums_ref, sums),
                       (place_ref, place)):
        ref[...] = jnp.where(column, value, ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _statistics_pallas(logits, inverse, *, interpret: bool = False):
    positions, vocab = logits.shape
    width, blocks = _blocks(vocab)
    rows = min(_ROWS, positions)
    lanes = -(-blocks // 128) * 128
    out = pl.BlockSpec((rows, lanes), lambda i, j: (i, 0))
    shape = jax.ShapeDtypeStruct((positions, lanes), jnp.float32)
    largest, sums, place = pl.pallas_call(
        functools.partial(_statistics_kernel, vocab=vocab, width=width),
        grid=(-(-positions // rows), blocks),
        in_specs=[pl.BlockSpec((rows, 1), lambda i, j: (i, 0)),
                  pl.BlockSpec((rows, width), lambda i, j: (i, j))],
        out_specs=[out, out, out],
        out_shape=[shape, shape, shape],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=8 * logits.size, transcendentals=logits.size,
            bytes_accessed=logits.size * logits.dtype.itemsize),
        name="sampler_statistics",
        interpret=interpret,
    )(inverse[:, None], logits)
    return (largest[:, :blocks], sums[:, :blocks],
            place[:, :blocks].astype(jnp.int32))


def block_statistics(logits, inverse, *, interpret: bool = False):
    """`logits` [positions, V] times `inverse` [positions] (one over the
    temperature), a block of `BLOCK` ids at a time: (the block's largest
    [positions, blocks] float32, the sum of its `exp(. - largest)`, the
    first id the largest stands at, int32). A block all at `-inf` has the
    largest `-inf` and the sum 0."""
    if interpret or platform.trace_platform() == "tpu":
        platform.KERNEL_TRACES.inc(op="sampler", path="pallas")
        return _statistics_pallas(logits, inverse, interpret=interpret)
    platform.KERNEL_TRACES.inc(op="sampler", path="reference")
    return statistics_reference(logits, inverse)


def block_reference(logits, block):
    """`block_logits` in `jax.numpy`."""
    vocab = logits.shape[1]
    width, blocks = _blocks(vocab)
    padded = jnp.pad(logits, ((0, 0), (0, blocks * width - vocab)))
    return jax.vmap(lambda row, at: jax.lax.dynamic_slice_in_dim(
        row, at * width, width))(padded, block)


def _block_kernel(block_ref, *refs):
    """Eight positions a step: `refs` are the eight rows [8, width] of the
    logits that hold them, each at its own position's block, and the
    output [8, width]; a position takes its row of its own."""
    del block_ref
    *held, out_ref = refs
    for row, ref in enumerate(held):
        out_ref[row:row + 1, :] = ref[row:row + 1, :]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _block_pallas(logits, block, *, interpret: bool = False):
    positions, vocab = logits.shape
    width, _ = _blocks(vocab)
    rows = min(8, positions)
    steps = -(-positions // rows)
    # a step's eight positions, the last step's past the end as the last
    block = jnp.pad(block, (0, steps * rows - positions), mode="edge")

    def held(row):
        return pl.BlockSpec(
            (rows, width), lambda i, block: (i, block[i * rows + row]))

    return pl.pallas_call(
        _block_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(steps,),
            in_specs=[held(row) for row in range(rows)],
            out_specs=pl.BlockSpec((rows, width), lambda i, block: (i, 0))),
        out_shape=jax.ShapeDtypeStruct((positions, width), logits.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="sampler_block",
        interpret=interpret,
    )(block, *([logits] * rows))


def block_logits(logits, block, *, interpret: bool = False):
    """Of `logits` [positions, V] each position's block number `block`
    [positions]: [positions, ids a block], the block's ids in their order
    from the block's first; past the vocabulary's end, in its last block,
    whatever."""
    if interpret or platform.trace_platform() == "tpu":
        return _block_pallas(logits, block, interpret=interpret)
    return block_reference(logits, block)


def _count_below(running, target):
    """How many entries of a running sum `[..., n]` are `<= target` and
    under the sum's last value: the place of the entry that first passes
    `target`, and never a place behind the last one that raised the sum."""
    return jnp.sum((running <= target[..., None])
                   & (running < running[..., -1:]), axis=-1, dtype=jnp.int32)


def draw_uniform(logits, inverse, u, *, interpret: bool = False):
    """`logits` [positions, V], `inverse` [positions] (one over the
    temperature) and `u` [positions] in [0, 1): (the id `u` falls on
    [positions] int32, the logarithm of its probability, the greedy id,
    the logarithm of the greedy id's probability) in the softmax of
    `logits * inverse`."""
    positions, vocab = logits.shape
    width, blocks = _blocks(vocab)
    largest, sums, place = block_statistics(
        logits, inverse, interpret=interpret)
    top = jnp.max(largest, axis=-1)
    first = jnp.argmax(largest, axis=-1)
    greedy = jnp.take_along_axis(place, first[:, None], axis=-1)[:, 0]
    running = jnp.cumsum(sums * jnp.exp(largest - top[:, None]), axis=-1)
    total = running[:, -1]
    target = u * total
    block = _count_below(running, target)
    before = jnp.max(jnp.where(
        jnp.arange(blocks) < block[:, None], running, 0.0), axis=-1)
    with jax.named_scope("sampler_pick"):
        picked = block_logits(logits, block, interpret=interpret).astype(
            jnp.float32) * inverse[:, None]
        ids = (block * width)[:, None] + jnp.arange(width, dtype=jnp.int32)
        inside = jnp.cumsum(jnp.where(
            ids < vocab, jnp.exp(picked - top[:, None]), 0.0), axis=-1)
        lane = _count_below(inside, target - before)
        drawn = jnp.sum(jnp.where(
            jnp.arange(width) == lane[:, None], picked, 0.0), axis=-1)
    log_total = jnp.log(total)
    return (block * width + lane, drawn - top - log_total, greedy,
            -log_total)


def sample(keys, logits, temperature, *, interpret: bool = False):
    """An id a position of `logits` [rows, ..., V], a row's from the row's
    own key of `keys` [rows], at `temperature` (a scalar, or [rows]): (ids
    [rows, ...] int32, the logarithm of the probability each was drawn
    with). At a temperature over 0 the softmax of `logits / temperature`
    in float32, one uniform number a position; at 0 the largest logit's
    id, and its probability in the softmax of the logits as they are. A
    position's draw reads its own logits and its own number of its row's
    key, nothing else: a row draws the same alone and among others."""
    lead, vocab = logits.shape[:-1], logits.shape[-1]
    along = (lead[0],) + (1,) * (len(lead) - 1)
    temperature = jnp.broadcast_to(
        jnp.asarray(temperature, jnp.float32), lead[:1]).reshape(along)
    warm = temperature > 0
    inverse = 1.0 / jnp.where(warm, temperature, 1.0)

    def flat(x):
        """[rows, ...] -> [positions], the rows last: beside the
        vocabulary, as a head's logits lie on a chip."""
        return jnp.moveaxis(jnp.broadcast_to(x, lead), 0, -1).reshape(-1)

    with jax.named_scope("sampler"):
        u = jax.vmap(lambda key: jax.random.uniform(
            key, lead[1:], jnp.float32))(keys)
        drawn, log_p, greedy, greedy_log_p = (
            jnp.moveaxis(x.reshape(lead[1:] + lead[:1]), -1, 0)
            for x in draw_uniform(
                jnp.moveaxis(logits, 0, -2).reshape(-1, vocab),
                flat(inverse), flat(u), interpret=interpret))
    return (jnp.where(warm, drawn, greedy),
            jnp.where(warm, log_p, greedy_log_p))
