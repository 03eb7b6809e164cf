"""Decode attention over a latent cache: `latent_decode_attention`.

Latent attention (models/kimi.py) caches, for every position, the
compressed key/value `c_kv` and the one rotary key all heads share, and
nothing else. A decode step absorbs the up-projection into the query
(`q_lat = q_nope @ W_K`), so its scores are `q_lat . c_kv + q_rope .
k_rope` and its context `softmax . c_kv`, both taken straight over the
cache: no key or value is ever expanded to the heads' width. One new token
a row, every head of the row against the row's whole cache.

Two paths, and shapes and the platform decide which:

- **the kernel** (on a TPU, where the latent is whole lanes; anywhere under
  `interpret=True`): one Pallas call in flash form. A grid step holds a
  block of `COLUMN_BLOCK` cached positions of `rows_a_step` rows; a row's
  heads meet the block once for the scores (float32, in VMEM, the mask
  applied there), and the block's first `C` values serve the context from
  the copy already there, under a running max, sum and context a row and
  head in float32. The cache crosses from HBM once and no `[R, H, S]`
  array exists in the program. Same precisions as the plain form: the
  cache's dtype for the operands, float32 accumulation and statistics, the
  weights rounded to the cache's dtype for the context product.

  It is bounded by what the mask shows, not by the cache's width: `mask`
  is reduced to a table of the column blocks in which one of a grid
  step's rows sees a position (`live_blocks`), and the grid walks the live
  (rows, block) pairs and no others (`walk`, handed to the kernel before
  its grid runs: a dead pair is neither fetched nor computed, and none
  stands between two live ones, where it would leave the second's transfer
  nothing to hide behind). A pass hands its rows over longest first, so
  the rows of a step see much the same blocks. A row that sees nothing in
  a block a batchmate brought goes through it with every weight 0 and its
  running max, sum and context kept to the bit, so a row's bits depend on
  its own operands alone, whichever rows share its grid step and whatever
  they see. Blocks are fixed columns in a fixed order.

  On a v5e (PERF.md section 6, PR 53) a (row, 128-column block) is 17.8
  MFLOP in matmuls of 64 query rows, which refill the MXU every 64 pushes:
  by count the kernel is as near the MXU's bound as the memory's. What
  helped: the rows of a step unrolled a phase at a time (all the score
  products, then all the softmaxes, then all the context products), the
  division by the running sum as a reciprocal a head, and the queries in
  and the context out heads first (`[H, R, C]`, which is how the batched
  products on either side of the call make and read them: the kernel
  brings a row's heads together in VMEM, where XLA copied 17 MB through
  HBM on either side).
- **the plain form** elsewhere: two batched matmuls a row and a float32
  softmax in XLA, the cache read twice.

`swarm_kernel_traces_total{op="latent_attention"}`: every traced call bumps
`absorbed` (both paths compute the absorbed form, which is what the label
says of the program and what a deployment's `expected_kernel_paths` looks
for), and the kernel bumps `pallas` beside it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import platform
from .flash_attention import _LANES, _NEG_INF, _lanes, _pad_to

# cached positions a column block: the grain at which the kernel skips
COLUMN_BLOCK = 128
# rows a grid step at most: at 128 columns of 576 bf16 values a row that
# is a step of 2.4 MB in
ROWS_A_STEP = 16
# what the call may take of VMEM: inside the compiler's own 16 MiB default
_VMEM_LIMIT = 16 * 1024 * 1024


def decode_reference(q_lat, q_rope, cache, mask, scale: float):
    """The plain form: two batched matmuls a row over the whole width."""
    latent = q_lat.shape[-1]
    query = jnp.concatenate([q_lat, q_rope], axis=-1).astype(cache.dtype)
    scores = jnp.einsum("rhc,rsc->rhs", query, cache,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask[:, None, :], scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1).astype(cache.dtype)
    return jnp.einsum("rhs,rsc->rhc", weights, cache[..., :latent],
                      preferred_element_type=jnp.float32).astype(cache.dtype)


def kernel_taken(latent: int, rows: int, interpret: bool = False) -> bool:
    """Whether a call of `rows` rows with a latent of this width, traced
    for the platform at hand, is the kernel's: where the rows part into
    grid steps, on a TPU where the latent is whole lanes, and anywhere
    under `interpret`."""
    return rows_a_step(rows) > 0 and (interpret or (
        platform.trace_platform() == "tpu" and latent % _LANES == 0))


def column_block(positions: int) -> int:
    """The kernel's column block over a cache of `positions`: a narrower
    cache is one block."""
    return min(COLUMN_BLOCK, positions)


def rows_a_step(rows: int) -> int:
    """The rows a grid step holds: the most that divide `rows` and are
    whole sublanes of the heads-first queries' blocks (a multiple of 8, or
    every row); 0 where no count is, and the plain form takes the call."""
    return max((n for n in range(1, min(rows, ROWS_A_STEP) + 1)
                if rows % n == 0 and (n % 8 == 0 or n == rows)), default=0)


def live_blocks(mask, block: int, rows: int):
    """[R, S] -> [R / rows, cdiv(S, block)]: the column blocks in which
    one of a grid step's `rows` rows sees a position. The kernel fetches
    and computes these and no others."""
    mask = _pad_to(mask, pl.cdiv(mask.shape[1], block) * block, 1)
    return jnp.any(mask.reshape(mask.shape[0] // rows, rows, -1, block),
                   axis=(1, 3))


def walk(live):
    """[steps, blocks] -> the kernel's walk, three int32 [steps * blocks]:
    the live (step, block) pairs in order, a grid step each, as the step,
    the block, and what the grid step is (bit 0: the first of its rows'
    steps, bit 1: the last, bit 2: live). Behind the live pairs the walk
    stays on the last one, whose blocks are then not transferred again and
    which computes nothing: a dead pair between two live ones would leave
    the second's transfer nothing to hide behind. Rows that see nothing at
    all walk block 0."""
    steps, blocks = live.shape
    live = live.at[:, 0].set(live[:, 0] | ~jnp.any(live, axis=1))
    flat = live.reshape(-1)
    count = jnp.sum(flat)
    at = jnp.arange(flat.size)
    pair = jnp.argsort(~flat, stable=True)[jnp.minimum(at, count - 1)]
    step, block = pair // blocks, pair % blocks
    alive = at < count
    first = alive & (block == jnp.argmax(live, axis=1)[step])
    last = alive & (block == blocks - 1 - jnp.argmax(
        live[:, ::-1], axis=1)[step])
    kind = first + 2 * last + 4 * alive
    return tuple(x.astype(jnp.int32) for x in (step, block, kind))


def _decode_kernel(step_ref, block_ref, kind_ref, q_lat_ref, q_rope_ref,
                   cache_ref, mask_ref, o_ref, m_ref, l_ref, acc_ref,
                   lat_ref, rope_ref, *, scale: float, positions: int):
    """One grid step, a (block of rows, column block) pair of the walk:
    step_ref, block_ref, kind_ref `walk`'s three; q_lat_ref [H, BR, C],
    q_rope_ref [H, BR, P], cache_ref [BR, BS, C + P], mask_ref [BR, blocks,
    BS] float32; o_ref [H, BR, C]; the state m_ref, l_ref [BR, H, 128] and
    acc_ref [BR, H, C] float32; lat_ref [BR, H, C] and rope_ref [BR, H, P]
    the step's queries, a row's heads together."""
    rows, heads, latent = acc_ref.shape
    block = cache_ref.shape[1]
    at = pl.program_id(0)
    j, kind = block_ref[at], kind_ref[at]

    @pl.when(kind % 2 == 1)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # the queries come heads first, as the product that makes them
        # leaves them: a row's heads are brought together here, once a
        # step's rows (through float32: the two leading axes of a packed
        # dtype do not swap)
        for src, dst in ((q_lat_ref, lat_ref), (q_rope_ref, rope_ref)):
            for lanes in range(0, src.shape[-1], _LANES):
                part = slice(lanes, min(lanes + _LANES, src.shape[-1]))
                dst[:, :, part] = jnp.swapaxes(
                    src[:, :, part].astype(jnp.float32), 0, 1).astype(
                    dst.dtype)

    def latents(r):
        c = cache_ref[r]
        if positions % block:
            # the cache's last block overhangs it: what lies past the
            # end is not the cache's and counts as zeros
            c = jnp.where(jax.lax.broadcasted_iota(
                jnp.int32, c.shape, 0) < positions - j * block, c,
                jnp.zeros_like(c))
        return c

    def scores(r):
        c, keys = latents(r), (((1,), (1,)), ((), ()))
        return (jax.lax.dot_general(
            lat_ref[r], c[:, :latent], keys,
            preferred_element_type=jnp.float32)
            + jax.lax.dot_general(
                rope_ref[r], c[:, latent:], keys,
                preferred_element_type=jnp.float32)) * scale

    def weights(r, s):
        """The row's weights of this block, with its running max and sum
        brought up to it; returns the weights and the factor the old
        context shrinks by."""
        seen = mask_ref[r, pl.ds(j, 1), :] > 0.0
        s = jnp.where(seen, s, _NEG_INF)
        m_prev = m_ref[r]
        m_next = jnp.maximum(m_prev, jnp.broadcast_to(
            jnp.max(s, axis=-1, keepdims=True), (heads, _LANES)))
        # a row that sees nothing in a block a batchmate brought leaves
        # its state as it was, to the bit: every weight 0 and the old
        # sums kept by a factor that is 1 and not the chip's exp(0)
        p = jnp.where(seen, jnp.exp(s - _lanes(m_next, block)), 0.0)
        alpha = jnp.where(m_next == m_prev, 1.0, jnp.exp(m_prev - m_next))
        l_ref[r] = alpha * l_ref[r] + jnp.broadcast_to(
            jnp.sum(p, axis=-1, keepdims=True), (heads, _LANES))
        m_ref[r] = m_next
        return p.astype(cache_ref.dtype), alpha

    def context(r, p, alpha):
        acc_ref[r] = acc_ref[r] * _lanes(alpha, latent) + jnp.dot(
            p, latents(r)[:, :latent], preferred_element_type=jnp.float32)

    @pl.when(kind >= 4)
    def _():
        # the rows are unrolled, a phase at a time: a row's matmuls do not
        # wait for its own exponentials
        raw = [scores(r) for r in range(rows)]
        soft = [weights(r, s) for r, s in enumerate(raw)]
        for r, (p, alpha) in enumerate(soft):
            context(r, p, alpha)

    @pl.when(kind % 4 >= 2)
    def _():
        def close(r, carry):
            # a reciprocal a head and a product a value: a division a
            # value is four times the divisions
            acc_ref[r] = acc_ref[r] * _lanes(1.0 / l_ref[r], latent)
            return carry

        jax.lax.fori_loop(0, rows, close, None)
        # and handed on heads first, as the product behind it reads them
        for lanes in range(0, latent, _LANES):
            part = slice(lanes, min(lanes + _LANES, latent))
            o_ref[:, :, part] = jnp.swapaxes(
                acc_ref[:, :, part], 0, 1).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _decode_pallas(q_lat, q_rope, cache, mask, scale: float,
                   interpret: bool = False):
    rows, heads, latent = q_lat.shape
    positions, width = cache.shape[1:]
    rope = width - latent
    assert q_rope.shape == (rows, heads, rope) and mask.shape == (
        rows, positions), (q_lat.shape, q_rope.shape, cache.shape, mask.shape)
    block, step_rows = column_block(positions), rows_a_step(rows)
    blocks = pl.cdiv(positions, block)
    steps = walk(live_blocks(mask, block, step_rows))
    seen = _pad_to(mask, blocks * block, 1).astype(jnp.float32).reshape(
        rows, blocks, block)
    q_lat, q_rope = q_lat.astype(cache.dtype), q_rope.astype(cache.dtype)

    def heads_first(at, step, block, kind):
        return 0, step[at], 0

    # heads first in and out: the batched products on either side (a head
    # its own matrix) make and read `[H, R, C]`, so the two transposes here
    # are XLA's to fold away and the relayout is the kernel's, in VMEM
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, positions=positions),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(rows // step_rows * blocks,),
            in_specs=[
                pl.BlockSpec((heads, step_rows, latent), heads_first),
                pl.BlockSpec((heads, step_rows, rope), heads_first),
                pl.BlockSpec((step_rows, block, width),
                             lambda at, step, block, kind: (
                                 step[at], block[at], 0)),
                pl.BlockSpec((step_rows, blocks, block),
                             lambda at, step, block, kind: (
                                 step[at], 0, 0))],
            out_specs=pl.BlockSpec((heads, step_rows, latent), heads_first),
            scratch_shapes=[
                pltpu.VMEM((step_rows, heads, _LANES), jnp.float32),
                pltpu.VMEM((step_rows, heads, _LANES), jnp.float32),
                pltpu.VMEM((step_rows, heads, latent), jnp.float32),
                pltpu.VMEM((step_rows, heads, latent), cache.dtype),
                pltpu.VMEM((step_rows, heads, rope), cache.dtype)]),
        out_shape=jax.ShapeDtypeStruct((heads, rows, latent), cache.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="latent_attention",
        interpret=interpret,
    )(*steps, jnp.swapaxes(q_lat, 0, 1), jnp.swapaxes(q_rope, 0, 1), cache,
      seen)
    return jnp.swapaxes(out, 0, 1)


@functools.partial(jax.named_call, name="latent_attention")
def latent_decode_attention(q_lat, q_rope, cache, mask, scale: float, *,
                            interpret: bool = False):
    """`q_lat` [R, H, C] and `q_rope` [R, H, P] against `cache`
    [R, S, C + P] (`c_kv | k_rope` a position); `mask` [R, S] says which
    positions a row may see (every row sees one at least). Returns the
    context [R, H, C] in the cache's dtype: the caller applies the value
    up-projection."""
    platform.KERNEL_TRACES.inc(op="latent_attention", path="absorbed")
    rows, _, latent = q_lat.shape
    if kernel_taken(latent, rows, interpret):
        platform.KERNEL_TRACES.inc(op="latent_attention", path="pallas")
        return _decode_pallas(q_lat, q_rope, cache, mask, scale,
                              interpret=interpret)
    return decode_reference(q_lat, q_rope, cache, mask, scale)
