"""Pallas flash attention for TPU (forward only — inference framework).

The hot attention in diffusion UNets/DiTs: latent self-attention at 1024^2
is 4096 tokens, where the O(S^2) score matrix (4096^2 x heads x f32) blows
HBM traffic; this kernel keeps the softmax state in VMEM and walks the
keys in tiles, so scores never round-trip to HBM (SURVEY §7 hard part #3).

Non-causal (diffusion attention has no causal mask), self- and cross-
attention (padded + masked KV for ragged text lengths like 77).

Layout: q [B, Sq, H, D], k/v [B, Skv, H, D] -> [B, Sq, H, D], matching
ops.attention. The TPU lowering wants the last two dims of every block to
be (multiple of 8, multiple of 128) or a full axis, so the wrapper moves
heads next to batch ([B, H, S_pad, D]) and every block is
(heads, rows, D) with D a full axis. The benchmark's roofline reader
finds the call by its name and reads these operand shapes, so the name,
the operand order and the layout are part of the yardstick.

How much one grid step holds is a rule on the call's shape alone,
`flash_blocks(Sq, Skv, H, D, dtype)` — no setting, no environment:

- keys: a sub-tile of up to `_SUB_K` keys, the multiple of 128 that pads
  the length least (2304 -> 768 x 3, 9216 -> 1024 x 9, 77 -> 128); a
  major block of as many sub-tiles as the step's VMEM count allows
  (every UNet and DiT length here: the whole axis), DMA'd once for a
  head and walked by an unrolled loop inside the step, so one tile's
  matmuls run under another's exponentials;
- queries: a block of up to `_MAX_Q` rows chosen the same way, half as
  many against more than `_MANY_SUBS` sub-tiles (rather the keys
  resident than a tall block); no axis is ever padded by more than 511;
- heads: as many of one batch row's heads in a step as keep it under
  `_STEP_SCORES` scores (a 77-key or 1024-token head alone is smaller
  than one good step), always a divisor of the head count;
- the step's VMEM bytes are counted from above (`step_vmem_bytes`), kept
  under `_VMEM_CAP`, and handed to the compiler as its limit.

One kernel. The running max / sum are lane-replicated [rows, 128] f32
values next to the f32 accumulator; the first key tile starts them and
the last one's division ends them, so a call whose keys fit one tile is
a plain softmax with no running state, and they pass through VMEM
scratch only when the keys span several major blocks of the grid. The
scale is folded into q when it is a power of two (exact in any float
dtype; 64^-0.5 is), else it multiplies the f32 scores. Padding is
masked in the last key sub-tile only, the only one that can hold any.

v5e, batch 8, kernel alone (PERF.md section 6, PR 25): 4096 x 10 heads
3.75 ms (8.79 with one head x 512 x 512 a step), 9216 x 5 9.34 (21.9),
1024 x 20 0.49 (1.18), 1024 x 77 keys x 20 0.095 (0.57). At D = 64 the
MXU can give half its peak; the long calls reach 46-47 % of it.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128

# the rule's constants (v5e sweep, PERF.md section 6, PR 25)
_MAX_Q = 1024                      # query rows a step, at most
_SUB_K = 1024                      # keys a score tile, at most
_MAX_PAD = 511                     # rows of padding an axis may get
_MANY_SUBS = 4                     # more sub-tiles: half the query rows
_STEP_SCORES = 4 * 1024 * 1024     # scores a step, where heads allow
_VMEM_CAP = 96 * 1024 * 1024       # of a v5e core's 128 MiB
_VMEM_SLACK = 8 * 1024 * 1024      # the compiler's own temporaries


class FlashBlocks(NamedTuple):
    """What one grid step holds: `block_h` heads x `block_q` query rows
    against a major block of `block_k_major` keys walked `block_k` at a
    time."""

    block_q: int
    block_k: int
    block_k_major: int
    block_h: int


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _tile(length: int, largest: int, sublanes: int) -> int:
    """The block for an axis of `length`: the whole axis, rounded to the
    dtype's tile, when it fits `largest`; else the multiple of 128 in
    (largest / 2, largest] that pads the axis least (the larger on a
    tie), among those that pad it by at most `_MAX_PAD`."""
    if length <= largest:
        return _round_up(length, sublanes)
    fits = [t for t in range(largest, largest // 2, -_LANES)
            if _round_up(length, t) - length <= _MAX_PAD]
    fits.append(_MAX_PAD + 1)  # pads by _MAX_PAD at most: never empty
    return min(fits, key=lambda t: (_round_up(length, t) - length, -t))


def step_vmem_bytes(block_q: int, block_k: int, block_k_major: int,
                    block_h: int, head_dim: int, itemsize: int) -> int:
    """Fast memory one grid step may hold, counted from above: blocks
    double-buffered with their lanes padded to 128; for every tile of
    the unrolled walk its scores, its exponentials and their
    operand-dtype copy (the compiler frees some early; it was seen to
    keep up to 2.2 of the 3); the softmax state as values and scratch."""
    lanes = _round_up(head_dim, _LANES)
    io = 2 * 2 * block_h * block_q * lanes * itemsize
    kv = 2 * 2 * block_h * block_k_major * lanes * itemsize
    tiles = block_h * (block_k_major // block_k)
    scores = 3 * tiles * block_q * block_k * 4
    state = 2 * block_h * block_q * (2 * _LANES + lanes) * 4
    return io + kv + scores + state


def flash_blocks(sq: int, skv: int, heads: int, head_dim: int,
                 dtype) -> FlashBlocks:
    """The rule: blocks for a call of these (per-chip) shapes."""
    itemsize = jnp.dtype(dtype).itemsize
    block_k = _tile(skv, _SUB_K, _LANES)
    subs = _round_up(skv, block_k) // block_k
    # rather the whole key axis in the step than a tall query block
    block_q = _tile(sq, _MAX_Q if subs <= _MANY_SUBS else _MAX_Q // 2,
                    32 // itemsize)  # 8 rows of 32 bits: 16 of bf16

    def count(group: int, block_h: int) -> int:
        return step_vmem_bytes(block_q, block_k, group * block_k, block_h,
                               head_dim, itemsize)

    group = max(g for g in range(1, subs + 1)
                if subs % g == 0 and (g == 1 or count(g, 1) <= _VMEM_CAP))
    block_k_major = group * block_k
    block_h = max(h for h in range(1, heads + 1) if heads % h == 0
                  and (h == 1 or (h * block_q * block_k_major <= _STEP_SCORES
                                  and count(group, h) <= _VMEM_CAP)))
    blocks = FlashBlocks(block_q, block_k, block_k_major, block_h)
    assert count(group, block_h) <= _VMEM_CAP, blocks
    return blocks


def _lanes(x, n: int):
    """A lane-replicated [rows, 128] value as [rows, n]."""
    if n <= _LANES:
        return x[:, :n]
    assert n % _LANES == 0, n
    return jnp.tile(x, (1, n // _LANES))


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *state, block_k: int,
                  kv_len: int, scale: float, fold_scale: bool):
    """One (batch, head block, q block, major kv block) grid step.

    q_ref/o_ref [HB, BQ, D]; k_ref/v_ref [HB, BKM, D], walked BK keys at
    a time by an unrolled loop, so the scheduler can run one tile's
    matmuls under another's exponentials. The running max and sum are
    lane-replicated [BQ, 128] f32 values, the accumulator [BQ, D] f32.
    `state` is empty when the step holds every key (the first tile
    starts the state, the last one's division ends it: with one tile, a
    plain softmax); else it is the three as scratch [HB, BQ, .], carried
    over the innermost grid axis.
    """
    block_h, block_q, head_dim = q_ref.shape
    subs = k_ref.shape[1] // block_k
    j = pl.program_id(3)
    ragged = kv_len % block_k != 0

    def tile(q, h, t, carry):
        rows = pl.ds(t * block_k, block_k)
        # QK^T runs in the INPUT dtype (bf16 on TPU) with f32
        # accumulation: the MXU computes bf16 x bf16 -> f32 natively at
        # full rate, while an f32 x f32 matmul costs several passes.
        s = jax.lax.dot_general(
            q, k_ref[h, rows, :],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [BQ, BK] f32
        if not fold_scale:
            s = s * scale
        # padding (ragged lengths like 77) is in the axis' last sub-tile
        if ragged and t == subs - 1:
            col = (j * k_ref.shape[1] + t * block_k
                   + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
            s = jnp.where(col < kv_len, s, _NEG_INF)
        m_next = jnp.broadcast_to(
            jnp.max(s, axis=-1, keepdims=True), (block_q, _LANES))
        if carry is not None:
            m_prev, l_prev, acc_prev = carry
            m_next = jnp.maximum(m_prev, m_next)
        p = jnp.exp(s - _lanes(m_next, block_k))
        l_next = jnp.broadcast_to(
            jnp.sum(p, axis=-1, keepdims=True), (block_q, _LANES))
        v = v_ref[h, rows, :]
        acc = jax.lax.dot_general(
            p.astype(v.dtype), v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if carry is not None:
            alpha = jnp.exp(m_prev - m_next)
            l_next = alpha * l_prev + l_next
            acc = acc_prev * _lanes(alpha, head_dim) + acc
        return m_next, l_next, acc

    if state:
        @pl.when(j == 0)
        def _():
            m_ref, l_ref, acc_ref = state
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

    for h in range(block_h):
        q = q_ref[h]
        if fold_scale:
            q = q * scale  # a power of two: exact
        carry = tuple(ref[h] for ref in state) if state else None
        for t in range(subs):
            carry = tile(q, h, t, carry)
        _, l, acc = carry
        if state:
            for ref, value in zip(state, carry):
                ref[h] = value

            @pl.when(j == pl.num_programs(3) - 1)
            def _(h=h, l=l, acc=acc):
                o_ref[h] = (acc / _lanes(l, head_dim)).astype(o_ref.dtype)
        else:
            o_ref[h] = (acc / _lanes(l, head_dim)).astype(o_ref.dtype)


def _pad_to(x, length: int, axis: int):
    pad = length - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("scale", "blocks", "interpret"))
def flash_attention(q, k, v, scale: float | None = None,
                    blocks: FlashBlocks | None = None,
                    interpret: bool = False):
    """[B, Sq, H, D] x [B, Skv, H, D] -> [B, Sq, H, D].

    `blocks` is for tests that force a branch at a small size; the
    program never passes it, and the rule decides.
    """
    b, sq, h, d = q.shape
    skv = k.shape[1]
    if scale is None:
        scale = d ** -0.5
    if blocks is None:
        blocks = flash_blocks(sq, skv, h, d, q.dtype)
    block_q, block_k, block_k_major, block_h = blocks
    assert block_k % _LANES == 0 and block_k_major % block_k == 0, blocks
    assert h % block_h == 0, (h, blocks)
    sq_pad = _round_up(sq, block_q)
    skv_pad = _round_up(skv, block_k_major)
    assert skv_pad - skv < block_k, (skv, blocks)
    vmem = step_vmem_bytes(*blocks, d, jnp.dtype(q.dtype).itemsize)

    # [B, S, H, D] -> [B, H, S_pad, D]
    q = _pad_to(q, sq_pad, 1).transpose(0, 2, 1, 3)
    k = _pad_to(k, skv_pad, 1).transpose(0, 2, 1, 3)
    v = _pad_to(v, skv_pad, 1).transpose(0, 2, 1, 3)

    major_blocks = skv_pad // block_k_major
    q_spec = pl.BlockSpec((None, block_h, block_q, d),
                          lambda bi, hi, i, j: (bi, hi, i, 0))
    kv_spec = pl.BlockSpec((None, block_h, block_k_major, d),
                           lambda bi, hi, i, j: (bi, hi, j, 0))
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, block_k=block_k, kv_len=skv, scale=scale,
            # a power of two scales q exactly, whatever the dtype
            fold_scale=math.frexp(scale)[0] == 0.5,
        ),
        grid=(b, h // block_h, sq_pad // block_q, major_blocks),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq_pad, d), q.dtype),
        # the softmax state, only where the grid carries it
        scratch_shapes=[] if major_blocks == 1 else [
            pltpu.VMEM((block_h, block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_h, block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_h, block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem + _VMEM_SLACK,
        ),
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)

    return out[:, :, :sq].transpose(0, 2, 1, 3)
