"""Pallas flash attention under a causal band whose keys are wider than
its values and whose softmax may hold a term that is no key: the attention
of MiMo-V2's prefill (models/mimo_v2.py), `ops.banded_attention`'s sibling.

Query `i` of `Sq` stands at key index `t = offset + i` (`offset` a number
or a traced scalar, so one compiled kernel serves every span of a row;
None: the queries are the last `Sq` positions of the keys) and sees key `u`
iff `floor <= u <= t` and, with a `window`, `t - u < window` (`floor`, data
too: the leading key columns that hold no position, a window layer's tail
before a row's first span). Query head `j` reads key head `j // G`, `G = Hq
/ Hkv` (MiMo-V2: 16 on a full layer, 8 on a window layer).

**The sink.** `sink` [Hq] float32 is one learned logit a query head that
joins the softmax's denominator and has no value: `p_u = exp(a_u - m) /
(sum_u' exp(a_u' - m) + exp(s_j - m))`, `m = max(max_u a_u, s_j)`. In a
flash kernel that is where the running state starts: `m = s_j`, `l = 1`,
nothing accumulated (without one: `m = -inf`, `l = 0`). The scores are
scaled before they meet it; the sink enters in float32.

**Layout.** q [B, Sq, Hq, Dk], k [B, Skv, Hkv, Dk], v [B, Skv, Hkv, Dv] ->
[B, Sq, Hq, Dv], `Dv` whole blocks of the 128 lanes. `Dk` (192: 64 rotated
dims and 128 that are not) is one and a half lane blocks, which no block of
`[B, S, H * Dk]` can address a head by, so queries and keys are PADDED with
zeros to the next whole block (256) on their way in and the heads are then
columns of `[B, S, H * 256]` as `ops.banded_attention` has them. The MXU of
a v5e contracts 128 at a time, so a 192-wide product takes the two passes a
256-wide one does: the padding costs the copy and a third more bytes of
queries and keys, not matrix time (PERF.md section 6, PR 57, has the
reading; the other layout, the rotated 64 and the plain 128 as two operands
a side, needs a 64-lane block a key head, which the lowering cannot tile).

The grid is (batch, key head, query block, visited key block). Without a
window a query block walks the key blocks from `floor`'s up to the one its
last query stands in, and the grid's last axis is `cdiv(offset + Sq,
block)`, a bound that is DATA: a key block in a span's future is no grid
step at all, so a row's first span does an eighth of its last one's work
and what the cache holds past the span's end is never fetched. With a
window a query block visits its band's blocks only (`first .. last`), the
axis as long as the longest band can be; a step past `last` computes
nothing and, its block index being the one before it, fetches nothing. The
mask is built only in a block the band's edge or the floor crosses. The
softmax state (running max and sum lane-replicated, float32 accumulator)
is VMEM scratch a query head of the group, carried over the last grid axis.

Blocks: 512 keys a step without a window and twice the window's
128-multiple with one (`ops.banded_attention`'s rule); the queries a step
so that the group's `G x block_q` rows of scores stay under 4096 (256 at a
group of 16, 512 at 8: `step_vmem_bytes` counts a step from above).

`swarm_kernel_traces_total{op="attention"}`: `wide_key` (this kernel), or
`reference` (plain `jax.numpy`, off the chip).
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
    _VMEM_CAP,
    _VMEM_SLACK,
    _lanes,
    _pad_to,
    _round_up,
)

_BLOCK = 512       # queries and keys a step, at most
_GROUP_ROWS = 4096  # a group's query heads x queries a step, at most


def wide_key_blocks(sq: int, skv: int, window: int, group: int, dtype
                    ) -> tuple[int, int]:
    """(block_q, block_k) for a call of these shapes."""
    sublanes = 32 // jnp.dtype(dtype).itemsize
    largest = _BLOCK
    if window:  # a band of `window` keys: blocks no longer than twice it
        largest = min(_BLOCK, 2 * _round_up(window, _LANES))
    rows = max(_LANES, min(largest, _GROUP_ROWS // group // _LANES * _LANES))
    return (min(_round_up(sq, sublanes), rows),
            min(_round_up(skv, _LANES), largest))


def step_vmem_bytes(block_q: int, block_k: int, group: int, key_dim: int,
                    value_dim: int, itemsize: int) -> int:
    """Fast memory one grid step may hold, counted from above: blocks
    double-buffered, a query head's scores, exponentials and their
    operand-dtype copy for every head of the unrolled loop, the state."""
    io = 2 * block_q * group * (key_dim + value_dim) * itemsize
    kv = 2 * block_k * (key_dim + value_dim) * itemsize
    scores = 3 * group * block_q * block_k * 4
    state = group * block_q * (2 * _LANES + value_dim) * 4
    return io + kv + scores + state + 2 * group * _LANES * 4


def _band(i, offset, floor, window: int, block_q: int, block_k: int,
          n_k: int):
    """(first, last): the key blocks query block `i` visits, both
    inclusive; `offset` and `floor` may be traced."""
    start = offset + i * block_q  # the block's first query's key index
    low = jnp.maximum(start - (window - 1), floor) if window else floor
    last = jnp.minimum((start + block_q - 1) // block_k, n_k - 1)
    return jnp.minimum(low // block_k, last), last


def _wide_key_kernel(at_ref, *refs, window: int, block_k: int, n_k: int,
                     key_dim: int, value_dim: int, scale: float,
                     fold_scale: bool, has_sink: bool):
    """One (batch, key head, query block, visited key block) step: at_ref
    [2] (the first query's key index, the floor); sink_ref [G, 128] float32
    where there is a sink; q_ref [BQ, G * Dk], k_ref [BK, Dk], v_ref [BK,
    Dv], o_ref [BQ, G * Dv]; state [G, BQ, .]."""
    sink_ref = refs[0] if has_sink else None
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs[int(has_sink):]
    block_q = q_ref.shape[0]
    group = q_ref.shape[1] // key_dim
    i, j = pl.program_id(2), pl.program_id(3)
    offset, floor = at_ref[0], at_ref[1]
    first, last = _band(i, offset, floor, window, block_q, block_k, n_k)
    block = first + j
    start = offset + i * block_q

    @pl.when(j == 0)
    def _():
        if has_sink:
            # the sink is the softmax's first term: its own maximum, a
            # weight of one in the sum, no value
            for g in range(group):
                m_ref[g] = jnp.broadcast_to(sink_ref[g:g + 1, :],
                                            (block_q, _LANES))
            l_ref[...] = jnp.ones_like(l_ref)
        else:
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def update(masked: bool):
        k, v = k_ref[...], v_ref[...]
        if masked:
            col = block * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            row = start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            seen = (col <= row) & (col >= floor)
            if window:
                seen = seen & (row - col < window)
        for g in range(group):
            q = q_ref[:, g * key_dim:(g + 1) * key_dim]
            if fold_scale:
                q = q * scale  # a power of two: exact
            s = jax.lax.dot_general(
                q, k, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [BQ, BK]
            if not fold_scale:
                s = s * scale
            if masked:
                s = jnp.where(seen, s, _NEG_INF)
            m_prev, l_prev = m_ref[g], l_ref[g]
            m_next = jnp.maximum(m_prev, jnp.broadcast_to(
                jnp.max(s, axis=-1, keepdims=True), (block_q, _LANES)))
            p = jnp.exp(s - _lanes(m_next, block_k))
            alpha = jnp.exp(m_prev - m_next)
            l_ref[g] = alpha * l_prev + jnp.broadcast_to(
                jnp.sum(p, axis=-1, keepdims=True), (block_q, _LANES))
            m_ref[g] = m_next
            acc_ref[g] = acc_ref[g] * _lanes(alpha, value_dim) + (
                jax.lax.dot_general(
                    p.astype(v.dtype), v,
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))

    # every query of the step sees every key of the block: its last key is
    # no later than the first query, its first no earlier than the floor
    # and, with a window, no further than the window from the last query
    whole = ((block + 1) * block_k - 1 <= start) & (block * block_k >= floor)
    if window:
        whole = whole & (start + block_q - 1 - block * block_k < window)
    inside = block <= last

    @pl.when(inside & whole)
    def _():
        update(masked=False)

    @pl.when(inside & jnp.logical_not(whole))
    def _():
        update(masked=True)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        for g in range(group):
            o_ref[:, g * value_dim:(g + 1) * value_dim] = (
                acc_ref[g] / _lanes(l_ref[g], value_dim)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "window", "blocks",
                                             "interpret"))
def _wide_key_pallas(q, k, v, sink=None, offset=None, floor=None,
                     scale: float | None = None, window: int = 0,
                     blocks: tuple[int, int] | None = None,
                     interpret: bool = False):
    b, sq, hq, dk = q.shape
    skv, hkv, dv = k.shape[1], k.shape[2], v.shape[-1]
    assert hq % hkv == 0 and k.shape[-1] == dk and v.shape[:3] == k.shape[:3], (
        q.shape, k.shape, v.shape)
    assert offset is not None or sq <= skv, (sq, skv)
    # a value head is whole blocks of lanes (the interpreter takes any)
    assert interpret or dv % _LANES == 0, dv
    group = hq // hkv
    if scale is None:
        scale = dk ** -0.5
    # a key head padded to whole blocks of lanes: zeros add nothing to a
    # dot product
    key_dim = _round_up(dk, _LANES)
    block_q, block_k = blocks or wide_key_blocks(sq, skv, window, group,
                                                 q.dtype)
    sq_pad, skv_pad = _round_up(sq, block_q), _round_up(skv, block_k)
    n_q, n_k = sq_pad // block_q, skv_pad // block_k
    vmem = step_vmem_bytes(block_q, block_k, group, key_dim, dv,
                           jnp.dtype(q.dtype).itemsize)
    assert vmem <= _VMEM_CAP, (vmem, block_q, block_k, group)
    if window:
        # the blocks an interval of `block_q + window - 1` keys can touch
        visited = min((block_q + window - 2) // block_k + 2, n_k)
    elif offset is None:
        visited = n_k
    else:  # up to the span's end and no further: a grid bound that is data
        visited = jnp.clip(pl.cdiv(jnp.asarray(offset, jnp.int32) + sq,
                                   block_k), 1, n_k)
    at = jnp.stack([jnp.asarray(skv - sq if offset is None else offset,
                                jnp.int32),
                    jnp.asarray(0 if floor is None else floor, jnp.int32)])

    # padding rows are later positions than any real one: no real query
    # sees a padded key, and a padded query's row is cut off below
    q = _pad_to(_pad_to(q, key_dim, 3), sq_pad, 1).reshape(
        b, sq_pad, hq * key_dim)
    k = _pad_to(_pad_to(k, key_dim, 3), skv_pad, 1).reshape(
        b, skv_pad, hkv * key_dim)
    v = _pad_to(v, skv_pad, 1).reshape(b, skv_pad, hkv * dv)

    def kv_index(bi, hi, i, j, at):
        first, last = _band(i, at[0], at[1], window, block_q, block_k, n_k)
        return bi, jnp.minimum(first + j, last), hi

    def q_index(bi, hi, i, j, at):
        return bi, i, hi

    operands = [q, k, v]
    in_specs = [pl.BlockSpec((None, block_q, group * key_dim), q_index),
                pl.BlockSpec((None, block_k, key_dim), kv_index),
                pl.BlockSpec((None, block_k, dv), kv_index)]
    if sink is not None:
        assert sink.shape == (hq,), (sink.shape, hq)
        operands.insert(0, jnp.broadcast_to(
            sink.astype(jnp.float32)[:, None], (hq, _LANES)))
        in_specs.insert(0, pl.BlockSpec(
            (group, _LANES), lambda bi, hi, i, j, at: (hi, 0)))
    out = pl.pallas_call(
        functools.partial(
            _wide_key_kernel, window=window, block_k=block_k, n_k=n_k,
            key_dim=key_dim, value_dim=dv, scale=scale,
            fold_scale=math.frexp(scale)[0] == 0.5,
            has_sink=sink is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, n_q, visited),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, block_q, group * dv), q_index),
            scratch_shapes=[
                pltpu.VMEM((group, block_q, _LANES), jnp.float32),
                pltpu.VMEM((group, block_q, _LANES), jnp.float32),
                pltpu.VMEM((group, block_q, dv), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, sq_pad, hq * dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem + _VMEM_SLACK,
        ),
        name="wide_key_attention",
        # what the call is, beside its padded operands: the device trace's
        # instruction carries it, written as shapes are so that whatever
        # reads an instruction's shapes reads it too (without a window
        # `keys` is the width of what the call was handed: which span of
        # it the call walked is data)
        metadata={"band": f"queries[{sq}] keys[{skv}] window[{window}] "
                          f"sink[{int(sink is not None)}] heads[{hq}] "
                          f"keyheads[{hkv}] keydim[{dk}] valuedim[{dv}]"},
        interpret=interpret,
    )(at, *operands)
    return out[:, :sq].reshape(b, sq, hq, dv)


def wide_key_reference(q, k, v, scale: float | None = None, window: int = 0,
                       sink=None, offset=None, floor=None):
    """Plain `jax.numpy`, every head's scores laid out (tiny sizes, and the
    path off the chip): the sink as one more column of the scores that is
    dropped after the softmax."""
    b, sq, hq, dk = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    if scale is None:
        scale = dk ** -0.5
    scores = jnp.einsum("bqhgd,bkhd->bhgqk",
                        q.reshape(b, sq, hkv, group, dk), k,
                        preferred_element_type=jnp.float32) * scale
    row = (skv - sq if offset is None else offset) + jnp.arange(sq)[:, None]
    col = jnp.arange(skv)[None, :]
    seen = (col <= row) & (col >= (0 if floor is None else floor))
    if window:
        seen = seen & (row - col < window)
    scores = jnp.where(seen, scores, -jnp.inf)
    if sink is not None:
        scores = jnp.concatenate([scores, jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(1, hkv, group, 1, 1),
            (b, hkv, group, sq, 1))], axis=-1)
    weights = jax.nn.softmax(scores, axis=-1)[..., :skv].astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", weights, v,
                     preferred_element_type=jnp.float32)
    return out.astype(v.dtype).reshape(b, sq, hq, v.shape[-1])


@functools.partial(jax.named_call, name="attention")
def wide_key_attention(q, k, v, scale: float | None = None, window: int = 0,
                       sink=None, offset=None, floor=None, *,
                       interpret: bool = False):
    """[B, Sq, Hq, Dk] x [B, Skv, Hkv, Dk] x [B, Skv, Hkv, Dv] -> [B, Sq,
    Hq, Dv], causal: query `i` at key index `offset + i` (a number or a
    traced scalar; None: the last `Sq` of the keys) sees the keys from
    `floor` (data too; None: 0) up to its own, of them the last `window`
    where one is given; `sink` [Hq] joins each head's softmax and has no
    value. The kernel on a TPU (one chip) and under `interpret`, else the
    plain form."""
    if interpret or platform.trace_platform() == "tpu":
        platform.KERNEL_TRACES.inc(op="attention", path="wide_key")
        return _wide_key_pallas(q, k, v, sink, offset, floor, scale=scale,
                                window=window, interpret=interpret)
    platform.KERNEL_TRACES.inc(op="attention", path="reference")
    return wide_key_reference(q, k, v, scale, window, sink, offset, floor)
