"""The selective state-space recurrence of a Mamba-2 mixer (state-space
duality, models/falcon_h1.py) and the short causal convolution in front of
it: a recurrent state a row and a head, a float32 matrix `S` [state,
head dim] that does not grow with the positions a row has seen.

For every position `t` of a row, a head `j` of group `g` at a time, with
`x_t` [head dim], a step `dt_t > 0` (after its softplus), the head's decay
rate `A_j < 0` and skip `D_j`, and the group's `B_t`, `C_t` [state]:

    S <- exp(dt_t A_j) S + B_t (x) (dt_t x_t);  y_t = C_t . S + D_j x_t

A head's matrix is kept `[state, head dim]` (Falcon-H1-34B: [256, 128], the
131,072 values the published `[head dim, state]` has): the head's dims lie
along the lanes, so the read `C . S` is a sum down the sublanes and `y`
comes out a lane-dense row, and the write is a column `B` against a row
`dt x`.

`ssd_step` is one position a row (a decode step): state in, state out in
place, `y` out. A step reads and writes the whole state and does five
operations a value, so it is bound by the memory: on a TPU a Pallas kernel
that reads each head's matrix once and writes it once and fetches `B` and
`C` a group and not a head (`pallas`), elsewhere the recurrence in
`jax.numpy` (`reference`); `interpret=True` runs the kernel interpreted,
for tests. The kernel leaves the state in HBM and moves it itself
(ops/state_rows.py): a grid step takes as many rows as two sets of
buffers hold (Falcon-H1-34B: 4 rows of 32 matrices, 16 MB a set), reads
them a row's group at a time (16 matrices, 2 MB), computes a group in
place as it lands, and writes the step's rows when the next step's have
been read. A row's arithmetic does not know which rows share its step.
Routed by ops/platform.py and counted by
`swarm_kernel_traces_total{op="ssd_step", path}`.

`ssd_chunks` is the same recurrence over `[rows, positions]` in chunks of
`chunk` positions (the published chunk form: prefill), `jax.numpy` and one
`lax.scan` over the chunks for the state, on any platform. Inside a chunk,
with `c` the running sum of `dt A` and `L_ij = exp(c_i - c_j)` for `i >=
j`: `y = (C B^T * L)(dt x) + exp(c) (C . S) + D x`, and between chunks
`S <- exp(c_last) S + (B exp(c_last - c))^T (dt x)`. A position at or past
its row's length has `dt = 0`: it decays nothing and writes nothing, so
the state a row leaves is the state at its own last position. Every
product is float32 at the highest matmul precision: a product that rounded
the state to bfloat16 would be a state kept in bfloat16.

`causal_conv` is the depthwise convolution over the last `taps` inputs of
each channel, for a span of positions or for one (a decode step), from the
`taps - 1` inputs before it (the tail a row's cache keeps).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import platform, state_rows

_HIGHEST = jax.lax.Precision.HIGHEST


def step_reference(x, dt, a, b, c, d, state):
    """The recurrence's one position in `jax.numpy`: `x` [R, H, P], `dt`
    [R, H], `a`, `d` [H], `b`, `c` [R, G, N] (head `j` reads group `j // (H
    / G)`), `state` [R, H, N, P] float32. Returns (`y` [R, H, P] float32,
    the state after the position)."""
    f32 = jnp.float32
    x, dt, b, c = (v.astype(f32) for v in (x, dt, b, c))
    per = x.shape[1] // b.shape[1]
    b, c = (jnp.repeat(v, per, axis=1) for v in (b, c))  # [R, H, N]
    state = (state * jnp.exp(dt * a.astype(f32))[..., None, None]
             + b[..., :, None] * (x * dt[..., None])[..., None, :])
    y = jnp.sum(state * c[..., :, None], axis=-2)
    return y + d.astype(f32)[:, None] * x, state


def _step_kernel(bc_ref, dtx_ref, decay_ref, state_hbm, y_ref, out_hbm, held,
                 reads, writes, *, per: int):
    """A grid step's rows: `bc_ref` [rows, G, N, 2] (a group's `B` in lane
    0, its `C` in lane 1: columns, so that they broadcast along the head
    dim's lanes), `dtx_ref` and `decay_ref` [rows, H, P] (`dt x` a head,
    and the head's decay already along the lanes), `y_ref` [rows, H, P];
    the state stays in HBM and goes through `held` a row's group at a time
    (`state_rows.stream`)."""

    def update(held, row, group):
        b = bc_ref[row, group, :, 0:1]
        c = bc_ref[row, group, :, 1:2]
        for head in range(group * per, (group + 1) * per):
            s = (held[row, head] * decay_ref[row, head:head + 1, :]
                 + b * dtx_ref[row, head:head + 1, :])
            held[row, head] = s
            y_ref[row, head:head + 1, :] = jnp.sum(s * c, axis=0,
                                                   keepdims=True)

    state_rows.stream(state_hbm, out_hbm, held, reads, writes, update)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _step_pallas(x, dt, a, b, c, d, state, *, block_rows: int | None = None,
                 interpret: bool = False):
    rows, heads, size, dim = state.shape
    groups = b.shape[1]
    if block_rows is None:
        block_rows = state_rows.rows_a_step(rows, 4 * heads * size * dim)
    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    along = (rows, heads, dim)
    dtx = x * dt[..., None]
    decay = jnp.broadcast_to(jnp.exp(dt * a.astype(f32))[..., None], along)
    # a group's `B` and `C` as columns: [R, G, N, 2]
    bc = jnp.stack([b.astype(f32), c.astype(f32)], axis=-1)
    small = pl.BlockSpec((block_rows, heads, dim), lambda r: (r, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    y, out = pl.pallas_call(
        functools.partial(_step_kernel, per=heads // groups),
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, groups, size, 2),
                               lambda r: (r, 0, 0, 0)),
                  small, small, in_hbm],
        out_specs=[small, in_hbm],
        out_shape=[jax.ShapeDtypeStruct(along, f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        scratch_shapes=state_rows.buffers(block_rows, heads, groups,
                                          (size, dim)),
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=state_rows.VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=5 * state.size, transcendentals=0,
            bytes_accessed=4 * (2 * state.size + 3 * rows * heads * dim
                                + 2 * rows * groups * size)),
        name="ssd_step",
        interpret=interpret,
    )(bc, dtx, decay, state)
    return y + d.astype(f32)[:, None] * x, out


def ssd_step(x, dt, a, b, c, d, state, *, interpret: bool = False):
    """One position a row through the recurrence: `x` [R, H, P], `dt` [R,
    H] (after its softplus), `a` (negative) and `d` [H], `b`, `c` [R, G,
    N], `state` [R, H, N, P] float32. Returns (`y` [R, H, P] float32, the
    skip `D x` in it, the state after the position: on the kernel's path
    the buffer it came in)."""
    if interpret or platform.trace_platform() == "tpu":
        platform.KERNEL_TRACES.inc(op="ssd_step", path="pallas")
        return _step_pallas(x, dt, a, b, c, d, state, interpret=interpret)
    platform.KERNEL_TRACES.inc(op="ssd_step", path="reference")
    return step_reference(x, dt, a, b, c, d, state)


def ssd_chunks(x, dt, a, b, c, d, lengths, state, start: int = 0,
               chunk: int = 128):
    """The recurrence over whole rows in the chunk form: `x` [R, S, H, P],
    `dt` [R, S, H], `a`, `d` [H], `b`, `c` [R, S, G, N], `state` [R, H, N,
    P] float32 as the rows stood before slot `start`; the slots are the
    rows' positions `start .. start + S`, of which a row's real ones are
    those under its `lengths` [R] (slots that fill the last chunk are
    added here, as no row's). Returns (`y` [R, S, H, P] float32: whatever
    at a slot that is no real position, the state after each row's last
    real position of these)."""
    rows, given, heads, dim = x.shape
    groups, size = b.shape[2:]
    per = heads // groups
    chunks = -(-given // chunk)
    slots = chunks * chunk
    f32 = jnp.float32
    real = (start + jnp.arange(given))[None, :] < lengths[:, None]
    dt = jnp.where(real[..., None], dt.astype(f32), 0.0)
    x = x.astype(f32)
    skip = d.astype(f32)[:, None] * x
    pad = ((0, 0), (0, slots - given))

    def chunked(v, *trailing):
        """[R, S, *trailing] -> [chunks, R, *trailing[:-1], chunk,
        trailing[-1]]: the positions of a chunk beside the last axis."""
        v = jnp.pad(v, pad + ((0, 0),) * (v.ndim - 2))
        v = v.reshape(rows, chunks, chunk, *trailing)
        return jnp.moveaxis(jnp.moveaxis(v, 2, -2), 1, 0)

    dtx = chunked(x * dt[..., None], groups, per, dim)
    # [chunks, R, G, per, chunk]
    rate = chunked((dt * a.astype(f32))[..., None], groups, per, 1)[..., 0]
    b, c = (chunked(v.astype(f32), groups, size) for v in (b, c))
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    einsum = functools.partial(jnp.einsum, precision=_HIGHEST)

    def one(state, xs):
        dtx, rate, b, c = xs
        run = jnp.cumsum(rate, axis=-1)  # c, [R, G, per, C]
        last = run[..., -1:]
        # exp only where i >= j: the other differences are positive
        across = jnp.exp(jnp.where(
            lower, run[..., :, None] - run[..., None, :], -jnp.inf))
        inside = einsum("rgin,rgjn->rgij", c, b)[:, :, None] * across
        y = (einsum("rghij,rghjp->rghip", inside, dtx)
             + einsum("rgin,rghnp->rghip", c, state)
             * jnp.exp(run)[..., None])
        state = (state * jnp.exp(last)[..., None]
                 + einsum("rgjn,rghjp->rghnp", b,
                          dtx * jnp.exp(last - run)[..., None]))
        return state, y

    state, y = jax.lax.scan(
        one, state.astype(f32).reshape(rows, groups, per, size, dim),
        (dtx, rate, b, c))
    # [chunks, R, G, per, chunk, P] -> [R, S, H, P]
    y = jnp.moveaxis(jnp.moveaxis(y, 0, 1), -2, 2)
    y = y.reshape(rows, slots, heads, dim)[:, :given]
    return y + skip, state.reshape(rows, heads, size, dim)


def causal_conv(x, tail, weight, bias):
    """The depthwise causal convolution and its SiLU over `x` [R, S,
    channels] (S = 1: a decode step), `tail` [R, taps - 1, channels] the
    inputs before it, `weight` [taps, channels] (the last tap on the
    position's own input), `bias` [channels]. Returns (the output [R, S,
    channels] in `x`'s dtype, the inputs `tail | x` [R, taps - 1 + S,
    channels] of which the caller keeps its next tail)."""
    slots, taps = x.shape[1], weight.shape[0]
    behind = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    mixed = sum(behind[:, tap:tap + slots].astype(jnp.float32)
                * weight[tap].astype(jnp.float32) for tap in range(taps))
    mixed = jax.nn.silu(mixed + bias.astype(jnp.float32))
    return mixed.astype(x.dtype), behind
