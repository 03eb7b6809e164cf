"""The gated delta rule of a linear-attention layer (Gated DeltaNet,
models/qwen3_next.py): a recurrent state a row and a head, a float32 matrix
`S` [keys, values] that does not grow with the positions a row has seen.

For every position `t` of a row, a head at a time, with `k_t` and `q_t`
[keys] (L2-normalised by the caller, `q` scaled), `v_t` [values], a log
decay `g_t <= 0` and a write strength `beta_t` in (0, 1):

    S <- S * exp(g_t);  m = S^T k_t;  d = (v_t - m) * beta_t
    S <- S + k_t (x) d; o_t = S^T q_t

`gated_delta_step` is one position a row (a decode step): state in, state
out in place, `o` out. A step reads and writes the whole state and does a
handful of operations a byte, so it is bound by the memory: on a TPU a
Pallas kernel that reads each head's matrix once and writes it once
(`pallas`), elsewhere the recurrence in `jax.numpy` (`reference`; as XLA
einsums on a chip the state would go through four fusions);
`interpret=True` runs the kernel interpreted, for tests. The kernel leaves
the state in HBM and moves it itself (ops/state_rows.py): a grid step
takes as many rows as two sets of buffers hold (Qwen3-Next: 8 rows of 32
matrices of [128, 128], 16 MB a set), reads them a row (2 MB) at a time,
computes a row in place as it lands, and writes the step's rows when the
next step's have been read. A row's arithmetic does not know which rows
share its step. Routed by ops/platform.py and counted by
`swarm_kernel_traces_total{op="gated_delta_step", path}`.

`gated_delta_chunks` is the same recurrence over `[rows, positions]` in
chunks of 64 positions (the published chunk form: prefill), `jax.numpy`
and one `lax.scan` over the chunks for the state, on any platform. Inside
a chunk, with `c` the running sum of `g` and `D_ij = exp(c_i - c_j)` for
`i >= j`: `A = -(beta k k^T * D)` strictly lower, `T = (I - A)^-1`,
`u = T (beta v)`, `w = T (beta k exp c)`; then a chunk at a time
`v' = u - w S`, `o = (q exp c) S + (q k^T * D, lower with diagonal) v'`,
`S <- S exp(c_last) + (k exp(c_last - c))^T v'`. `A` is nilpotent (strictly
lower, 64 wide), so `T = (I + A)(I + A^2)(I + A^4) ... (I + A^32)`: the
matrix forward substitution gives, as eleven small matmuls. A position at
or past its row's length has `beta = 0`, `g = 0`, `k = 0`: it changes
nothing, so the state a row leaves is the state at its own last position.
Every product is float32 at the highest matmul precision: a product that
rounded the state to bfloat16 would be a state kept in bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import platform, state_rows

# positions of a chunk of the chunk form
CHUNK = 64
_HIGHEST = jax.lax.Precision.HIGHEST


def step_reference(q, k, v, g, beta, state):
    """The recurrence's one position in `jax.numpy`: `q`, `k` [R, H, K],
    `v` [R, H, V], `g`, `beta` [R, H], `state` [R, H, K, V] float32.
    Returns (`o` [R, H, V] float32, the state after the position)."""
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    state = state * jnp.exp(g)[..., None, None]
    m = jnp.sum(state * k[..., :, None], axis=-2)
    d = (v - m) * beta[..., None]
    state = state + k[..., :, None] * d[..., None, :]
    return jnp.sum(state * q[..., :, None], axis=-2), state


def _step_kernel(qk_ref, v_ref, decay_ref, beta_ref, state_hbm, o_ref,
                 out_hbm, held, reads, writes):
    """A grid step's rows: `qk_ref` [rows, K, 2 H] (a head's query in lane
    `h`, its key in lane `H + h`: a column a head, so that it broadcasts
    along the values' lanes), `v_ref`, `decay_ref`, `beta_ref`, `o_ref`
    [rows, H, V] (the two scalars a head already along the lanes); the
    state stays in HBM and goes through `held` a row at a time
    (`state_rows.stream`)."""
    heads = v_ref.shape[1]

    def update(held, row, _):  # a row is one chunk
        for head in range(heads):
            q = qk_ref[row, :, head:head + 1]
            k = qk_ref[row, :, heads + head:heads + head + 1]
            s = held[row, head] * decay_ref[row, head:head + 1, :]
            m = jnp.sum(s * k, axis=0, keepdims=True)
            d = ((v_ref[row, head:head + 1, :] - m)
                 * beta_ref[row, head:head + 1, :])
            s = s + k * d
            held[row, head] = s
            o_ref[row, head:head + 1, :] = jnp.sum(s * q, axis=0,
                                                   keepdims=True)

    state_rows.stream(state_hbm, out_hbm, held, reads, writes, update)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _step_pallas(q, k, v, g, beta, state, *, block_rows: int | None = None,
                 interpret: bool = False):
    rows, heads, keys, values = state.shape
    if block_rows is None:
        block_rows = state_rows.rows_a_step(rows, 4 * heads * keys * values)
    f32 = jnp.float32
    # a head's query and key as columns: [R, K, 2 heads]
    qk = jnp.concatenate([q.astype(f32), k.astype(f32)], axis=1).transpose(
        0, 2, 1)
    along = (rows, heads, values)
    decay = jnp.broadcast_to(jnp.exp(g.astype(f32))[..., None], along)
    strength = jnp.broadcast_to(beta.astype(f32)[..., None], along)
    small = pl.BlockSpec((block_rows, heads, values), lambda r: (r, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    o, out = pl.pallas_call(
        _step_kernel,
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, keys, 2 * heads),
                               lambda r: (r, 0, 0)),
                  small, small, small, in_hbm],
        out_specs=[small, in_hbm],
        out_shape=[jax.ShapeDtypeStruct(along, f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        scratch_shapes=state_rows.buffers(block_rows, heads, 1,
                                          (keys, values)),
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=state_rows.VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=7 * state.size, transcendentals=0,
            bytes_accessed=4 * (2 * state.size + 4 * rows * heads * values
                                + 2 * rows * heads * keys)),
        name="gated_delta_step",
        interpret=interpret,
    )(qk, v.astype(f32), decay, strength, state)
    return o, out


def gated_delta_step(q, k, v, g, beta, state, *, interpret: bool = False):
    """One position a row through the rule: `q`, `k` [R, H, K] (normalised,
    `q` scaled; a key head that serves several value heads already
    repeated), `v` [R, H, V], `g` (the log decay) and `beta` [R, H],
    `state` [R, H, K, V] float32. Returns (`o` [R, H, V] float32, the
    state after the position: on the kernel's path the buffer it came
    in)."""
    if interpret or platform.trace_platform() == "tpu":
        platform.KERNEL_TRACES.inc(op="gated_delta_step", path="pallas")
        return _step_pallas(q, k, v, g, beta, state, interpret=interpret)
    platform.KERNEL_TRACES.inc(op="gated_delta_step", path="reference")
    return step_reference(q, k, v, g, beta, state)


def _inverse_of_unit_lower(a):
    """`(I - a)^-1` for `a` [..., C, C] strictly lower: `a^C = 0`, so the
    product of `(I + a^(2^n))` up to `2^n >= C / 2` is the whole Neumann
    series."""
    size = a.shape[-1]
    eye = jnp.eye(size, dtype=a.dtype)
    out, power, reach = eye + a, a, 2
    while reach < size:
        power = jnp.matmul(power, power, precision=_HIGHEST)
        out = jnp.matmul(out, eye + power, precision=_HIGHEST)
        reach *= 2
    return out


def gated_delta_chunks(q, k, v, g, beta, lengths, state, start: int = 0,
                       chunk: int = CHUNK):
    """The rule over whole rows in the chunk form: `q`, `k` [R, S, H, K],
    `v` [R, S, H, V], `g`, `beta` [R, S, H], `state` [R, H, K, V] float32
    as the rows stood before slot `start`; the slots are the rows'
    positions `start .. start + S`, of which a row's real ones are those
    under its `lengths` [R] (slots that fill the last chunk are added
    here, as no row's). Returns (`o` [R, S, H, V] float32: whatever at a
    slot that is no real position, the state after each row's last real
    position of these)."""
    rows, given, heads, _ = q.shape
    chunks = -(-given // chunk)
    slots = chunks * chunk
    if slots != given:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, slots - given))
                    + ((0, 0),) * (x.ndim - 2)) for x in (q, k, v, g, beta))
    f32 = jnp.float32
    real = (start + jnp.arange(slots))[None, :] < jnp.minimum(
        lengths, start + given)[:, None]
    real = real[..., None]
    g = jnp.where(real, g.astype(f32), 0.0)
    beta = jnp.where(real, beta.astype(f32), 0.0)
    k = jnp.where(real[..., None], k.astype(f32), 0.0)

    def chunked(x):
        """[R, S, H, ...] -> [chunks, R, H, chunk, ...]."""
        x = x.reshape(rows, chunks, chunk, heads, *x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v = chunked(q.astype(f32)), chunked(k), chunked(v.astype(f32))
    beta = chunked(beta)[..., None]
    run = jnp.cumsum(chunked(g), axis=-1)  # c, [chunks, R, H, C]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # exp only where i >= j: the other differences are positive
    decay = jnp.exp(jnp.where(lower, run[..., :, None] - run[..., None, :],
                              -jnp.inf))
    matmul = functools.partial(jnp.matmul, precision=_HIGHEST)
    k_t = jnp.swapaxes(k, -1, -2)
    inverse = _inverse_of_unit_lower(
        -jnp.where(jnp.tril(lower, -1), matmul(k * beta, k_t) * decay, 0.0))
    u = matmul(inverse, v * beta)
    w = matmul(inverse, k * beta * jnp.exp(run)[..., None])
    inside = matmul(q, k_t) * decay  # lower with diagonal: decay is 0 above
    last = run[..., -1:]
    q_in = q * jnp.exp(run)[..., None]
    k_out = k * jnp.exp(last - run)[..., None]

    def chunk(state, xs):
        u, w, inside, q_in, k_out, last = xs
        fresh = u - matmul(w, state)
        o = matmul(q_in, state) + matmul(inside, fresh)
        state = state * jnp.exp(last)[..., None] + matmul(
            jnp.swapaxes(k_out, -1, -2), fresh)
        return state, o

    state, o = jax.lax.scan(chunk, state.astype(f32),
                            (u, w, inside, q_in, k_out, last))
    # [chunks, R, H, C, V] -> [R, S, H, V]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)
    return o.reshape(rows, slots, heads, -1)[:, :given], state
