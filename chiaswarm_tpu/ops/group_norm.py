"""Fused GroupNorm(+SiLU) for TPU (VERDICT r04 next-step #2).

GroupNorm is the UNet families' highest-traffic non-matmul op (~60
instances per SDXL UNet call). XLA's fused schedule is 2 HBM reads + 1
write per GN (stats pass + apply pass); this Pallas kernel does the whole
thing in VMEM — ONE read + one write — whenever a batch row's [N, C]
tile fits the conservative on-chip budget (`fused_tile_bytes` against
`_vmem_budget`); bigger tiles take the XLA path, which is already
near-roofline for its schedule, until an on-hardware sweep raises
CHIASWARM_FUSED_GN_MAX_BYTES with measured footprints. SiLU fuses into
the same pass, as does the affine.

Dispatch: `group_norm(x, scale, bias, ...)` is a trace-time branch on
platform and shape only (ops/platform.py): the kernel on TPU for tiles
the budget admits, the f32-stats reference XLA fuses itself everywhere
else — CPU, oversize tiles, channel counts the groups do not divide.
There is no fallback if the kernel fails to lower: the admission rule is
checked against the TPU compiler in tests/test_kernels_compile_tpu.py,
and a refusal past it fails the compile, loudly.
Numerics vs flax.linen.GroupNorm are pinned by tests/test_group_norm.py.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.sharding import PartitionSpec as P

from .platform import KERNEL_TRACES, active_mesh, batch_axis, trace_platform

# Fast-memory budget for one batch row's tile (`fused_tile_bytes`). The
# default is deliberately conservative: a VMEM overflow is a COMPILE-TIME
# failure in every UNet GN site, and the compiler's own scoped limit on a
# v5e is 16 MiB. CHIASWARM_FUSED_GN_MAX_BYTES raises it for on-hardware
# sweeps once the kernel's real footprint is measured.
_DEFAULT_VMEM_TILE_BYTES = 6 * 1024 * 1024


def _vmem_budget() -> int:
    return int(os.environ.get("CHIASWARM_FUSED_GN_MAX_BYTES",
                              _DEFAULT_VMEM_TILE_BYTES))


def _chunk_rows(n: int, c: int) -> int:
    """Rows of the [N, C] tile the kernel turns to f32 at a time: the
    largest power of two that divides N and keeps one f32 chunk under
    256 KiB (so the f32 intermediates stay a small, fixed part of the
    footprint), or the whole tile when N has no such divisor."""
    rows = 8
    while n % (rows * 2) == 0 and rows * 2 * c * 4 <= 256 * 1024:
        rows *= 2
    return rows if n % rows == 0 else n


def fused_tile_bytes(n: int, c: int, itemsize: int) -> int:
    """Fast memory one [N, C] row of the batch costs the kernel: its input
    and output blocks, each double-buffered by the Pallas pipeline (the
    next row's DMA overlaps this row's compute) with channels padded to
    the 128-lane tile, plus a few f32 chunks."""
    c_pad = -(-c // 128) * 128
    return 4 * n * c_pad * itemsize + 4 * _chunk_rows(n, c) * c_pad * 4


def _gn_kernel(x_ref, scale_ref, bias_ref, o_ref, *, groups: int, eps: float,
               silu: bool):
    """One batch row: x_ref [N, C] -> o_ref [N, C], stats in f32.

    The tile stays in its serving dtype in VMEM; both passes over it (sum
    / sum of squares, then normalize) walk it in row chunks so only one
    chunk is ever f32. Everything is kept 2-D: Mosaic has no layout for
    the [C] -> [G, C/G] cast the obvious per-group fold needs, so groups
    are folded and spread back through a [G, C] membership mask instead
    (VPU selects and reductions, exact in f32).
    """
    n, c = x_ref.shape
    cg = c // groups
    rows = _chunk_rows(n, c)

    def chunk(i):
        if rows == n:  # one chunk: a static slice needs no alignment proof
            return slice(None)
        return pl.ds(pl.multiple_of(i * rows, rows), rows)

    def moments(i, carry):
        s1, s2 = carry
        xf = x_ref[chunk(i), :].astype(jnp.float32)
        return (s1 + jnp.sum(xf, axis=0, keepdims=True),
                s2 + jnp.sum(xf * xf, axis=0, keepdims=True))

    zero = jnp.zeros((1, c), jnp.float32)
    s1, s2 = jax.lax.fori_loop(0, n // rows, moments, (zero, zero))

    channel = jax.lax.broadcasted_iota(jnp.int32, (groups, c), 1)
    first = jax.lax.broadcasted_iota(jnp.int32, (groups, c), 0) * cg
    member = (channel >= first) & (channel < first + cg)    # [G, C]

    def fold(per_channel):      # [1, C] -> [G, 1]
        return jnp.sum(jnp.where(member, per_channel, 0.0), axis=1,
                       keepdims=True)

    def spread(per_group):      # [G, 1] -> [1, C]
        return jnp.sum(jnp.where(member, per_group, 0.0), axis=0,
                       keepdims=True)

    count = jnp.float32(n * cg)
    mean = fold(s1) / count
    var = fold(s2) / count - mean * mean
    rstd = jax.lax.rsqrt(var + eps)

    scale_c = scale_ref[...].astype(jnp.float32) * spread(rstd)   # [1, C]
    bias_c = bias_ref[...].astype(jnp.float32) - spread(mean) * scale_c

    def apply(i, carry):
        y = x_ref[chunk(i), :].astype(jnp.float32) * scale_c + bias_c
        if silu:
            y = y * jax.nn.sigmoid(y)
        o_ref[chunk(i), :] = y.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, n // rows, apply, 0)


@functools.partial(
    jax.jit, static_argnames=("groups", "eps", "silu", "interpret")
)
def _fused_group_norm(x3, scale, bias, groups: int, eps: float, silu: bool,
                      interpret: bool = False):
    """x3 [B, N, C] -> [B, N, C] via the single-pass kernel."""
    b, n, c = x3.shape
    tile = pl.BlockSpec((None, n, c), lambda i: (i, 0, 0))
    vec = pl.BlockSpec((1, c), lambda i: (0, 0))
    return pl.pallas_call(
        functools.partial(_gn_kernel, groups=groups, eps=eps, silu=silu),
        grid=(b,),
        in_specs=[tile, vec, vec],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((b, n, c), x3.dtype),
        name="fused_group_norm",
        interpret=interpret,
    )(x3, scale.reshape(1, c), bias.reshape(1, c))


def _reference_group_norm(x, scale, bias, groups: int, eps: float,
                          silu: bool, dtype):
    """f32-stats reference (flax.linen.GroupNorm semantics); XLA fuses
    this into its own 2-read-1-write schedule."""
    orig_shape = x.shape
    c = orig_shape[-1]
    xf = x.astype(jnp.float32).reshape(*orig_shape[:-1], groups, c // groups)
    red = tuple(range(1, xf.ndim - 2)) + (xf.ndim - 1,)
    mean = jnp.mean(xf, axis=red, keepdims=True)
    # fast variance (E[x^2] - mean^2): flax's GroupNorm default and the
    # same form the kernel's one-pass accumulation uses
    var = jnp.mean(jnp.square(xf), axis=red, keepdims=True) - jnp.square(mean)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    y = y.reshape(orig_shape)
    y = y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    if silu:
        y = y * jax.nn.sigmoid(y)
    return y.astype(dtype)


def group_norm(x, scale, bias, *, groups: int = 32, eps: float = 1e-5,
               act: str | None = None, dtype=None, interpret: bool = False):
    """GroupNorm over the channel-last axis with optional fused SiLU.

    x: [..., C] (diffusion blocks pass [B, H, W, C]); scale/bias: [C].
    """
    if dtype is None:
        dtype = x.dtype
    silu = act == "silu"
    c = x.shape[-1]

    n = 1
    for d in x.shape[1:-1]:
        n *= d
    use_kernel = (
        (interpret or trace_platform() == "tpu")
        and x.ndim >= 3
        and c % groups == 0
        and fused_tile_bytes(n, c, x.dtype.itemsize) <= _vmem_budget()
    )
    if not use_kernel:
        KERNEL_TRACES.inc(op="group_norm", path="reference")
        return _reference_group_norm(x, scale, bias, groups, eps, silu, dtype)

    KERNEL_TRACES.inc(op="group_norm", path="fused")
    kernel = functools.partial(_fused_group_norm, groups=groups, eps=eps,
                               silu=silu, interpret=interpret)
    mesh = active_mesh()
    if mesh is not None:
        # opaque to the SPMD partitioner (ops/platform.py mesh_scope):
        # batch rows over `data`; the activations a GroupNorm sees are
        # whole on every chip of the tensor axis, so each normalizes its own
        rows = P(batch_axis(mesh, x.shape[0]), None, None)
        kernel = jax.shard_map(kernel, mesh=mesh, in_specs=(rows, P(), P()),
                               out_specs=rows, check_vma=False)
    out = kernel(x.reshape(x.shape[0], n, c), jnp.asarray(scale),
                 jnp.asarray(bias))
    return out.reshape(x.shape).astype(dtype)
