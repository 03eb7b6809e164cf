"""Pallas flash attention for TPU (forward only — inference framework).

The hot attention in diffusion UNets/DiTs: latent self-attention at 1024^2
is 4096 tokens, where the O(S^2) score matrix (4096^2 x heads x f32) blows
HBM traffic; this kernel keeps the online-softmax state in VMEM and streams
KV blocks, so scores never round-trip to HBM (SURVEY §7 hard part #3).

Non-causal (diffusion attention has no causal mask), self- and cross-
attention (padded + masked KV for ragged text lengths like 77).

Layout: q [B, Sq, H, D], k/v [B, Skv, H, D] -> [B, Sq, H, D], matching
ops.attention. The TPU lowering wants the last two dims of every block to
be (multiple of 8, multiple of 128) or a full axis, so a block cannot
carry a size-1 head axis second to last: the wrapper moves heads next to
batch ([B, H, S, D]) and every block is (block, D) with D a full axis.
That transpose is two extra HBM passes per operand; folding heads into
the lane axis instead is a tuning job for a later PR. KV blocks ride the
innermost grid axis with the running max / sum / accumulator in VMEM
scratch, so VMEM use does not grow with the sequence (9216 tokens at
SD2.1 768^2 costs what 1024 do).

Block sizes are env-tunable for on-hardware sweeps:
CHIASWARM_FLASH_BLOCK_Q / CHIASWARM_FLASH_BLOCK_K (default 512).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _env_blocks() -> tuple[int, int]:
    # read fresh on every call: an in-process sweep that re-exports the
    # env vars must get new kernels, not the first trace's cached blocks
    return (
        int(os.environ.get("CHIASWARM_FLASH_BLOCK_Q", "512")),
        int(os.environ.get("CHIASWARM_FLASH_BLOCK_K", "512")),
    )


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  block_k: int, kv_len: int, scale: float):
    """One (batch, head, q-block, kv-block) grid step of the online softmax.

    q_ref/o_ref [BQ, D]; k_ref/v_ref [BK, D]; scratch m/l [BQ, 1] and
    acc [BQ, D] in f32, carried across the innermost (kv) grid axis.
    """
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # QK^T runs in the INPUT dtype (bf16 on TPU) with f32 accumulation:
    # the MXU computes bf16 x bf16 -> f32 natively at full rate, while an
    # f32 x f32 matmul costs several passes. The softmax scale applies to
    # the f32 scores after the dot, so no precision is lost to scaling.
    v = v_ref[...]
    s = jax.lax.dot_general(
        q_ref[...], k_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # [BQ, BK] f32
    # mask KV padding (ragged cross-attention lengths)
    if kv_len % block_k:
        col = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < kv_len, s, _NEG_INF)
    m = m_ref[...]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        o_ref[...] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


def _pad_to(x, length: int, axis: int):
    pad = length - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def flash_attention(q, k, v, scale: float | None = None,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool = False):
    """[B, Sq, H, D] x [B, Skv, H, D] -> [B, Sq, H, D].

    Env defaults are resolved OUTSIDE the jitted impl so the jit cache is
    keyed on the concrete block sizes — otherwise a block_q=None call
    would silently reuse whichever sizes the first trace saw.
    """
    env_q, env_k = _env_blocks()
    return _flash_impl(
        q, k, v,
        scale=scale,
        block_q=block_q if block_q is not None else env_q,
        block_k=block_k if block_k is not None else env_k,
        interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("scale", "block_q", "block_k", "interpret")
)
def _flash_impl(q, k, v, scale: float | None, block_q: int, block_k: int,
                interpret: bool):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    b, sq, h, d = q.shape
    skv = k.shape[1]

    # blocks stay multiples of (16, 128) — the bf16 tile — or the whole
    # padded axis
    block_q = min(block_q, _round_up(sq, 16))
    block_k = min(block_k, _round_up(skv, 128))
    sq_pad = _round_up(sq, block_q)
    skv_pad = _round_up(skv, block_k)

    # [B, S, H, D] -> [B, H, S_pad, D]
    q = _pad_to(q, sq_pad, 1).transpose(0, 2, 1, 3)
    k = _pad_to(k, skv_pad, 1).transpose(0, 2, 1, 3)
    v = _pad_to(v, skv_pad, 1).transpose(0, 2, 1, 3)

    q_spec = pl.BlockSpec((None, None, block_q, d),
                          lambda bi, hi, i, j: (bi, hi, i, 0))
    kv_spec = pl.BlockSpec((None, None, block_k, d),
                           lambda bi, hi, i, j: (bi, hi, j, 0))
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, block_k=block_k, kv_len=skv, scale=scale
        ),
        grid=(b, h, sq_pad // block_q, skv_pad // block_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary"),
        ),
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)

    return out[:, :, :sq].transpose(0, 2, 1, 3)


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m
