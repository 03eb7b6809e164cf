"""Plain references for the two kernels: softmax attention and
GroupNorm(+SiLU), float32 `jax.numpy`, highest matmul precision."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


@jax.jit
def attention(q, k, v):
    """[B, Sq, H, D] x [B, Skv, H, D] -> [B, Sq, H, D] in float32, a batch
    row and a block of queries at a time (softmax is over keys, so query
    blocks are independent; the whole [H, Sq, Skv] scores of 9216 tokens
    would be 1.7 GB a row and show in the cell's memory peak)."""
    q, k, v = (jnp.asarray(x, jnp.float32) for x in (q, k, v))
    b, sq, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    block = next(n for n in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1)
                 if sq % n == 0)

    def one_block(args):
        q, row = args
        logits = jnp.einsum("qhd,khd->hqk", q, k[row]) * scale
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(logits, axis=-1),
                          v[row])

    rows = jnp.repeat(jnp.arange(b), sq // block)
    with jax.default_matmul_precision("highest"):
        out = jax.lax.map(one_block, (q.reshape(-1, block, h, d), rows))
    return out.reshape(b, sq, h, d)


@jax.jit
def group_norm_silu(x, scale, bias):
    """GroupNorm over 32 groups of the last axis of `[B, H, W, C]`, eps
    1e-5, then SiLU, in float32."""
    x = jnp.asarray(x, jnp.float32)
    b, h, w, c = x.shape
    g = x.reshape(b, h * w, 32, c // 32)
    mean = g.mean(axis=(1, 3), keepdims=True)
    var = ((g - mean) ** 2).mean(axis=(1, 3), keepdims=True)
    y = ((g - mean) / jnp.sqrt(var + 1e-5)).reshape(b, h, w, c)
    y = y * scale + bias
    return y * jax.nn.sigmoid(y)
