"""Decode attention over a latent cache: `latent_decode_attention`.

Latent attention (models/kimi.py) caches, for every position, the
compressed key/value `c_kv` and the one rotary key all heads share, and
nothing else. A decode step absorbs the up-projection into the query
(`q_lat = q_nope @ W_K`), so its scores are `q_lat . c_kv + q_rope .
k_rope` and its context `softmax . c_kv`, both taken straight over the
cache: no key or value is ever expanded to the heads' width. One new token
a row, every head of the row against the row's whole cache.

One path, plain XLA (two batched matmuls a row and a float32 softmax): the
cache is read twice, which a fused kernel would halve; the op is its own so
that one can take its place (`swarm_kernel_traces_total{op="latent_attention"}`
says which path a program traced).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import platform


@functools.partial(jax.named_call, name="latent_attention")
def latent_decode_attention(q_lat, q_rope, cache, mask, scale: float):
    """`q_lat` [R, H, C] and `q_rope` [R, H, P] against `cache`
    [R, S, C + P] (`c_kv | k_rope` a position); `mask` [R, S] says which
    positions a row may see. Returns the context [R, H, C] in the cache's
    dtype: the caller applies the value up-projection."""
    latent = q_lat.shape[-1]
    platform.KERNEL_TRACES.inc(op="latent_attention", path="absorbed")
    query = jnp.concatenate([q_lat, q_rope], axis=-1).astype(cache.dtype)
    scores = jnp.einsum("rhc,rsc->rhs", query, cache,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask[:, None, :], scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1).astype(cache.dtype)
    return jnp.einsum("rhs,rsc->rhc", weights, cache[..., :latent],
                      preferred_element_type=jnp.float32).astype(cache.dtype)
