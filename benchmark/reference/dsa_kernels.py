"""Plain float32 references for the three operations of a learned key
selection (`ops/lightning_indexer.py`, `ops/sparse_latent_attention.py`),
at the highest matmul precision, none of them a kernel: the index scores a
head at a time, the selection by `jax.lax.top_k`, attention by a masked
softmax a head at a time (so that a 4096-query span against 32768 keys
fits where it runs), the decode's attention over the whole
cache under the selection's mask. Operands come rounded to the serving
dtype and are converted here, so both sides see the same values.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def rounded_to_8_bits(x):
    """Symmetric, one scale a tensor over its finite entries (an index
    score of `-inf`, a key not seen, stays), in `x`'s dtype: the lower
    precision of the controls."""
    x32 = x.astype(jnp.float32)
    finite = jnp.isfinite(x32)
    scale = jnp.max(jnp.where(finite, jnp.abs(x32), 0.0)) / 127.0
    return jnp.where(finite, jnp.round(x32 / scale) * scale, x32).astype(
        x.dtype)


@jax.jit
def index_scores(q, w, k):
    """`q` [Sq, heads, D], `w` [Sq, heads], `k` [Skv, D] -> `I` [Sq, Skv]
    float32, the queries the last `Sq` positions of the keys, `-inf` where
    a query does not see a key."""
    sq, skv = q.shape[0], k.shape[0]
    keys = k.astype(jnp.float32).T

    def head(total, one):
        q_head, w_head = one
        products = jnp.dot(q_head, keys, precision=_HIGHEST)
        return total + jnp.maximum(products, 0.0) * w_head[:, None], None

    total, _ = jax.lax.scan(
        head, jnp.zeros((sq, skv), jnp.float32),
        (q.astype(jnp.float32).transpose(1, 0, 2),
         w.astype(jnp.float32).T))
    seen = jnp.arange(skv)[None, :] <= (skv - sq + jnp.arange(sq))[:, None]
    return jnp.where(seen, total, -jnp.inf)


@functools.partial(jax.jit, static_argnames=("topk",))
def selected_block(rows, topk: int):
    """`selection` for one block of queries `rows` [Q, Skv]."""
    values, columns = jax.lax.top_k(rows, min(topk, rows.shape[-1]))
    return jnp.zeros(rows.shape, bool).at[
        jnp.arange(rows.shape[0])[:, None], columns].set(values > -jnp.inf)


def selection(scores, topk: int, block: int = 512):
    """[Sq, Skv] bool: the `topk` largest visible scores a query (`-inf`:
    not visible), ties to the lower position, by `jax.lax.top_k` a block
    of `block` queries at a time, on the device that holds `scores` (one
    compiled program a `[block, Skv]`: the chip's compiler takes 12 s for
    a sort of 32768, the host under one)."""
    sq = scores.shape[0]
    block = min(block, sq)
    padded = jnp.pad(scores, ((0, -sq % block), (0, 0)),
                     constant_values=-jnp.inf)
    return jnp.concatenate([
        selected_block(jax.lax.dynamic_slice_in_dim(padded, at, block), topk)
        for at in range(0, padded.shape[0], block)])[:sq]


@functools.partial(jax.jit, static_argnames=("scale", "heads"))
def masked_attention(q, k, v, mask, scale: float, heads: int):
    """`q` [Sq, H * D], `k`, `v` [Skv, H * D], `mask` [Sq, Skv] (nonzero:
    the query attends to the key) -> [Sq, H * D] float32: the softmax over
    the masked-in keys alone, a head at a time (a head's `[Sq, Skv]` scores
    laid out whole: half a GB at the cell's span; its keys and values
    converted as it is reached)."""
    sq, skv = q.shape[0], k.shape[0]
    keep = mask != 0
    q, k, v = (x.reshape(x.shape[0], heads, -1) for x in (q, k, v))

    def head(n):
        queries, keys, values = (jax.lax.dynamic_index_in_dim(
            x, n, axis=1, keepdims=False).astype(jnp.float32)
            for x in (q, k, v))
        scores = jnp.dot(queries, keys.T, precision=_HIGHEST) * scale
        weights = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
        return jnp.dot(weights, values, precision=_HIGHEST)

    return jax.lax.map(head, jnp.arange(heads)).transpose(1, 0, 2).reshape(
        sq, -1)


@functools.partial(jax.jit, static_argnames=("scale",))
def latent_attention(q_lat, q_rope, cache, mask, scale: float):
    """The absorbed decode attention over the WHOLE cache under `mask` [R,
    S] (the selection as a mask, nothing gathered): `q_lat` [R, H, C],
    `q_rope` [R, H, P], `cache` [R, S, C + P] -> the context [R, H, C]
    float32."""
    latent = q_lat.shape[-1]
    cache = cache.astype(jnp.float32)
    query = jnp.concatenate([q_lat, q_rope], axis=-1).astype(jnp.float32)
    scores = jnp.einsum("rhc,rsc->rhs", query, cache,
                        precision=_HIGHEST) * scale
    weights = jax.nn.softmax(
        jnp.where(mask[:, None, :], scores, -jnp.inf), axis=-1)
    return jnp.einsum("rhs,rsc->rhc", weights, cache[..., :latent],
                      precision=_HIGHEST)
