"""Plain reference for the operation the `mimo_v2` family brings: causal
attention with grouped-query heads whose keys are wider than their values,
under an optional window, with an optional sink in the softmax. Float32
`jax.numpy` at the highest matmul precision, a query head at a time (one
head's scores at the cell's full-layer shape, 4096 queries against 32768
keys, are 0.54 GB in float32), the visibility written out and the sink as
one more column of the scores that is dropped after the softmax."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("scale", "window"))
def wide_key_attention(q, k, v, scale: float, window: int = 0, sink=None,
                       offset=None, floor=None):
    """q [Sq, Hq, Dk], k [Skv, Hkv, Dk], v [Skv, Hkv, Dv] -> [Sq, Hq, Dv]:
    query `i` at key index `offset + i` (None: the last `Sq` of the keys)
    sees key `u` iff `floor <= u <= offset + i` (None: 0) and, with a
    window, `offset + i - u < window`; query head `j` reads key head `j //
    (Hq / Hkv)`; `sink` [Hq] joins each head's softmax and has no value."""
    q, k, v = (jnp.asarray(x, jnp.float32) for x in (q, k, v))
    sq, heads, _ = q.shape
    skv, group = k.shape[0], heads // k.shape[1]
    t = jnp.arange(sq)[:, None] + (skv - sq if offset is None else offset)
    u = jnp.arange(skv)[None, :]
    seen = (u <= t) & (u >= (0 if floor is None else floor))
    if window:
        seen = seen & (t - u < window)

    def one(head):
        mine = jax.lax.dynamic_index_in_dim(q, head, 1, keepdims=False)
        keys = jax.lax.dynamic_index_in_dim(k, head // group, 1, False)
        values = jax.lax.dynamic_index_in_dim(v, head // group, 1, False)
        scores = jnp.where(seen, (mine @ keys.T) * scale, -jnp.inf)
        if sink is not None:
            scores = jnp.concatenate([scores, jnp.broadcast_to(
                sink.astype(jnp.float32)[head], (sq, 1))], axis=-1)
        return jax.nn.softmax(scores, axis=-1)[:, :skv] @ values

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one, jnp.arange(heads)).transpose(1, 0, 2)
