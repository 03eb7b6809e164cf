"""Plain references for the operations the `kimi` family brings: the
grouped matmul over held experts, decode attention over a latent cache, and
causal attention whose values are narrower than its keys. Float32
`jax.numpy` at the highest matmul precision, one loop where the program has
a kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def expert_ffn(h, chosen_local, gate, up, down):
    """`h` [T, hidden]; `chosen_local` [T, k]: each token's chosen experts
    as indices into the held ones (any other value: not held). Returns
    [T, k, hidden]: `E_e(h_t)` for a held pair, zero for another. A loop
    over the held experts, each on every token, masked."""
    h = jnp.asarray(h, jnp.float32)
    out = jnp.zeros((*chosen_local.shape, h.shape[-1]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        for e in range(gate.shape[0]):
            g, u, d = (jnp.asarray(w[e], jnp.float32)
                       for w in (gate, up, down))
            hidden = h @ g
            one = ((hidden * jax.nn.sigmoid(hidden)) * (h @ u)) @ d
            out = jnp.where((chosen_local == e)[..., None],
                            one[:, None, :], out)
    return out


def latent_attention(q_lat, q_rope, cache, mask, scale):
    """[R, H, C], [R, H, P] against [R, S, C + P] under `mask` [R, S]:
    softmax((q_lat . c + q_rope . k_rope) * scale) . c, float32."""
    q_lat, q_rope, cache = (jnp.asarray(x, jnp.float32)
                            for x in (q_lat, q_rope, cache))
    latent = q_lat.shape[-1]
    with jax.default_matmul_precision("highest"):
        scores = (jnp.einsum("rhc,rsc->rhs", q_lat, cache[..., :latent])
                  + jnp.einsum("rhp,rsp->rhs", q_rope, cache[..., latent:]))
        weights = jax.nn.softmax(
            jnp.where(mask[:, None, :], scores * scale, -jnp.inf), axis=-1)
        return jnp.einsum("rhs,rsc->rhc", weights, cache[..., :latent])


def causal_attention(q, k, v, scale):
    """[B, S, H, D] x [B, S, H, D] x [B, S, H, Dv] -> [B, S, H, Dv]: query
    i sees keys 0..i, float32."""
    q, k, v = (jnp.asarray(x, jnp.float32) for x in (q, k, v))
    s = q.shape[1]
    with jax.default_matmul_precision("highest"):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", weights, v)
