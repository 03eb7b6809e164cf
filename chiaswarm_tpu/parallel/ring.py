"""Ring attention: sequence-parallel exact attention over a mesh axis.

For sequences too long for one chip's HBM (1024^2 latents -> 4096 tokens is
fine; video/DiT workloads go much longer), the sequence is sharded over the
``seq`` mesh axis and KV blocks rotate around the ring via `ppermute` while
each device keeps its Q block. Softmax is accumulated online (flash-style
running max / sum), so the full [S, S] score matrix never exists and each
hop overlaps compute with ICI transfer. Reference framework has no analog
(SURVEY §2.6 sequence parallelism: absent); this is a rebuild-first feature.

Shapes inside shard_map: q, k, v are the LOCAL blocks [B, S/n, H, D].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import SEQ_AXIS


def _block_attend(q, k, v, scale):
    """One Q-block x KV-block partial attention.

    Returns (unnormalized_out [B,Sq,H,D], row_max [B,H,Sq], row_sum [B,H,Sq])
    in float32 for stable cross-block merging.
    """
    # bf16 x bf16 -> f32 in one MXU pass (accumulation already f32 on TPU;
    # preferred_element_type keeps the f32 result instead of downcasting)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    m = jnp.max(logits, axis=-1)
    p = jnp.exp(logits - m[..., None])
    s = jnp.sum(p, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)
    return out.astype(jnp.float32), m, s


def ring_attention(q, k, v, axis_name: str = SEQ_AXIS, scale: float | None = None):
    """Exact attention over sequence blocks distributed on `axis_name`.

    Must run inside shard_map/pjit with q/k/v sequence-sharded on that axis.
    Online-softmax merge across hops keeps numerics equal to full attention
    (verified against the single-device path in tests/test_parallel.py).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    n = jax.lax.psum(1, axis_name)

    out, m, s = _block_attend(q, k, v, scale)

    def hop(i, carry):
        out, m, s, k, v = carry
        # rotate KV one step around the ring (ICI-neighbor exchange)
        perm = [(j, (j + 1) % n) for j in range(n)]
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        b_out, b_m, b_s = _block_attend(q, k, v, scale)
        # merge running (out, max, sum) with the new block's
        new_m = jnp.maximum(m, b_m)
        alpha = jnp.exp(m - new_m)
        beta = jnp.exp(b_m - new_m)
        out = out * _rowscale(alpha) + b_out * _rowscale(beta)
        s = s * alpha + b_s * beta
        return out, new_m, s, k, v

    out, m, s, _, _ = jax.lax.fori_loop(1, n, hop, (out, m, s, k, v))
    return (out / _rowscale(s)).astype(q.dtype)


def _rowscale(x):
    # [B,H,Sq] -> [B,Sq,H,1] to broadcast over head dim of [B,Sq,H,D]
    return jnp.transpose(x, (0, 2, 1))[..., None]


def ring_shard_map(mesh: Mesh, scale: float | None = None,
                   shard_batch: bool = False):
    """The shard_map'd ring-attention entry: [B,S,H,D] sequence-sharded on
    the seq axis. Shared by the host-array wrapper below and the trace-time
    routing in ops/attention.py.

    `shard_batch` additionally shards B over the data axis — without it,
    entering shard_map from a batch-sharded enclosing program would
    all-gather the batch and make every data-axis row redundantly compute
    the same attention. Callers enable it when B divides the data size.
    """
    from .mesh import DATA_AXIS

    spec = P(DATA_AXIS if shard_batch else None, SEQ_AXIS, None, None)
    return jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, scale=scale),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )


def ring_self_attention_sharded(mesh: Mesh, q, k, v, scale: float | None = None):
    """Convenience wrapper: shard [B,S,H,D] host arrays over the seq axis and
    run ring attention under shard_map. For use outside an enclosing pjit
    (tests, standalone ops); pipelines route here via
    `ops.platform.mesh_scope`.
    """
    sharding = NamedSharding(mesh, P(None, SEQ_AXIS, None, None))
    q, k, v = (jax.device_put(x, sharding) for x in (q, k, v))
    return ring_shard_map(mesh, scale)(q, k, v)
