"""Flash-attention kernel numerics vs the reference path (interpret mode)."""

import jax.numpy as jnp
import numpy as np
import pytest

from chiaswarm_tpu.ops.attention import reference_attention
from chiaswarm_tpu.ops.flash_attention import flash_attention


def _rand(shape, dtype, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), dtype)


@pytest.mark.parametrize("sq,skv", [(256, 256), (256, 77), (130, 256), (64, 64)])
def test_matches_reference_f32(sq, skv):
    b, h, d = 2, 3, 32
    q = _rand((b, sq, h, d), jnp.float32, 0)
    k = _rand((b, skv, h, d), jnp.float32, 1)
    v = _rand((b, skv, h, d), jnp.float32, 2)
    got = flash_attention(q, k, v, block_q=128, block_k=128, interpret=True)
    want = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_matches_reference_bf16():
    b, sq, skv, h, d = 1, 128, 77, 2, 64
    q = _rand((b, sq, h, d), jnp.bfloat16, 3)
    k = _rand((b, skv, h, d), jnp.bfloat16, 4)
    v = _rand((b, skv, h, d), jnp.bfloat16, 5)
    got = flash_attention(q, k, v, block_q=64, block_k=128, interpret=True)
    want = reference_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=3e-2
    )


def test_custom_scale():
    b, s, h, d = 1, 64, 1, 16
    q, k, v = (_rand((b, s, h, d), jnp.float32, i) for i in range(3))
    got = flash_attention(q, k, v, scale=0.5, block_q=64, block_k=64, interpret=True)
    want = reference_attention(q, k, v, scale=0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("heads,tensor,split", [
    (4, 4, "heads"),   # head count divides the tensor axis
    (5, 2, "rows"),    # it does not: query rows split, K/V whole per chip
])
def test_flash_route_splits_the_kernel_over_a_mesh(heads, tensor, split):
    """A Pallas call is opaque to the SPMD partitioner, so under a
    multi-chip mesh the dispatch runs it in shard_map (batch over data,
    heads or query rows over tensor); the result is the reference's."""
    import jax

    from chiaswarm_tpu.ops import attention as attention_ops
    from chiaswarm_tpu.ops.platform import mesh_scope
    from chiaswarm_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(jax.devices(), tensor=tensor)  # data = 8 / tensor
    b, sq, skv, d = mesh.shape["data"], 64, 77, 32
    q = _rand((b, sq, heads, d), jnp.float32, 6)
    k = _rand((b, skv, heads, d), jnp.float32, 7)
    v = _rand((b, skv, heads, d), jnp.float32, 8)
    with mesh_scope(mesh):
        got = jax.jit(
            lambda q, k, v: attention_ops._flash_route(
                q, k, v, None, interpret=True)
        )(q, k, v)
    want = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    spec = got.sharding.spec
    assert spec[0] == "data"
    assert (spec[2] if split == "heads" else spec[1]) == "tensor"
