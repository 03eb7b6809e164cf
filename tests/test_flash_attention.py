"""Flash-attention kernel numerics vs the reference path (interpret mode)."""

import jax.numpy as jnp
import numpy as np
import pytest

from chiaswarm_tpu.ops.attention import reference_attention
from chiaswarm_tpu.ops.flash_attention import (
    _MAX_PAD,
    _VMEM_CAP,
    _VMEM_SLACK,
    FlashBlocks,
    flash_attention,
    flash_blocks,
    step_vmem_bytes,
)


def _rand(shape, dtype, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), dtype)


@pytest.mark.parametrize("sq,skv", [(256, 256), (256, 77), (130, 256), (64, 64)])
def test_matches_reference_f32(sq, skv):
    b, h, d = 2, 3, 32
    q = _rand((b, sq, h, d), jnp.float32, 0)
    k = _rand((b, skv, h, d), jnp.float32, 1)
    v = _rand((b, skv, h, d), jnp.float32, 2)
    got = flash_attention(q, k, v, blocks=FlashBlocks(128, 128, 128, 1),
                          interpret=True)
    want = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_matches_reference_bf16():
    b, sq, skv, h, d = 1, 128, 77, 2, 64
    q = _rand((b, sq, h, d), jnp.bfloat16, 3)
    k = _rand((b, skv, h, d), jnp.bfloat16, 4)
    v = _rand((b, skv, h, d), jnp.bfloat16, 5)
    got = flash_attention(q, k, v, blocks=FlashBlocks(64, 128, 128, 1),
                          interpret=True)
    want = reference_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=3e-2
    )


def test_custom_scale():
    b, s, h, d = 1, 64, 1, 16
    q, k, v = (_rand((b, s, h, d), jnp.float32, i) for i in range(3))
    got = flash_attention(q, k, v, scale=0.5, interpret=True)
    want = reference_attention(q, k, v, scale=0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# (sq, skv, heads, d, blocks (None: the rule's), scale, dtype): one case a
# branch the rule can take, at sizes interpret mode walks in a second
_BRANCHES = [
    pytest.param(256, 256, 2, 64, None, None, jnp.float32,
                 id="one-pass"),
    pytest.param(256, 77, 2, 64, None, None, jnp.float32,
                 id="one-pass-ragged-77"),
    pytest.param(256, 512, 1, 32, FlashBlocks(128, 128, 512, 1), None,
                 jnp.float32, id="inner-key-loop"),
    pytest.param(256, 512, 2, 32, FlashBlocks(128, 128, 256, 2), None,
                 jnp.float32, id="carried-with-inner-key-loop"),
    pytest.param(128, 300, 1, 32, FlashBlocks(128, 128, 384, 1), None,
                 jnp.float32, id="inner-key-loop-ragged"),
    pytest.param(128, 300, 3, 32, FlashBlocks(128, 128, 128, 3), None,
                 jnp.float32, id="carried-ragged-3-heads"),
    pytest.param(130, 256, 1, 32, FlashBlocks(64, 128, 256, 1), None,
                 jnp.float32, id="length-no-block-divides"),
    pytest.param(128, 77, 5, 64, None, None, jnp.float32,
                 id="5-heads-a-step"),
    pytest.param(128, 256, 6, 32, FlashBlocks(128, 128, 256, 3), None,
                 jnp.float32, id="3-of-6-heads-a-step"),
    pytest.param(128, 256, 3, 32, None, None, jnp.float32,
                 id="3-heads-a-step"),
    pytest.param(128, 256, 2, 128, FlashBlocks(64, 128, 256, 2), None,
                 jnp.float32, id="head-width-128"),
    pytest.param(128, 256, 2, 64, None, 0.3, jnp.float32,
                 id="unfolded-scale-one-pass"),
    pytest.param(128, 256, 1, 64, FlashBlocks(64, 128, 256, 1), 0.3,
                 jnp.float32, id="unfolded-scale-inner-key-loop"),
    pytest.param(256, 512, 2, 64, FlashBlocks(128, 256, 512, 2), None,
                 jnp.bfloat16, id="bf16-inner-key-loop"),
]


@pytest.mark.parametrize("sq,skv,h,d,blocks,scale,dtype", _BRANCHES)
def test_every_branch_matches_reference(sq, skv, h, d, blocks, scale, dtype):
    q = _rand((2, sq, h, d), dtype, 10)
    k = _rand((2, skv, h, d), dtype, 11)
    v = _rand((2, skv, h, d), dtype, 12)
    got = flash_attention(q, k, v, scale=scale, blocks=blocks,
                          interpret=True)
    want = reference_attention(q, k, v, scale=scale)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=2e-5 if dtype == jnp.float32 else 3e-2)


def _listed_shapes():
    """Every attention shape a benchmarked configuration lists, the
    shapes `_flash_route` hands a chip of four, and Flux's joint call."""
    import json
    from pathlib import Path

    shapes = {(4608, 4608, 24, 128), (2304, 9216, 5, 64), (1024, 1024, 5, 64),
              (1024, 77, 5, 64), (4109, 4109, 3, 64)}
    for path in sorted(
            (Path(__file__).parents[1] / "benchmark" / "configs").glob("*.json")):
        # a configuration whose attention never takes the flash kernel
        # lists none (benchmark/README.md: the key is optional)
        shapes.update(map(tuple, json.loads(path.read_text()).get(
            "attention_shapes", ())))
    return sorted(shapes)


@pytest.mark.parametrize("sq,skv,h,d", _listed_shapes())
def test_the_rule_alone(sq, skv, h, d):
    """Blocks are tile multiples that divide what they must, no axis is
    padded past what the roofline reader matches (511), and the step's
    VMEM count is the one the call hands the compiler, under the cap.
    The rule sees no batch: 2 and 8 rows get the same blocks."""
    blocks = flash_blocks(sq, skv, h, d, jnp.bfloat16)
    block_q, block_k, block_k_major, block_h = blocks
    assert block_q % 16 == 0 and block_k % 128 == 0
    assert block_k_major % block_k == 0 and h % block_h == 0
    assert -sq % block_q <= _MAX_PAD and -skv % block_k_major <= _MAX_PAD
    assert -skv % block_k_major < block_k  # padding in the last sub-tile only
    vmem = step_vmem_bytes(*blocks, d, 2)
    # the limit the call sets stays inside a v5e core's 128 MiB
    assert vmem <= _VMEM_CAP and vmem + _VMEM_SLACK < 128 * 2 ** 20


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
