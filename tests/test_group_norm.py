"""Fused GroupNorm(+SiLU) numerics: the Pallas kernel (interpret mode),
the reference path, and flax.linen.GroupNorm must agree — the kernel
replaces nn.GroupNorm inside every converted diffusion block, so any
divergence here is a checkpoint-parity break."""

import flax.linen as nn
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chiaswarm_tpu.ops.group_norm import (
    _fused_group_norm,
    _reference_group_norm,
    group_norm,
)


def _flax_gn(x, scale, bias, groups, eps):
    gn = nn.GroupNorm(num_groups=groups, epsilon=eps)
    variables = {"params": {"scale": scale, "bias": bias}}
    return gn.apply(variables, x)


def _rand(shape, dtype, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape) * 2.0 + 0.3, dtype)


@pytest.mark.parametrize("shape,groups", [
    ((2, 8, 8, 32), 32),
    ((2, 8, 8, 64), 32),
    ((1, 16, 16, 96), 32),   # cg=3: ragged-ish group width
    ((3, 5, 7, 64), 16),     # odd spatial dims
    ((2, 64, 32), 16),       # 3D token tensors (KAttention [B,S,C])
    ((1, 4, 8, 8, 32), 16),  # 5D video tensors ([B,F,H,W,C])
])
def test_kernel_matches_flax_f32(shape, groups):
    x = _rand(shape, jnp.float32, 0)
    scale = _rand((shape[-1],), jnp.float32, 1)
    bias = _rand((shape[-1],), jnp.float32, 2)
    got = group_norm(x, scale, bias, groups=groups, eps=1e-5, interpret=True)
    want = _flax_gn(x, scale, bias, groups, 1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_kernel_matches_flax_silu_fused():
    x = _rand((2, 8, 8, 64), jnp.float32, 3)
    scale = _rand((64,), jnp.float32, 4)
    bias = _rand((64,), jnp.float32, 5)
    got = group_norm(x, scale, bias, groups=32, eps=1e-6, act="silu",
                     interpret=True)
    want = nn.silu(_flax_gn(x, scale, bias, 32, 1e-6))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_kernel_bf16_tolerance():
    x = _rand((2, 8, 8, 64), jnp.bfloat16, 6)
    scale = _rand((64,), jnp.float32, 7)
    bias = _rand((64,), jnp.float32, 8)
    got = group_norm(x, scale, bias, groups=32, act="silu", interpret=True)
    assert got.dtype == jnp.bfloat16
    want = nn.silu(_flax_gn(x.astype(jnp.float32), scale, bias, 32, 1e-5))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=5e-2)


def test_reference_path_matches_flax():
    x = _rand((2, 4, 4, 32), jnp.float32, 9)
    scale = _rand((32,), jnp.float32, 10)
    bias = _rand((32,), jnp.float32, 11)
    got = _reference_group_norm(x, scale, bias, 32, 1e-5, False, jnp.float32)
    want = _flax_gn(x, scale, bias, 32, 1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_kernel_matches_reference_path_exactly_shaped():
    # dispatch-level agreement: the two implementations the platform rule
    # switches between must agree on the same inputs
    x = _rand((2, 8, 8, 64), jnp.float32, 12)
    scale = _rand((64,), jnp.float32, 13)
    bias = _rand((64,), jnp.float32, 14)
    a = _fused_group_norm(x.reshape(2, 64, 64), scale, bias, 32, 1e-5, True,
                          interpret=True).reshape(x.shape)
    b = _reference_group_norm(x, scale, bias, 32, 1e-5, True, jnp.float32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=2e-5, rtol=2e-5)


def test_oversize_tile_falls_back(monkeypatch):
    import chiaswarm_tpu.ops.group_norm as gnmod

    calls = []
    orig = gnmod._fused_group_norm

    def spy(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    monkeypatch.setattr(gnmod, "_fused_group_norm", spy)
    monkeypatch.setenv("CHIASWARM_FUSED_GN_MAX_BYTES", "64")  # force fallback
    x = _rand((1, 8, 8, 32), jnp.float32, 15)
    scale, bias = jnp.ones((32,)), jnp.zeros((32,))
    out = group_norm(x, scale, bias, groups=32, interpret=True)
    assert not calls
    assert out.shape == x.shape


def test_dispatch_follows_platform_and_shape_only(monkeypatch):
    """No toggle and no try-and-catch: off the TPU every call traces the
    reference path, on it every admitted tile traces the kernel, and each
    decision is counted where chip_smoke.py can read it."""
    import chiaswarm_tpu.ops.group_norm as gnmod
    from chiaswarm_tpu.ops.platform import KERNEL_TRACES

    calls = []
    monkeypatch.setattr(
        gnmod, "_fused_group_norm",
        lambda x3, *a, **k: calls.append(1) or x3)
    x = _rand((1, 4, 4, 32), jnp.float32, 16)
    args = (x, jnp.ones((32,)), jnp.zeros((32,)))

    def counts():
        return tuple(KERNEL_TRACES.value(op="group_norm", path=p)
                     for p in ("reference", "fused"))

    ref0, fused0 = counts()
    out = group_norm(*args, groups=32)
    assert not calls and out.shape == x.shape
    assert counts() == (ref0 + 1, fused0)

    monkeypatch.setattr(gnmod, "trace_platform", lambda: "tpu")
    group_norm(*args, groups=32)
    assert calls == [1]
    assert counts() == (ref0 + 1, fused0 + 1)
    # ... but not params initialised on the host while the backend is TPU
    monkeypatch.undo()
    with jax.default_device(jax.devices("cpu")[0]):
        assert gnmod.trace_platform() == "cpu"


def test_kernel_splits_over_a_mesh_by_batch_row():
    """Under a multi-chip mesh the kernel runs in shard_map (Mosaic calls
    cannot be partitioned automatically): rows over `data`, whole on the
    tensor axis. Same values as the unsplit kernel."""
    from chiaswarm_tpu.ops.platform import mesh_scope
    from chiaswarm_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(jax.devices(), tensor=4)  # data = 2
    x = _rand((2, 8, 8, 64), jnp.float32, 17)
    scale = _rand((64,), jnp.float32, 18)
    bias = _rand((64,), jnp.float32, 19)
    want = group_norm(x, scale, bias, groups=32, act="silu", interpret=True)
    with mesh_scope(mesh):
        got = jax.jit(lambda x: group_norm(
            x, scale, bias, groups=32, act="silu", interpret=True))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-6)
    assert got.sharding.spec[0] == "data"
