"""The GEGLU gate's arithmetic (`ops.activations.gelu_erf`): the erf GELU in
float32 `erf` form, rounded once, against float64 and against the plain
reference it replaced, `nn.gelu(x, approximate=False)` (the `erfc` form).
The bounds are CPU readings with headroom (PERF.md, PR 30, has the chip's,
whose `erf` expansion is another)."""

import math

import flax.linen as nn
import numpy as np
import pytest
from scipy.special import erfc

import jax
import jax.numpy as jnp

from chiaswarm_tpu.models.layers import GEGLU
from chiaswarm_tpu.ops.activations import gelu_erf


def _exact(x64):
    return 0.5 * x64 * erfc(-x64 / math.sqrt(2.0))


def _f64(x):
    return np.asarray(x.astype(jnp.float32)).astype(np.float64)


def _bf16_ulp(value):
    """The spacing of bf16 (8 significant bits) at `value`."""
    exponent = np.floor(np.log2(np.maximum(np.abs(value), 2.0 ** -126)))
    return 2.0 ** (exponent - 7)


def _plain(x):
    return nn.gelu(x, approximate=False)


def _gate(x):
    return gelu_erf(x).astype(x.dtype)


@pytest.fixture(scope="module")
def every_bf16():
    """Every finite bf16 value through both forms, jitted as the programs
    run them, beside the float64 value."""
    bits = jnp.arange(65536, dtype=jnp.uint32).astype(jnp.uint16)
    values = jax.lax.bitcast_convert_type(bits, jnp.bfloat16)
    finite = np.isfinite(np.asarray(values.astype(jnp.float32)))
    values = values[np.nonzero(finite)[0]]
    x64 = _f64(values)
    assert x64.size == 65280
    exact = _exact(x64)
    return {"x": x64, "exact": exact,
            "gate": np.abs(_f64(jax.jit(_gate)(values)) - exact),
            "plain": np.abs(_f64(jax.jit(_plain)(values)) - exact)}


@pytest.mark.parametrize("bound", ["ulp", "tail", "mean", "far"])
def test_gate_over_every_finite_bf16(every_bf16, bound):
    x, exact, err = every_bf16["x"], every_bf16["exact"], every_bf16["gate"]
    large = np.abs(exact) >= 2.0 ** -14
    if bound == "ulp":  # CPU reading 0.80 (the plain reference: 1.25)
        assert (err[large] / _bf16_ulp(exact[large])).max() <= 1.0
    elif bound == "tail":  # CPU reading 5.4e-7
        assert err[~large].max() <= 2.0 ** -17
    elif bound == "mean":  # CPU readings 2.69e-5 against 3.21e-5
        near = np.abs(x) <= 16.0
        assert err[near].mean() <= every_bf16["plain"][near].mean()
    else:  # g < -6: the argument is held, so nothing grows with |g|
        assert err[x < -6.0].max() <= 6e-7


@pytest.mark.parametrize("bound", ["absolute", "relative"])
def test_gate_float32_grid(bound):
    x = np.linspace(-12.0, 12.0, 100001).astype(np.float32)
    exact = _exact(x.astype(np.float64))
    out = jax.jit(gelu_erf)(jnp.asarray(x))
    assert out.dtype == jnp.float32
    err = np.abs(np.asarray(out).astype(np.float64) - exact)
    if bound == "absolute":  # CPU reading 1.9e-6 (the plain reference: 3.8e-7)
        assert err.max() <= 8e-6
    else:  # CPU reading 1.9e-7
        positive = exact > 0
        assert (err[positive] / exact[positive]).max() <= 1e-6


@pytest.fixture(scope="module")
def geglu_sdxl_width():
    """`GEGLU(5120, bf16)` on random tokens at SDXL's 1280 -> 2 x 5120, with
    the projection's two halves as the module saw them."""
    rng = np.random.default_rng(30)
    x = jnp.asarray(rng.standard_normal((2, 64, 1280)), jnp.bfloat16)
    module = GEGLU(5120, dtype=jnp.bfloat16)
    params = jax.jit(module.init)(jax.random.key(0), x)

    def plain(params, x):
        proj = nn.Dense(10240, dtype=jnp.bfloat16).apply(
            {"params": params["params"]["proj"]}, x)
        h, gate = jnp.split(proj, 2, axis=-1)
        return h * _plain(gate), h, gate

    out = jax.jit(module.apply)(params, x)
    reference, h, gate = jax.jit(plain)(params, x)
    assert out.dtype == reference.dtype == jnp.bfloat16
    h = _f64(h)
    return {"out": _f64(out), "plain": _f64(reference), "h": h,
            "exact": h * _exact(_f64(gate))}


@pytest.mark.parametrize("against", ["float64", "plain", "plain_is_no_closer"])
def test_geglu_bf16_at_sdxl_width(geglu_sdxl_width, against):
    """ISSUE 30 asked for "equal to the plain reference to 1 bf16 ulp of the
    larger". That bound is wrong: the plain product is itself up to 2.5 ulp
    from the float64 one (gelu rounded to bf16, then the product), so the
    two differ by up to 4 ulp here while the new one is within 0.63 ulp of
    float64. So: the new product against float64 to 1 ulp plus the gate's
    absolute bound carried through `h`; against the plain one to 1 ulp of
    the larger plus the plain one's own distance from float64; and never
    further from float64 than the plain one at its worst."""
    d = geglu_sdxl_width
    out, plain, exact = d["out"], d["plain"], d["exact"]
    tail = np.abs(d["h"]) * 2.0 ** -17
    err, plain_err = np.abs(out - exact), np.abs(plain - exact)
    if against == "float64":
        assert (err <= _bf16_ulp(exact) + tail).all()
    elif against == "plain":
        larger = np.maximum(np.abs(out), np.abs(plain))
        assert (np.abs(out - plain)
                <= _bf16_ulp(larger) + plain_err + tail).all()
    else:
        in_range = np.abs(exact) >= 2.0 ** -14
        assert ((err / _bf16_ulp(exact))[in_range].max()
                <= (plain_err / _bf16_ulp(exact))[in_range].max())


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_geglu_rounds_once(dtype):
    """The output is the module's dtype; after the projection nothing is
    narrowed to bf16 but the result (with float32 nothing is cast at all)."""
    module = GEGLU(16, dtype=dtype)
    x = jnp.ones((2, 4, 8), dtype)
    params = module.init(jax.random.key(0), x)
    assert module.apply(params, x).dtype == dtype
    eqns = jax.make_jaxpr(module.apply)(params, x).jaxpr.eqns
    split = next(i for i, e in enumerate(eqns) if e.primitive.name == "split")
    after = eqns[split + 1:]
    names = [e.primitive.name for e in after]
    assert "erf" in names and "erfc" not in names and "tanh" not in names
    casts = [(i, e.params["new_dtype"]) for i, e in enumerate(after)
             if e.primitive.name == "convert_element_type"]
    if dtype == jnp.float32:
        assert casts == []
    else:
        assert [i for i, new in casts if new == jnp.bfloat16] \
            == [len(after) - 1]
