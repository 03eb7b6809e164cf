"""Activations whose plain jax form costs more on the chip than the function needs."""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

_SQRT_HALF = 0.7071067811865476


def gelu_erf(x):
    """The erf GELU, `0.5 * x * (1 + erf(x / sqrt 2))`, evaluated and
    returned in float32 (in `x`'s own dtype if that is wider): the caller
    rounds, once, after whatever it multiplies the result by.

    Not `nn.gelu(x, approximate=False)`: that is `x * erfc(-x / sqrt 2) / 2`,
    and XLA expands a float32 `erfc` into three branches that are all
    computed and then selected (an exponential and two divides among ~80
    elementwise instructions an element), where `erf` is one rational
    polynomial. v5e has no bf16 vector unit, so a bf16 gate pays for all of
    it in float32, and as the producer of a matmul's operand (`GEGLU` into
    `ff/net_2`) it held that matmul at 44 % of the MXU (PERF.md, PR 30).
    This is the expression torch's `F.gelu` evaluates in float `opmath`.

    No intermediate may be rounded to bf16: `1 + erf` rounded there is off
    by 45 % at x = -3. Below x = -4 (|gelu| < 6e-5) `1 + erf` cancels and
    the error is absolute, under 1e-6: less than a bf16 activation can tell
    apart. The argument is held at -6, where the function has reached 0 to
    6e-9: XLA's CPU `erf` stops at -1 + 1.8e-7, and `0.5 * x` times that
    grows with |x| (-2.7e31 at the largest bf16). The TPU's reaches -1.
    Bounds pinned by tests/test_geglu_numerics.py.
    """
    x = jnp.maximum(x.astype(jnp.promote_types(x.dtype, jnp.float32)), -6.0)
    return 0.5 * x * (1.0 + lax.erf(x * _SQRT_HALF))
