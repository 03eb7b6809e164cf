"""Shared diffusion building blocks (flax.linen, NHWC).

Block semantics match the SD/SDXL architecture family so HF checkpoints
convert 1:1 (conversion.py), but the code is organized TPU-first: tensors
stay NHWC, attention routes through ops.dot_product_attention (Pallas flash
on TPU), and everything traces to static shapes.

`Dense`, `Conv`, `LayerNorm` and `Embed` are first-party subclasses of
flax's layers, under flax's names, because of `DeclaredParams`: flax learns
the shape a bound parameter should have by tracing its initialiser, once a
parameter a trace of `apply`; these compare it with the shape the layer
passes that initialiser, which was half of an SDXL start's tracing (PR 56).
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp
import numpy as np
from flax import errors
from flax.core import meta

from .. import telemetry
from ..ops import dot_product_attention
from ..ops.activations import gelu_erf
from ..ops.group_norm import group_norm

PARAM_READS = telemetry.counter(
    "swarm_param_reads_total",
    "Parameters a flax module read from the tree bound for `apply`, "
    "counted while tracing, by path (declared: its shape compared with "
    "the shape the layer passes its initialiser, nothing traced; traced: "
    "flax's own path, which traces the initialiser to learn the shape)",
    ("path",))


def _declared_shape(init_args, init_kwargs) -> tuple | None:
    """The shape a `param` call hands its initialiser as a plain tuple (or
    list: flax's norms) of ints in first place, with no keyword; else
    None."""
    if init_kwargs or not init_args:
        return None
    shape = init_args[0]
    if type(shape) not in (tuple, list) or not all(
            type(d) is int for d in shape):
        return None
    return tuple(shape)


class DeclaredParams:
    """Mixin for a flax module: `param` of a parameter that is already in
    the bound tree compares its shape with the shape the call declares.

    `flax.core.scope.Scope.param` (flax 0.12.3) gets the expected shape
    from `jax.eval_shape` of the initialiser, a trace of a `lecun_normal`
    a parameter to compare two tuples. Every initialiser of jax's takes
    the shape it returns as its first argument, so where the call has that
    tuple this does what `Module.param` and `Scope.param` do around the
    check, raises the same `ScopeParamShapeError`, and traces nothing. Any
    other call (the parameter absent: `init`; keywords; a shape that is
    not plain ints; a value in a box, `nn.Partitioned`) is flax's own
    path, unchanged."""

    def param(self, name, init_fn, *init_args, unbox=True, **init_kwargs):
        scope = self.scope
        if scope is not None and scope.has_variable("params", name):
            declared = _declared_shape(init_args, init_kwargs)
            value = scope.get_variable("params", name)
            if declared is not None and not isinstance(
                    value, meta.AxisMetadata):
                return self._bound_param(name, value, declared)
            PARAM_READS.inc(path="traced")
        return super().param(
            name, init_fn, *init_args, unbox=unbox, **init_kwargs)

    def _bound_param(self, name, value, declared):
        # `Module.param` and `Scope.param` around the check, in their order
        if not self._initialization_allowed:
            raise ValueError(
                "Parameters must be initialized in `setup()` or in a method "
                "wrapped in `@compact`")
        if self._name_taken(name, collection="params"):
            raise errors.NameInUseError(
                "param", name, self.__class__.__name__)
        self.scope.reserve(name, "params")
        if np.shape(value) != declared:
            raise errors.ScopeParamShapeError(
                name, self.scope.path_text, np.shape(value), declared)
        self._state.children[name] = "params"
        PARAM_READS.inc(path="declared")
        return value


class Dense(DeclaredParams, nn.Dense):
    """`nn.Dense` (an unnamed child is still `Dense_0`)."""


class Conv(DeclaredParams, nn.Conv):
    """`nn.Conv`."""


class LayerNorm(DeclaredParams, nn.LayerNorm):
    """`nn.LayerNorm`."""


class Embed(DeclaredParams, nn.Embed):
    """`nn.Embed`."""


class FusedGroupNorm(DeclaredParams, nn.Module):
    """Drop-in nn.GroupNorm with an optionally fused SiLU epilogue.

    Param tree ("scale"/"bias", [C] f32) is identical to nn.GroupNorm, so
    checkpoint conversion is unchanged; compute routes through
    ops.group_norm — the single-pass Pallas kernel on TPU (1 HBM read +
    1 write vs the 2+1 of a separate norm + activation), the XLA-fused
    reference elsewhere (CHIASWARM_DISABLE_FUSED_GN=1 forces the latter
    for A/B). Numerics pinned by tests/test_group_norm.py.
    """

    num_groups: int = 32
    epsilon: float = 1e-5
    dtype: jnp.dtype = jnp.float32
    act: str | None = None

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
        return group_norm(
            x, scale, bias, groups=self.num_groups, eps=self.epsilon,
            act=self.act, dtype=self.dtype,
        )


def timestep_embedding(
    timesteps,
    dim: int,
    *,
    max_period: float = 10000.0,
    flip_sin_to_cos: bool = True,
    downscale_freq_shift: float = 0.0,
    dtype=jnp.float32,
):
    """Sinusoidal timestep features [B] -> [B, dim] (SD convention: cos-first)."""
    half = dim // 2
    exponent = -jnp.log(max_period) * jnp.arange(half, dtype=jnp.float32)
    exponent = exponent / (half - downscale_freq_shift)
    freqs = jnp.exp(exponent)
    args = jnp.asarray(timesteps, jnp.float32)[:, None] * freqs[None, :]
    emb = jnp.concatenate([jnp.sin(args), jnp.cos(args)], axis=-1)
    if flip_sin_to_cos:
        emb = jnp.concatenate([emb[:, half:], emb[:, :half]], axis=-1)
    return emb.astype(dtype)


class TimestepEmbedding(nn.Module):
    """2-layer MLP lifting sinusoidal features to the UNet's temb width."""

    dim: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, sample):
        sample = Dense(self.dim, dtype=self.dtype, name="linear_1")(sample)
        sample = nn.silu(sample)
        return Dense(self.dim, dtype=self.dtype, name="linear_2")(sample)


class ResnetBlock2D(nn.Module):
    out_channels: int
    # diffusers: UNet resnets norm at 1e-5, VAE resnets at 1e-6
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, temb=None):
        residual = x
        h = FusedGroupNorm(32, epsilon=self.eps, dtype=self.dtype,
                           act="silu", name="norm1")(x)
        h = Conv(
            self.out_channels, (3, 3), padding=((1, 1), (1, 1)), dtype=self.dtype,
            name="conv1",
        )(h)

        if temb is not None:
            temb_proj = Dense(self.out_channels, dtype=self.dtype, name="time_emb_proj")(
                nn.silu(temb)
            )
            h = h + temb_proj[:, None, None, :]

        h = FusedGroupNorm(32, epsilon=self.eps, dtype=self.dtype,
                           act="silu", name="norm2")(h)
        h = Conv(
            self.out_channels, (3, 3), padding=((1, 1), (1, 1)), dtype=self.dtype,
            name="conv2",
        )(h)

        if residual.shape[-1] != self.out_channels:
            residual = Conv(
                self.out_channels, (1, 1), dtype=self.dtype, name="conv_shortcut"
            )(residual)
        return h + residual


class Attention(nn.Module):
    """Multi-head attention over [B, S, C] with optional cross context."""

    num_heads: int
    head_dim: int
    out_dim: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, hidden, context=None):
        context = hidden if context is None else context
        inner = self.num_heads * self.head_dim
        q = Dense(inner, use_bias=False, dtype=self.dtype, name="to_q")(hidden)
        k = Dense(inner, use_bias=False, dtype=self.dtype, name="to_k")(context)
        v = Dense(inner, use_bias=False, dtype=self.dtype, name="to_v")(context)

        b, sq, _ = q.shape
        sk = k.shape[1]
        q = q.reshape(b, sq, self.num_heads, self.head_dim)
        k = k.reshape(b, sk, self.num_heads, self.head_dim)
        v = v.reshape(b, sk, self.num_heads, self.head_dim)

        out = dot_product_attention(q, k, v)
        out = out.reshape(b, sq, inner)
        return Dense(self.out_dim, dtype=self.dtype, name="to_out_0")(out)


class GEGLU(nn.Module):
    dim: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        h = Dense(self.dim * 2, dtype=self.dtype, name="proj")(x)
        h, gate = jnp.split(h, 2, axis=-1)
        # erf gelu, diffusers parity: float32 inside, one rounding
        return (h * gelu_erf(gate)).astype(self.dtype)


class FeedForward(nn.Module):
    dim: int
    mult: int = 4
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = GEGLU(self.dim * self.mult, dtype=self.dtype, name="net_0")(x)
        return Dense(self.dim, dtype=self.dtype, name="net_2")(x)


class BasicTransformerBlock(nn.Module):
    """self-attn -> cross-attn -> GEGLU MLP, pre-LN residual wiring."""

    dim: int
    num_heads: int
    head_dim: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, hidden, context):
        attn = Attention(
            self.num_heads, self.head_dim, self.dim, dtype=self.dtype, name="attn1"
        )
        hidden = hidden + attn(
            LayerNorm(epsilon=1e-5, dtype=self.dtype, name="norm1")(hidden)
        )
        cross = Attention(
            self.num_heads, self.head_dim, self.dim, dtype=self.dtype, name="attn2"
        )
        hidden = hidden + cross(
            LayerNorm(epsilon=1e-5, dtype=self.dtype, name="norm2")(hidden), context
        )
        ff = FeedForward(self.dim, dtype=self.dtype, name="ff")
        return hidden + ff(
            LayerNorm(epsilon=1e-5, dtype=self.dtype, name="norm3")(hidden)
        )


class Transformer2DModel(nn.Module):
    """Spatial transformer: NHWC -> tokens -> N blocks -> NHWC residual."""

    num_heads: int
    head_dim: int
    num_layers: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, context):
        b, h, w, c = x.shape
        residual = x
        hidden = FusedGroupNorm(32, epsilon=1e-6, dtype=self.dtype,
                                name="norm")(x)
        hidden = hidden.reshape(b, h * w, c)
        hidden = Dense(c, dtype=self.dtype, name="proj_in")(hidden)
        for i in range(self.num_layers):
            hidden = BasicTransformerBlock(
                c,
                self.num_heads,
                self.head_dim,
                dtype=self.dtype,
                name=f"transformer_blocks_{i}",
            )(hidden, context)
        hidden = Dense(c, dtype=self.dtype, name="proj_out")(hidden)
        return hidden.reshape(b, h, w, c) + residual


class Downsample2D(nn.Module):
    out_channels: int
    # VAE encoder uses asymmetric (0,1) padding (diffusers parity); UNet (1,1)
    asymmetric_pad: bool = False
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        pad = ((0, 1), (0, 1)) if self.asymmetric_pad else ((1, 1), (1, 1))
        return Conv(
            self.out_channels,
            (3, 3),
            strides=(2, 2),
            padding=pad,
            dtype=self.dtype,
            name="conv",
        )(x)


class Upsample2D(nn.Module):
    out_channels: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, h, w, c = x.shape
        x = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)  # nearest 2x
        return Conv(
            self.out_channels, (3, 3), padding=((1, 1), (1, 1)), dtype=self.dtype,
            name="conv",
        )(x)
