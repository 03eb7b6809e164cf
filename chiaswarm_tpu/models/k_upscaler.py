"""SD-x2 latent upscaler UNet: the K-diffusion upscaler graph diffusers
serves as `UNet2DConditionModel` with K-blocks — rebuilt as one flax
module in NHWC.

Reference behavior replaced: swarm/post_processors/upscale.py:5-36 loads
`StableDiffusionLatentUpscalePipeline` per upscale job; its UNet is a
distinct family from every other UNet in the inventory: Gaussian-Fourier
time features with a 896-d conditioning projection folded INTO the
timestep embedding (cat of a fixed 128-d noise-level embed and the CLIP
pooler output), AdaGroupNorm everywhere (affine-free GroupNorm whose
scale/shift are a plain Linear of the time embedding), gelu resnets with
bias-free shortcuts, fixed (non-learned) blur kernels for down/up
sampling, K-attention blocks with layer-normed cross states, a 1x1
conv-in over 8 channels (noise + conditioning latents), no mid block, no
output norm, and a 5-channel 1x1 conv-out whose last channel is dropped.

Skip wiring (channel shapes pin it): each down level contributes its
pre-downsample output; the deepest up block concatenates the bottom
hidden with itself (the K-UNet's symmetric 2x-width entry), shallower up
blocks concatenate the mirrored down output after upsampling.

Module names line up with the diffusers state-dict names so conversion
(models/conversion.py convert_k_upscaler) is a mechanical rename.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .layers import Conv, DeclaredParams, Dense, LayerNorm


@dataclasses.dataclass(frozen=True)
class KUpscalerConfig:
    in_channels: int = 8
    out_channels: int = 5
    block_out_channels: tuple[int, ...] = (384, 768, 1280, 1280)
    layers_per_block: int = 4
    cross_attention_dim: int = 768
    attention_head_dim: int = 64
    resnet_group_size: int = 32
    time_cond_proj_dim: int = 896
    cross_attention: tuple[bool, ...] = (False, True, True, True)
    # self-attention lives at the bottom of the U (deepest down + deepest
    # up); conversion infers the real placement from attn1 key presence
    down_self_attention: tuple[bool, ...] = (False, False, False, True)
    up_self_attention: tuple[bool, ...] = (True, False, False, False)
    attention_bias: bool = True


TINY_K_UPSCALER = KUpscalerConfig(
    block_out_channels=(32, 64),
    layers_per_block=2,
    cross_attention_dim=32,
    attention_head_dim=8,
    resnet_group_size=16,
    # tiny CLIP pools 32-wide + a 16-wide fixed noise embed (the real
    # model is 768 + 128 = 896)
    time_cond_proj_dim=48,
    cross_attention=(False, True),
    down_self_attention=(False, True),
    up_self_attention=(True, False),
)


def _blur_kernel(scale: float) -> np.ndarray:
    k1 = np.asarray([1.0, 3.0, 3.0, 1.0], np.float32) / 8.0 * scale
    return np.outer(k1, k1)


class KDownsample2D(nn.Module):
    """Fixed depthwise 4x4 blur, stride 2, reflect pad 1 — no params."""

    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        x = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)), mode="reflect")
        kernel = jnp.asarray(_blur_kernel(1.0), self.dtype)
        kernel = jnp.tile(kernel[:, :, None, None], (1, 1, 1, c))
        return jax.lax.conv_general_dilated(
            x.astype(self.dtype), kernel, (2, 2), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=c,
        )


class KUpsample2D(nn.Module):
    """Fixed depthwise transposed 4x4 blur, stride 2 (torch
    conv_transpose2d(stride=2, padding=3) on a reflect-pad-1 input ==
    input dilation 2 + VALID conv with the symmetric kernel) — no
    params."""

    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        x = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)), mode="reflect")
        kernel = jnp.asarray(_blur_kernel(2.0), self.dtype)
        kernel = jnp.tile(kernel[:, :, None, None], (1, 1, 1, c))
        return jax.lax.conv_general_dilated(
            x.astype(self.dtype), kernel, (1, 1), ((0, 0), (0, 0)),
            lhs_dilation=(2, 2),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=c,
        )


class AdaGroupNorm(nn.Module):
    """Affine-free GroupNorm; scale/shift from a Linear of the time
    embedding (no activation): x_norm * (1 + scale) + shift."""

    groups: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, temb):
        c = x.shape[-1]
        emb = Dense(2 * c, dtype=self.dtype, name="linear")(temb)
        scale, shift = jnp.split(emb[:, None, None, :], 2, axis=-1)
        x = nn.GroupNorm(
            self.groups, epsilon=1e-5, use_bias=False, use_scale=False,
            dtype=self.dtype,
        )(x)
        return x * (1.0 + scale) + shift


class KResnetBlock(nn.Module):
    """diffusers ResnetBlockCondNorm2D (ada_group): AdaGN -> gelu -> conv,
    twice; bias-free 1x1 shortcut on width change."""

    out_channels: int
    group_size: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, temb):
        in_ch = x.shape[-1]
        h = AdaGroupNorm(
            max(1, in_ch // self.group_size), dtype=self.dtype, name="norm1"
        )(x, temb)
        h = nn.gelu(h, approximate=False)
        h = Conv(
            self.out_channels, (3, 3), dtype=self.dtype, name="conv1"
        )(h)
        h = AdaGroupNorm(
            max(1, self.out_channels // self.group_size), dtype=self.dtype,
            name="norm2",
        )(h, temb)
        h = nn.gelu(h, approximate=False)
        h = Conv(
            self.out_channels, (3, 3), dtype=self.dtype, name="conv2"
        )(h)
        if in_ch != self.out_channels:
            x = Conv(
                self.out_channels, (1, 1), use_bias=False, dtype=self.dtype,
                name="conv_shortcut",
            )(x)
        return x + h


class KAttention(nn.Module):
    """diffusers Attention as the K blocks build it: optional q/k/v bias,
    to_out.0 with bias, layer-normed cross states (norm_cross)."""

    inner: int
    head_dim: int
    use_bias: bool = True
    cross_norm: bool = False
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, q_in, kv_in):
        heads = max(1, self.inner // self.head_dim)
        dim = self.inner // heads
        b, n, _ = q_in.shape
        if self.cross_norm:
            kv_in = LayerNorm(
                epsilon=1e-5, dtype=self.dtype, name="norm_cross"
            )(kv_in)
        s = kv_in.shape[1]
        q = Dense(self.inner, use_bias=self.use_bias, dtype=self.dtype,
                  name="to_q")(q_in)
        k = Dense(self.inner, use_bias=self.use_bias, dtype=self.dtype,
                  name="to_k")(kv_in)
        v = Dense(self.inner, use_bias=self.use_bias, dtype=self.dtype,
                  name="to_v")(kv_in)
        q = q.reshape(b, n, heads, dim)
        k = k.reshape(b, s, heads, dim)
        v = v.reshape(b, s, heads, dim)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        weights = nn.softmax(logits * (dim ** -0.5), axis=-1).astype(
            self.dtype
        )
        out = jnp.einsum("bhqk,bkhd->bqhd", weights, v).reshape(
            b, n, self.inner
        )
        return Dense(self.inner, dtype=self.dtype, name="to_out_0")(out)


class KAttentionBlock(nn.Module):
    """AdaGN-normed token-space attention: optional self (attn1) then
    cross (attn2) over layer-normed encoder states, both residual."""

    head_dim: int
    group_size: int
    self_attention: bool = False
    attention_bias: bool = True
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, temb, context):
        b, h, w, c = x.shape
        groups = max(1, c // self.group_size)
        if self.self_attention:
            norm = AdaGroupNorm(groups, dtype=self.dtype, name="norm1")(
                x, temb
            )
            tokens = norm.reshape(b, h * w, c)
            attn = KAttention(
                c, self.head_dim, use_bias=self.attention_bias,
                dtype=self.dtype, name="attn1",
            )(tokens, tokens)
            x = x + attn.reshape(b, h, w, c)
        norm = AdaGroupNorm(groups, dtype=self.dtype, name="norm2")(x, temb)
        tokens = norm.reshape(b, h * w, c)
        attn = KAttention(
            c, self.head_dim, use_bias=self.attention_bias, cross_norm=True,
            dtype=self.dtype, name="attn2",
        )(tokens, context)
        return x + attn.reshape(b, h, w, c)


class KUpscalerUNet(DeclaredParams, nn.Module):
    """[B,H,W,8] (noise latents + conditioning latents) + [B] continuous
    timesteps (log(sigma)/4) + [B,S,cross] CLIP states + [B,896]
    timestep_cond -> [B,H,W,out_channels]."""

    config: KUpscalerConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, sample, timesteps, encoder_hidden_states,
                 timestep_cond):
        cfg = self.config
        n = len(cfg.block_out_channels)
        c0 = cfg.block_out_channels[0]

        # GaussianFourierProjection(log=False, flip_sin_to_cos=True):
        # cat(cos, sin) of 2*pi*w*t with a frozen random weight vector
        w = self.param(
            "time_proj_weight", nn.initializers.normal(16.0), (c0,)
        )
        args = (
            jnp.asarray(timesteps, jnp.float32)[:, None]
            * jax.lax.stop_gradient(jnp.asarray(w, jnp.float32))[None, :]
            * (2.0 * np.pi)
        )
        t_emb = jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)
        t_emb = t_emb.astype(self.dtype)
        # TimestepEmbedding with cond_proj + gelu act AND post-act
        t_emb = t_emb + Dense(
            2 * c0, use_bias=False, dtype=self.dtype,
            name="time_embedding_cond_proj",
        )(jnp.asarray(timestep_cond, self.dtype))
        t_emb = Dense(
            2 * c0, dtype=self.dtype, name="time_embedding_linear_1"
        )(t_emb)
        t_emb = nn.gelu(t_emb, approximate=False)
        t_emb = Dense(
            2 * c0, dtype=self.dtype, name="time_embedding_linear_2"
        )(t_emb)
        temb = nn.gelu(t_emb, approximate=False)

        context = jnp.asarray(encoder_hidden_states, self.dtype)
        x = Conv(
            c0, (1, 1), dtype=self.dtype, name="conv_in"
        )(jnp.asarray(sample, self.dtype))

        skips = []
        for i in range(n):
            out_ch = cfg.block_out_channels[i]
            for j in range(cfg.layers_per_block):
                x = KResnetBlock(
                    out_ch, cfg.resnet_group_size, dtype=self.dtype,
                    name=f"down_blocks_{i}_resnets_{j}",
                )(x, temb)
                if cfg.cross_attention[i]:
                    x = KAttentionBlock(
                        cfg.attention_head_dim, cfg.resnet_group_size,
                        self_attention=cfg.down_self_attention[i],
                        attention_bias=cfg.attention_bias,
                        dtype=self.dtype,
                        name=f"down_blocks_{i}_attentions_{j}",
                    )(x, temb, context)
            skips.append(x)
            if i != n - 1:
                x = KDownsample2D(dtype=self.dtype)(x)

        rev = tuple(reversed(cfg.block_out_channels))
        for lvl in range(n):
            i = n - 1 - lvl
            out_ch = rev[lvl]
            k_out = rev[min(lvl + 1, n - 1)]
            x = jnp.concatenate([x, skips.pop()], axis=-1)
            nb = cfg.layers_per_block
            for j in range(nb):
                width = k_out if j == nb - 1 else out_ch
                x = KResnetBlock(
                    width, cfg.resnet_group_size, dtype=self.dtype,
                    name=f"up_blocks_{lvl}_resnets_{j}",
                )(x, temb)
                if cfg.cross_attention[i]:
                    x = KAttentionBlock(
                        cfg.attention_head_dim, cfg.resnet_group_size,
                        self_attention=cfg.up_self_attention[lvl],
                        attention_bias=cfg.attention_bias,
                        dtype=self.dtype,
                        name=f"up_blocks_{lvl}_attentions_{j}",
                    )(x, temb, context)
            if lvl != n - 1:
                x = KUpsample2D(dtype=self.dtype)(x)

        return Conv(
            cfg.out_channels, (1, 1), dtype=self.dtype, name="conv_out"
        )(x)
