"""AutoencoderKL (the SD latent VAE), flax.linen, NHWC.

Reference behavior being replaced: diffusers VAE with slicing/tiling memory
knobs (swarm/diffusion/diffusion_func.py:134-146). On TPU the decode runs
as one fused program; for batches, decode is shard_mapped over the mesh's
data axis instead of sliced sequentially (pipelines/stable_diffusion.py).
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax.numpy as jnp

from .layers import (
    Conv,
    Dense,
    Downsample2D,
    FusedGroupNorm,
    ResnetBlock2D,
    Upsample2D,
)
from ..ops import dot_product_attention


@dataclasses.dataclass(frozen=True)
class VAEConfig:
    in_channels: int = 3
    latent_channels: int = 4
    block_out_channels: tuple[int, ...] = (128, 256, 512, 512)
    layers_per_block: int = 2
    scaling_factor: float = 0.18215  # 0.13025 for SDXL, 0.3611 for Flux
    shift_factor: float = 0.0  # Flux: 0.1159 (latents are shifted, then scaled)
    # Flux VAE checkpoints ship without the 1x1 (post_)quant convs
    use_quant_conv: bool = True


class VAEAttention(nn.Module):
    """Single-head spatial self-attention used in the VAE mid blocks."""

    channels: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, h, w, c = x.shape
        residual = x
        hidden = FusedGroupNorm(32, epsilon=1e-6, dtype=self.dtype,
                                name="group_norm")(x)
        hidden = hidden.reshape(b, h * w, c)
        q = Dense(c, dtype=self.dtype, name="to_q")(hidden)
        k = Dense(c, dtype=self.dtype, name="to_k")(hidden)
        v = Dense(c, dtype=self.dtype, name="to_v")(hidden)
        out = dot_product_attention(
            q[:, :, None, :], k[:, :, None, :], v[:, :, None, :]
        )[:, :, 0, :]
        out = Dense(c, dtype=self.dtype, name="to_out_0")(out)
        return out.reshape(b, h, w, c) + residual


class Encoder(nn.Module):
    config: VAEConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, pixels):
        cfg = self.config
        x = Conv(
            cfg.block_out_channels[0], (3, 3), padding=((1, 1), (1, 1)),
            dtype=self.dtype, name="conv_in",
        )(pixels)

        for b, out_ch in enumerate(cfg.block_out_channels):
            for i in range(cfg.layers_per_block):
                x = ResnetBlock2D(out_ch, eps=1e-6, dtype=self.dtype, name=f"down_blocks_{b}_resnets_{i}"
                )(x)
            if b != len(cfg.block_out_channels) - 1:
                x = Downsample2D(
                    out_ch,
                    asymmetric_pad=True,
                    dtype=self.dtype,
                    name=f"down_blocks_{b}_downsamplers_0",
                )(x)

        mid_ch = cfg.block_out_channels[-1]
        x = ResnetBlock2D(mid_ch, eps=1e-6, dtype=self.dtype, name="mid_block_resnets_0")(x)
        x = VAEAttention(mid_ch, dtype=self.dtype, name="mid_block_attentions_0")(x)
        x = ResnetBlock2D(mid_ch, eps=1e-6, dtype=self.dtype, name="mid_block_resnets_1")(x)

        x = FusedGroupNorm(32, epsilon=1e-6, dtype=self.dtype, act="silu",
                           name="conv_norm_out")(x)
        # moments: mean + logvar
        return Conv(
            2 * cfg.latent_channels, (3, 3), padding=((1, 1), (1, 1)),
            dtype=self.dtype, name="conv_out",
        )(x)


class Decoder(nn.Module):
    config: VAEConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, latents):
        cfg = self.config
        mid_ch = cfg.block_out_channels[-1]
        x = Conv(
            mid_ch, (3, 3), padding=((1, 1), (1, 1)), dtype=self.dtype, name="conv_in"
        )(latents)

        x = ResnetBlock2D(mid_ch, eps=1e-6, dtype=self.dtype, name="mid_block_resnets_0")(x)
        x = VAEAttention(mid_ch, dtype=self.dtype, name="mid_block_attentions_0")(x)
        x = ResnetBlock2D(mid_ch, eps=1e-6, dtype=self.dtype, name="mid_block_resnets_1")(x)

        for b, out_ch in enumerate(reversed(cfg.block_out_channels)):
            for i in range(cfg.layers_per_block + 1):
                x = ResnetBlock2D(out_ch, eps=1e-6, dtype=self.dtype, name=f"up_blocks_{b}_resnets_{i}"
                )(x)
            if b != len(cfg.block_out_channels) - 1:
                x = Upsample2D(
                    out_ch, dtype=self.dtype, name=f"up_blocks_{b}_upsamplers_0"
                )(x)

        x = FusedGroupNorm(32, epsilon=1e-6, dtype=self.dtype, act="silu",
                           name="conv_norm_out")(x)
        return Conv(
            cfg.in_channels, (3, 3), padding=((1, 1), (1, 1)), dtype=self.dtype,
            name="conv_out",
        )(x)


class AutoencoderKL(nn.Module):
    config: VAEConfig
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        self.encoder = Encoder(self.config, dtype=self.dtype)
        self.decoder = Decoder(self.config, dtype=self.dtype)
        if self.config.use_quant_conv:
            self.quant_conv = Conv(
                2 * self.config.latent_channels, (1, 1), dtype=self.dtype
            )
            self.post_quant_conv = Conv(
                self.config.latent_channels, (1, 1), dtype=self.dtype
            )
        else:  # Flux layout: encoder/decoder connect directly to the latents
            self.quant_conv = lambda x: x
            self.post_quant_conv = lambda x: x

    def encode(self, pixels, rng=None):
        """pixels [B,H,W,3] in [-1,1] -> scaled latents [B,H/8,W/8,C]."""
        moments = self.quant_conv(self.encoder(pixels))
        mean, logvar = jnp.split(moments, 2, axis=-1)
        if rng is not None:
            import jax

            std = jnp.exp(0.5 * jnp.clip(logvar, -30.0, 20.0))
            mean = mean + std * jax.random.normal(rng, mean.shape, mean.dtype)
        return (mean - self.config.shift_factor) * self.config.scaling_factor

    def decode(self, latents):
        """scaled latents -> pixels [B,H,W,3] in [-1,1]."""
        latents = latents / self.config.scaling_factor + self.config.shift_factor
        return self.decoder(self.post_quant_conv(latents))

    def __call__(self, pixels):
        return self.decode(self.encode(pixels))
