"""Paella VQGAN (Stable Cascade stage A) — decode path, NHWC flax.

The reference's `StableCascadeDecoderPipeline` (swarm/diffusion/
pipeline_steps.py:70-90) finishes jobs by running the stage-B latents
through this model's `decode` (diffusers `PaellaVQModel.decode` with
`force_not_quantize` defaulting the quantizer away), so serving only needs
the up path: latent 1x1 in-conv -> MixingResidualBlock stack (12
bottleneck blocks at the deep level, 1 at the shallow) -> transposed-conv
2x -> 1x1 out conv + pixel-shuffle 2x == a 4x spatial decode overall.

Conversion (`convert_paella_vq` in conversion.py) maps the decode-side
keys (`up_blocks.*`, `out_block.*`) and ignores the encoder/quantizer
tables, which serving never touches.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax.numpy as jnp

from .cascade_unet import ConvTransposed2D, pixel_shuffle
from .layers import Conv, DeclaredParams, Dense, LayerNorm


@dataclasses.dataclass(frozen=True)
class PaellaVQConfig:
    out_channels: int = 3
    up_down_scale_factor: int = 2
    levels: int = 2
    bottleneck_blocks: int = 12
    embed_dim: int = 384
    latent_channels: int = 4
    scale_factor: float = 0.3764

    def c_levels(self) -> tuple[int, ...]:
        return tuple(
            self.embed_dim // (2**i) for i in reversed(range(self.levels))
        )


TINY_PAELLA_VQ = PaellaVQConfig(
    levels=2, bottleneck_blocks=2, embed_dim=32, latent_channels=4
)


class MixingResidualBlock(DeclaredParams, nn.Module):
    """LN-modulated depthwise (edge-padded 3x3) + channel MLP, with six
    learned per-block gammas gating each branch (Paella block)."""

    channels: int
    embed_dim: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        mods = self.param("gammas", nn.initializers.zeros, (6,)).astype(x.dtype)

        def ln(v):
            return LayerNorm(
                epsilon=1e-6, use_scale=False, use_bias=False, dtype=self.dtype
            )(v)

        h = ln(x) * (1 + mods[0]) + mods[1]
        h = jnp.pad(h, ((0, 0), (1, 1), (1, 1), (0, 0)), mode="edge")
        h = Conv(
            self.channels,
            (3, 3),
            padding="VALID",
            feature_group_count=self.channels,
            dtype=self.dtype,
            name="depthwise_1",
        )(h)
        x = x + h * mods[2]
        h = ln(x) * (1 + mods[3]) + mods[4]
        h = Dense(self.embed_dim, dtype=self.dtype, name="channelwise_0")(h)
        h = nn.gelu(h, approximate=False)
        h = Dense(self.channels, dtype=self.dtype, name="channelwise_2")(h)
        return x + h * mods[5]


class PaellaVQDecoder(nn.Module):
    config: PaellaVQConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, latents):
        """[B, h, w, latent_channels] (already divided by scale_factor at
        the call site, diffusers decode semantics) -> [B, 4h, 4w, 3]."""
        cfg = self.config
        c_levels = cfg.c_levels()
        x = latents.astype(self.dtype)
        idx = 0
        x = Conv(
            c_levels[-1], (1, 1), dtype=self.dtype, name=f"up_blocks_{idx}_0"
        )(x)
        idx += 1
        for i in range(cfg.levels):
            ch = c_levels[cfg.levels - 1 - i]
            for _ in range(cfg.bottleneck_blocks if i == 0 else 1):
                x = MixingResidualBlock(
                    ch, ch * 4, dtype=self.dtype, name=f"up_blocks_{idx}"
                )(x)
                idx += 1
            if i < cfg.levels - 1:
                x = ConvTransposed2D(
                    c_levels[cfg.levels - 2 - i],
                    kernel_size=4,
                    stride=2,
                    padding=1,
                    dtype=self.dtype,
                    name=f"up_blocks_{idx}",
                )(x)
                idx += 1
        x = Conv(
            cfg.out_channels * cfg.up_down_scale_factor**2,
            (1, 1),
            dtype=self.dtype,
            name="out_block_0",
        )(x)
        return pixel_shuffle(x, cfg.up_down_scale_factor)
