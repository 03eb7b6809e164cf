"""AutoencoderKLTemporalDecoder — the SVD video VAE, NHWC flax.

The encoder is the standard AutoencoderKL encoder (reused from vae.py: the
conditioning frame is encoded per-image), but the DECODER interleaves
temporal ResNets with the spatial ones (SpatioTemporalResBlock with a
"learned" alpha blend and switched mix) and finishes with a (3,1,1) conv
over the frame axis, which is what removes SVD's frame flicker. Matches
the diffusers graph so `convert_svd_vae` (conversion.py) maps checkpoints
mechanically; there is a `quant_conv` but NO post-quant conv.

Serving: StableVideoDiffusionPipeline decode (pipelines/video.py), where
the reference calls `pipe.decode_latents` with VAE slicing enabled
(/root/reference/swarm/video/img2vid.py:26-31) — here the whole
frame-batched decode is one jitted program.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax.numpy as jnp

from .layers import Conv, FusedGroupNorm, Upsample2D
from .svd_unet import SpatioTemporalResBlock
from .vae import Encoder, VAEAttention, VAEConfig


@dataclasses.dataclass(frozen=True)
class SVDVAEConfig:
    in_channels: int = 3
    latent_channels: int = 4
    block_out_channels: tuple[int, ...] = (128, 256, 512, 512)
    layers_per_block: int = 2
    scaling_factor: float = 0.18215

    def encoder_config(self) -> VAEConfig:
        return VAEConfig(
            in_channels=self.in_channels,
            latent_channels=self.latent_channels,
            block_out_channels=self.block_out_channels,
            layers_per_block=self.layers_per_block,
            scaling_factor=self.scaling_factor,
        )


TINY_SVD_VAE = SVDVAEConfig(
    block_out_channels=(32, 32), layers_per_block=1
)


class TemporalDecoder(nn.Module):
    config: SVDVAEConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, latents, num_frames: int):
        """[B*F, h, w, latent] (unscaled) -> [B*F, 8h, 8w, 3]."""
        cfg = self.config
        mid_ch = cfg.block_out_channels[-1]

        def st_block(name, out_ch, h):
            return SpatioTemporalResBlock(
                out_ch,
                eps=1e-6,
                temporal_eps=1e-5,
                has_temb=False,
                merge_strategy="learned",
                switch_spatial_to_temporal_mix=True,
                dtype=self.dtype,
                name=name,
            )(h, None, num_frames)

        x = Conv(
            mid_ch, (3, 3), padding=((1, 1), (1, 1)), dtype=self.dtype,
            name="conv_in",
        )(latents)

        x = st_block("mid_block_resnets_0", mid_ch, x)
        x = VAEAttention(mid_ch, dtype=self.dtype, name="mid_block_attentions_0")(x)
        x = st_block("mid_block_resnets_1", mid_ch, x)

        for b, out_ch in enumerate(reversed(cfg.block_out_channels)):
            for i in range(cfg.layers_per_block + 1):
                x = st_block(f"up_blocks_{b}_resnets_{i}", out_ch, x)
            if b != len(cfg.block_out_channels) - 1:
                x = Upsample2D(
                    out_ch, dtype=self.dtype, name=f"up_blocks_{b}_upsamplers_0"
                )(x)

        x = FusedGroupNorm(32, epsilon=1e-6, dtype=self.dtype, act="silu",
                           name="conv_norm_out")(x)
        x = Conv(
            cfg.in_channels, (3, 3), padding=((1, 1), (1, 1)), dtype=self.dtype,
            name="conv_out",
        )(x)
        # final temporal smoothing conv over the frame axis
        bf, hh, ww, c = x.shape
        x = x.reshape(bf // num_frames, num_frames, hh, ww, c)
        x = Conv(
            cfg.in_channels,
            (3, 1, 1),
            padding=((1, 1), (0, 0), (0, 0)),
            dtype=self.dtype,
            name="time_conv_out",
        )(x)
        return x.reshape(bf, hh, ww, c)


class AutoencoderKLTemporalDecoder(nn.Module):
    config: SVDVAEConfig
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        self.encoder = Encoder(self.config.encoder_config(), dtype=self.dtype)
        self.decoder = TemporalDecoder(self.config, dtype=self.dtype)
        self.quant_conv = Conv(
            2 * self.config.latent_channels, (1, 1), dtype=self.dtype
        )
        # NB: no post_quant_conv in this family

    def encode(self, pixels, rng=None):
        """pixels [B,H,W,3] in [-1,1] -> UNSCALED latent mean [B,h,w,C]
        (SVD conditions on the raw mean; denoise latents get the
        scaling_factor at the pipeline level)."""
        moments = self.quant_conv(self.encoder(pixels))
        mean, logvar = jnp.split(moments, 2, axis=-1)
        if rng is not None:
            import jax

            std = jnp.exp(0.5 * jnp.clip(logvar, -30.0, 20.0))
            mean = mean + std * jax.random.normal(rng, mean.shape, mean.dtype)
        return mean

    def decode(self, latents, num_frames: int):
        """SCALED latents [B*F,h,w,C] -> pixels [-1,1]."""
        latents = latents / self.config.scaling_factor
        return self.decoder(latents, num_frames)

    def __call__(self, pixels, num_frames: int = 1):
        lat = self.encode(pixels) * self.config.scaling_factor
        return self.decode(lat, num_frames)
