"""ControlNet (Zhang et al.) as a flax module over the shared UNet blocks.

Replaces the reference's per-job `ControlNetModel.from_pretrained`
(swarm/diffusion/diffusion_func.py:52-73). The control branch copies the
UNet's down/mid path, embeds the conditioning image through a small conv
stack, and emits zero-initialized 1x1-conv residuals that are added to the
main UNet's skip connections — so an unconverted (random/zero) ControlNet
is exactly a no-op on the base model, which the tests rely on.

Weight layout mirrors HF `ControlNetModel` for mechanical conversion.
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp

from .layers import Conv, TimestepEmbedding, timestep_embedding
from .unet2d import CrossAttnDownBlock, UNet2DConfig, UNetMidBlock


class ControlNetConditioningEmbedding(nn.Module):
    """Control image [B, H, W, 3] -> feature map at latent resolution.

    `downscale` must equal the VAE's spatial factor (8 for SD-family, where
    the channel ramp 16->32->96->256 matches HF; smaller for tiny test VAEs,
    where the ramp truncates).
    """

    out_channels: int
    downscale: int = 8
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, cond):
        n_down = max((self.downscale - 1).bit_length(), 1)  # log2, >= 1
        block_channels = ((16, 32, 96, 256) * 2)[: n_down + 1]
        x = Conv(
            block_channels[0], (3, 3), padding=((1, 1), (1, 1)),
            dtype=self.dtype, name="conv_in",
        )(cond)
        x = nn.silu(x)
        for i in range(len(block_channels) - 1):
            x = Conv(
                block_channels[i], (3, 3), padding=((1, 1), (1, 1)),
                dtype=self.dtype, name=f"blocks_{2 * i}",
            )(x)
            x = nn.silu(x)
            x = Conv(
                block_channels[i + 1], (3, 3), strides=(2, 2),
                padding=((1, 1), (1, 1)), dtype=self.dtype,
                name=f"blocks_{2 * i + 1}",
            )(x)
            x = nn.silu(x)
        # zero conv: starts as identity-off
        return Conv(
            self.out_channels, (3, 3), padding=((1, 1), (1, 1)),
            kernel_init=nn.initializers.zeros, bias_init=nn.initializers.zeros,
            dtype=self.dtype, name="conv_out",
        )(x)


def _zero_conv(channels, dtype, name):
    return Conv(
        channels, (1, 1), kernel_init=nn.initializers.zeros,
        bias_init=nn.initializers.zeros, dtype=dtype, name=name,
    )


class ControlNetModel(nn.Module):
    """Down+mid copy of the UNet emitting per-skip residuals.

    __call__(sample, timesteps, encoder_hidden_states, controlnet_cond,
    conditioning_scale) -> (down_residuals tuple, mid_residual).
    """

    config: UNet2DConfig
    cond_downscale: int = 8  # = the paired VAE's spatial latent factor
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, sample, timesteps, encoder_hidden_states, controlnet_cond,
                 conditioning_scale=1.0, added_cond=None):
        cfg = self.config
        if jnp.ndim(timesteps) == 0:
            timesteps = jnp.broadcast_to(timesteps, (sample.shape[0],))

        temb_dim = cfg.block_out_channels[0] * 4
        t_feat = timestep_embedding(
            timesteps,
            cfg.block_out_channels[0],
            flip_sin_to_cos=cfg.flip_sin_to_cos,
            downscale_freq_shift=cfg.freq_shift,
            dtype=self.dtype,
        )
        temb = TimestepEmbedding(temb_dim, dtype=self.dtype, name="time_embedding")(
            t_feat
        )

        if cfg.addition_embed_dim and added_cond is not None:
            tid_feat = timestep_embedding(
                added_cond["time_ids"].reshape(-1),
                cfg.addition_time_embed_dim,
                flip_sin_to_cos=cfg.flip_sin_to_cos,
                downscale_freq_shift=cfg.freq_shift,
                dtype=self.dtype,
            ).reshape(sample.shape[0], -1)
            add_feat = jnp.concatenate([added_cond["text_embeds"], tid_feat], axis=-1)
            temb = temb + TimestepEmbedding(
                temb_dim, dtype=self.dtype, name="add_embedding"
            )(add_feat)

        x = Conv(
            cfg.block_out_channels[0], (3, 3), padding=((1, 1), (1, 1)),
            dtype=self.dtype, name="conv_in",
        )(sample)
        x = x + ControlNetConditioningEmbedding(
            cfg.block_out_channels[0], downscale=self.cond_downscale,
            dtype=self.dtype, name="controlnet_cond_embedding",
        )(controlnet_cond)

        heads = cfg.heads_per_block()
        skips = [x]
        for b, out_ch in enumerate(cfg.block_out_channels):
            last = b == len(cfg.block_out_channels) - 1
            x, block_skips = CrossAttnDownBlock(
                cfg,
                out_ch,
                cfg.transformer_layers[b],
                heads[b],
                add_downsample=not last,
                dtype=self.dtype,
                name=f"down_blocks_{b}",
            )(x, temb, encoder_hidden_states)
            skips.extend(block_skips)

        x = UNetMidBlock(
            cfg,
            cfg.block_out_channels[-1],
            cfg.mid_transformer_layers,
            heads[-1],
            dtype=self.dtype,
            name="mid_block",
        )(x, temb, encoder_hidden_states)

        down_res = tuple(
            _zero_conv(s.shape[-1], self.dtype, f"controlnet_down_blocks_{i}")(s)
            * conditioning_scale
            for i, s in enumerate(skips)
        )
        mid_res = (
            _zero_conv(x.shape[-1], self.dtype, "controlnet_mid_block")(x)
            * conditioning_scale
        )
        return down_res, mid_res
