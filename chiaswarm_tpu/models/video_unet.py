"""Temporal video UNet: the SD block stack + motion (temporal-attention)
modules, covering AnimateDiff-style txt2vid and SVD/I2VGenXL-style img2vid.

Reference behavior replaced: swarm/video/tx2vid.py:26-48 (AnimateDiff motion
adapters loaded per job onto a torch UNet) and swarm/video/img2vid.py
(StableVideoDiffusion). TPU-first inversions:

- frames ride the batch dim for all spatial ops ([B*F, H, W, C] — keeps the
  MXU fed with large convs/matmuls), and temporal mixing happens in compact
  [B*H*W, F, C] self-attention blocks after each spatial stage, matching the
  AnimateDiff motion-module graph for weight conversion;
- the whole clip denoises as ONE scan program — no per-frame Python loop
  (the reference's vid2vid runs up to 100 sequential pipeline invocations,
  swarm/video/pix2pix.py:47-68);
- img2vid conditions by concatenating the encoded conditioning frame onto
  every frame's latent channels (SVD layout: in_channels 8).
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax.numpy as jnp

from .layers import (
    Attention,
    Conv,
    Dense,
    Downsample2D,
    FeedForward,
    FusedGroupNorm,
    LayerNorm,
    ResnetBlock2D,
    TimestepEmbedding,
    Transformer2DModel,
    Upsample2D,
    timestep_embedding,
)
from .unet2d import UNet2DConfig


@dataclasses.dataclass(frozen=True)
class VideoUNetConfig:
    base: UNet2DConfig = UNet2DConfig()
    num_frames: int = 16
    temporal_pos_max: int = 32  # max frames the positional table supports


def _sinusoidal_pe(n: int, dim: int, dtype) -> jnp.ndarray:
    """diffusers SinusoidalPositionalEmbedding layout: sin/cos INTERLEAVED
    (pe[:, 0::2]=sin, pe[:, 1::2]=cos) — converted attention weights were
    trained against this layout, so the concatenated variant would silently
    scramble positions."""
    position = jnp.arange(n, dtype=jnp.float32)[:, None]
    div = jnp.exp(
        jnp.arange(0, dim, 2, dtype=jnp.float32) * (-jnp.log(10000.0) / dim)
    )
    args = position * div[None]
    pe = jnp.zeros((n, dim), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(args))
    pe = pe.at[:, 1::2].set(jnp.cos(args))
    return pe.astype(dtype)


class _TemporalBlock(nn.Module):
    """diffusers motion BasicTransformerBlock: two temporal SELF-attentions
    and a GEGLU FF, with the sinusoidal positions applied to the NORMED
    input of each attention (positional_embeddings='sinusoidal')."""

    channels: int
    num_heads: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, hidden, pos):
        c = self.channels
        hd = c // self.num_heads
        y = LayerNorm(epsilon=1e-5, dtype=self.dtype, name="norm1")(hidden)
        hidden = hidden + Attention(
            self.num_heads, hd, c, dtype=self.dtype, name="attn1"
        )(y + pos[None])
        y = LayerNorm(epsilon=1e-5, dtype=self.dtype, name="norm2")(hidden)
        hidden = hidden + Attention(
            self.num_heads, hd, c, dtype=self.dtype, name="attn2"
        )(y + pos[None])
        y = LayerNorm(epsilon=1e-5, dtype=self.dtype, name="norm3")(hidden)
        return hidden + FeedForward(c, dtype=self.dtype, name="ff")(y)


class TemporalTransformer(nn.Module):
    """Self-attention over the frame axis at fixed spatial positions.

    Input [BF, H, W, C]; `num_frames` is the RUNTIME clip length (static at
    trace time), passed per call because jobs may request fewer frames than
    the configured maximum — deriving it from config would fold the CFG
    uncond/cond halves into one clip.

    The graph IS diffusers' AnimateDiff motion module (group norm ->
    proj_in -> temporal transformer blocks -> zero-init proj_out ->
    residual), parameter-for-parameter, so real motion-adapter checkpoints
    convert mechanically (conversion.py convert_motion_adapter).
    """

    channels: int
    num_heads: int = 8
    num_layers: int = 1
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, num_frames: int):
        bf, h, w, c = x.shape
        if bf % num_frames:
            raise ValueError(
                f"batch*frames {bf} not divisible by num_frames {num_frames}"
            )
        b = bf // num_frames
        residual = x
        hidden = FusedGroupNorm(32, epsilon=1e-6, dtype=self.dtype,
                                name="norm")(x)
        # [B, F, H, W, C] -> [B*H*W, F, C]
        hidden = hidden.reshape(b, num_frames, h, w, c)
        hidden = hidden.transpose(0, 2, 3, 1, 4).reshape(b * h * w, num_frames, c)
        hidden = Dense(c, dtype=self.dtype, name="proj_in")(hidden)

        pos = _sinusoidal_pe(num_frames, c, self.dtype)
        heads = self.num_heads if c % self.num_heads == 0 else max(
            1, min(self.num_heads, c // 8)
        )
        for i in range(self.num_layers):
            hidden = _TemporalBlock(
                c, heads, dtype=self.dtype, name=f"transformer_blocks_{i}"
            )(hidden, pos)

        # zero-init output projection: an unconverted motion module is a
        # no-op on the spatial model (AnimateDiff init convention)
        hidden = Dense(
            c, kernel_init=nn.initializers.zeros, dtype=self.dtype,
            name="proj_out",
        )(hidden)
        hidden = hidden.reshape(b, h, w, num_frames, c)
        hidden = hidden.transpose(0, 3, 1, 2, 4).reshape(bf, h, w, c)
        return residual + hidden


class VideoUNet(nn.Module):
    """[B*F, H, W, C] latents -> noise prediction, temporally mixed."""

    config: VideoUNetConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, sample, timesteps, encoder_hidden_states, num_frames=None):
        cfg = self.config.base
        # runtime clip length (static per compile); defaults to the config
        # maximum for single-clip calls like param init
        frames = int(num_frames) if num_frames is not None else self.config.num_frames
        if frames > self.config.temporal_pos_max:
            raise ValueError(
                f"num_frames {frames} exceeds temporal_pos_max "
                f"{self.config.temporal_pos_max}"
            )
        if jnp.ndim(timesteps) == 0:
            timesteps = jnp.broadcast_to(timesteps, (sample.shape[0],))

        temb_dim = cfg.block_out_channels[0] * 4
        t_feat = timestep_embedding(
            timesteps, cfg.block_out_channels[0],
            flip_sin_to_cos=cfg.flip_sin_to_cos,
            downscale_freq_shift=cfg.freq_shift, dtype=self.dtype,
        )
        temb = TimestepEmbedding(temb_dim, dtype=self.dtype, name="time_embedding")(
            t_feat
        )

        x = Conv(
            cfg.block_out_channels[0], (3, 3), padding=((1, 1), (1, 1)),
            dtype=self.dtype, name="conv_in",
        )(sample)

        heads = cfg.heads_per_block()
        skips = [x]
        for bidx, out_ch in enumerate(cfg.block_out_channels):
            last = bidx == len(cfg.block_out_channels) - 1
            for i in range(cfg.layers_per_block):
                x = ResnetBlock2D(
                    out_ch, dtype=self.dtype, name=f"down_{bidx}_resnets_{i}"
                )(x, temb)
                if cfg.transformer_layers[bidx] > 0:
                    x = Transformer2DModel(
                        heads[bidx], out_ch // heads[bidx],
                        cfg.transformer_layers[bidx], dtype=self.dtype,
                        name=f"down_{bidx}_attentions_{i}",
                    )(x, encoder_hidden_states)
                x = TemporalTransformer(
                    out_ch, dtype=self.dtype,
                    name=f"down_{bidx}_motion_modules_{i}",
                )(x, frames)
                skips.append(x)
            if not last:
                x = Downsample2D(out_ch, dtype=self.dtype, name=f"down_{bidx}_downsample")(x)
                skips.append(x)

        mid_ch = cfg.block_out_channels[-1]
        x = ResnetBlock2D(mid_ch, dtype=self.dtype, name="mid_resnets_0")(x, temb)
        x = Transformer2DModel(
            heads[-1], mid_ch // heads[-1], cfg.mid_transformer_layers,
            dtype=self.dtype, name="mid_attentions_0",
        )(x, encoder_hidden_states)
        x = TemporalTransformer(
            mid_ch, dtype=self.dtype, name="mid_motion_modules_0"
        )(x, frames)
        x = ResnetBlock2D(mid_ch, dtype=self.dtype, name="mid_resnets_1")(x, temb)

        for bidx, out_ch in enumerate(reversed(cfg.block_out_channels)):
            rev = len(cfg.block_out_channels) - 1 - bidx
            last = bidx == len(cfg.block_out_channels) - 1
            for i in range(cfg.layers_per_block + 1):
                x = jnp.concatenate([x, skips.pop()], axis=-1)
                x = ResnetBlock2D(
                    out_ch, dtype=self.dtype, name=f"up_{bidx}_resnets_{i}"
                )(x, temb)
                if cfg.transformer_layers[rev] > 0:
                    x = Transformer2DModel(
                        heads[rev], out_ch // heads[rev],
                        cfg.transformer_layers[rev], dtype=self.dtype,
                        name=f"up_{bidx}_attentions_{i}",
                    )(x, encoder_hidden_states)
                x = TemporalTransformer(
                    out_ch, dtype=self.dtype,
                    name=f"up_{bidx}_motion_modules_{i}",
                )(x, frames)
            if not last:
                x = Upsample2D(out_ch, dtype=self.dtype, name=f"up_{bidx}_upsample")(x)

        x = FusedGroupNorm(32, epsilon=1e-5, dtype=self.dtype, act="silu",
                           name="conv_norm_out")(x)
        return Conv(
            cfg.out_channels, (3, 3), padding=((1, 1), (1, 1)), dtype=self.dtype,
            name="conv_out",
        )(x)
