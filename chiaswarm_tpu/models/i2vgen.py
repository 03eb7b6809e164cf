"""I2VGenXLUNet — the default image-to-video graph the reference serves
(swarm/job_arguments.py:143 resolves img2vid jobs to I2VGenXLPipeline).

The trunk is the UNet3DConditionModel block structure (models/unet3d.py
unet3d_backbone: resnet + temporal conv + spatial/temporal transformers,
frames riding the batch axis). Around it, I2VGenXL adds:
- an FPS embedding summed into the time embedding;
- a per-frame image-latents stream: 1x1/3x3 conv projection to latent
  width, a tiny frame-axis transformer encoder at every pixel, then
  channel-concat with the noisy latents into an 8-channel conv_in;
- context tokens assembled from THREE sources: the CLIP text states, an
  8x8 grid of first-frame latent features (conv stack + adaptive 32x32
  average pool + two stride-2 convs to cross width), and the CLIP image
  embedding lifted to `in_channels` extra tokens.

Module names line up with the diffusers state-dict names so conversion
(models/conversion.py convert_i2vgen_unet) is a mechanical rename over
unet3d_rename plus the flat conditioning-module names.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax.numpy as jnp

from .layers import (
    Conv,
    Dense,
    LayerNorm,
    TimestepEmbedding,
    timestep_embedding,
)
from .unet3d import UNet3DConfig, unet3d_backbone


@dataclasses.dataclass(frozen=True)
class I2VGenConfig:
    in_channels: int = 4
    out_channels: int = 4
    block_out_channels: tuple[int, ...] = (320, 640, 1280, 1280)
    layers_per_block: int = 2
    attention: tuple[bool, ...] = (True, True, True, False)
    attention_head_dim: int = 64
    cross_attention_dim: int = 1024
    norm_num_groups: int = 32

    def trunk(self) -> UNet3DConfig:
        return UNet3DConfig(
            in_channels=2 * self.in_channels,
            out_channels=self.out_channels,
            block_out_channels=self.block_out_channels,
            layers_per_block=self.layers_per_block,
            attention=self.attention,
            attention_head_dim=self.attention_head_dim,
            cross_attention_dim=self.cross_attention_dim,
            norm_num_groups=self.norm_num_groups,
        )


TINY_I2VGEN = I2VGenConfig(
    block_out_channels=(32, 64),
    layers_per_block=1,
    attention=(True, False),
    attention_head_dim=8,
    cross_attention_dim=16,
    norm_num_groups=8,
)


def adaptive_avg_pool(x, out_hw: int):
    """torch AdaptiveAvgPool2d semantics on NHWC (per-cell slice means
    with floor/ceil bin edges); shapes are static so the python loop
    traces away."""
    import math

    b, h, w, c = x.shape
    rows = jnp.stack(
        [
            jnp.mean(
                x[:, math.floor(i * h / out_hw): math.ceil((i + 1) * h / out_hw)],
                axis=1,
            )
            for i in range(out_hw)
        ],
        axis=1,
    )
    return jnp.stack(
        [
            jnp.mean(
                rows[:, :, math.floor(j * w / out_hw): math.ceil((j + 1) * w / out_hw)],
                axis=2,
            )
            for j in range(out_hw)
        ],
        axis=2,
    )


class _TemporalEncoder(nn.Module):
    """I2VGenXLTransformerTemporalEncoder: pre-LN self-attention + gelu
    feed-forward over the frame axis at each pixel (dim = latent width)."""

    dim: int
    heads: int = 2
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, tokens):
        b, f, d = tokens.shape
        head_dim = max(1, self.dim // self.heads)
        h = LayerNorm(epsilon=1e-5, dtype=self.dtype, name="norm1")(tokens)
        q = Dense(self.dim, use_bias=False, dtype=self.dtype,
                  name="attn1_to_q")(h)
        k = Dense(self.dim, use_bias=False, dtype=self.dtype,
                  name="attn1_to_k")(h)
        v = Dense(self.dim, use_bias=False, dtype=self.dtype,
                  name="attn1_to_v")(h)
        q = q.reshape(b, f, self.heads, head_dim)
        k = k.reshape(b, f, self.heads, head_dim)
        v = v.reshape(b, f, self.heads, head_dim)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        weights = nn.softmax(logits * (head_dim ** -0.5), axis=-1)
        attn = jnp.einsum(
            "bhqk,bkhd->bqhd", weights.astype(self.dtype), v
        ).reshape(b, f, self.dim)
        attn = Dense(self.dim, dtype=self.dtype, name="attn1_to_out_0")(
            attn
        )
        tokens = tokens + attn
        ff = Dense(4 * self.dim, dtype=self.dtype,
                   name="ff_net_0_proj")(tokens)
        ff = nn.gelu(ff, approximate=False)
        ff = Dense(self.dim, dtype=self.dtype, name="ff_net_2")(ff)
        return tokens + ff


class I2VGenXLUNet(nn.Module):
    """sample [B*F, H, W, 4] + timesteps [B] + fps [B] +
    image_latents [B*F, H, W, 4] (frame 0 real, frames 1.. the pipeline's
    position-ramp maps) + image_embeddings [B, cross] +
    encoder_hidden_states [B, S, cross] -> [B*F, H, W, 4]."""

    config: I2VGenConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, sample, timesteps, fps, image_latents,
                 image_embeddings, encoder_hidden_states, num_frames: int):
        cfg = self.config
        c0 = cfg.block_out_channels[0]
        bf = sample.shape[0]
        b = bf // num_frames

        if jnp.ndim(timesteps) == 0:
            timesteps = jnp.broadcast_to(timesteps, (b,))
        if jnp.ndim(fps) == 0:
            fps = jnp.broadcast_to(fps, (b,))
        temb_dim = c0 * 4
        temb = TimestepEmbedding(
            temb_dim, dtype=self.dtype, name="time_embedding"
        )(timestep_embedding(timesteps, c0, dtype=self.dtype))
        temb = temb + TimestepEmbedding(
            temb_dim, dtype=self.dtype, name="fps_embedding"
        )(timestep_embedding(fps, c0, dtype=self.dtype))
        temb = jnp.repeat(temb, num_frames, axis=0)  # [B*F, temb]

        # context tokens: [text | first-frame latent grid | image embed]
        first = image_latents.reshape(
            b, num_frames, *image_latents.shape[1:]
        )[:, 0]
        y = Conv(
            8 * cfg.in_channels, (3, 3), padding=((1, 1), (1, 1)),
            dtype=self.dtype, name="image_latents_context_embedding_0",
        )(jnp.asarray(first, self.dtype))
        y = adaptive_avg_pool(nn.silu(y), 32)
        y = Conv(
            16 * cfg.in_channels, (3, 3), strides=(2, 2),
            padding=((1, 1), (1, 1)), dtype=self.dtype,
            name="image_latents_context_embedding_3",
        )(y)
        y = Conv(
            cfg.cross_attention_dim, (3, 3), strides=(2, 2),
            padding=((1, 1), (1, 1)), dtype=self.dtype,
            name="image_latents_context_embedding_5",
        )(nn.silu(y))
        latent_tokens = y.reshape(b, -1, cfg.cross_attention_dim)

        img = Dense(temb_dim, dtype=self.dtype,
                    name="context_embedding_0")(
            jnp.asarray(image_embeddings, self.dtype)
        )
        img = Dense(
            cfg.in_channels * cfg.cross_attention_dim, dtype=self.dtype,
            name="context_embedding_2",
        )(nn.silu(img))
        img_tokens = img.reshape(b, cfg.in_channels, cfg.cross_attention_dim)

        ctx = jnp.concatenate(
            [
                jnp.asarray(encoder_hidden_states, self.dtype),
                latent_tokens,
                img_tokens,
            ],
            axis=1,
        )
        ctx = jnp.repeat(ctx, num_frames, axis=0)  # [B*F, S+HW/16+C, D]

        # per-frame image-latents stream -> channel concat with the noise
        il = Conv(
            4 * cfg.in_channels, (1, 1), dtype=self.dtype,
            name="image_latents_proj_in_0",
        )(jnp.asarray(image_latents, self.dtype))
        il = Conv(
            4 * cfg.in_channels, (3, 3), padding=((1, 1), (1, 1)),
            dtype=self.dtype, name="image_latents_proj_in_2",
        )(nn.silu(il))
        il = Conv(
            cfg.in_channels, (3, 3), padding=((1, 1), (1, 1)),
            dtype=self.dtype, name="image_latents_proj_in_4",
        )(nn.silu(il))
        h, w = il.shape[1], il.shape[2]
        tokens = il.reshape(b, num_frames, h * w, cfg.in_channels)
        tokens = tokens.transpose(0, 2, 1, 3).reshape(
            b * h * w, num_frames, cfg.in_channels
        )
        tokens = _TemporalEncoder(
            cfg.in_channels, dtype=self.dtype,
            name="image_latents_temporal_encoder",
        )(tokens)
        il = tokens.reshape(b, h * w, num_frames, cfg.in_channels)
        il = il.transpose(0, 2, 1, 3).reshape(bf, h, w, cfg.in_channels)

        x = jnp.concatenate(
            [jnp.asarray(sample, self.dtype), il], axis=-1
        )
        return unet3d_backbone(
            cfg.trunk(), self.dtype, x, temb, ctx, num_frames
        )
