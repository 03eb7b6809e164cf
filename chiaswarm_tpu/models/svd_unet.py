"""Stable Video Diffusion UNet (UNetSpatioTemporalConditionModel) — the
TRUE architecture, NHWC flax.

The reference serves img2vid with this model via
`StableVideoDiffusionPipeline.from_pretrained`
(/root/reference/swarm/video/img2vid.py:16-31). Structure per the diffusers
graph so checkpoints convert mechanically:

- every resnet is a SpatioTemporalResBlock: a spatial ResnetBlock2D
  followed by a temporal ResnetBlock (3D convs over (frame,1,1) windows),
  blended by a learned AlphaBlender mix factor;
- every attention stage is a TransformerSpatioTemporalModel: a spatial
  BasicTransformerBlock (cross-attending the 1-token CLIP image embed)
  paired with a TemporalBasicTransformerBlock that attends across frames
  per spatial position (with its own GEGLU `ff_in` and a sinusoidal
  frame-position embedding), blended by another AlphaBlender;
- micro-conditioning: (fps, motion_bucket_id, noise_aug_strength) each get
  a 256-d fourier embedding -> `add_embedding` MLP summed into the time
  embedding (SDXL-style).

The video batch is laid out [B*F, H, W, C] with a STATIC num_frames so the
whole denoise scan jits once per (frames, size) bucket; frame-axis
reshapes are free layout changes under XLA.

Conversion: conversion.py::convert_svd_unet / infer_svd_unet_config;
parity vs an exact-key torch mirror in tests/test_svd_conversion.py.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax.numpy as jnp

from .layers import (
    BasicTransformerBlock,
    Conv,
    DeclaredParams,
    Dense,
    Downsample2D,
    FusedGroupNorm,
    LayerNorm,
    TimestepEmbedding,
    Upsample2D,
    timestep_embedding,
)


@dataclasses.dataclass(frozen=True)
class SVDUNetConfig:
    in_channels: int = 8  # 4 noise + 4 conditioning-frame latents
    out_channels: int = 4
    block_out_channels: tuple[int, ...] = (320, 640, 1280, 1280)
    layers_per_block: int = 2
    # per level: spatio-temporal transformer stages present
    attention: tuple[bool, ...] = (True, True, True, False)
    num_attention_heads: tuple[int, ...] = (5, 10, 20, 20)
    cross_attention_dim: int = 1024
    transformer_layers_per_block: int = 1
    addition_time_embed_dim: int = 256
    projection_class_embeddings_input_dim: int = 768  # 3 ids x 256


TINY_SVD_UNET = SVDUNetConfig(
    in_channels=8,
    out_channels=4,
    block_out_channels=(32, 64),  # GroupNorm(32) floors the tiny width
    layers_per_block=1,
    attention=(True, False),
    num_attention_heads=(4, 4),
    cross_attention_dim=24,
    addition_time_embed_dim=8,
    projection_class_embeddings_input_dim=24,
)


class AlphaBlender(DeclaredParams, nn.Module):
    """Learned spatial/temporal mix: alpha = sigmoid(mix_factor); frames
    flagged image-only take the spatial branch outright."""

    switch_spatial_to_temporal_mix: bool = False
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x_spatial, x_temporal, image_only_indicator=None):
        mix = self.param("mix_factor", nn.initializers.constant(0.5), (1,))
        alpha = nn.sigmoid(mix.astype(jnp.float32))[0]
        if image_only_indicator is not None:
            # [B, F] bool -> broadcast over the trailing feature axes
            flags = image_only_indicator.astype(bool)
            while flags.ndim < x_spatial.ndim:
                flags = flags[..., None]
            alpha = jnp.where(flags, 1.0, alpha)
        alpha = jnp.asarray(alpha, x_spatial.dtype)
        if self.switch_spatial_to_temporal_mix:
            alpha = 1.0 - alpha
        return alpha * x_spatial + (1.0 - alpha) * x_temporal


class TemporalResnetBlock(nn.Module):
    """ResNet over the frame axis: 3D convs with (3,1,1) kernels on
    [B, F, H, W, C]."""

    out_channels: int
    eps: float = 1e-6
    has_temb: bool = True
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, temb=None):
        residual = x
        h = FusedGroupNorm(32, epsilon=self.eps, dtype=self.dtype,
                           act="silu", name="norm1")(x)
        h = Conv(
            self.out_channels,
            (3, 1, 1),
            padding=((1, 1), (0, 0), (0, 0)),
            dtype=self.dtype,
            name="conv1",
        )(h)
        if self.has_temb and temb is not None:
            # temb [B, F, C_t] -> per-frame shift
            proj = Dense(
                self.out_channels, dtype=self.dtype, name="time_emb_proj"
            )(nn.silu(temb))
            h = h + proj[:, :, None, None, :]
        h = FusedGroupNorm(32, epsilon=self.eps, dtype=self.dtype,
                           act="silu", name="norm2")(h)
        h = Conv(
            self.out_channels,
            (3, 1, 1),
            padding=((1, 1), (0, 0), (0, 0)),
            dtype=self.dtype,
            name="conv2",
        )(h)
        if residual.shape[-1] != self.out_channels:
            residual = Conv(
                self.out_channels, (1, 1, 1), dtype=self.dtype,
                name="conv_shortcut",
            )(residual)
        return h + residual


class SpatioTemporalResBlock(nn.Module):
    """Spatial ResnetBlock2D + TemporalResnetBlock + AlphaBlender.

    Submodule names mirror the diffusers keys (spatial_res_block /
    temporal_res_block / time_mixer)."""

    out_channels: int
    eps: float = 1e-5
    temporal_eps: float | None = None
    has_temb: bool = True
    switch_spatial_to_temporal_mix: bool = False
    # "learned_with_images" (UNet) respects image_only_indicator;
    # "learned" (temporal VAE decoder) is a pure sigmoid blend
    merge_strategy: str = "learned_with_images"
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, temb, num_frames: int, image_only_indicator=None):
        from .layers import ResnetBlock2D

        h = ResnetBlock2D(
            self.out_channels, eps=self.eps, dtype=self.dtype,
            name="spatial_res_block",
        )(x, temb)
        bf, hh, ww, c = h.shape
        b = bf // num_frames
        h5 = h.reshape(b, num_frames, hh, ww, c)
        temb5 = (
            temb.reshape(b, num_frames, -1) if temb is not None else None
        )
        ht = TemporalResnetBlock(
            self.out_channels,
            eps=self.temporal_eps if self.temporal_eps is not None else self.eps,
            has_temb=self.has_temb,
            dtype=self.dtype,
            name="temporal_res_block",
        )(h5, temb5)
        mixed = AlphaBlender(
            switch_spatial_to_temporal_mix=self.switch_spatial_to_temporal_mix,
            dtype=self.dtype,
            name="time_mixer",
        )(
            h5,
            ht,
            image_only_indicator
            if self.merge_strategy == "learned_with_images"
            else None,
        )
        return mixed.reshape(bf, hh, ww, c)


class TemporalBasicTransformerBlock(nn.Module):
    """Attention across frames per spatial position, with an input GEGLU
    projection (ff_in) and optional cross-attention to the first frame's
    conditioning tokens."""

    dim: int
    num_heads: int
    head_dim: int
    cross: bool = True
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, hidden, num_frames: int, context=None):
        from .layers import Attention, FeedForward

        bf, s, c = hidden.shape
        b = bf // num_frames
        # [B*F, S, C] -> [B*S, F, C]
        hidden = hidden.reshape(b, num_frames, s, c).transpose(0, 2, 1, 3)
        hidden = hidden.reshape(b * s, num_frames, c)

        residual = hidden
        h = LayerNorm(epsilon=1e-5, dtype=self.dtype, name="norm_in")(hidden)
        h = FeedForward(self.dim, dtype=self.dtype, name="ff_in")(h)
        hidden = h + residual  # is_res: dim == time_mix_inner_dim in SVD

        attn = Attention(
            self.num_heads, self.head_dim, self.dim, dtype=self.dtype,
            name="attn1",
        )
        hidden = hidden + attn(
            LayerNorm(epsilon=1e-5, dtype=self.dtype, name="norm1")(hidden)
        )
        if self.cross:
            cross_attn = Attention(
                self.num_heads, self.head_dim, self.dim, dtype=self.dtype,
                name="attn2",
            )
            hidden = hidden + cross_attn(
                LayerNorm(epsilon=1e-5, dtype=self.dtype, name="norm2")(
                    hidden
                ),
                context,
            )
        hidden = hidden + FeedForward(self.dim, dtype=self.dtype, name="ff")(
            LayerNorm(epsilon=1e-5, dtype=self.dtype, name="norm3")(hidden)
        )
        hidden = hidden.reshape(b, s, num_frames, c).transpose(0, 2, 1, 3)
        return hidden.reshape(bf, s, c)


class TransformerSpatioTemporal(nn.Module):
    """Spatial transformer + frame-axis transformer pair with a learned
    blend; conditioning context is the 1-token CLIP image embed (the
    temporal blocks see the FIRST frame's context per diffusers)."""

    num_heads: int
    head_dim: int
    num_layers: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, context, num_frames: int, image_only_indicator=None):
        bf, hh, ww, c = x.shape
        b = bf // num_frames
        inner = self.num_heads * self.head_dim
        residual = x

        hidden = FusedGroupNorm(32, epsilon=1e-6, dtype=self.dtype,
                                name="norm")(x)
        hidden = hidden.reshape(bf, hh * ww, c)
        hidden = Dense(inner, dtype=self.dtype, name="proj_in")(hidden)

        # frame-position embedding added before each temporal block
        frame_ids = jnp.tile(jnp.arange(num_frames), (b,))
        t_feat = timestep_embedding(frame_ids, c, dtype=self.dtype)
        emb = _time_pos_embed(t_feat, c, self.dtype)[:, None, :]

        # temporal cross-attention context: first frame's tokens, repeated
        # per spatial position -> [B*S, 1, C_ctx]
        ctx_first = context.reshape(b, num_frames, -1, context.shape[-1])[:, 0]
        time_context = jnp.broadcast_to(
            ctx_first[:, None],
            (b, hh * ww, ctx_first.shape[1], ctx_first.shape[2]),
        ).reshape(b * hh * ww, ctx_first.shape[1], ctx_first.shape[2])

        # ONE blender shared by all layers (diffusers has a single
        # time_mixer on the transformer, reused per layer)
        blender = AlphaBlender(dtype=self.dtype, name="time_mixer")

        for i in range(self.num_layers):
            hidden = BasicTransformerBlock(
                inner,
                self.num_heads,
                self.head_dim,
                dtype=self.dtype,
                name=f"transformer_blocks_{i}",
            )(hidden, context)
            mix = hidden + emb.astype(hidden.dtype)
            mix = TemporalBasicTransformerBlock(
                inner,
                self.num_heads,
                self.head_dim,
                dtype=self.dtype,
                name=f"temporal_transformer_blocks_{i}",
            )(mix, num_frames, time_context)
            hidden = _blend_tokens(
                blender, hidden, mix, image_only_indicator, b, num_frames
            )
        hidden = Dense(c, dtype=self.dtype, name="proj_out")(hidden)
        return hidden.reshape(bf, hh, ww, c) + residual


def _blend_tokens(blender, spatial, temporal, image_only_indicator, b, f):
    """AlphaBlender over [B*F, S, C] token tensors (indicator per frame)."""
    if image_only_indicator is not None:
        s, c = spatial.shape[1], spatial.shape[2]
        sp = spatial.reshape(b, f, s, c)
        tp = temporal.reshape(b, f, s, c)
        out = blender(sp, tp, image_only_indicator)
        return out.reshape(b * f, s, c)
    return blender(spatial, temporal, None)


def _time_pos_embed(t_feat, in_channels, dtype):
    """diffusers TimestepEmbedding(in_channels, in_channels*4,
    out_dim=in_channels): asymmetric in/out widths, so inline Denses."""
    h = Dense(in_channels * 4, dtype=dtype, name="time_pos_embed_linear_1")(
        t_feat
    )
    h = nn.silu(h)
    return Dense(in_channels, dtype=dtype, name="time_pos_embed_linear_2")(h)


class UNetSpatioTemporalConditionModel(nn.Module):
    config: SVDUNetConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(
        self,
        sample,  # [B, F, H, W, C_in] (noise latents ++ cond-frame latents)
        timesteps,  # [B] or scalar
        encoder_hidden_states,  # [B, 1, cross] CLIP image embed tokens
        added_time_ids,  # [B, 3] (fps, motion_bucket_id, noise_aug)
        image_only_indicator=None,  # [B, F]; zeros for video generation
    ):
        cfg = self.config
        b, num_frames = sample.shape[0], sample.shape[1]
        if jnp.ndim(timesteps) == 0:
            timesteps = jnp.broadcast_to(timesteps, (b,))
        if image_only_indicator is None:
            image_only_indicator = jnp.zeros((b, num_frames), jnp.float32)

        temb_dim = cfg.block_out_channels[0] * 4
        t_feat = timestep_embedding(
            timesteps, cfg.block_out_channels[0], dtype=self.dtype
        )
        temb = TimestepEmbedding(temb_dim, dtype=self.dtype, name="time_embedding")(
            t_feat
        )
        tid_feat = timestep_embedding(
            added_time_ids.reshape(-1),
            cfg.addition_time_embed_dim,
            dtype=self.dtype,
        ).reshape(b, -1)
        temb = temb + TimestepEmbedding(
            temb_dim, dtype=self.dtype, name="add_embedding"
        )(tid_feat)

        # flatten frames into the batch; conditioning repeats per frame
        x = sample.reshape(
            b * num_frames, sample.shape[2], sample.shape[3], sample.shape[4]
        )
        temb = jnp.repeat(temb, num_frames, axis=0)
        context = jnp.repeat(
            encoder_hidden_states.astype(self.dtype), num_frames, axis=0
        )

        x = Conv(
            cfg.block_out_channels[0], (3, 3), padding=((1, 1), (1, 1)),
            dtype=self.dtype, name="conv_in",
        )(x.astype(self.dtype))

        def res_block(prefix, j, out_ch, h):
            return SpatioTemporalResBlock(
                out_ch, dtype=self.dtype, name=f"{prefix}_resnets_{j}"
            )(h, temb, num_frames, image_only_indicator)

        def attn_block(prefix, j, level, h):
            return TransformerSpatioTemporal(
                cfg.num_attention_heads[level],
                cfg.block_out_channels[level] // cfg.num_attention_heads[level],
                cfg.transformer_layers_per_block,
                dtype=self.dtype,
                name=f"{prefix}_attentions_{j}",
            )(h, context, num_frames, image_only_indicator)

        levels = len(cfg.block_out_channels)
        skips = [x]
        for i, out_ch in enumerate(cfg.block_out_channels):
            prefix = f"down_blocks_{i}"
            for j in range(cfg.layers_per_block):
                x = res_block(prefix, j, out_ch, x)
                if cfg.attention[i]:
                    x = attn_block(prefix, j, i, x)
                skips.append(x)
            if i != levels - 1:
                x = Downsample2D(
                    out_ch, dtype=self.dtype, name=f"{prefix}_downsamplers_0"
                )(x)
                skips.append(x)

        x = res_block("mid_block", 0, cfg.block_out_channels[-1], x)
        x = attn_block("mid_block", 0, levels - 1, x)
        x = res_block("mid_block", 1, cfg.block_out_channels[-1], x)

        for bi, out_ch in enumerate(reversed(cfg.block_out_channels)):
            rev = levels - 1 - bi
            prefix = f"up_blocks_{bi}"
            for j in range(cfg.layers_per_block + 1):
                x = jnp.concatenate([x, skips.pop()], axis=-1)
                x = res_block(prefix, j, out_ch, x)
                if cfg.attention[rev]:
                    x = attn_block(prefix, j, rev, x)
            if bi != levels - 1:
                x = Upsample2D(
                    out_ch, dtype=self.dtype, name=f"{prefix}_upsamplers_0"
                )(x)

        x = FusedGroupNorm(32, epsilon=1e-5, dtype=self.dtype, act="silu",
                           name="conv_norm_out")(x)
        x = Conv(
            cfg.out_channels, (3, 3), padding=((1, 1), (1, 1)), dtype=self.dtype,
            name="conv_out",
        )(x)
        return x.reshape(
            b, num_frames, x.shape[1], x.shape[2], cfg.out_channels
        )
