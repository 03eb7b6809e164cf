"""Conditional UNet2D covering the SD family (SD1.x/2.x, SDXL, inpaint
variants) as configs of one flax module.

Replaces the reference's per-job diffusers UNet loads
(swarm/diffusion/diffusion_func.py:103). Architecture matches the HF
`UNet2DConditionModel` graph so weights convert mechanically, but execution
is NHWC with attention routed through the TPU kernel path. SDXL's extra
conditioning (pooled text embeds + time ids) is the `addition_embed`
branch.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax.numpy as jnp

from .layers import (
    BasicTransformerBlock,
    Conv,
    Dense,
    Downsample2D,
    FusedGroupNorm,
    ResnetBlock2D,
    TimestepEmbedding,
    Transformer2DModel,
    Upsample2D,
    timestep_embedding,
)


@dataclasses.dataclass(frozen=True)
class UNet2DConfig:
    in_channels: int = 4
    out_channels: int = 4
    block_out_channels: tuple[int, ...] = (320, 640, 1280, 1280)
    # per down block: number of transformer layers; 0 = plain ResNet block
    transformer_layers: tuple[int, ...] = (1, 1, 1, 0)
    mid_transformer_layers: int = 1
    layers_per_block: int = 2
    # per-block head COUNT. NB: HF SD configs store this in a field misnamed
    # `attention_head_dim` — for the SD family diffusers reads it as the
    # number of heads (SD1.5: 8 heads of dim 40; SD2.1/XL: (5,10,20) heads
    # of dim 64). Keep the semantics, fix the name.
    num_attention_heads: int | tuple[int, ...] = 8
    # SDXL additional conditioning: projection dim of pooled text embeds
    addition_embed_dim: int = 0  # 0 = disabled
    addition_time_embed_dim: int = 256
    # AudioLDM-style FiLM conditioning: a `simple_projection` class
    # embedding (Linear from e.g. the 512-d CLAP joint space into temb),
    # concatenated to — not summed with — the time embedding when
    # `class_embeddings_concat` (diffusers UNet2DConditionModel semantics;
    # the resnet time projections then see 2x temb width)
    class_embed_dim: int = 0  # 0 = disabled
    class_embeddings_concat: bool = False
    # 0 = the transformer blocks self-attend (encoder_hidden_states=None,
    # AudioLDM's layout) instead of cross-attending to a text sequence
    cross_attention_dim: int = 768
    flip_sin_to_cos: bool = True
    freq_shift: float = 0.0

    def heads_per_block(self) -> tuple[int, ...]:
        if isinstance(self.num_attention_heads, int):
            return (self.num_attention_heads,) * len(self.block_out_channels)
        return tuple(self.num_attention_heads)


class CrossAttnDownBlock(nn.Module):
    config: UNet2DConfig
    out_channels: int
    n_transformer: int
    num_heads: int
    add_downsample: bool
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, temb, context):
        skips = []
        for i in range(self.config.layers_per_block):
            x = ResnetBlock2D(self.out_channels, dtype=self.dtype, name=f"resnets_{i}")(
                x, temb
            )
            if self.n_transformer > 0:
                x = Transformer2DModel(
                    self.num_heads,
                    self.out_channels // self.num_heads,
                    self.n_transformer,
                    dtype=self.dtype,
                    name=f"attentions_{i}",
                )(x, context)
            skips.append(x)
        if self.add_downsample:
            x = Downsample2D(self.out_channels, dtype=self.dtype, name="downsamplers_0")(x)
            skips.append(x)
        return x, skips


class CrossAttnUpBlock(nn.Module):
    config: UNet2DConfig
    out_channels: int
    n_transformer: int
    num_heads: int
    add_upsample: bool
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, skips, temb, context):
        for i in range(self.config.layers_per_block + 1):
            x = jnp.concatenate([x, skips.pop()], axis=-1)
            x = ResnetBlock2D(self.out_channels, dtype=self.dtype, name=f"resnets_{i}")(
                x, temb
            )
            if self.n_transformer > 0:
                x = Transformer2DModel(
                    self.num_heads,
                    self.out_channels // self.num_heads,
                    self.n_transformer,
                    dtype=self.dtype,
                    name=f"attentions_{i}",
                )(x, context)
        if self.add_upsample:
            x = Upsample2D(self.out_channels, dtype=self.dtype, name="upsamplers_0")(x)
        return x


class UNetMidBlock(nn.Module):
    config: UNet2DConfig
    channels: int
    n_transformer: int
    num_heads: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, temb, context):
        x = ResnetBlock2D(self.channels, dtype=self.dtype, name="resnets_0")(x, temb)
        x = Transformer2DModel(
            self.num_heads,
            self.channels // self.num_heads,
            self.n_transformer,
            dtype=self.dtype,
            name="attentions_0",
        )(x, context)
        return ResnetBlock2D(self.channels, dtype=self.dtype, name="resnets_1")(x, temb)


class UNet2DConditionModel(nn.Module):
    config: UNet2DConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(
        self,
        sample,  # [B, H, W, C_in] latents
        timesteps,  # [B] or scalar
        encoder_hidden_states,  # [B, S, cross_attention_dim]
        added_cond: dict | None = None,  # SDXL: {"text_embeds": [B,D], "time_ids": [B,6]}
        down_residuals: tuple | None = None,  # ControlNet per-skip residuals
        mid_residual=None,  # ControlNet mid-block residual
        class_labels=None,  # AudioLDM: [B, class_embed_dim] CLAP embedding
    ):
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
        temb = TimestepEmbedding(temb_dim, dtype=self.dtype, name="time_embedding")(t_feat)

        if cfg.addition_embed_dim:
            # SDXL micro-conditioning (size/crop time ids + pooled text embeds)
            add = added_cond or {}
            time_ids = add["time_ids"]
            text_embeds = add["text_embeds"]
            tid_feat = timestep_embedding(
                time_ids.reshape(-1),
                cfg.addition_time_embed_dim,
                flip_sin_to_cos=cfg.flip_sin_to_cos,
                downscale_freq_shift=cfg.freq_shift,
                dtype=self.dtype,
            ).reshape(sample.shape[0], -1)
            add_feat = jnp.concatenate([text_embeds, tid_feat], axis=-1)
            temb = temb + TimestepEmbedding(
                temb_dim, dtype=self.dtype, name="add_embedding"
            )(add_feat)

        if cfg.class_embed_dim:
            class_emb = Dense(
                temb_dim, dtype=self.dtype, name="class_embedding"
            )(class_labels.astype(self.dtype))
            if cfg.class_embeddings_concat:
                temb = jnp.concatenate([temb, class_emb], axis=-1)
            else:
                temb = temb + class_emb

        x = Conv(
            cfg.block_out_channels[0], (3, 3), padding=((1, 1), (1, 1)),
            dtype=self.dtype, name="conv_in",
        )(sample)

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

        if down_residuals is not None:
            skips = [s + r for s, r in zip(skips, down_residuals)]

        x = UNetMidBlock(
            cfg,
            cfg.block_out_channels[-1],
            cfg.mid_transformer_layers,
            heads[-1],
            dtype=self.dtype,
            name="mid_block",
        )(x, temb, encoder_hidden_states)

        if mid_residual is not None:
            x = x + mid_residual

        for b, out_ch in enumerate(reversed(cfg.block_out_channels)):
            rev = len(cfg.block_out_channels) - 1 - b
            last = b == len(cfg.block_out_channels) - 1
            x = CrossAttnUpBlock(
                cfg,
                out_ch,
                cfg.transformer_layers[rev],
                heads[rev],
                add_upsample=not last,
                dtype=self.dtype,
                name=f"up_blocks_{b}",
            )(x, skips, temb, encoder_hidden_states)

        x = FusedGroupNorm(32, epsilon=1e-5, dtype=self.dtype, act="silu",
                           name="conv_norm_out")(x)
        return Conv(
            cfg.out_channels, (3, 3), padding=((1, 1), (1, 1)), dtype=self.dtype,
            name="conv_out",
        )(x)
