"""Kandinsky 2.2 decoder UNet: the diffusers `UNet2DConditionModel`
instance kandinsky-community/kandinsky-2-2-decoder ships (reference loads
it per job via KandinskyV22Pipeline, swarm/diffusion/pipeline_steps.py:7-38)
— rebuilt as one flax module in NHWC with attention on the TPU kernel path.

Architecture facts this module encodes (from the checkpoint's unet
config.json): ResnetDownsample/SimpleCrossAttn down blocks, SimpleCrossAttn
mid/up blocks, `scale_shift` AdaGN resnets, resnet-based down/upsamplers,
added-KV attention (image-projection tokens concatenated with the spatial
self-attention KV), image conditioning through BOTH the additive time-embed
branch (ImageTimeEmbedding) and the cross-attention tokens (ImageProjection)
— no text cross-attention at all; the prior's CLIP image embedding is the
only conditioning.

Module names line up with the merged diffusers state-dict names so
conversion (models/conversion.py convert_kandinsky_unet) is mechanical.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax.numpy as jnp

from .layers import (
    Conv,
    DeclaredParams,
    Dense,
    FusedGroupNorm,
    LayerNorm,
    TimestepEmbedding,
    timestep_embedding,
)


@dataclasses.dataclass(frozen=True)
class K22UNetConfig:
    in_channels: int = 4
    out_channels: int = 8  # learned variance: pipeline keeps channels [:4]
    block_out_channels: tuple[int, ...] = (384, 768, 1280, 1280)
    layers_per_block: int = 3
    attention_head_dim: int = 64
    cross_attention_dim: int = 768
    encoder_hid_dim: int = 1280  # CLIP image-embedding width
    # ImageProjection token count; conversion infers the real value from
    # `encoder_hid_proj.image_embeds.weight`'s output width
    image_proj_tokens: int = 32
    # which down blocks carry attention (block 0 is pure resnet)
    down_attention: tuple[bool, ...] = (False, True, True, True)
    norm_num_groups: int = 32
    # "image": K2.2 — a single CLIP image embedding feeds BOTH the additive
    #   time branch (ImageTimeEmbedding) and the ImageProjection tokens.
    # "text": DeepFloyd IF — T5 states feed an attention-pooled
    #   TextTimeEmbedding and a Linear encoder_hid projection.
    # "text_image": K2.1 — MCLIP text states + pooled text embed + prior
    #   image embed feed TextImageTimeEmbedding (additive) and
    #   TextImageProjection (image tokens prepended to projected text).
    conditioning: str = "image"
    # K2.1: width of the prior image embedding entering the text_image
    # projections (encoder_hid_dim is the TEXT hidden width there)
    image_embed_dim: int = 768
    act: str = "silu"  # resnet/out nonlinearity ("gelu" for IF)
    # IF super-resolution stages carry a second timestep conditioning (the
    # aug/noise level) through a class embedding
    class_embed_timestep: bool = False
    addition_embed_heads: int = 64  # TextTimeEmbedding pool heads


TINY_K22_UNET = K22UNetConfig(
    block_out_channels=(32, 64),
    layers_per_block=1,
    attention_head_dim=8,
    cross_attention_dim=16,
    encoder_hid_dim=32,
    image_proj_tokens=2,
    down_attention=(False, True),
    norm_num_groups=8,
)

# DeepFloyd IF-I (pixel-space base stage) real geometry analog; conversion
# re-derives the true numbers from the checkpoint
IF_UNET = K22UNetConfig(
    in_channels=3,
    out_channels=6,  # pixels + learned variance
    block_out_channels=(704, 1408, 2112, 2816),
    layers_per_block=3,
    attention_head_dim=64,
    cross_attention_dim=2048,
    encoder_hid_dim=4096,  # T5-XXL hidden width
    image_proj_tokens=0,  # text mode: no ImageProjection tokens
    down_attention=(False, True, True, True),
    conditioning="text",
    act="gelu",
    addition_embed_heads=64,
)

TINY_IF_UNET = K22UNetConfig(
    in_channels=3,
    out_channels=3,
    block_out_channels=(32, 64),
    layers_per_block=1,
    attention_head_dim=8,
    cross_attention_dim=16,
    encoder_hid_dim=32,
    image_proj_tokens=0,  # text mode: no ImageProjection tokens
    down_attention=(False, True),
    norm_num_groups=8,
    conditioning="text",
    act="gelu",
    addition_embed_heads=4,
)

TINY_IF_SR_UNET = dataclasses.replace(
    TINY_IF_UNET, in_channels=6, class_embed_timestep=True
)


def _act(name: str):
    if name == "gelu":
        # erf gelu, diffusers parity (approximate=True would silently
        # diverge from converted IF checkpoints)
        return lambda x: nn.gelu(x, approximate=False)
    return nn.silu


class KResnetBlock(nn.Module):
    """diffusers ResnetBlock2D with time_embedding_norm='scale_shift' and
    optional resnet-internal down/up sampling (avg-pool / nearest-2x applied
    to both branches BEFORE conv1, matching Downsample2D/Upsample2D with
    use_conv=False)."""

    out_channels: int
    groups: int = 32
    down: bool = False
    up: bool = False
    act: str = "silu"
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, temb):
        act = _act(self.act)
        h = FusedGroupNorm(self.groups, epsilon=1e-5, dtype=self.dtype,
                           name="norm1")(x)
        h = act(h)
        if self.down:
            x = nn.avg_pool(x, (2, 2), strides=(2, 2))
            h = nn.avg_pool(h, (2, 2), strides=(2, 2))
        elif self.up:
            x = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
            h = jnp.repeat(jnp.repeat(h, 2, axis=1), 2, axis=2)
        h = Conv(self.out_channels, (3, 3), padding=((1, 1), (1, 1)),
                 dtype=self.dtype, name="conv1")(h)
        # scale_shift AdaGN: the projection emits [scale | shift]; the temb
        # nonlinearity is the BLOCK's act (diffusers ResnetBlock2D applies
        # self.nonlinearity to temb, so IF uses gelu here too)
        t = Dense(2 * self.out_channels, dtype=self.dtype,
                  name="time_emb_proj")(act(temb))
        scale, shift = jnp.split(t[:, None, None, :], 2, axis=-1)
        h = FusedGroupNorm(self.groups, epsilon=1e-5, dtype=self.dtype,
                           name="norm2")(h)
        h = h * (1.0 + scale) + shift
        h = act(h)
        h = Conv(self.out_channels, (3, 3), padding=((1, 1), (1, 1)),
                 dtype=self.dtype, name="conv2")(h)
        if x.shape[-1] != self.out_channels:
            x = Conv(self.out_channels, (1, 1), dtype=self.dtype,
                     name="conv_shortcut")(x)
        return x + h


class KAttention(nn.Module):
    """diffusers Attention with AttnAddedKVProcessor: token-space group norm,
    self KV concatenated AFTER the added (image-projection) KV, residual
    over the spatial map."""

    heads: int
    head_dim: int
    channels: int
    groups: int = 32
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, context):
        """x [B, H, W, C]; context [B, N, cross_dim] -> [B, H, W, C]."""
        b, h, w, c = x.shape
        tokens = x.reshape(b, h * w, c)
        # torch GroupNorm over [B, C, S]: stats over (group channels, S) —
        # flax GroupNorm on [B, S, C] reduces identically
        norm = FusedGroupNorm(self.groups, epsilon=1e-5, dtype=self.dtype,
                              name="group_norm")(tokens)
        inner = self.heads * self.head_dim
        q = Dense(inner, dtype=self.dtype, name="to_q")(norm)
        k_self = Dense(inner, dtype=self.dtype, name="to_k")(norm)
        v_self = Dense(inner, dtype=self.dtype, name="to_v")(norm)
        k_add = Dense(inner, dtype=self.dtype, name="add_k_proj")(
            context.astype(self.dtype)
        )
        v_add = Dense(inner, dtype=self.dtype, name="add_v_proj")(
            context.astype(self.dtype)
        )
        k = jnp.concatenate([k_add, k_self], axis=1)
        v = jnp.concatenate([v_add, v_self], axis=1)
        shape4 = lambda t: t.reshape(b, t.shape[1], self.heads, self.head_dim)
        from ..ops import dot_product_attention

        out = dot_product_attention(shape4(q), shape4(k), shape4(v))
        out = out.reshape(b, h * w, inner)
        out = Dense(self.channels, dtype=self.dtype, name="to_out_0")(out)
        return x + out.reshape(b, h, w, self.channels)


class KDownBlock(nn.Module):
    """ResnetDownsampleBlock2D / SimpleCrossAttnDownBlock2D: `layers`
    resnets (each followed by attention when `attend`), then a resnet
    downsampler. Skips collected after every resnet(+attn) and after the
    downsampler — identical skip cadence to the SD UNet."""

    config: K22UNetConfig
    out_channels: int
    attend: bool
    add_downsample: bool
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, temb, context):
        cfg = self.config
        skips = []
        for i in range(cfg.layers_per_block):
            x = KResnetBlock(self.out_channels, groups=cfg.norm_num_groups,
                             act=cfg.act, dtype=self.dtype,
                             name=f"resnets_{i}")(x, temb)
            if self.attend:
                x = KAttention(
                    self.out_channels // cfg.attention_head_dim,
                    cfg.attention_head_dim, self.out_channels,
                    groups=cfg.norm_num_groups, dtype=self.dtype,
                    name=f"attentions_{i}",
                )(x, context)
            skips.append(x)
        if self.add_downsample:
            x = KResnetBlock(self.out_channels, groups=cfg.norm_num_groups,
                             down=True, act=cfg.act, dtype=self.dtype,
                             name="downsamplers_0")(x, temb)
            skips.append(x)
        return x, skips


class KUpBlock(nn.Module):
    """SimpleCrossAttnUpBlock2D / ResnetUpsampleBlock2D with the resnet
    upsampler."""

    config: K22UNetConfig
    out_channels: int
    attend: bool
    add_upsample: bool
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, skips, temb, context):
        cfg = self.config
        for i in range(cfg.layers_per_block + 1):
            x = jnp.concatenate([x, skips.pop()], axis=-1)
            x = KResnetBlock(self.out_channels, groups=cfg.norm_num_groups,
                             act=cfg.act, dtype=self.dtype,
                             name=f"resnets_{i}")(x, temb)
            if self.attend:
                x = KAttention(
                    self.out_channels // cfg.attention_head_dim,
                    cfg.attention_head_dim, self.out_channels,
                    groups=cfg.norm_num_groups, dtype=self.dtype,
                    name=f"attentions_{i}",
                )(x, context)
        if self.add_upsample:
            x = KResnetBlock(self.out_channels, groups=cfg.norm_num_groups,
                             up=True, act=cfg.act, dtype=self.dtype,
                             name="upsamplers_0")(x, temb)
        return x


class AttentionPooling(DeclaredParams, nn.Module):
    """diffusers AttentionPooling (IF's TextTimeEmbedding pool): a mean+
    positional class token attends the sequence; its attention output is
    the pooled vector."""

    num_heads: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, s, width = x.shape
        pos = self.param(
            "positional_embedding", nn.initializers.normal(width**-0.5),
            (1, width),
        ).astype(self.dtype)
        cls = jnp.mean(x, axis=1, keepdims=True) + pos[None]
        seq = jnp.concatenate([cls, x], axis=1)
        hd = width // self.num_heads
        shape = lambda t: t.reshape(b, t.shape[1], self.num_heads, hd)
        q = shape(Dense(width, dtype=self.dtype, name="q_proj")(cls))
        k = shape(Dense(width, dtype=self.dtype, name="k_proj")(seq))
        v = shape(Dense(width, dtype=self.dtype, name="v_proj")(seq))
        scale = hd**-0.5
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        w = nn.softmax(logits.astype(jnp.float32), axis=-1).astype(self.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, 1, width)
        return out[:, 0]


class K22UNet(nn.Module):
    config: K22UNetConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, sample, timesteps, cond, class_labels=None):
        """sample [B, H, W, C_in], timesteps [B] -> [B, H, W, C_out].

        `cond` is the image embedding [B, E] (conditioning="image") or the
        T5 states [B, S, E] (conditioning="text"). `class_labels` [B] is
        the IF super-res aug/noise level (class_embed_timestep)."""
        cfg = self.config
        if jnp.ndim(timesteps) == 0:
            timesteps = jnp.broadcast_to(timesteps, (sample.shape[0],))

        temb_dim = cfg.block_out_channels[0] * 4
        t_feat = timestep_embedding(
            timesteps, cfg.block_out_channels[0], dtype=self.dtype
        )
        temb = TimestepEmbedding(temb_dim, dtype=self.dtype,
                                 name="time_embedding")(t_feat)
        if not isinstance(cond, dict):
            cond = cond.astype(self.dtype)
        if cfg.conditioning == "image":
            # addition_embed_type="image" (ImageTimeEmbedding): the image
            # embed joins the timestep embedding additively
            aug = Dense(temb_dim, dtype=self.dtype, name="aug_emb_proj")(cond)
            aug = LayerNorm(dtype=self.dtype, name="aug_emb_norm")(aug)
            temb = temb + aug
            # encoder_hid_dim_type="image_proj" (ImageProjection): the image
            # embed also becomes the cross-attention token sequence
            ctx = Dense(
                cfg.image_proj_tokens * cfg.cross_attention_dim,
                dtype=self.dtype, name="hid_proj",
            )(cond).reshape(-1, cfg.image_proj_tokens, cfg.cross_attention_dim)
            ctx = LayerNorm(dtype=self.dtype, name="hid_proj_norm")(ctx)
        elif cfg.conditioning == "text_image":
            # K2.1: `cond` is a dict {"text_states" [B,S,Dt], "text_embeds"
            # [B,Dt'], "image_embeds" [B,Di]}.
            # addition_embed_type="text_image" (TextImageTimeEmbedding):
            # LN(text_proj(pooled text)) + image_proj(image embed)
            text_states = cond["text_states"].astype(self.dtype)
            text_embeds = cond["text_embeds"].astype(self.dtype)
            image_embeds = cond["image_embeds"].astype(self.dtype)
            aug_text = LayerNorm(dtype=self.dtype, name="aug_emb_text_norm")(
                Dense(temb_dim, dtype=self.dtype,
                      name="aug_emb_text_proj")(text_embeds)
            )
            aug_img = Dense(temb_dim, dtype=self.dtype,
                            name="aug_emb_image_proj")(image_embeds)
            temb = temb + aug_text + aug_img
            # encoder_hid_dim_type="text_image_proj" (TextImageProjection):
            # image tokens prepended to the projected text sequence (no LN)
            img_tokens = Dense(
                cfg.image_proj_tokens * cfg.cross_attention_dim,
                dtype=self.dtype, name="hid_proj_image",
            )(image_embeds).reshape(
                -1, cfg.image_proj_tokens, cfg.cross_attention_dim
            )
            txt_tokens = Dense(cfg.cross_attention_dim, dtype=self.dtype,
                               name="hid_proj_text")(text_states)
            ctx = jnp.concatenate([img_tokens, txt_tokens], axis=1)
        else:
            # IF: addition_embed_type="text" (TextTimeEmbedding = LN ->
            # attention pool -> proj -> LN), encoder_hid_dim_type="text_proj"
            aug = LayerNorm(dtype=self.dtype, name="aug_emb_norm1")(cond)
            aug = AttentionPooling(cfg.addition_embed_heads, dtype=self.dtype,
                                   name="aug_emb_pool")(aug)
            aug = Dense(temb_dim, dtype=self.dtype, name="aug_emb_proj")(aug)
            aug = LayerNorm(dtype=self.dtype, name="aug_emb_norm2")(aug)
            temb = temb + aug
            ctx = Dense(cfg.cross_attention_dim, dtype=self.dtype,
                        name="hid_proj")(cond)
        if cfg.class_embed_timestep:
            # IF-II: the SR noise level rides a second timestep embedding
            if class_labels is None:
                class_labels = jnp.zeros_like(timesteps)
            c_feat = timestep_embedding(
                class_labels, cfg.block_out_channels[0], dtype=self.dtype
            )
            temb = temb + TimestepEmbedding(
                temb_dim, dtype=self.dtype, name="class_embedding"
            )(c_feat)

        x = Conv(cfg.block_out_channels[0], (3, 3),
                 padding=((1, 1), (1, 1)), dtype=self.dtype,
                 name="conv_in")(sample)

        skips = [x]
        for b, out_ch in enumerate(cfg.block_out_channels):
            last = b == len(cfg.block_out_channels) - 1
            x, block_skips = KDownBlock(
                cfg, out_ch, attend=cfg.down_attention[b],
                add_downsample=not last, dtype=self.dtype,
                name=f"down_blocks_{b}",
            )(x, temb, ctx)
            skips.extend(block_skips)

        mid_ch = cfg.block_out_channels[-1]
        x = KResnetBlock(mid_ch, groups=cfg.norm_num_groups, act=cfg.act,
                         dtype=self.dtype, name="mid_block_resnets_0")(x, temb)
        x = KAttention(
            mid_ch // cfg.attention_head_dim, cfg.attention_head_dim, mid_ch,
            groups=cfg.norm_num_groups, dtype=self.dtype,
            name="mid_block_attentions_0",
        )(x, ctx)
        x = KResnetBlock(mid_ch, groups=cfg.norm_num_groups, act=cfg.act,
                         dtype=self.dtype, name="mid_block_resnets_1")(x, temb)

        for b, out_ch in enumerate(reversed(cfg.block_out_channels)):
            rev = len(cfg.block_out_channels) - 1 - b
            last = b == len(cfg.block_out_channels) - 1
            x = KUpBlock(
                cfg, out_ch, attend=cfg.down_attention[rev],
                add_upsample=not last, dtype=self.dtype,
                name=f"up_blocks_{b}",
            )(x, skips, temb, ctx)

        x = FusedGroupNorm(cfg.norm_num_groups, epsilon=1e-5,
                           dtype=self.dtype, act="silu",
                           name="conv_norm_out")(x)
        return Conv(cfg.out_channels, (3, 3), padding=((1, 1), (1, 1)),
                    dtype=self.dtype, name="conv_out")(x)
