"""AudioLDM2 UNet + projection model — the dual-conditioned mel
diffusion graph behind `AudioLDM2Pipeline`.

Reference behavior replaced: the reference resolves any diffusers
pipeline class by name for txt2audio jobs
(swarm/job_arguments.py get_type + swarm/audio/audioldm.py:12-21), so a
`parameters.pipeline_type = "AudioLDM2Pipeline"` job runs AudioLDM2.

The UNet is the standard 2D block plan (resnet + transformer per layer,
mid with a resnet sandwich) with ONE structural twist: every attention
slot is a PAIR of sequential single-block transformers, the first
cross-attending the GPT-2 generated sequence (language-model width), the
second the T5 states (its own width), both with key-padding masks. The
projection model is four learned SOS/EOS vectors plus one Linear per
text tower, assembling the joint GPT-2 input sequence.

Module names line up with the diffusers state-dict names so conversion
(models/conversion.py convert_audioldm2_unet) is a mechanical rename.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax.numpy as jnp

from .layers import (
    Conv,
    DeclaredParams,
    Dense,
    FeedForward,
    FusedGroupNorm,
    LayerNorm,
    ResnetBlock2D,
    TimestepEmbedding,
    timestep_embedding,
)


@dataclasses.dataclass(frozen=True)
class AudioLDM2UNetConfig:
    in_channels: int = 8
    out_channels: int = 8
    block_out_channels: tuple[int, ...] = (128, 256, 384, 640)
    layers_per_block: int = 2
    attention: tuple[bool, ...] = (True, True, True, True)
    # diffusers quirk: UNet2DConditionModel reads `attention_head_dim`
    # as the HEAD COUNT (num_attention_heads = ... or attention_head_dim)
    attention_head_dim: int = 8
    # one entry per per-layer attention slot: (generated/GPT-2 width,
    # text/T5 width)
    cross_attention_dims: tuple[int, ...] = (768, 1024)
    norm_num_groups: int = 32


TINY_AUDIOLDM2_UNET = AudioLDM2UNetConfig(
    block_out_channels=(32, 64),
    layers_per_block=1,
    attention=(True, True),
    attention_head_dim=8,
    # widths match TINY_GPT2.hidden_size and a narrowed TINY_T5
    cross_attention_dims=(32, 16),
    norm_num_groups=8,
)


class MaskedTransformer2D(nn.Module):
    """Single-block Transformer2DModel with key-padding-masked cross
    attention (diffusers audioldm2 semantics; keys norm/proj_in/
    transformer_blocks.0.{norm1,attn1,norm2,attn2,norm3,ff}/proj_out)."""

    num_heads: int
    head_dim: int
    groups: int = 32
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, context, context_mask=None):
        b, h, w, c = x.shape
        residual = x
        hidden = FusedGroupNorm(
            self.groups, epsilon=1e-6, dtype=self.dtype, name="norm"
        )(x)
        hidden = hidden.reshape(b, h * w, c)
        hidden = Dense(c, dtype=self.dtype, name="proj_in")(hidden)

        def attention(q_in, kv_in, mask, name):
            inner = self.num_heads * self.head_dim
            q = Dense(inner, use_bias=False, dtype=self.dtype,
                      name=f"{name}_to_q")(q_in)
            k = Dense(inner, use_bias=False, dtype=self.dtype,
                      name=f"{name}_to_k")(kv_in)
            v = Dense(inner, use_bias=False, dtype=self.dtype,
                      name=f"{name}_to_v")(kv_in)
            n, s = q.shape[1], k.shape[1]
            q = q.reshape(b, n, self.num_heads, self.head_dim)
            k = k.reshape(b, s, self.num_heads, self.head_dim)
            v = v.reshape(b, s, self.num_heads, self.head_dim)
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
            logits = logits * (self.head_dim ** -0.5)
            if mask is not None:
                logits = jnp.where(
                    mask[:, None, None, :].astype(bool), logits, -1e9
                )
            weights = nn.softmax(logits, axis=-1).astype(self.dtype)
            out = jnp.einsum("bhqk,bkhd->bqhd", weights, v).reshape(
                b, n, inner
            )
            return Dense(c, dtype=self.dtype, name=f"{name}_to_out_0")(
                out
            )

        blk = "transformer_blocks_0"
        normed = LayerNorm(epsilon=1e-5, dtype=self.dtype,
                           name=f"{blk}_norm1")(hidden)
        hidden = hidden + attention(normed, normed, None, f"{blk}_attn1")
        hidden = hidden + attention(
            LayerNorm(epsilon=1e-5, dtype=self.dtype,
                      name=f"{blk}_norm2")(hidden),
            jnp.asarray(context, self.dtype), context_mask,
            f"{blk}_attn2",
        )
        h2 = LayerNorm(epsilon=1e-5, dtype=self.dtype,
                       name=f"{blk}_norm3")(hidden)
        hidden = hidden + FeedForward(
            c, dtype=self.dtype, name=f"{blk}_ff"
        )(h2)
        hidden = Dense(c, dtype=self.dtype, name="proj_out")(hidden)
        return hidden.reshape(b, h, w, c) + residual


class AudioLDM2UNet(nn.Module):
    """[B, T, F, C] mel latents + [B] timesteps + the two context
    sequences (+ masks) -> [B, T, F, C] noise prediction."""

    config: AudioLDM2UNetConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, sample, timesteps, ctx0, mask0, ctx1, mask1):
        cfg = self.config
        g = cfg.norm_num_groups
        heads = cfg.attention_head_dim  # head COUNT (diffusers quirk)
        dim_of = lambda ch: max(1, ch // heads)
        ctxs = ((ctx0, mask0), (ctx1, mask1))

        temb = TimestepEmbedding(
            cfg.block_out_channels[0] * 4, dtype=self.dtype,
            name="time_embedding",
        )(timestep_embedding(timesteps, cfg.block_out_channels[0],
                             dtype=self.dtype))

        x = Conv(
            cfg.block_out_channels[0], (3, 3), padding=((1, 1), (1, 1)),
            dtype=self.dtype, name="conv_in",
        )(jnp.asarray(sample, self.dtype))

        n = len(cfg.block_out_channels)
        n_ctx = len(ctxs)
        skips = [x]
        for bidx, out_ch in enumerate(cfg.block_out_channels):
            for i in range(cfg.layers_per_block):
                x = ResnetBlock2D(
                    out_ch, dtype=self.dtype,
                    name=f"down_{bidx}_resnets_{i}",
                )(x, temb)
                if cfg.attention[bidx]:
                    # attention slot indices: attentions_{i*n_ctx + idx}
                    for idx, (ctx, mask) in enumerate(ctxs):
                        x = MaskedTransformer2D(
                            heads, dim_of(out_ch),
                            groups=g, dtype=self.dtype,
                            name=f"down_{bidx}_attentions_{i * n_ctx + idx}",
                        )(x, ctx, mask)
                skips.append(x)
            if bidx != n - 1:
                x = Conv(
                    out_ch, (3, 3), strides=(2, 2),
                    padding=((1, 1), (1, 1)), dtype=self.dtype,
                    name=f"down_{bidx}_downsample",
                )(x)
                skips.append(x)

        mid_ch = cfg.block_out_channels[-1]
        x = ResnetBlock2D(mid_ch, dtype=self.dtype, name="mid_resnets_0")(
            x, temb
        )
        for idx, (ctx, mask) in enumerate(ctxs):
            x = MaskedTransformer2D(
                heads, dim_of(mid_ch), groups=g,
                dtype=self.dtype, name=f"mid_attentions_{idx}",
            )(x, ctx, mask)
        x = ResnetBlock2D(mid_ch, dtype=self.dtype, name="mid_resnets_1")(
            x, temb
        )

        for bidx, out_ch in enumerate(reversed(cfg.block_out_channels)):
            rev = n - 1 - bidx
            for i in range(cfg.layers_per_block + 1):
                x = jnp.concatenate([x, skips.pop()], axis=-1)
                x = ResnetBlock2D(
                    out_ch, dtype=self.dtype, name=f"up_{bidx}_resnets_{i}"
                )(x, temb)
                if cfg.attention[rev]:
                    for idx, (ctx, mask) in enumerate(ctxs):
                        x = MaskedTransformer2D(
                            heads, dim_of(out_ch),
                            groups=g, dtype=self.dtype,
                            name=f"up_{bidx}_attentions_{i * n_ctx + idx}",
                        )(x, ctx, mask)
            if bidx != n - 1:
                x = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
                x = Conv(
                    out_ch, (3, 3), padding=((1, 1), (1, 1)),
                    dtype=self.dtype, name=f"up_{bidx}_upsample",
                )(x)

        x = FusedGroupNorm(g, epsilon=1e-5, dtype=self.dtype, act="silu",
                           name="conv_norm_out")(x)
        return Conv(
            cfg.out_channels, (3, 3), padding=((1, 1), (1, 1)),
            dtype=self.dtype, name="conv_out",
        )(x)


class AudioLDM2Projection(DeclaredParams, nn.Module):
    """diffusers AudioLDM2ProjectionModel: per-tower Linear into the
    language-model width plus learned SOS/EOS vectors; output is the
    joint [sos|clap|eos|sos_1|t5|eos_1] GPT-2 input sequence + mask."""

    language_model_dim: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h0, m0, h1, m1):
        lm = self.language_model_dim
        b = h0.shape[0]
        h0 = Dense(lm, dtype=self.dtype, name="projection")(
            jnp.asarray(h0, self.dtype)
        )
        h1 = Dense(lm, dtype=self.dtype, name="projection_1")(
            jnp.asarray(h1, self.dtype)
        )

        def specials(name0, name1):
            sos = self.param(name0, nn.initializers.ones, (lm,))
            eos = self.param(name1, nn.initializers.ones, (lm,))
            return (
                jnp.broadcast_to(jnp.asarray(sos, self.dtype), (b, 1, lm)),
                jnp.broadcast_to(jnp.asarray(eos, self.dtype), (b, 1, lm)),
            )

        sos0, eos0 = specials("sos_embed", "eos_embed")
        sos1, eos1 = specials("sos_embed_1", "eos_embed_1")
        ones = jnp.ones((b, 1), m0.dtype)
        seq = jnp.concatenate([sos0, h0, eos0, sos1, h1, eos1], axis=1)
        mask = jnp.concatenate([ones, m0, ones, ones, m1, ones], axis=-1)
        return seq, mask
