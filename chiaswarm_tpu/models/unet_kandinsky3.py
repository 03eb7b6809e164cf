"""Kandinsky 3 UNet: the diffusers `Kandinsky3UNet` graph rebuilt as one
flax module in NHWC.

Reference behavior replaced: swarm/test.py:130-147 schedules
kandinsky-community/kandinsky-3 through AutoPipeline; diffusers serves it
with Kandinsky3UNet — a distinct block family from every other UNet in the
inventory: every norm is a *conditional* group norm (affine-free GroupNorm
whose scale/shift come from a zero-init MLP of the time embedding), res
blocks are 4-sub-block bottlenecks (1-3-3-1 kernels at `max(in,out)//2`
hidden width) with up/down-sampling threaded through specific sub-block
positions, and attention blocks are token-space (flattened h*w) with
conv1x1 feed-forwards. Text conditioning is FLAN-UL2 T5 states projected
by a bias-free Linear, entering both through cross-attention at the three
lower resolutions and through an attention pooling added to the time
embedding.

Module names line up with the flattened diffusers state-dict names so
conversion (models/conversion.py convert_kandinsky3_unet) is a mechanical
rename; the two ConvTranspose kernels per up-path resnet are the only
layout special-cases (IOHW, unlike conv's OIHW).
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
class K3UNetConfig:
    in_channels: int = 4
    time_embedding_dim: int = 1536
    groups: int = 32
    attention_head_dim: int = 64
    layers_per_block: int = 3
    block_out_channels: tuple[int, ...] = (384, 768, 1536, 3072)
    cross_attention_dim: int = 4096
    encoder_hid_dim: int = 4096
    add_cross_attention: tuple[bool, ...] = (False, True, True, True)
    add_self_attention: tuple[bool, ...] = (False, True, True, True)
    expansion_ratio: int = 4
    compression_ratio: int = 2

    @property
    def init_channels(self) -> int:
        return self.block_out_channels[0] // 2


# layers_per_block >= 2: the up-block channel plan
# [(in+cat, in)] + [(in, in)]*(n-2) + [(in, out)] degenerates below that
TINY_K3_UNET = K3UNetConfig(
    time_embedding_dim=32,
    groups=4,
    attention_head_dim=8,
    layers_per_block=2,
    block_out_channels=(16, 32),
    cross_attention_dim=32,
    encoder_hid_dim=32,
    add_cross_attention=(False, True),
    add_self_attention=(False, True),
)


class ConditionalGroupNorm(nn.Module):
    """Affine-free GroupNorm modulated by a zero-init MLP of the time
    embedding: x_norm * (scale(temb) + 1) + shift(temb)."""

    groups: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, temb):
        c = x.shape[-1]
        ctx = Dense(2 * c, dtype=self.dtype, name="context_mlp_1")(
            nn.silu(temb)
        )
        scale, shift = jnp.split(ctx[:, None, None, :], 2, axis=-1)
        x = nn.GroupNorm(
            self.groups, epsilon=1e-5, use_bias=False, use_scale=False,
            dtype=self.dtype,
        )(x)
        return x * (scale + 1.0) + shift


class ConvTranspose2x2(DeclaredParams, nn.Module):
    """torch ConvTranspose2d(kernel=2, stride=2): stride equals kernel so
    every input pixel maps to a disjoint 2x2 output block — an einsum, not
    a real transposed convolution. Kernel layout (2, 2, in, out)."""

    features: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, h, w, c = x.shape
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (2, 2, c, self.features),
        )
        bias = self.param("bias", nn.initializers.zeros, (self.features,))
        y = jnp.einsum(
            "bhwi,klio->bhkwlo", x, jnp.asarray(kernel, self.dtype)
        )
        y = y.reshape(b, 2 * h, 2 * w, self.features)
        return y + jnp.asarray(bias, self.dtype)


class K3Attention(nn.Module):
    """Bias-free attention (to_q/to_k/to_v/to_out_0), softmax in fp32.
    `inner` is both the query width and the output width; K/V project from
    whatever width the context carries."""

    inner: int
    head_dim: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, q_in, kv_in, mask=None):
        heads = max(1, self.inner // self.head_dim)
        dim = self.inner // heads
        b, n, _ = q_in.shape
        s = kv_in.shape[1]
        q = Dense(self.inner, use_bias=False, dtype=self.dtype,
                  name="to_q")(q_in)
        k = Dense(self.inner, use_bias=False, dtype=self.dtype,
                  name="to_k")(kv_in)
        v = Dense(self.inner, use_bias=False, dtype=self.dtype,
                  name="to_v")(kv_in)
        q = q.reshape(b, n, heads, dim)
        k = k.reshape(b, s, heads, dim)
        v = v.reshape(b, s, heads, dim)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        logits = logits * (dim ** -0.5)
        if mask is not None:
            big_neg = jnp.asarray(-1e9, jnp.float32)
            logits = jnp.where(
                mask[:, None, None, :].astype(bool), logits, big_neg
            )
        weights = nn.softmax(logits, axis=-1).astype(self.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", weights, v).reshape(
            b, n, self.inner
        )
        return Dense(
            self.inner, use_bias=False, dtype=self.dtype, name="to_out_0"
        )(out)


class K3EncoderProj(nn.Module):
    """diffusers Kandinsky3EncoderProj: bias-free Linear + LayerNorm over
    the T5 states before they condition anything."""

    cross_attention_dim: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = Dense(
            self.cross_attention_dim, use_bias=False, dtype=self.dtype,
            name="projection_linear",
        )(x)
        return LayerNorm(
            epsilon=1e-5, dtype=self.dtype, name="projection_norm"
        )(x)


class K3AttentionPooling(nn.Module):
    """Mean-of-context query attends over the context; the pooled vector
    adds onto the time embedding (diffusers Kandinsky3AttentionPooling)."""

    num_channels: int
    head_dim: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, temb, context, mask=None):
        pooled = K3Attention(
            self.num_channels, self.head_dim, dtype=self.dtype,
            name="attention",
        )(jnp.mean(context, axis=1, keepdims=True), context, mask)
        return temb + pooled[:, 0, :]


class K3Block(nn.Module):
    """norm -> silu -> (up) -> conv -> (down): one bottleneck sub-block.
    `up_resolution` None keeps resolution, True transposed-up-2x BEFORE
    the conv, False strided-down-2x AFTER it."""

    out_channels: int
    kernel_size: int = 3
    up_resolution: bool | None = None
    groups: int = 32
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, temb):
        x = ConditionalGroupNorm(
            self.groups, dtype=self.dtype, name="group_norm"
        )(x, temb)
        x = nn.silu(x)
        if self.up_resolution is True:
            x = ConvTranspose2x2(
                x.shape[-1], dtype=self.dtype, name="up_sample"
            )(x)
        pad = "SAME" if self.kernel_size > 1 else "VALID"
        x = Conv(
            self.out_channels,
            (self.kernel_size, self.kernel_size),
            padding=pad,
            dtype=self.dtype,
            name="projection",
        )(x)
        if self.up_resolution is False:
            x = Conv(
                self.out_channels, (2, 2), strides=(2, 2), padding="VALID",
                dtype=self.dtype, name="down_sample",
            )(x)
        return x


class K3ResNetBlock(nn.Module):
    """Four-sub-block bottleneck (kernels 1-3-3-1 at max(in,out)//ratio
    width) with a shortcut that mirrors any resolution change."""

    out_channels: int
    compression_ratio: int = 2
    up_resolutions: tuple[bool | None, ...] = (None, None, None, None)
    groups: int = 32
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, temb):
        in_channels = x.shape[-1]
        kernel_sizes = (1, 3, 3, 1)
        hidden = max(in_channels, self.out_channels) // self.compression_ratio
        widths = [hidden, hidden, hidden, self.out_channels]
        out = x
        for idx, (w, ks, up) in enumerate(
            zip(widths, kernel_sizes, self.up_resolutions)
        ):
            out = K3Block(
                w, kernel_size=ks, up_resolution=up, groups=self.groups,
                dtype=self.dtype, name=f"resnet_blocks_{idx}",
            )(out, temb)
        if True in self.up_resolutions:
            x = ConvTranspose2x2(
                in_channels, dtype=self.dtype, name="shortcut_up_sample"
            )(x)
        if in_channels != self.out_channels:
            x = Conv(
                self.out_channels, (1, 1), dtype=self.dtype,
                name="shortcut_projection",
            )(x)
        if False in self.up_resolutions:
            x = Conv(
                self.out_channels, (2, 2), strides=(2, 2), padding="VALID",
                dtype=self.dtype, name="shortcut_down_sample",
            )(x)
        return x + out


class K3AttentionBlock(nn.Module):
    """Token-space attention over the flattened feature map (self when no
    context, cross otherwise) + conv1x1 feed-forward, both residual and
    both entered through conditional group norms."""

    head_dim: int
    expansion_ratio: int = 4
    groups: int = 32
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, temb, context=None, context_mask=None):
        b, h, w, c = x.shape
        out = ConditionalGroupNorm(
            self.groups, dtype=self.dtype, name="in_norm"
        )(x, temb)
        tokens = out.reshape(b, h * w, c)
        kv = context if context is not None else tokens
        mask = context_mask if context is not None else None
        attn = K3Attention(
            c, self.head_dim, dtype=self.dtype, name="attention"
        )(tokens, kv, mask)
        x = x + attn.reshape(b, h, w, c)
        out = ConditionalGroupNorm(
            self.groups, dtype=self.dtype, name="out_norm"
        )(x, temb)
        ff = Conv(
            self.expansion_ratio * c, (1, 1), use_bias=False,
            dtype=self.dtype, name="feed_forward_0",
        )(out)
        ff = Conv(
            c, (1, 1), use_bias=False, dtype=self.dtype,
            name="feed_forward_2",
        )(nn.silu(ff))
        return x + ff


class K3DownBlock(nn.Module):
    """[self-attn] then layers_per_block x (resnet_in -> [cross-attn] ->
    resnet_out); the last resnet_out's third sub-block strided-downsamples
    when this level downsamples."""

    config: K3UNetConfig
    out_channels: int
    cross: bool
    self_attention: bool
    down_sample: bool
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, temb, context=None, context_mask=None):
        cfg = self.config
        if self.self_attention:
            x = K3AttentionBlock(
                cfg.attention_head_dim, cfg.expansion_ratio, cfg.groups,
                dtype=self.dtype, name="attentions_0",
            )(x, temb)
        nb = cfg.layers_per_block
        for j in range(nb):
            x = K3ResNetBlock(
                self.out_channels, cfg.compression_ratio,
                groups=cfg.groups, dtype=self.dtype,
                name=f"resnets_in_{j}",
            )(x, temb)
            if self.cross:
                x = K3AttentionBlock(
                    cfg.attention_head_dim, cfg.expansion_ratio, cfg.groups,
                    dtype=self.dtype, name=f"attentions_{j + 1}",
                )(x, temb, context, context_mask)
            last = j == nb - 1
            up_res = (
                (None, None, False, None)
                if (last and self.down_sample)
                else (None, None, None, None)
            )
            x = K3ResNetBlock(
                self.out_channels, cfg.compression_ratio,
                up_resolutions=up_res, groups=cfg.groups, dtype=self.dtype,
                name=f"resnets_out_{j}",
            )(x, temb)
        return x


class K3UpBlock(nn.Module):
    """layers_per_block x (resnet_in -> [cross-attn] -> resnet_out) then
    [self-attn]; the first resnet_in's second sub-block transposed-
    upsamples when this level upsamples. Channel plan
    [(in+cat, in)] + [(in, in)]*(n-2) + [(in, out)], where resnet_in keeps
    the pair's input width and resnet_out moves to the pair's output."""

    config: K3UNetConfig
    in_channels: int  # the level's base width; the skip concat adds cat_dim
    out_channels: int
    cross: bool
    self_attention: bool
    up_sample: bool
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, temb, context=None, context_mask=None):
        cfg = self.config
        nb = cfg.layers_per_block
        base = self.in_channels
        pairs = (
            [(x.shape[-1], base)]
            + [(base, base)] * (nb - 2)
            + [(base, self.out_channels)]
        )
        for j, (ic, oc) in enumerate(pairs):
            up_res = (
                (None, True, None, None)
                if (j == 0 and self.up_sample)
                else (None, None, None, None)
            )
            x = K3ResNetBlock(
                ic, cfg.compression_ratio, up_resolutions=up_res,
                groups=cfg.groups, dtype=self.dtype, name=f"resnets_in_{j}",
            )(x, temb)
            if self.cross:
                x = K3AttentionBlock(
                    cfg.attention_head_dim, cfg.expansion_ratio, cfg.groups,
                    dtype=self.dtype, name=f"attentions_{j + 1}",
                )(x, temb, context, context_mask)
            x = K3ResNetBlock(
                oc, cfg.compression_ratio, groups=cfg.groups,
                dtype=self.dtype, name=f"resnets_out_{j}",
            )(x, temb)
        if self.self_attention:
            x = K3AttentionBlock(
                cfg.attention_head_dim, cfg.expansion_ratio, cfg.groups,
                dtype=self.dtype, name="attentions_0",
            )(x, temb)
        return x


class Kandinsky3UNet(nn.Module):
    """[B,H,W,4] latents + [B] timesteps + [B,S,encoder_hid_dim] T5 states
    (+ [B,S] 0/1 mask) -> [B,H,W,4] noise prediction."""

    config: K3UNetConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, sample, timesteps, encoder_hidden_states,
                 encoder_attention_mask=None):
        cfg = self.config
        n = len(cfg.block_out_channels)
        init_ch = cfg.init_channels

        temb_in = timestep_embedding(
            timesteps, init_ch, flip_sin_to_cos=False,
            downscale_freq_shift=1.0, dtype=self.dtype,
        )
        temb = TimestepEmbedding(
            cfg.time_embedding_dim, dtype=self.dtype, name="time_embedding"
        )(temb_in)

        context = K3EncoderProj(
            cfg.cross_attention_dim, dtype=self.dtype,
            name="encoder_hid_proj",
        )(jnp.asarray(encoder_hidden_states, self.dtype))
        temb = K3AttentionPooling(
            cfg.time_embedding_dim, cfg.attention_head_dim,
            dtype=self.dtype, name="add_time_condition",
        )(temb, context, encoder_attention_mask)

        x = Conv(
            init_ch, (3, 3), dtype=self.dtype, name="conv_in"
        )(jnp.asarray(sample, self.dtype))

        hidden_dims = (init_ch,) + tuple(cfg.block_out_channels)
        skips = []
        for i in range(n):
            x = K3DownBlock(
                cfg,
                cfg.block_out_channels[i],
                cross=cfg.add_cross_attention[i],
                self_attention=cfg.add_self_attention[i],
                down_sample=i != n - 1,
                dtype=self.dtype,
                name=f"down_blocks_{i}",
            )(x, temb, context, encoder_attention_mask)
            if i != n - 1:
                skips.append(x)

        for lvl in range(n):
            i = n - 1 - lvl  # source level this up block mirrors
            if lvl != 0:
                x = jnp.concatenate([x, skips.pop()], axis=-1)
            x = K3UpBlock(
                cfg,
                in_channels=cfg.block_out_channels[i],
                out_channels=hidden_dims[i],
                cross=cfg.add_cross_attention[i],
                self_attention=cfg.add_self_attention[i],
                up_sample=lvl != 0,
                dtype=self.dtype,
                name=f"up_blocks_{lvl}",
            )(x, temb, context, encoder_attention_mask)

        x = FusedGroupNorm(
            cfg.groups, epsilon=1e-5, dtype=self.dtype, act="silu",
            name="conv_norm_out",
        )(x)
        return Conv(
            cfg.in_channels, (3, 3), dtype=self.dtype, name="conv_out"
        )(x)
