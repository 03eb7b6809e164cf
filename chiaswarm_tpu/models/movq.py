"""MoVQ image codec: Kandinsky 2.x's pixel stage (diffusers `VQModel` with
`norm_type="spatial"`), replacing the AutoencoderKL stand-in the round-2
Kandinsky pipeline decoded through.

Reference behavior replaced: KandinskyV22Pipeline's `movq.decode(latents,
force_not_quantize=True)` and Img2Img's `movq.encode(image).latents`
(swarm/diffusion/pipeline_steps.py:7-38 loads them per job). Serving never
runs the vector quantizer: diffusion latents are continuous, so decode maps
latents -> post_quant_conv -> spatially-normalized decoder where every norm
is conditioned on the latents themselves (SpatialNorm: group-norm modulated
by 1x1 convs of the nearest-resized latent map).

Module names line up with the merged diffusers state-dict names
(models/conversion.py movq_rename flattens block interiors); the codebook
(`quantize.embedding`) is intentionally not part of the module — it is dead
weight for the continuous-latent serving path.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from .layers import Conv, Dense, FusedGroupNorm


@dataclasses.dataclass(frozen=True)
class MoVQConfig:
    in_channels: int = 3
    out_channels: int = 3
    latent_channels: int = 4
    vq_embed_dim: int = 4
    block_out_channels: tuple[int, ...] = (128, 256, 256, 512)
    layers_per_block: int = 2
    norm_num_groups: int = 32
    # K2.2's movq has no latent scaling (scaling_factor 1.0)
    scaling_factor: float = 1.0


TINY_MOVQ = MoVQConfig(
    block_out_channels=(16, 32), layers_per_block=1, norm_num_groups=8
)


def movq_config_from_json(cj: dict | None) -> MoVQConfig:
    """Geometry from a diffusers VQModel config.json (Kandinsky 3's movq
    differs from 2.2's only in fields this reads)."""
    cj = cj or {}
    base = MoVQConfig()
    return MoVQConfig(
        in_channels=int(cj.get("in_channels", base.in_channels)),
        out_channels=int(cj.get("out_channels", base.out_channels)),
        latent_channels=int(cj.get("latent_channels", base.latent_channels)),
        vq_embed_dim=int(cj.get("vq_embed_dim", base.vq_embed_dim)),
        block_out_channels=tuple(
            int(c) for c in cj.get("block_out_channels",
                                   base.block_out_channels)
        ),
        layers_per_block=int(
            cj.get("layers_per_block", base.layers_per_block)
        ),
        norm_num_groups=int(
            cj.get("norm_num_groups", base.norm_num_groups)
        ),
        scaling_factor=float(cj.get("scaling_factor", base.scaling_factor)),
    )


class SpatialNorm(nn.Module):
    """GroupNorm whose scale/shift are 1x1 convs of the (nearest-resized)
    latent map — the 'Mo' in MoVQ (modulated quantized vectors)."""

    channels: int
    groups: int = 32
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, f, zq):
        b, h, w, _ = f.shape
        zq = jax.image.resize(
            zq.astype(self.dtype), (b, h, w, zq.shape[-1]), "nearest"
        )
        norm = FusedGroupNorm(self.groups, epsilon=1e-6, dtype=self.dtype,
                              name="norm_layer")(f)
        y = Conv(self.channels, (1, 1), dtype=self.dtype, name="conv_y")(zq)
        bb = Conv(self.channels, (1, 1), dtype=self.dtype, name="conv_b")(zq)
        return norm * y + bb


class VQResnet(nn.Module):
    """VQ resnet (eps 1e-6, no temb); `spatial=True` swaps both norms for
    SpatialNorm conditioned on the latent map (decoder side)."""

    out_channels: int
    groups: int = 32
    spatial: bool = False
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, zq=None):
        def norm(name, h):
            if self.spatial:
                return SpatialNorm(h.shape[-1], groups=self.groups,
                                   dtype=self.dtype, name=name)(h, zq)
            return FusedGroupNorm(self.groups, epsilon=1e-6, dtype=self.dtype,
                                  name=name)(h)

        h = nn.silu(norm("norm1", x))
        h = Conv(self.out_channels, (3, 3), padding=((1, 1), (1, 1)),
                 dtype=self.dtype, name="conv1")(h)
        h = nn.silu(norm("norm2", h))
        h = Conv(self.out_channels, (3, 3), padding=((1, 1), (1, 1)),
                 dtype=self.dtype, name="conv2")(h)
        if x.shape[-1] != self.out_channels:
            x = Conv(self.out_channels, (1, 1), dtype=self.dtype,
                     name="conv_shortcut")(x)
        return x + h


class VQAttention(nn.Module):
    """Single-head VQ-GAN mid attention; spatial norm on the decoder side."""

    channels: int
    groups: int = 32
    spatial: bool = False
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, zq=None):
        b, h, w, c = x.shape
        if self.spatial:
            norm = SpatialNorm(c, groups=self.groups, dtype=self.dtype,
                               name="spatial_norm")(x, zq)
        else:
            norm = FusedGroupNorm(self.groups, epsilon=1e-6, dtype=self.dtype,
                                  name="group_norm")(x)
        tokens = norm.reshape(b, h * w, c)
        q = Dense(c, dtype=self.dtype, name="to_q")(tokens)
        k = Dense(c, dtype=self.dtype, name="to_k")(tokens)
        v = Dense(c, dtype=self.dtype, name="to_v")(tokens)
        from ..ops import dot_product_attention

        out = dot_product_attention(
            q[:, :, None, :], k[:, :, None, :], v[:, :, None, :]
        )[:, :, 0, :]
        out = Dense(c, dtype=self.dtype, name="to_out_0")(out)
        return x + out.reshape(b, h, w, c)


class MoVQEncoder(nn.Module):
    config: MoVQConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, pixels):
        cfg = self.config
        g = cfg.norm_num_groups
        x = Conv(cfg.block_out_channels[0], (3, 3),
                 padding=((1, 1), (1, 1)), dtype=self.dtype,
                 name="conv_in")(pixels)
        for b, out_ch in enumerate(cfg.block_out_channels):
            for i in range(cfg.layers_per_block):
                x = VQResnet(out_ch, groups=g, dtype=self.dtype,
                             name=f"down_blocks_{b}_resnets_{i}")(x)
            if b != len(cfg.block_out_channels) - 1:
                # Downsample2D(use_conv=True): asymmetric (0,1) pad, stride 2
                x = jnp.pad(x, ((0, 0), (0, 1), (0, 1), (0, 0)))
                x = Conv(
                    out_ch, (3, 3), strides=(2, 2), padding="VALID",
                    dtype=self.dtype,
                    name=f"down_blocks_{b}_downsamplers_0_conv",
                )(x)
        ch = cfg.block_out_channels[-1]
        x = VQResnet(ch, groups=g, dtype=self.dtype,
                     name="mid_block_resnets_0")(x)
        x = VQAttention(ch, groups=g, dtype=self.dtype,
                        name="mid_block_attentions_0")(x)
        x = VQResnet(ch, groups=g, dtype=self.dtype,
                     name="mid_block_resnets_1")(x)
        x = FusedGroupNorm(g, epsilon=1e-6, dtype=self.dtype, act="silu",
                           name="conv_norm_out")(x)
        return Conv(cfg.latent_channels, (3, 3), padding=((1, 1), (1, 1)),
                    dtype=self.dtype, name="conv_out")(x)


class MoVQDecoder(nn.Module):
    config: MoVQConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, zq):
        """x: post_quant_conv latents; zq: PRE-post_quant_conv latents (the
        spatial-norm conditioning, diffusers VQModel.decode)."""
        cfg = self.config
        g = cfg.norm_num_groups
        rev = tuple(reversed(cfg.block_out_channels))
        ch = rev[0]
        x = Conv(ch, (3, 3), padding=((1, 1), (1, 1)), dtype=self.dtype,
                 name="conv_in")(x)
        x = VQResnet(ch, groups=g, spatial=True, dtype=self.dtype,
                     name="mid_block_resnets_0")(x, zq)
        x = VQAttention(ch, groups=g, spatial=True, dtype=self.dtype,
                        name="mid_block_attentions_0")(x, zq)
        x = VQResnet(ch, groups=g, spatial=True, dtype=self.dtype,
                     name="mid_block_resnets_1")(x, zq)
        for b, out_ch in enumerate(rev):
            for i in range(cfg.layers_per_block + 1):
                x = VQResnet(out_ch, groups=g, spatial=True, dtype=self.dtype,
                             name=f"up_blocks_{b}_resnets_{i}")(x, zq)
            if b != len(rev) - 1:
                x = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
                x = Conv(
                    out_ch, (3, 3), padding=((1, 1), (1, 1)),
                    dtype=self.dtype,
                    name=f"up_blocks_{b}_upsamplers_0_conv",
                )(x)
        x = SpatialNorm(rev[-1], groups=g, dtype=self.dtype,
                        name="conv_norm_out")(x, zq)
        x = nn.silu(x)
        return Conv(cfg.out_channels, (3, 3), padding=((1, 1), (1, 1)),
                    dtype=self.dtype, name="conv_out")(x)


class MoVQ(nn.Module):
    """Encoder + decoder + the two 1x1 quant convs; `encode`/`decode` are
    the serving entry points (`__call__` exists so `init` touches every
    param once)."""

    config: MoVQConfig
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        self.encoder = MoVQEncoder(self.config, dtype=self.dtype)
        self.decoder = MoVQDecoder(self.config, dtype=self.dtype)
        self.quant_conv = Conv(self.config.vq_embed_dim, (1, 1),
                               dtype=self.dtype)
        self.post_quant_conv = Conv(self.config.latent_channels, (1, 1),
                                    dtype=self.dtype)

    def __call__(self, pixels):
        return self.decode(self.encode(pixels))

    def encode(self, pixels):
        """[B, H, W, 3] in [-1, 1] -> continuous latents (VQ encoders are
        deterministic — no sampling, and serving skips quantization)."""
        return self.quant_conv(self.encoder(pixels))

    def decode(self, latents):
        return self.decoder(self.post_quant_conv(latents), latents)
