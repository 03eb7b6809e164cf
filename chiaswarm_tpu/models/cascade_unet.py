"""Stable Cascade (Wuerstchen v3) UNet — the TRUE architecture, NHWC flax.

One module class covers both released parameterisations, exactly as the
diffusers `StableCascadeUNet` does for the checkpoints the reference serves
via `StableCascadeDecoderPipeline` (/root/reference/swarm/diffusion/
pipeline_steps.py:70-90):

- stage C ("prior"): patch_size 1, two 2048-wide levels that never change
  spatial resolution (`switch_level=[False]` makes the down/upscalers plain
  1x1 convs), every layer = ResBlock + TimestepBlock + AttnBlock, text
  conditioning from pooled+sequence CLIP-bigG plus an (optional) image
  embed.
- stage B ("decoder"): patch_size 2, four levels (320/640/1280/1280) with
  strided-conv downscalers and transposed-conv upscalers, attention only in
  the two deep levels, conditioned on the stage-C latent through
  `effnet_mapper` and on pooled text only.

Blocks are ConvNeXt-style (depthwise conv -> LayerNorm -> wide GELU MLP
with a GlobalResponseNorm), NOT the SD ResNet/Transformer stack — which is
why this family gets its own module instead of UNet2DConditionModel.

Weight conversion + geometry inference live in models/conversion.py
(`convert_cascade_unet` / `infer_cascade_unet_config`); numeric parity vs
an exact-key torch mirror is tested in tests/test_cascade_conversion.py.
"""

from __future__ import annotations

import dataclasses
import math

import flax.linen as nn
import jax.numpy as jnp

from .layers import Conv, DeclaredParams, Dense, LayerNorm


@dataclasses.dataclass(frozen=True)
class CascadeUNetConfig:
    in_channels: int = 16
    out_channels: int = 16
    patch_size: int = 1
    timestep_ratio_embedding_dim: int = 64
    conditioning_dim: int = 2048
    block_out_channels: tuple[int, ...] = (2048, 2048)
    num_attention_heads: tuple[int, ...] = (32, 32)
    down_num_layers_per_block: tuple[int, ...] = (8, 24)
    up_num_layers_per_block: tuple[int, ...] = (24, 8)
    down_blocks_repeat_mappers: tuple[int, ...] = (1, 1)
    up_blocks_repeat_mappers: tuple[int, ...] = (1, 1)
    # per level: does each layer carry an AttnBlock (block types are always
    # ResBlock + TimestepBlock [+ AttnBlock] in the released configs)
    attention: tuple[bool, ...] = (True, True)
    clip_text_pooled_in_channels: int = 1280
    clip_text_in_channels: int = 0  # 0 = absent (stage B)
    clip_image_in_channels: int = 0  # 0 = absent (stage B)
    clip_seq: int = 4
    effnet_in_channels: int = 0  # stage B: 16 (the stage-C latent space)
    pixel_mapper_in_channels: int = 0  # stage B: 3 (semantic pixels, zeros)
    kernel_size: int = 3
    self_attn: bool = True
    timestep_conditioning_type: tuple[str, ...] = ("sca", "crp")
    # None -> strided-conv scalers (stage B); a tuple -> 1x1-conv scalers
    # with optional bilinear re-scale per boundary (stage C: (False,))
    switch_level: tuple[bool, ...] | None = None

    @property
    def t_embed_total(self) -> int:
        return self.timestep_ratio_embedding_dim * (
            1 + len(self.timestep_conditioning_type)
        )


# tiny hermetic-test parameterisations of both stages
TINY_CASCADE_C = CascadeUNetConfig(
    in_channels=16,
    out_channels=16,
    patch_size=1,
    timestep_ratio_embedding_dim=8,
    conditioning_dim=32,
    block_out_channels=(32, 32),
    num_attention_heads=(4, 4),
    down_num_layers_per_block=(1, 2),
    up_num_layers_per_block=(2, 1),
    down_blocks_repeat_mappers=(1, 2),
    up_blocks_repeat_mappers=(2, 1),
    attention=(True, True),
    clip_text_pooled_in_channels=16,
    clip_text_in_channels=16,
    clip_image_in_channels=12,
    clip_seq=2,
    timestep_conditioning_type=("sca", "crp"),
    switch_level=(False,),
)
TINY_CASCADE_B = CascadeUNetConfig(
    in_channels=4,
    out_channels=4,
    patch_size=2,
    timestep_ratio_embedding_dim=8,
    conditioning_dim=16,
    block_out_channels=(16, 32),
    num_attention_heads=(0, 4),
    down_num_layers_per_block=(1, 2),
    up_num_layers_per_block=(2, 1),
    down_blocks_repeat_mappers=(1, 1),
    up_blocks_repeat_mappers=(2, 1),
    attention=(False, True),
    clip_text_pooled_in_channels=16,
    effnet_in_channels=16,
    pixel_mapper_in_channels=3,
    clip_seq=2,
    timestep_conditioning_type=("sca",),
    switch_level=None,
)


def timestep_ratio_embedding(r, dim: int, max_positions: float = 10000.0):
    """Sinusoidal embedding of a [0,1] timestep RATIO (r * 1e4 positions)."""
    r = jnp.asarray(r, jnp.float32) * max_positions
    half = dim // 2
    emb = math.log(max_positions) / (half - 1)
    emb = jnp.exp(jnp.arange(half, dtype=jnp.float32) * -emb)
    emb = r[:, None] * emb[None, :]
    emb = jnp.concatenate([jnp.sin(emb), jnp.cos(emb)], axis=1)
    if dim % 2 == 1:
        emb = jnp.pad(emb, ((0, 0), (0, 1)))
    return emb


def _ln(x, dtype):
    """The family's LayerNorm: last-axis, no affine, eps 1e-6."""
    return LayerNorm(
        epsilon=1e-6, use_scale=False, use_bias=False, dtype=dtype
    )(x)


def pixel_unshuffle(x, p: int):
    """NHWC space-to-depth with torch PixelUnshuffle channel order."""
    if p == 1:
        return x
    b, h, w, c = x.shape
    x = x.reshape(b, h // p, p, w // p, p, c)
    x = x.transpose(0, 1, 3, 5, 2, 4)  # [b, h/p, w/p, c, dy, dx]
    return x.reshape(b, h // p, w // p, c * p * p)


def pixel_shuffle(x, p: int):
    if p == 1:
        return x
    b, h, w, cpp = x.shape
    c = cpp // (p * p)
    x = x.reshape(b, h, w, c, p, p)
    x = x.transpose(0, 1, 4, 2, 5, 3)  # [b, h, dy, w, dx, c]
    return x.reshape(b, h * p, w * p, c)


def interpolate_bilinear_align_corners(x, out_h: int, out_w: int):
    """Bilinear resize with torch align_corners=True semantics (used for
    the effnet/pixels maps and the switch-level skip rescale; jax.image
    only offers half-pixel sampling)."""
    b, h, w, c = x.shape
    if h == out_h and w == out_w:
        return x

    def axis_weights(n_in, n_out):
        if n_out == 1 or n_in == 1:
            pos = jnp.zeros((n_out,), jnp.float32)
        else:
            pos = jnp.linspace(0.0, n_in - 1.0, n_out)
        lo = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, n_in - 1)
        hi = jnp.clip(lo + 1, 0, n_in - 1)
        frac = pos - lo.astype(jnp.float32)
        return lo, hi, frac

    ylo, yhi, yf = axis_weights(h, out_h)
    xlo, xhi, xf = axis_weights(w, out_w)
    top = x[:, ylo][:, :, xlo] * (1 - xf)[None, None, :, None] + x[:, ylo][
        :, :, xhi
    ] * xf[None, None, :, None]
    bot = x[:, yhi][:, :, xlo] * (1 - xf)[None, None, :, None] + x[:, yhi][
        :, :, xhi
    ] * xf[None, None, :, None]
    return top * (1 - yf)[None, :, None, None] + bot * yf[None, :, None, None]


class GlobalResponseNorm(DeclaredParams, nn.Module):
    """ConvNeXt-v2 GRN over NHWC (spatial L2 per channel, mean-normalised)."""

    dim: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        gamma = self.param("gamma", nn.initializers.zeros, (1, 1, 1, self.dim))
        beta = self.param("beta", nn.initializers.zeros, (1, 1, 1, self.dim))
        agg = jnp.sqrt(
            jnp.sum(jnp.square(x.astype(jnp.float32)), axis=(1, 2), keepdims=True)
        )
        stand = agg / (jnp.mean(agg, axis=-1, keepdims=True) + 1e-6)
        stand = stand.astype(x.dtype)
        return gamma.astype(x.dtype) * (x * stand) + beta.astype(x.dtype) + x


class CascadeResBlock(nn.Module):
    """depthwise conv -> LN -> [skip concat] -> Dense(4c) GELU GRN Dense."""

    channels: int
    kernel_size: int = 3
    c_skip: int = 0
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, x_skip=None):
        res = x
        k = self.kernel_size
        h = Conv(
            self.channels,
            (k, k),
            padding=((k // 2, k // 2), (k // 2, k // 2)),
            feature_group_count=self.channels,
            dtype=self.dtype,
            name="depthwise",
        )(x)
        h = _ln(h, self.dtype)
        if x_skip is not None:
            h = jnp.concatenate([h, x_skip.astype(h.dtype)], axis=-1)
        h = Dense(self.channels * 4, dtype=self.dtype, name="channelwise_0")(h)
        h = nn.gelu(h, approximate=False)
        h = GlobalResponseNorm(
            self.channels * 4, dtype=self.dtype, name="channelwise_2"
        )(h)
        h = Dense(self.channels, dtype=self.dtype, name="channelwise_4")(h)
        return h + res


class CascadeTimestepBlock(nn.Module):
    """AdaLN-style scale/shift from the (chunked) timestep-ratio embedding."""

    channels: int
    conds: tuple[str, ...]
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, t_embed):
        chunks = jnp.split(t_embed, 1 + len(self.conds), axis=1)
        ab = Dense(self.channels * 2, dtype=self.dtype, name="mapper")(chunks[0])
        a, b = jnp.split(ab, 2, axis=1)
        for i, cname in enumerate(self.conds):
            abc = Dense(
                self.channels * 2, dtype=self.dtype, name=f"mapper_{cname}"
            )(chunks[i + 1])
            ac, bc = jnp.split(abc, 2, axis=1)
            a, b = a + ac, b + bc
        return x * (1 + a[:, None, None, :]) + b[:, None, None, :]


class CascadeAttnBlock(nn.Module):
    """LN -> attention where K/V = [image tokens (if self_attn)] + mapped
    conditioning tokens; biased q/k/v projections (diffusers Attention
    with bias=True)."""

    channels: int
    num_heads: int
    self_attn: bool = True
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, kv):
        from ..ops import dot_product_attention

        b, h, w, c = x.shape
        kvm = Dense(c, dtype=self.dtype, name="kv_mapper_1")(nn.silu(kv))
        nx = _ln(x, self.dtype).reshape(b, h * w, c)
        kv_full = jnp.concatenate([nx, kvm], axis=1) if self.self_attn else kvm

        head_dim = c // self.num_heads
        q = Dense(c, dtype=self.dtype, name="attention_to_q")(nx)
        k = Dense(c, dtype=self.dtype, name="attention_to_k")(kv_full)
        v = Dense(c, dtype=self.dtype, name="attention_to_v")(kv_full)
        sk = kv_full.shape[1]
        out = dot_product_attention(
            q.reshape(b, h * w, self.num_heads, head_dim),
            k.reshape(b, sk, self.num_heads, head_dim),
            v.reshape(b, sk, self.num_heads, head_dim),
        ).reshape(b, h * w, c)
        out = Dense(c, dtype=self.dtype, name="attention_to_out_0")(out)
        return x + out.reshape(b, h, w, c)


class ConvTransposed2D(DeclaredParams, nn.Module):
    """torch ConvTranspose2d equivalent (kernel k, stride s, padding p) via
    an input-dilated forward convolution. The kernel param is stored
    ALREADY flipped/transposed to [kh, kw, in, out] forward-conv layout
    (conversion.py does the flip), so apply is a plain conv."""

    features: int
    kernel_size: int
    stride: int
    padding: int = 0
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        import jax

        k, s, p = self.kernel_size, self.stride, self.padding
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (k, k, x.shape[-1], self.features),
        )
        bias = self.param("bias", nn.initializers.zeros, (self.features,))
        pad = k - 1 - p
        out = jax.lax.conv_general_dilated(
            x.astype(self.dtype),
            kernel.astype(self.dtype),
            window_strides=(1, 1),
            padding=((pad, pad), (pad, pad)),
            lhs_dilation=(s, s),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        return out + bias.astype(out.dtype)


class StableCascadeUNet(nn.Module):
    config: CascadeUNetConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(
        self,
        sample,  # [B, H, W, in_channels]
        timestep_ratio,  # [B] in [0, 1]
        clip_text_pooled,  # [B, S_p, pooled_in] (S_p usually 1)
        clip_text=None,  # [B, S, text_in] (stage C)
        clip_img=None,  # [B, S_i, img_in] (stage C)
        effnet=None,  # [B, ch, cw, effnet_in] stage-C latent (stage B)
        pixels=None,  # [B, 8, 8, 3] semantic pixels (stage B, zeros)
    ):
        cfg = self.config
        b = sample.shape[0]
        levels = len(cfg.block_out_channels)

        # --- timestep-ratio embedding (main + one chunk per conditioning) ---
        t_embed = timestep_ratio_embedding(
            timestep_ratio, cfg.timestep_ratio_embedding_dim
        )
        zero_cond = timestep_ratio_embedding(
            jnp.zeros_like(jnp.asarray(timestep_ratio, jnp.float32)),
            cfg.timestep_ratio_embedding_dim,
        )
        for _ in cfg.timestep_conditioning_type:
            t_embed = jnp.concatenate([t_embed, zero_cond], axis=1)
        t_embed = t_embed.astype(self.dtype)

        # --- CLIP conditioning tokens: [text, image, pooled] order ---
        ctp = Dense(
            cfg.conditioning_dim * cfg.clip_seq,
            dtype=self.dtype,
            name="clip_txt_pooled_mapper",
        )(clip_text_pooled.astype(self.dtype))
        ctp = ctp.reshape(b, -1, cfg.conditioning_dim)
        if cfg.clip_text_in_channels and clip_text is not None:
            pieces = [
                Dense(
                    cfg.conditioning_dim, dtype=self.dtype, name="clip_txt_mapper"
                )(clip_text.astype(self.dtype))
            ]
            if cfg.clip_image_in_channels:
                if clip_img is None:
                    clip_img = jnp.zeros(
                        (b, 1, cfg.clip_image_in_channels), self.dtype
                    )
                ci = Dense(
                    cfg.conditioning_dim * cfg.clip_seq,
                    dtype=self.dtype,
                    name="clip_img_mapper",
                )(clip_img.astype(self.dtype))
                pieces.append(ci.reshape(b, -1, cfg.conditioning_dim))
            clip = jnp.concatenate(pieces + [ctp], axis=1)
        else:
            clip = ctp
        clip = _ln(clip, self.dtype)

        # --- input embedding: pixel-unshuffle + 1x1 conv + LN ---
        x = pixel_unshuffle(sample.astype(self.dtype), cfg.patch_size)
        x = Conv(
            cfg.block_out_channels[0], (1, 1), dtype=self.dtype, name="embedding_1"
        )(x)
        x = _ln(x, self.dtype)

        if cfg.effnet_in_channels and effnet is not None:
            e = Conv(
                cfg.block_out_channels[0] * 4,
                (1, 1),
                dtype=self.dtype,
                name="effnet_mapper_0",
            )(
                interpolate_bilinear_align_corners(
                    effnet.astype(self.dtype), x.shape[1], x.shape[2]
                )
            )
            e = nn.gelu(e, approximate=False)
            e = Conv(
                cfg.block_out_channels[0],
                (1, 1),
                dtype=self.dtype,
                name="effnet_mapper_2",
            )(e)
            x = x + _ln(e, self.dtype)
        if cfg.pixel_mapper_in_channels:
            if pixels is None:
                pixels = jnp.zeros((b, 8, 8, cfg.pixel_mapper_in_channels))
            p = Conv(
                cfg.block_out_channels[0] * 4,
                (1, 1),
                dtype=self.dtype,
                name="pixels_mapper_0",
            )(pixels.astype(self.dtype))
            p = nn.gelu(p, approximate=False)
            p = Conv(
                cfg.block_out_channels[0],
                (1, 1),
                dtype=self.dtype,
                name="pixels_mapper_2",
            )(p)
            x = x + interpolate_bilinear_align_corners(
                _ln(p, self.dtype), x.shape[1], x.shape[2]
            )

        def level_blocks(prefix, level, n_layers, c_skip_first):
            """Build the flattened per-level block list (matching the torch
            ModuleList flattening) as (kind, module) pairs."""
            ch = cfg.block_out_channels[level]
            blocks = []
            idx = 0
            for layer in range(n_layers):
                c_skip = c_skip_first if layer == 0 else 0
                blocks.append(
                    (
                        "res",
                        CascadeResBlock(
                            ch,
                            cfg.kernel_size,
                            c_skip=c_skip,
                            dtype=self.dtype,
                            name=f"{prefix}_{idx}",
                        ),
                    )
                )
                idx += 1
                blocks.append(
                    (
                        "time",
                        CascadeTimestepBlock(
                            ch,
                            cfg.timestep_conditioning_type,
                            dtype=self.dtype,
                            name=f"{prefix}_{idx}",
                        ),
                    )
                )
                idx += 1
                if cfg.attention[level]:
                    blocks.append(
                        (
                            "attn",
                            CascadeAttnBlock(
                                ch,
                                cfg.num_attention_heads[level],
                                self_attn=cfg.self_attn,
                                dtype=self.dtype,
                                name=f"{prefix}_{idx}",
                            ),
                        )
                    )
                    idx += 1
            return blocks

        def run_blocks(blocks, x, skip=None):
            first = True
            for kind, mod in blocks:
                if kind == "res":
                    s = skip if first else None
                    if s is not None and (
                        x.shape[1] != s.shape[1] or x.shape[2] != s.shape[2]
                    ):
                        x = interpolate_bilinear_align_corners(
                            x, s.shape[1], s.shape[2]
                        )
                    x = mod(x, s)
                    first = False
                elif kind == "time":
                    x = mod(x, t_embed)
                else:
                    x = mod(x, clip)
            return x

        # --- down path ---
        level_outputs = []
        for i in range(levels):
            if i > 0:
                x = _ln(x, self.dtype)
                if cfg.switch_level is not None:
                    # 1x1 mapping conv, then optional bilinear downscale
                    x = Conv(
                        cfg.block_out_channels[i],
                        (1, 1),
                        dtype=self.dtype,
                        name=f"down_downscalers_{i}_1",
                    )(x)
                    if cfg.switch_level[i - 1]:
                        x = interpolate_bilinear_align_corners(
                            x, x.shape[1] // 2, x.shape[2] // 2
                        )
                else:
                    # torch Conv2d(k=2, s=2) has padding=0: VALID, so odd
                    # grids floor (flax SAME would zero-pad and diverge)
                    x = Conv(
                        cfg.block_out_channels[i],
                        (2, 2),
                        strides=(2, 2),
                        padding="VALID",
                        dtype=self.dtype,
                        name=f"down_downscalers_{i}_1",
                    )(x)
            blocks = level_blocks(
                f"down_blocks_{i}", i, cfg.down_num_layers_per_block[i], 0
            )
            n_rep = cfg.down_blocks_repeat_mappers[i]
            for r in range(n_rep):
                x = run_blocks(blocks, x)
                if r < n_rep - 1:
                    x = Conv(
                        cfg.block_out_channels[i],
                        (1, 1),
                        dtype=self.dtype,
                        name=f"down_repeat_mappers_{i}_{r}",
                    )(x)
            level_outputs.insert(0, x)

        # --- up path (enumeration 0 = deepest level) ---
        x = level_outputs[0]
        for j in range(levels):
            i = levels - 1 - j  # original level index
            c_skip = cfg.block_out_channels[i] if j > 0 else 0
            blocks = level_blocks(
                f"up_blocks_{j}", i, cfg.up_num_layers_per_block[j], c_skip
            )
            skip = level_outputs[j] if j > 0 else None
            n_rep = cfg.up_blocks_repeat_mappers[j]
            for r in range(n_rep):
                x = run_blocks(blocks, x, skip=skip)
                if r < n_rep - 1:
                    x = Conv(
                        cfg.block_out_channels[i],
                        (1, 1),
                        dtype=self.dtype,
                        name=f"up_repeat_mappers_{j}_{r}",
                    )(x)
            if i > 0:
                x = _ln(x, self.dtype)
                if cfg.switch_level is not None:
                    if cfg.switch_level[i - 1]:
                        x = interpolate_bilinear_align_corners(
                            x, x.shape[1] * 2, x.shape[2] * 2
                        )
                    x = Conv(
                        cfg.block_out_channels[i - 1],
                        (1, 1),
                        dtype=self.dtype,
                        name=f"up_upscalers_{j}_1",
                    )(x)
                else:
                    x = ConvTransposed2D(
                        cfg.block_out_channels[i - 1],
                        kernel_size=2,
                        stride=2,
                        dtype=self.dtype,
                        name=f"up_upscalers_{j}_1",
                    )(x)

        # --- classifier head: LN + 1x1 conv + pixel-shuffle ---
        x = _ln(x, self.dtype)
        x = Conv(
            cfg.out_channels * cfg.patch_size**2,
            (1, 1),
            dtype=self.dtype,
            name="clf_1",
        )(x)
        return pixel_shuffle(x, cfg.patch_size)
