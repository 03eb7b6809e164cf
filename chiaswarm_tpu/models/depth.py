"""DPT monocular depth estimator (ViT backbone + reassemble/fusion head).

Reference behavior replaced: swarm/pre_processors/controlnet.py:94-119 runs
transformers' DPT pipeline on CUDA for the `depth` preprocessor, and
swarm/pre_processors/depth_estimator.py:8-24 feeds Kandinsky's depth hint.
TPU rebuild: one flax module, jitted end-to-end; module naming tracks the
HF DPTForDepthEstimation graph so conversion (convert_dpt) is mechanical.

Structure (DPT-Large geometry by default):
- ViT backbone (pre-LN), features tapped at 4 intermediate layers;
- reassemble: readout-projected tokens -> spatial maps at /4, /8, /16, /32
  of the input resolution (convtranspose / identity / strided conv);
- RefineNet-style fusion: deepest-first residual conv units, 2x upsample
  per stage; 3-conv head -> one depth channel.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from .layers import Conv, DeclaredParams, Dense, LayerNorm


@dataclasses.dataclass(frozen=True)
class DPTConfig:
    image_size: int = 384
    patch_size: int = 16
    hidden_size: int = 1024
    num_layers: int = 24
    num_heads: int = 16
    taps: tuple[int, ...] = (5, 11, 17, 23)  # tapped encoder layers
    reassemble_channels: tuple[int, ...] = (256, 512, 1024, 1024)
    fusion_dim: int = 256
    head_dim: int = 32


# patch 16 is load-bearing: the reassemble factors (4x, 2x, 1x, 0.5x) are
# tuned for a /16 token grid so the fused map lands at /2 of the input
TINY_DPT = DPTConfig(
    image_size=64, patch_size=16, hidden_size=32, num_layers=4, num_heads=4,
    taps=(0, 1, 2, 3), reassemble_channels=(16, 24, 32, 32), fusion_dim=16,
    head_dim=8,
)


class _ViTBlock(nn.Module):
    hidden: int
    heads: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, s, _ = x.shape
        hd = self.hidden // self.heads
        y = LayerNorm(dtype=self.dtype, name="ln1")(x)
        q = Dense(self.hidden, dtype=self.dtype, name="q")(y)
        k = Dense(self.hidden, dtype=self.dtype, name="k")(y)
        v = Dense(self.hidden, dtype=self.dtype, name="v")(y)
        q, k, v = (t.reshape(b, s, self.heads, hd) for t in (q, k, v))
        from ..ops import dot_product_attention

        attn = dot_product_attention(q, k, v).reshape(b, s, self.hidden)
        x = x + Dense(self.hidden, dtype=self.dtype, name="out")(attn)
        y = LayerNorm(dtype=self.dtype, name="ln2")(x)
        y = Dense(4 * self.hidden, dtype=self.dtype, name="fc1")(y)
        y = nn.gelu(y, approximate=False)
        return x + Dense(self.hidden, dtype=self.dtype, name="fc2")(y)


class _ResidualConvUnit(nn.Module):
    channels: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        y = nn.relu(x)
        y = Conv(self.channels, (3, 3), padding=((1, 1), (1, 1)),
                 dtype=self.dtype, name="conv1")(y)
        y = nn.relu(y)
        y = Conv(self.channels, (3, 3), padding=((1, 1), (1, 1)),
                 dtype=self.dtype, name="conv2")(y)
        return x + y


def _resize2x(x):
    b, h, w, c = x.shape
    return jax.image.resize(x, (b, 2 * h, 2 * w, c), "bilinear")


class DPTDepthModel(DeclaredParams, nn.Module):
    config: DPTConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, pixels):
        """pixels [B, H, W, 3] normalized -> inverse depth [B, H, W]."""
        cfg = self.config
        x = Conv(
            cfg.hidden_size, (cfg.patch_size, cfg.patch_size),
            strides=(cfg.patch_size, cfg.patch_size), dtype=self.dtype,
            name="patch_embed",
        )(pixels)
        b, gh, gw, _ = x.shape
        x = x.reshape(b, gh * gw, cfg.hidden_size)
        cls = self.param(
            "cls_token", nn.initializers.zeros, (1, 1, cfg.hidden_size)
        ).astype(self.dtype)
        x = jnp.concatenate([jnp.broadcast_to(cls, (b, 1, cfg.hidden_size)), x],
                            axis=1)
        pos = self.param(
            "pos_embed", nn.initializers.normal(0.02),
            (1, gh * gw + 1, cfg.hidden_size),
        ).astype(self.dtype)
        x = x + pos

        taps = {}
        for i in range(cfg.num_layers):
            x = _ViTBlock(cfg.hidden_size, cfg.num_heads, dtype=self.dtype,
                          name=f"layer_{i}")(x)
            if i in cfg.taps:
                taps[i] = x

        features = []
        for k, layer_idx in enumerate(cfg.taps):
            t = taps[layer_idx]
            tokens, cls_tok = t[:, 1:], t[:, :1]
            # readout "project": concat cls onto every token, project back
            readout = jnp.concatenate(
                [tokens, jnp.broadcast_to(cls_tok, tokens.shape)], axis=-1
            )
            tokens = nn.gelu(
                Dense(cfg.hidden_size, dtype=self.dtype,
                      name=f"reassemble_{k}_readout")(readout),
                approximate=False,
            )
            fmap = tokens.reshape(b, gh, gw, cfg.hidden_size)
            ch = cfg.reassemble_channels[k]
            fmap = Conv(ch, (1, 1), dtype=self.dtype,
                        name=f"reassemble_{k}_project")(fmap)
            if k == 0:  # /16 -> /4
                fmap = nn.ConvTranspose(
                    ch, (4, 4), strides=(4, 4), dtype=self.dtype,
                    name="reassemble_0_resize",
                )(fmap)
            elif k == 1:  # /16 -> /8
                fmap = nn.ConvTranspose(
                    ch, (2, 2), strides=(2, 2), dtype=self.dtype,
                    name="reassemble_1_resize",
                )(fmap)
            elif k == 3:  # /16 -> /32
                fmap = Conv(
                    ch, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)),
                    dtype=self.dtype, name="reassemble_3_resize",
                )(fmap)
            fmap = Conv(
                cfg.fusion_dim, (3, 3), padding=((1, 1), (1, 1)),
                use_bias=False, dtype=self.dtype, name=f"conv_{k}",
            )(fmap)
            features.append(fmap)

        # RefineNet fusion, deepest first: residual_layer1 transforms the
        # LATERAL feature joining the fused stream (HF DPTFeatureFusionLayer:
        # fused = fused + rcu1(lateral); rcu2 on the sum). The 2x upsample
        # here is half-pixel bilinear vs HF's align_corners=True — a
        # boundary-pixel-level divergence only.
        fused = None
        for k in reversed(range(len(features))):
            lateral = features[k]
            if fused is None:
                hidden = lateral
            else:
                hidden = fused + _ResidualConvUnit(
                    cfg.fusion_dim, dtype=self.dtype, name=f"fusion_{k}_rcu1"
                )(lateral)
            hidden = _ResidualConvUnit(
                cfg.fusion_dim, dtype=self.dtype, name=f"fusion_{k}_rcu2"
            )(hidden)
            hidden = _resize2x(hidden)
            fused = Conv(
                cfg.fusion_dim, (1, 1), dtype=self.dtype,
                name=f"fusion_{k}_project",
            )(hidden)

        y = Conv(cfg.fusion_dim // 2, (3, 3), padding=((1, 1), (1, 1)),
                 dtype=self.dtype, name="head_conv1")(fused)
        y = _resize2x(y)
        y = Conv(cfg.head_dim, (3, 3), padding=((1, 1), (1, 1)),
                 dtype=self.dtype, name="head_conv2")(y)
        y = nn.relu(y)
        y = Conv(1, (1, 1), dtype=self.dtype, name="head_conv3")(y)
        y = nn.relu(y)
        return y[..., 0]
