"""M-LSD line-segment detector (MobileV2_MLSD_Large) — the learned
annotator behind the `mlsd` preprocessor.

Reference behavior replaced: swarm/pre_processors/controlnet.py:31
(controlnet_aux MLSDdetector fetched per call). The graph is a 4-channel
MobileNetV2 trunk (first 14 feature blocks, ReLU6, inverted residuals)
whose five FPN taps feed a chain of A/B/C fusion blocks (1x1 fuse +
align-corners 2x upsampling, 3x3 residual refine, dilated head) emitting
a 16-channel map at input/2; channels 7..16 carry the TP-map (center
heat + start/end displacements) that the host decodes into line
segments.

Every BatchNorm folds into its preceding conv at conversion
(models/conversion.py convert_mlsd), so the flax graph is pure
conv+relu6. Module names are this package's own (the torch checkpoint's
Sequential indices don't survive folding); conversion owns the mapping.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax.numpy as jnp

from .layers import Conv


# MobileNetV2 inverted-residual plan the MLSD trunk uses: (t, c, n, s)
MBV2_SETTING = ((1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2),
                (6, 64, 4, 2), (6, 96, 3, 1))
FPN_TAPS = (1, 3, 6, 10, 13)


@dataclasses.dataclass(frozen=True)
class MLSDConfig:
    in_channels: int = 4  # RGB + constant alpha plane
    stem_channels: int = 32
    head_channels: int = 64
    out_channels: int = 16


def relu6(x):
    return jnp.clip(x, 0.0, 6.0)


def resize_align_corners_2x(x):
    """F.interpolate(scale_factor=2, mode='bilinear', align_corners=True)
    — the shared cascade_unet helper carries the align-corners math."""
    from .cascade_unet import interpolate_bilinear_align_corners

    b, h, w, c = x.shape
    return interpolate_bilinear_align_corners(x, 2 * h, 2 * w)


class _ConvRelu6(nn.Module):
    features: int
    kernel: int = 3
    stride: int = 1
    groups: int = 1
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        pad = (self.kernel - 1) // 2
        x = Conv(
            self.features, (self.kernel, self.kernel),
            strides=(self.stride, self.stride),
            padding=((pad, pad), (pad, pad)),
            feature_group_count=self.groups,
            dtype=self.dtype, name="conv",
        )(x)
        return relu6(x)


class _InvertedResidual(nn.Module):
    out_channels: int
    stride: int
    expand_ratio: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        in_ch = x.shape[-1]
        hidden = round(in_ch * self.expand_ratio)
        h = x
        if self.expand_ratio != 1:
            h = _ConvRelu6(hidden, kernel=1, dtype=self.dtype,
                           name="expand")(h)
        h = _ConvRelu6(
            hidden, kernel=3, stride=self.stride, groups=hidden,
            dtype=self.dtype, name="depthwise",
        )(h)
        h = Conv(self.out_channels, (1, 1), dtype=self.dtype,
                 name="project")(h)
        if self.stride == 1 and in_ch == self.out_channels:
            h = x + h
        return h


class _BlockA(nn.Module):
    """1x1 fuse of a lateral tap and the carried feature map (optionally
    align-corners 2x upsampled), concatenated."""

    out_channels: int
    upscale: bool = True
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, lateral, carried):
        b = Conv(self.out_channels, (1, 1), dtype=self.dtype,
                 name="conv1")(carried)
        b = nn.relu(b)
        a = Conv(self.out_channels, (1, 1), dtype=self.dtype,
                 name="conv2")(lateral)
        a = nn.relu(a)
        if self.upscale:
            b = resize_align_corners_2x(b)
        return jnp.concatenate([a, b], axis=-1)


class _BlockB(nn.Module):
    """3x3 residual refine then 3x3 reduce."""

    out_channels: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        h = Conv(x.shape[-1], (3, 3), padding=((1, 1), (1, 1)),
                 dtype=self.dtype, name="conv1")(x)
        x = nn.relu(h) + x
        x = Conv(self.out_channels, (3, 3), padding=((1, 1), (1, 1)),
                 dtype=self.dtype, name="conv2")(x)
        return nn.relu(x)


class _BlockC(nn.Module):
    """Dilated 3x3 -> 3x3 -> 1x1 head."""

    out_channels: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        x = Conv(c, (3, 3), padding=((5, 5), (5, 5)),
                 kernel_dilation=(5, 5), dtype=self.dtype,
                 name="conv1")(x)
        x = nn.relu(x)
        x = Conv(c, (3, 3), padding=((1, 1), (1, 1)), dtype=self.dtype,
                 name="conv2")(x)
        x = nn.relu(x)
        return Conv(self.out_channels, (1, 1), dtype=self.dtype,
                    name="conv3")(x)


class MLSDNet(nn.Module):
    """[B, H, W, 4] in [-1, 1] -> [B, H/2, W/2, 9] TP map
    (channel 0 = center logit, 1..4 = start/end displacements)."""

    config: MLSDConfig = MLSDConfig()
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        x = _ConvRelu6(cfg.stem_channels, kernel=3, stride=2,
                       dtype=self.dtype, name="features_0")(x)
        taps = {}
        idx = 1
        for t, c, n, s in MBV2_SETTING:
            for i in range(n):
                x = _InvertedResidual(
                    c, s if i == 0 else 1, t, dtype=self.dtype,
                    name=f"features_{idx}",
                )(x)
                if idx in FPN_TAPS:
                    taps[idx] = x
                idx += 1
        c1, c2, c3, c4, c5 = (taps[i] for i in FPN_TAPS)

        hc = cfg.head_channels
        x = _BlockA(hc, upscale=False, dtype=self.dtype, name="block15")(
            c4, c5
        )
        x = _BlockB(hc, dtype=self.dtype, name="block16")(x)
        x = _BlockA(hc, dtype=self.dtype, name="block17")(c3, x)
        x = _BlockB(hc, dtype=self.dtype, name="block18")(x)
        x = _BlockA(hc, dtype=self.dtype, name="block19")(c2, x)
        x = _BlockB(hc, dtype=self.dtype, name="block20")(x)
        x = _BlockA(hc, dtype=self.dtype, name="block21")(c1, x)
        x = _BlockB(hc, dtype=self.dtype, name="block22")(x)
        x = _BlockC(cfg.out_channels, dtype=self.dtype, name="block23")(x)
        # the TP map is the trailing 9 channels (7 auxiliary training
        # channels are dropped exactly as upstream does)
        return x[..., 7:]
