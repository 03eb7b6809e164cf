"""HED edge detector (lllyasviel's ControlNetHED, Apache-2.0 weights) —
the learned annotator behind the `scribble` and `softedge` preprocessors.

Reference behavior replaced: swarm/pre_processors/controlnet.py:51-57
(controlnet_aux HEDdetector fetched per call). The graph is a VGG-style
backbone with 5 stages; each stage emits a 1-channel edge logit map via a
1x1 projection, the host resizes all 5 to the input canvas and sigmoids
their mean. Module/param names line up with the checkpoint's state dict
(norm, blockN.convs.M, blockN.projection) so conversion is mechanical.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax.numpy as jnp

from .layers import Conv, DeclaredParams


@dataclasses.dataclass(frozen=True)
class HEDConfig:
    channels: tuple[int, ...] = (64, 128, 256, 512, 512)
    layers: tuple[int, ...] = (2, 2, 3, 3, 3)


TINY_HED = HEDConfig(channels=(8, 16), layers=(1, 1))


class _Block(nn.Module):
    out_channels: int
    n_convs: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        for i in range(self.n_convs):
            x = Conv(self.out_channels, (3, 3), padding=((1, 1), (1, 1)),
                     dtype=self.dtype, name=f"convs_{i}")(x)
            x = nn.relu(x)
        proj = Conv(1, (1, 1), dtype=self.dtype, name="projection")(x)
        return x, proj


class HEDNet(DeclaredParams, nn.Module):
    """[B, H, W, 3] raw RGB in 0..255 -> list of per-stage edge logit maps
    (each [B, H/2^i, W/2^i, 1])."""

    config: HEDConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, pixels):
        cfg = self.config
        # learned input normalization, stored in the checkpoint's NCHW
        # layout [1, 3, 1, 1]
        norm = self.param(
            "norm", nn.initializers.zeros, (1, 3, 1, 1)
        ).astype(self.dtype)
        x = pixels.astype(self.dtype) - norm.transpose(0, 2, 3, 1)
        projections = []
        for i, (ch, n) in enumerate(zip(cfg.channels, cfg.layers)):
            if i > 0:
                x = nn.max_pool(x, (2, 2), strides=(2, 2))
            x, proj = _Block(ch, n, dtype=self.dtype, name=f"block{i + 1}")(x)
            projections.append(proj)
        return projections
