"""LineArt generator (informative-drawings `Generator`) — the learned
annotator behind the `lineart` preprocessor.

Reference behavior replaced: swarm/pre_processors/controlnet.py:43
(controlnet_aux LineartDetector, sk_model.pth / sk_model2.pth coarse).
The graph is a compact image-to-sketch translator: reflect-padded 7x7
stem, two stride-2 downsamples, three residual blocks, two transposed-
conv upsamples, a 7x7 head with sigmoid — every norm an InstanceNorm
(affine-free, so the checkpoint carries only conv weights).

The two ConvTranspose2d(3, stride 2, padding 1, output_padding 1) layers
convert at load into equivalent input-dilated convs (kernel flipped,
asymmetric (1,2) padding), so the flax graph is pure convs
(models/conversion.py convert_lineart owns the mapping).
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from .layers import Conv, DeclaredParams


@dataclasses.dataclass(frozen=True)
class LineartConfig:
    base_channels: int = 64
    n_residual_blocks: int = 3


TINY_LINEART = LineartConfig(base_channels=8, n_residual_blocks=1)


def instance_norm(x, eps: float = 1e-5):
    """torch InstanceNorm2d(affine=False): per-sample per-channel spatial
    standardization (biased variance, matching torch)."""
    mean = jnp.mean(x, axis=(1, 2), keepdims=True)
    var = jnp.var(x, axis=(1, 2), keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps)


def _reflect_conv(x, features, kernel, pad, dtype, name):
    x = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)), mode="reflect")
    return Conv(features, (kernel, kernel), padding="VALID",
                dtype=dtype, name=name)(x)


class _UpConv(DeclaredParams, nn.Module):
    """ConvTranspose2d(3, stride=2, padding=1, output_padding=1) as an
    input-dilated conv; the kernel arrives pre-flipped from conversion."""

    features: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (3, 3, x.shape[-1], self.features),
        )
        bias = self.param("bias", nn.initializers.zeros, (self.features,))
        y = jax.lax.conv_general_dilated(
            x.astype(self.dtype), jnp.asarray(kernel, self.dtype),
            (1, 1), ((1, 2), (1, 2)), lhs_dilation=(2, 2),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        return y + jnp.asarray(bias, self.dtype)


class LineartGenerator(nn.Module):
    """[B, H, W, 3] in [0, 1] -> [B, H, W, 1] sketch probability (dark
    strokes near 0 on a white ~1 page, before the caller inverts)."""

    config: LineartConfig = LineartConfig()
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        c = cfg.base_channels
        x = jnp.asarray(x, self.dtype)
        x = _reflect_conv(x, c, 7, 3, self.dtype, "model0_conv")
        x = nn.relu(instance_norm(x))
        x = Conv(2 * c, (3, 3), strides=(2, 2),
                 padding=((1, 1), (1, 1)), dtype=self.dtype,
                 name="model1_conv0")(x)
        x = nn.relu(instance_norm(x))
        x = Conv(4 * c, (3, 3), strides=(2, 2),
                 padding=((1, 1), (1, 1)), dtype=self.dtype,
                 name="model1_conv1")(x)
        x = nn.relu(instance_norm(x))
        for i in range(cfg.n_residual_blocks):
            h = _reflect_conv(x, 4 * c, 3, 1, self.dtype,
                              f"res_{i}_conv0")
            h = nn.relu(instance_norm(h))
            h = _reflect_conv(h, 4 * c, 3, 1, self.dtype,
                              f"res_{i}_conv1")
            x = x + instance_norm(h)
        x = _UpConv(2 * c, dtype=self.dtype, name="model3_conv0")(x)
        x = nn.relu(instance_norm(x))
        x = _UpConv(c, dtype=self.dtype, name="model3_conv1")(x)
        x = nn.relu(instance_norm(x))
        x = _reflect_conv(x, 1, 7, 3, self.dtype, "model4_conv")
        return nn.sigmoid(x)
