"""UperNet semantic segmentation (ConvNeXt backbone), flax/NHWC.

The reference's `segmentation` ControlNet annotator runs
UperNetForSemanticSegmentation (openmmlab/upernet-convnext-small) over
ADE20K (reference swarm/pre_processors/controlnet.py:122-141). This is
the real graph rebuilt TPU-first: ConvNeXt stages (depthwise 7x7 +
channels-last LN + pointwise MLP + layer scale — all MXU/VPU friendly in
NHWC), PSP pyramid pooling, FPN top-down fusion, pixel classifier.

BatchNorms in the UperNet conv modules fold into the conv kernels at
conversion time (conversion.convert_upernet), so runtime is conv+ReLU.
Numeric parity vs transformers' UperNetForSemanticSegmentation is
asserted in tests/test_segmentation_conversion.py.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from .layers import Conv, DeclaredParams, Dense


@dataclasses.dataclass(frozen=True)
class UperNetConfig:
    depths: tuple[int, ...] = (3, 3, 27, 3)  # convnext-small
    hidden_sizes: tuple[int, ...] = (96, 192, 384, 768)
    hidden_size: int = 512  # decode head channels
    num_labels: int = 150  # ADE20K
    pool_scales: tuple[int, ...] = (1, 2, 3, 6)
    layer_norm_eps: float = 1e-6


TINY_UPERNET = UperNetConfig(
    depths=(1, 1, 1, 1), hidden_sizes=(8, 16, 24, 32), hidden_size=16,
    num_labels=5,
)


def upernet_config_from_json(config_json: dict | None) -> UperNetConfig:
    """The ONE config.json parse shared by the resident Segmenter and
    `initialize --check`, so verify and serving cannot drift."""
    cfg = UperNetConfig()
    cj = config_json or {}
    bb = cj.get("backbone_config", {})
    return UperNetConfig(
        depths=tuple(bb.get("depths", cfg.depths)),
        hidden_sizes=tuple(bb.get("hidden_sizes", cfg.hidden_sizes)),
        hidden_size=int(cj.get("hidden_size", cfg.hidden_size)),
        num_labels=int(cj.get("num_labels", cfg.num_labels)),
        pool_scales=tuple(cj.get("pool_scales", cfg.pool_scales)),
    )


def _ln(x, scale, bias, eps):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


class _ChannelsLN(DeclaredParams, nn.Module):
    """LayerNorm over the channel axis of an NHWC map (torch's
    ConvNextLayerNorm data_format=channels_first, transposed)."""

    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (c,))
        bias = self.param("bias", nn.initializers.zeros, (c,))
        return _ln(
            x, jnp.asarray(scale, x.dtype), jnp.asarray(bias, x.dtype),
            self.eps,
        )


class _ConvNextLayer(DeclaredParams, nn.Module):
    dim: int
    eps: float
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        h = Conv(
            self.dim, (7, 7), padding=((3, 3), (3, 3)),
            feature_group_count=self.dim, dtype=self.dtype, name="dwconv",
        )(x)
        h = _ChannelsLN(self.eps, dtype=self.dtype, name="norm")(h)
        h = Dense(4 * self.dim, dtype=self.dtype, name="pwconv1")(h)
        h = nn.gelu(h, approximate=False)
        h = Dense(self.dim, dtype=self.dtype, name="pwconv2")(h)
        gamma = self.param(
            "layer_scale", nn.initializers.ones, (self.dim,)
        )
        return x + h * jnp.asarray(gamma, h.dtype)


class _ConvRelu(nn.Module):
    """UperNetConvModule with the BatchNorm folded into the conv."""

    channels: int
    kernel: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        p = self.kernel // 2
        return nn.relu(
            Conv(
                self.channels, (self.kernel, self.kernel),
                padding=((p, p), (p, p)), dtype=self.dtype, name="conv",
            )(x)
        )


def _adaptive_avg_pool(x, out: int):
    """torch AdaptiveAvgPool2d semantics: per-cell windows
    [floor(i*H/out), ceil((i+1)*H/out))."""
    b, h, w, c = x.shape
    rows = []
    for i in range(out):
        h0, h1 = (i * h) // out, -(-((i + 1) * h) // out)
        cols = []
        for j in range(out):
            w0, w1 = (j * w) // out, -(-((j + 1) * w) // out)
            cols.append(x[:, h0:h1, w0:w1].mean(axis=(1, 2)))
        rows.append(jnp.stack(cols, axis=1))
    return jnp.stack(rows, axis=1)  # [B, out, out, C]


def _resize(x, hw):
    return jax.image.resize(
        x, (x.shape[0], hw[0], hw[1], x.shape[-1]), "bilinear"
    )


class UperNetSegmenter(nn.Module):
    config: UperNetConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, pixels):
        """[B, H, W, 3] (normalized) -> logits [B, H, W, num_labels]."""
        cfg = self.config
        eps = cfg.layer_norm_eps

        x = Conv(
            cfg.hidden_sizes[0], (4, 4), strides=(4, 4), dtype=self.dtype,
            name="patch_embeddings",
        )(pixels)
        x = _ChannelsLN(eps, dtype=self.dtype, name="embeddings_norm")(x)

        feats = []
        for s, (depth, dim) in enumerate(zip(cfg.depths, cfg.hidden_sizes)):
            if s > 0:
                x = _ChannelsLN(
                    eps, dtype=self.dtype, name=f"downsample_norm_{s}"
                )(x)
                x = Conv(
                    dim, (2, 2), strides=(2, 2), dtype=self.dtype,
                    name=f"downsample_conv_{s}",
                )(x)
            for j in range(depth):
                x = _ConvNextLayer(
                    dim, eps, dtype=self.dtype, name=f"stage_{s}_layer_{j}"
                )(x)
            feats.append(
                _ChannelsLN(eps, dtype=self.dtype, name=f"feature_norm_{s}")(x)
            )

        # PSP over the top feature
        top = feats[-1]
        hw = top.shape[1:3]
        psp = [top]
        for k, scale in enumerate(cfg.pool_scales):
            pooled = _adaptive_avg_pool(top, scale)
            pooled = _ConvRelu(
                cfg.hidden_size, 1, dtype=self.dtype, name=f"psp_{k}"
            )(pooled)
            psp.append(_resize(pooled, hw))
        psp_out = _ConvRelu(
            cfg.hidden_size, 3, dtype=self.dtype, name="bottleneck"
        )(jnp.concatenate(psp, axis=-1))

        # FPN top-down
        laterals = [
            _ConvRelu(cfg.hidden_size, 1, dtype=self.dtype, name=f"lateral_{i}")(
                feats[i]
            )
            for i in range(len(feats) - 1)
        ] + [psp_out]
        for i in range(len(laterals) - 1, 0, -1):
            laterals[i - 1] = laterals[i - 1] + _resize(
                laterals[i], laterals[i - 1].shape[1:3]
            )
        outs = [
            _ConvRelu(cfg.hidden_size, 3, dtype=self.dtype, name=f"fpn_{i}")(
                laterals[i]
            )
            for i in range(len(laterals) - 1)
        ] + [laterals[-1]]
        size0 = outs[0].shape[1:3]
        outs = [outs[0]] + [_resize(o, size0) for o in outs[1:]]
        fused = _ConvRelu(
            cfg.hidden_size, 3, dtype=self.dtype, name="fpn_bottleneck"
        )(jnp.concatenate(outs, axis=-1))
        logits = Conv(
            cfg.num_labels, (1, 1), dtype=self.dtype, name="classifier"
        )(fused)
        return _resize(
            logits.astype(jnp.float32), pixels.shape[1:3]
        )
