"""PiDiNet soft-edge detector (table5_pidinet, 'carv4' config) — the
learned annotator behind the `softedge` preprocessor.

Reference behavior replaced: swarm/pre_processors/controlnet.py:56-57
(controlnet_aux PidiNetDetector fetched per call). The graph is four
stages of pixel-difference-convolution blocks (depthwise 3x3/5x5 +
pointwise, residual, maxpool+1x1-shortcut on stride), each stage refined
by a compact dilation module (CDCM: 4 parallel dilated 3x3) and spatial
attention (CSAM), reduced to a 1-channel edge logit, bilinearly upsampled
to the input canvas, and fused by a 1x1 classifier; every map exits
through a sigmoid.

The checkpoint stores RAW pixel-difference kernels; conversion
(models/conversion.py convert_pidinet) re-parameterizes cd/ad/rd kernels
into equivalent vanilla convs (the authors' published convert_pdc math),
so this flax graph is plain convs.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from .layers import Conv

# the released table5_pidinet config: (cd, ad, rd, cv) per stage
CARV4 = ("cd", "ad", "rd", "cv") * 4
STAGE_PLANES = (60, 120, 240, 240)
DIL = 24


class _PDCBlock(nn.Module):
    """Converted PDC block: [maxpool + 1x1 shortcut on stride] depthwise
    conv (5x5 for rd, 3x3 otherwise) -> relu -> pointwise, residual."""

    pdc: str
    out_channels: int
    stride: int = 1
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        in_ch = x.shape[-1]
        if self.stride > 1:
            x = nn.max_pool(x, (2, 2), strides=(2, 2))
        k = 5 if self.pdc == "rd" else 3
        p = k // 2
        y = Conv(
            in_ch, (k, k), padding=((p, p), (p, p)),
            feature_group_count=in_ch, use_bias=False, dtype=self.dtype,
            name="conv1",
        )(x)
        y = nn.relu(y)
        y = Conv(
            self.out_channels, (1, 1), use_bias=False, dtype=self.dtype,
            name="conv2",
        )(y)
        if self.stride > 1:
            x = Conv(
                self.out_channels, (1, 1), dtype=self.dtype, name="shortcut"
            )(x)
        return y + x


class _CDCM(nn.Module):
    """Compact dilation module: 1x1 then four parallel dilated 3x3
    (dilations 5/7/9/11), summed."""

    out_channels: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = nn.relu(x)
        x = Conv(self.out_channels, (1, 1), dtype=self.dtype,
                 name="conv1")(x)
        out = 0
        for i, d in enumerate((5, 7, 9, 11)):
            out = out + Conv(
                self.out_channels, (3, 3), padding=((d, d), (d, d)),
                kernel_dilation=(d, d), use_bias=False, dtype=self.dtype,
                name=f"conv2_{i + 1}",
            )(x)
        return out


class _CSAM(nn.Module):
    """Compact spatial attention: 1x1 -> 3x3 -> sigmoid gate."""

    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        y = nn.relu(x)
        y = Conv(4, (1, 1), dtype=self.dtype, name="conv1")(y)
        y = Conv(1, (3, 3), padding=((1, 1), (1, 1)), use_bias=False,
                 dtype=self.dtype, name="conv2")(y)
        return x * nn.sigmoid(y)


class PiDiNet(nn.Module):
    """[B, H, W, 3] in [0, 1] -> [B, H, W, 1] fused edge probability
    (the last of upstream's five sigmoid outputs)."""

    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, h, w, _ = x.shape
        x = Conv(
            STAGE_PLANES[0], (3, 3), padding=((1, 1), (1, 1)),
            use_bias=False, dtype=self.dtype, name="init_block",
        )(jnp.asarray(x, self.dtype))
        stage_outs = []
        for s in range(4):
            n_blocks = 3 if s == 0 else 4
            for j in range(n_blocks):
                # stage 0's first pdc layer IS the init conv above, so its
                # blocks cover pdc layers 1..3; stage s>0 covers 4s..4s+3
                # and starts with a strided block
                layer = j + 1 if s == 0 else s * 4 + j
                x = _PDCBlock(
                    CARV4[layer], STAGE_PLANES[s],
                    stride=2 if (s > 0 and j == 0) else 1,
                    dtype=self.dtype,
                    name=f"block{s + 1}_{j + 1}",
                )(x)
            stage_outs.append(x)

        logits = []
        for i, xi in enumerate(stage_outs):
            y = _CDCM(DIL, dtype=self.dtype, name=f"dilations_{i}")(xi)
            y = _CSAM(dtype=self.dtype, name=f"attentions_{i}")(y)
            y = Conv(1, (1, 1), dtype=self.dtype,
                     name=f"conv_reduces_{i}")(y)
            logits.append(
                jax.image.resize(
                    y.astype(jnp.float32), (b, h, w, 1), "bilinear"
                )
            )
        fused = Conv(1, (1, 1), dtype=self.dtype, name="classifier")(
            jnp.concatenate(logits, axis=-1).astype(self.dtype)
        )
        return nn.sigmoid(fused.astype(jnp.float32))
