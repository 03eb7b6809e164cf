"""HiFi-GAN vocoder generator (the AudioLDM mel->waveform stage).

Reference behavior replaced: the reference's AudioLDMPipeline carries a
`SpeechT5HifiGan` vocoder inside diffusers (swarm/audio/audioldm.py:23-29
just calls the pipeline). This flax module mirrors the transformers
`SpeechT5HifiGan` graph — conv_pre -> N ConvTranspose upsample stages,
each fused with multi-receptive-field residual blocks (kernels 3/7/11,
dilations 1/3/5) -> conv_post -> tanh — so checkpoints convert
mechanically (conversion.convert_hifigan). NWC layout; the whole vocoder
is one fused conv program on the MXU.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax.numpy as jnp

from .layers import Conv, DeclaredParams


@dataclasses.dataclass(frozen=True)
class HifiGanConfig:
    model_in_dim: int = 64  # mel bins
    upsample_initial_channel: int = 512
    upsample_rates: tuple[int, ...] = (5, 4, 2, 2, 2)
    upsample_kernel_sizes: tuple[int, ...] = (16, 16, 8, 4, 4)
    resblock_kernel_sizes: tuple[int, ...] = (3, 7, 11)
    resblock_dilation_sizes: tuple[tuple[int, ...], ...] = (
        (1, 3, 5), (1, 3, 5), (1, 3, 5),
    )
    leaky_relu_slope: float = 0.1
    normalize_before: bool = True


TINY_HIFIGAN = HifiGanConfig(
    model_in_dim=8,
    upsample_initial_channel=16,
    upsample_rates=(4, 2),
    upsample_kernel_sizes=(8, 4),
    resblock_kernel_sizes=(3,),
    resblock_dilation_sizes=((1, 3),),
)


class _ResBlock(nn.Module):
    """HifiGanResidualBlock: dilated conv pairs with leaky-relu."""

    channels: int
    kernel_size: int
    dilations: tuple[int, ...]
    slope: float
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        for i, d in enumerate(self.dilations):
            h = nn.leaky_relu(x, self.slope)
            h = Conv(
                self.channels, (self.kernel_size,), kernel_dilation=(d,),
                dtype=self.dtype, name=f"convs1_{i}",
            )(h)
            h = nn.leaky_relu(h, self.slope)
            h = Conv(
                self.channels, (self.kernel_size,), dtype=self.dtype,
                name=f"convs2_{i}",
            )(h)
            x = x + h
        return x


class HifiGanGenerator(DeclaredParams, nn.Module):
    config: HifiGanConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, mel):
        """log-mel [B, T, n_mels] -> waveform [B, T * prod(rates)]."""
        cfg = self.config
        if cfg.normalize_before:
            mean = self.param(
                "mean", nn.initializers.zeros, (cfg.model_in_dim,)
            )
            scale = self.param(
                "scale", nn.initializers.ones, (cfg.model_in_dim,)
            )
            mel = (mel - mean) / scale
        x = Conv(
            cfg.upsample_initial_channel, (7,), dtype=self.dtype,
            name="conv_pre",
        )(mel.astype(self.dtype))
        n_kernels = len(cfg.resblock_kernel_sizes)
        for i, (rate, k) in enumerate(
            zip(cfg.upsample_rates, cfg.upsample_kernel_sizes)
        ):
            x = nn.leaky_relu(x, cfg.leaky_relu_slope)
            ch = cfg.upsample_initial_channel // (2 ** (i + 1))
            # torch ConvTranspose1d(pad=(k-rate)//2) == full (VALID)
            # transpose conv cropped by that pad on both ends; SAME only
            # coincides when k-rate is even, and the real AudioLDM vocoder
            # hits an odd case (kernel 16, rate 5)
            x = nn.ConvTranspose(
                ch, (k,), strides=(rate,), padding="VALID",
                dtype=self.dtype, name=f"upsampler_{i}",
            )(x)
            pad = (k - rate) // 2
            if pad:
                x = x[:, pad:-pad]
            # multi-receptive-field fusion: mean of the per-kernel resblocks
            acc = None
            for j, (rk, dil) in enumerate(
                zip(cfg.resblock_kernel_sizes, cfg.resblock_dilation_sizes)
            ):
                r = _ResBlock(
                    ch, rk, tuple(dil), cfg.leaky_relu_slope,
                    dtype=self.dtype, name=f"resblocks_{i * n_kernels + j}",
                )(x)
                acc = r if acc is None else acc + r
            x = acc / n_kernels
        x = nn.leaky_relu(x, cfg.leaky_relu_slope)
        x = Conv(1, (7,), dtype=self.dtype, name="conv_post")(x)
        return jnp.tanh(x)[..., 0]
