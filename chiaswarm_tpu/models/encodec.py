"""EnCodec neural audio codec — decoder path, flax/NLC.

Bark's waveform stage: the reference's `generate_audio` decodes the 8-book
EnCodec tokens through facebook/encodec_24khz (reference
swarm/audio/bark.py:16-21 via suno's codec). This is the decode-only
rebuild: RVQ codebook-sum -> SEANet decoder (conv, 2-layer LSTM,
per-ratio transposed conv + residual blocks) -> waveform.

Layout is [B, T, C] (TPU-friendly channels-last; torch reference is
[B, C, T]). Weight-normalized conv weights fold into plain kernels at
conversion time (conversion.convert_encodec_decoder), so runtime is plain
convs. Causal padding follows transformers' EncodecConv1d exactly:
left-pad (k-1)*dilation in the configured pad mode ("reflect" for the
24 kHz model); transposed convs trim (k - stride) from the right
(trim_right_ratio=1). Numeric parity vs transformers EncodecModel.decode
is asserted in tests/test_bark_conversion.py.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from .layers import Conv, DeclaredParams


@dataclasses.dataclass(frozen=True)
class EncodecConfig:
    hidden_size: int = 128
    num_filters: int = 32
    upsampling_ratios: tuple[int, ...] = (8, 5, 4, 2)
    kernel_size: int = 7
    last_kernel_size: int = 7
    residual_kernel_size: int = 3
    dilation_growth_rate: int = 2
    num_residual_layers: int = 1
    num_lstm_layers: int = 2
    compress: int = 2
    codebook_size: int = 1024
    audio_channels: int = 1
    pad_mode: str = "reflect"
    use_conv_shortcut: bool = True


TINY_ENCODEC = EncodecConfig(
    hidden_size=16, num_filters=4, upsampling_ratios=(4, 2),
    kernel_size=7, last_kernel_size=7, residual_kernel_size=3,
    num_lstm_layers=1, codebook_size=64,
)


class _CausalConv(nn.Module):
    """EncodecConv1d, causal: left-pad (k-1)*dilation, stride 1."""

    out_channels: int
    kernel_size: int
    dilation: int = 1
    pad_mode: str = "reflect"
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        pad = (self.kernel_size - 1) * self.dilation
        if pad:
            mode = "reflect" if self.pad_mode == "reflect" else "constant"
            # reflect needs T > pad; generated audio always has many frames
            x = jnp.pad(x, ((0, 0), (pad, 0), (0, 0)), mode=mode)
        return Conv(
            self.out_channels, (self.kernel_size,),
            kernel_dilation=(self.dilation,), padding="VALID",
            dtype=self.dtype, name="conv",
        )(x)


class _CausalConvTranspose(nn.Module):
    """EncodecConvTranspose1d, causal: trim (k - stride) from the right."""

    out_channels: int
    kernel_size: int
    stride: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        y = nn.ConvTranspose(
            self.out_channels, (self.kernel_size,), strides=(self.stride,),
            padding="VALID", transpose_kernel=True,
            dtype=self.dtype, name="conv",
        )(x)
        trim = self.kernel_size - self.stride
        return y[:, : y.shape[1] - trim] if trim else y


class _LSTM(DeclaredParams, nn.Module):
    """torch-layout LSTM stack with residual (EncodecLSTM semantics).

    Parameters keep the torch names/shapes (weight_ih_l0 [4H, H], gate
    order i,f,g,o) so conversion is a verbatim copy; the recurrence is a
    lax.scan over time.
    """

    dim: int
    num_layers: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):  # [B, T, C]
        residual = x
        h0 = jnp.zeros((x.shape[0], self.dim), x.dtype)
        for layer in range(self.num_layers):
            w_ih = self.param(
                f"weight_ih_l{layer}", nn.initializers.zeros,
                (4 * self.dim, self.dim),
            )
            w_hh = self.param(
                f"weight_hh_l{layer}", nn.initializers.zeros,
                (4 * self.dim, self.dim),
            )
            b_ih = self.param(
                f"bias_ih_l{layer}", nn.initializers.zeros, (4 * self.dim,)
            )
            b_hh = self.param(
                f"bias_hh_l{layer}", nn.initializers.zeros, (4 * self.dim,)
            )
            # hoist the input projection out of the scan: one big matmul
            gates_x = x @ w_ih.T + b_ih + b_hh

            def step(carry, gx, w_hh=w_hh):
                h, c = carry
                gates = gx + h @ w_hh.T
                i, f, g, o = jnp.split(gates, 4, axis=-1)
                c = nn.sigmoid(f) * c + nn.sigmoid(i) * jnp.tanh(g)
                h = nn.sigmoid(o) * jnp.tanh(c)
                return (h, c), h

            _, hs = jax.lax.scan(
                step, (h0, h0), jnp.moveaxis(gates_x, 0, 1)
            )
            x = jnp.moveaxis(hs, 0, 1)
        return x + residual


class _ResnetBlock(nn.Module):
    config: EncodecConfig
    dim: int
    dilations: tuple[int, ...]
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        hidden = self.dim // cfg.compress
        kernel_sizes = (cfg.residual_kernel_size, 1)
        h = x
        # block indices interleave ELU modules like the torch ModuleList
        # (block.0 = ELU, block.1 = conv, block.2 = ELU, block.3 = conv)
        for i, (k, dil) in enumerate(zip(kernel_sizes, self.dilations)):
            h = nn.elu(h)
            out_ch = self.dim if i == len(kernel_sizes) - 1 else hidden
            h = _CausalConv(
                out_ch, k, dilation=dil, pad_mode=cfg.pad_mode,
                dtype=self.dtype, name=f"block_{2 * i + 1}",
            )(h)
        if cfg.use_conv_shortcut:
            x = _CausalConv(
                self.dim, 1, pad_mode=cfg.pad_mode, dtype=self.dtype,
                name="shortcut",
            )(x)
        return x + h


class _Decoder(nn.Module):
    config: EncodecConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        scaling = 2 ** len(cfg.upsampling_ratios)
        idx = 0
        x = _CausalConv(
            scaling * cfg.num_filters, cfg.kernel_size,
            pad_mode=cfg.pad_mode, dtype=self.dtype, name=f"layers_{idx}",
        )(x)
        idx += 1
        x = _LSTM(
            scaling * cfg.num_filters, cfg.num_lstm_layers,
            dtype=self.dtype, name=f"layers_{idx}",
        )(x)
        idx += 1
        for ratio in cfg.upsampling_ratios:
            current = scaling * cfg.num_filters
            x = nn.elu(x)
            idx += 1  # the ELU occupies a ModuleList slot in torch
            x = _CausalConvTranspose(
                current // 2, ratio * 2, ratio, dtype=self.dtype,
                name=f"layers_{idx}",
            )(x)
            idx += 1
            for j in range(cfg.num_residual_layers):
                x = _ResnetBlock(
                    cfg, current // 2,
                    (cfg.dilation_growth_rate ** j, 1),
                    dtype=self.dtype, name=f"layers_{idx}",
                )(x)
                idx += 1
            scaling //= 2
        x = nn.elu(x)
        idx += 1
        return _CausalConv(
            cfg.audio_channels, cfg.last_kernel_size,
            pad_mode=cfg.pad_mode, dtype=self.dtype, name=f"layers_{idx}",
        )(x)


class EncodecDecoderModel(DeclaredParams, nn.Module):
    """RVQ codes [B, K, T] -> waveform [B, T * hop] (hop = prod(ratios))."""

    config: EncodecConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, codes):
        cfg = self.config
        b, k, t = codes.shape
        quantized = jnp.zeros((b, t, cfg.hidden_size), self.dtype)
        for i in range(k):
            embed = self.param(
                f"codebook_{i}", nn.initializers.normal(0.02),
                (cfg.codebook_size, cfg.hidden_size),
            )
            quantized = quantized + jnp.asarray(embed, self.dtype)[codes[:, i]]
        wav = _Decoder(cfg, dtype=self.dtype, name="decoder")(quantized)
        return wav[..., 0] if cfg.audio_channels == 1 else wav

    @property
    def hop(self) -> int:
        out = 1
        for r in self.config.upsampling_ratios:
            out *= r
        return out
