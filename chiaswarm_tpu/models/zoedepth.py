"""ZoeDepth metric depth (transformers ZoeDepthForDepthEstimation,
BEiT-large backbone, ZoeD_N single-configuration head) — the learned
model behind the `zoe depth` preprocessor.

Reference behavior replaced: swarm/pre_processors/zoe_depth.py:8-13
(torch-hub ZoeDepth invoked per call). The graph, ported from the
installed transformers modeling source as ground truth:
- BEiT: patch conv + CLS token, 24 pre-LN blocks with per-layer 2D
  relative-position-bias tables (bias-free key projection, layer-scale
  lambdas), four tap points (after layers 6/12/18/24) that keep the CLS
  token for the DPT readout;
- DPT-style neck: readout-projected reassemble to four resolutions
  (transposed-conv x4/x2, identity, strided conv x0.5), 3x3 projections,
  top-down fusion with pre-activation residual units and align-corners
  2x upsampling;
- relative-depth head (3 convs) whose 32-feature activation conditions
- the metric-bins head: seed bin regressor (softplus, unnormed),
  four attractor layers (inv-attractor contraction with the upstream
  default alpha=300/gamma=2 — the config fields are unused upstream),
  projector MLPs over the fused pyramid, and a conditional log-binomial
  softmax (Stirling log-binom) over bin centers.

Serving runs a FIXED square canvas equal to the trained window (the
relative-position tables then index directly, no bilinear table
interpolation). Module names line up with the transformers state-dict
names so conversion (models/conversion.py convert_zoedepth) is a
mechanical rename.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .cascade_unet import interpolate_bilinear_align_corners
from .layers import Conv, DeclaredParams, Dense, LayerNorm


@dataclasses.dataclass(frozen=True)
class ZoeConfig:
    # BEiT backbone
    image_size: int = 384
    patch_size: int = 16
    hidden_size: int = 1024
    num_layers: int = 24
    num_heads: int = 16
    intermediate_size: int = 4096
    layer_norm_eps: float = 1e-12
    out_indices: tuple[int, ...] = (6, 12, 18, 24)
    # neck + heads
    reassemble_factors: tuple[float, ...] = (4, 2, 1, 0.5)
    neck_hidden_sizes: tuple[int, ...] = (96, 192, 384, 768)
    fusion_hidden_size: int = 256
    bottleneck_features: int = 256
    num_relative_features: int = 32
    num_attractors: tuple[int, ...] = (16, 8, 4, 1)
    bin_embedding_dim: int = 128
    n_bins: int = 64
    min_depth: float = 1e-3
    max_depth: float = 10.0
    min_temp: float = 0.0212
    max_temp: float = 50.0
    # transformers single-head defaults (NOT scaled from bin_embedding_dim
    # — the multi-head variant does that, the single head does not)
    seed_mlp_dim: int = 256
    projector_mlp_dim: int = 128

    @property
    def window(self) -> int:
        return self.image_size // self.patch_size


TINY_ZOE = ZoeConfig(
    image_size=64,
    patch_size=16,
    hidden_size=32,
    num_layers=4,
    num_heads=4,
    intermediate_size=64,
    out_indices=(1, 2, 3, 4),
    neck_hidden_sizes=(8, 16, 24, 32),
    fusion_hidden_size=16,
    bottleneck_features=16,
    num_relative_features=8,
    num_attractors=(4, 2, 2, 1),
    bin_embedding_dim=16,
    n_bins=8,
)


def beit_relative_position_index(window: int) -> np.ndarray:
    """(W^2+1)^2 index into the (2W-1)^2+3 bias table (CLS rows use the
    trailing three special entries) — transformers BeitRelativePositionBias
    semantics at the trained window."""
    num_rel = (2 * window - 1) ** 2 + 3
    coords = np.stack(np.meshgrid(np.arange(window), np.arange(window),
                                  indexing="ij"))
    flat = coords.reshape(2, -1)
    rel = flat[:, :, None] - flat[:, None, :]
    rel = rel.transpose(1, 2, 0).copy()
    rel[:, :, 0] += window - 1
    rel[:, :, 1] += window - 1
    rel[:, :, 0] *= 2 * window - 1
    area = window * window
    index = np.zeros((area + 1, area + 1), np.int32)
    index[1:, 1:] = rel.sum(-1)
    index[0, 0:] = num_rel - 3
    index[0:, 0] = num_rel - 2
    index[0, 0] = num_rel - 1
    return index


class _BeitSelfAttention(DeclaredParams, nn.Module):
    config: ZoeConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        b, s, d = x.shape
        heads = cfg.num_heads
        hd = d // heads
        q = Dense(d, dtype=self.dtype, name="query")(x)
        k = Dense(d, use_bias=False, dtype=self.dtype, name="key")(x)
        v = Dense(d, dtype=self.dtype, name="value")(x)
        q = q.reshape(b, s, heads, hd)
        k = k.reshape(b, s, heads, hd)
        v = v.reshape(b, s, heads, hd)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        logits = logits * (hd ** -0.5)
        table = self.param(
            "relative_position_bias",
            nn.initializers.zeros,
            ((2 * cfg.window - 1) ** 2 + 3, heads),
        )
        index = beit_relative_position_index(cfg.window)
        bias = jnp.asarray(table)[jnp.asarray(index.reshape(-1))]
        bias = bias.reshape(s, s, heads).transpose(2, 0, 1)
        logits = logits + bias[None].astype(jnp.float32)
        weights = nn.softmax(logits, axis=-1).astype(self.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", weights, v).reshape(b, s, d)


class _BeitAttention(nn.Module):
    """transformers BeitAttention: self-attention + output dense (the
    nested `attention.attention` / `attention.output` key shape)."""

    config: ZoeConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        y = _BeitSelfAttention(self.config, dtype=self.dtype,
                               name="attention")(x)

        class _Out(nn.Module):
            dtype: jnp.dtype = jnp.float32

            @nn.compact
            def __call__(self, h):
                return Dense(h.shape[-1], dtype=self.dtype,
                             name="dense")(h)

        return _Out(dtype=self.dtype, name="output")(y)


class _BeitLayer(DeclaredParams, nn.Module):
    config: ZoeConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        d = cfg.hidden_size
        attn = _BeitAttention(cfg, dtype=self.dtype, name="attention")(
            LayerNorm(epsilon=cfg.layer_norm_eps, dtype=self.dtype,
                      name="layernorm_before")(x)
        )
        lambda_1 = self.param("lambda_1", nn.initializers.ones, (d,))
        x = x + attn * jnp.asarray(lambda_1, self.dtype)
        h = LayerNorm(epsilon=cfg.layer_norm_eps, dtype=self.dtype,
                      name="layernorm_after")(x)

        class _Mid(nn.Module):
            width: int
            dtype: jnp.dtype = jnp.float32

            @nn.compact
            def __call__(self, z):
                return nn.gelu(
                    Dense(self.width, dtype=self.dtype, name="dense")(z),
                    approximate=False,
                )

        class _Out(nn.Module):
            width: int
            dtype: jnp.dtype = jnp.float32

            @nn.compact
            def __call__(self, z):
                return Dense(self.width, dtype=self.dtype,
                             name="dense")(z)

        h = _Mid(cfg.intermediate_size, dtype=self.dtype,
                 name="intermediate")(h)
        h = _Out(d, dtype=self.dtype, name="output")(h)
        lambda_2 = self.param("lambda_2", nn.initializers.ones, (d,))
        return x + h * jnp.asarray(lambda_2, self.dtype)


class BeitBackbone(nn.Module):
    """[B, H, W, 3] (H = W = image_size) -> four [B, S+1, hidden] taps
    (CLS kept for the DPT readout)."""

    config: ZoeConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, pixels):
        cfg = self.config
        b = pixels.shape[0]
        p = cfg.patch_size

        class _Embeddings(DeclaredParams, nn.Module):
            dtype: jnp.dtype = jnp.float32

            @nn.compact
            def __call__(self, px):
                class _Patch(nn.Module):
                    dtype: jnp.dtype = jnp.float32

                    @nn.compact
                    def __call__(self, z):
                        return Conv(
                            cfg.hidden_size, (p, p), strides=(p, p),
                            padding="VALID", dtype=self.dtype,
                            name="projection",
                        )(z)

                tokens = _Patch(dtype=self.dtype, name="patch_embeddings")(px)
                tokens = tokens.reshape(b, -1, cfg.hidden_size)
                cls = self.param(
                    "cls_token", nn.initializers.zeros,
                    (1, 1, cfg.hidden_size),
                )
                cls = jnp.broadcast_to(
                    jnp.asarray(cls, self.dtype), (b, 1, cfg.hidden_size)
                )
                return jnp.concatenate([cls, tokens], axis=1)

        x = _Embeddings(dtype=self.dtype, name="embeddings")(
            jnp.asarray(pixels, self.dtype)
        )

        class _Encoder(nn.Module):
            dtype: jnp.dtype = jnp.float32

            @nn.compact
            def __call__(self, h):
                taps = []
                for i in range(cfg.num_layers):
                    h = _BeitLayer(cfg, dtype=self.dtype,
                                   name=f"layer_{i}")(h)
                    if (i + 1) in cfg.out_indices:
                        taps.append(h)
                return taps

        return _Encoder(dtype=self.dtype, name="encoder")(x)


class _ConvTransposeSame(DeclaredParams, nn.Module):
    """torch ConvTranspose2d(kernel=k, stride=k): disjoint k x k output
    blocks — an einsum. Kernel layout (k, k, in, out)."""

    features: int
    k: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, h, w, c = x.shape
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (self.k, self.k, c, self.features),
        )
        bias = self.param("bias", nn.initializers.zeros, (self.features,))
        y = jnp.einsum("bhwi,klio->bhkwlo", x,
                       jnp.asarray(kernel, self.dtype))
        y = y.reshape(b, self.k * h, self.k * w, self.features)
        return y + jnp.asarray(bias, self.dtype)


class _PreActResidual(nn.Module):
    width: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        h = nn.relu(x)
        h = Conv(self.width, (3, 3), padding=((1, 1), (1, 1)),
                 dtype=self.dtype, name="convolution1")(h)
        h = nn.relu(h)
        h = Conv(self.width, (3, 3), padding=((1, 1), (1, 1)),
                 dtype=self.dtype, name="convolution2")(h)
        return x + h


class _FusionLayer(nn.Module):
    width: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, residual=None):
        if residual is not None:
            if residual.shape != x.shape:
                residual = jax.image.resize(
                    residual, x.shape, "bilinear"
                ).astype(residual.dtype)
            x = x + _PreActResidual(self.width, dtype=self.dtype,
                                    name="residual_layer1")(residual)
        x = _PreActResidual(self.width, dtype=self.dtype,
                            name="residual_layer2")(x)
        b, h, w, c = x.shape
        x = interpolate_bilinear_align_corners(x, 2 * h, 2 * w)
        return Conv(self.width, (1, 1), dtype=self.dtype,
                    name="projection")(x)


def _log_binom(n, k, eps=1e-7):
    n = n + eps
    k = k + eps
    return n * jnp.log(n) - k * jnp.log(k) - (n - k) * jnp.log(n - k + eps)


class _ConditionalLogBinomial(nn.Module):
    """mlp.0 (1x1) -> gelu -> mlp.2 (1x1, 4ch) -> softplus, split into a
    binomial probability and temperature, then the Stirling log-binomial
    softmax over n_bins classes."""

    config: ZoeConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, main, condition):
        cfg = self.config
        x = jnp.concatenate([main, condition], axis=-1)
        bottleneck = x.shape[-1] // 2
        x = Conv(bottleneck, (1, 1), dtype=self.dtype, name="mlp_0")(x)
        x = nn.gelu(x, approximate=False)
        x = Conv(4, (1, 1), dtype=self.dtype, name="mlp_2")(x)
        x = nn.softplus(x.astype(jnp.float32))
        eps = 1e-4
        prob = x[..., :2] + eps
        prob = prob[..., 0] / (prob[..., 0] + prob[..., 1])
        temp = x[..., 2:] + eps
        temp = temp[..., 0] / (temp[..., 0] + temp[..., 1])
        temp = (cfg.max_temp - cfg.min_temp) * temp + cfg.min_temp
        prob = jnp.clip(prob, eps, 1.0)[..., None]
        one_minus = jnp.clip(1.0 - prob, eps, 1.0)
        k_idx = jnp.arange(cfg.n_bins, dtype=jnp.float32)
        k_minus_1 = jnp.float32(cfg.n_bins - 1)
        y = (
            _log_binom(k_minus_1, k_idx)
            + k_idx * jnp.log(prob)
            + (k_minus_1 - k_idx) * jnp.log(one_minus)
        )
        return nn.softmax(y / temp[..., None], axis=-1)


class _Mlp1x1(nn.Module):
    """conv1 -> relu -> conv2 (+ optional trailing activation)."""

    mid: int
    out: int
    trailing: str | None = None
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = Conv(self.mid, (1, 1), dtype=self.dtype, name="conv1")(x)
        x = nn.relu(x)
        x = Conv(self.out, (1, 1), dtype=self.dtype, name="conv2")(x)
        if self.trailing == "softplus":
            x = nn.softplus(x.astype(jnp.float32)).astype(x.dtype)
        return x


class ZoeDepthModel(nn.Module):
    """[B, S, S, 3] normalized pixels (S = config.image_size) ->
    [B, S, S] metric depth (meters)."""

    config: ZoeConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, pixels):
        cfg = self.config
        b = pixels.shape[0]
        win = cfg.window
        taps = BeitBackbone(cfg, dtype=self.dtype, name="backbone")(pixels)

        # --- neck: reassemble ---
        class _Reassemble(nn.Module):
            dtype: jnp.dtype = jnp.float32

            @nn.compact
            def __call__(self, taps):
                out = []
                for i, (tap, ch, factor) in enumerate(zip(
                    taps, cfg.neck_hidden_sizes, cfg.reassemble_factors
                )):
                    cls, tokens = tap[:, 0], tap[:, 1:]
                    readout = jnp.broadcast_to(
                        cls[:, None, :], tokens.shape
                    )

                    class _Readout(nn.Module):
                        dtype: jnp.dtype = jnp.float32

                        @nn.compact
                        def __call__(self, z):
                            # torch key readout_projects.N.0 -> "proj"
                            # (a bare digit child would collide with the
                            # digit-merge rename)
                            return nn.gelu(
                                Dense(cfg.hidden_size, dtype=self.dtype,
                                      name="proj")(z),
                                approximate=False,
                            )

                    h = _Readout(dtype=self.dtype, name=f"readout_projects_{i}")(
                        jnp.concatenate([tokens, readout], axis=-1)
                    )
                    h = h.reshape(b, win, win, cfg.hidden_size)

                    class _Layer(nn.Module):
                        dtype: jnp.dtype = jnp.float32

                        @nn.compact
                        def __call__(self, z):
                            z = Conv(ch, (1, 1), dtype=self.dtype,
                                     name="projection")(z)
                            if factor > 1:
                                z = _ConvTransposeSame(
                                    ch, int(factor), dtype=self.dtype,
                                    name="resize",
                                )(z)
                            elif factor < 1:
                                s = int(1 / factor)
                                z = Conv(
                                    ch, (3, 3), strides=(s, s),
                                    padding=((1, 1), (1, 1)),
                                    dtype=self.dtype, name="resize",
                                )(z)
                            return z

                    out.append(_Layer(dtype=self.dtype,
                                      name=f"layers_{i}")(h))
                return out

        class _Neck(nn.Module):
            dtype: jnp.dtype = jnp.float32

            @nn.compact
            def __call__(self, taps):
                feats = _Reassemble(dtype=self.dtype,
                                    name="reassemble_stage")(taps)
                feats = [
                    Conv(cfg.fusion_hidden_size, (3, 3),
                         padding=((1, 1), (1, 1)), use_bias=False,
                         dtype=self.dtype, name=f"convs_{i}")(f)
                    for i, f in enumerate(feats)
                ]

                class _Fusion(nn.Module):
                    dtype: jnp.dtype = jnp.float32

                    @nn.compact
                    def __call__(self, feats):
                        fused_states = []
                        fused = None
                        for j, f in enumerate(feats[::-1]):
                            layer = _FusionLayer(
                                cfg.fusion_hidden_size, dtype=self.dtype,
                                name=f"layers_{j}",
                            )
                            fused = layer(f) if fused is None else layer(
                                fused, f
                            )
                            fused_states.append(fused)
                        return fused_states

                fused = _Fusion(dtype=self.dtype, name="fusion_stage")(feats)
                return fused, feats[-1]

        fused_states, bottleneck = _Neck(dtype=self.dtype, name="neck")(taps)

        # --- relative head ---
        class _RelativeHead(nn.Module):
            dtype: jnp.dtype = jnp.float32

            @nn.compact
            def __call__(self, h):
                h = Conv(cfg.fusion_hidden_size // 2, (3, 3),
                         padding=((1, 1), (1, 1)), dtype=self.dtype,
                         name="conv1")(h)
                bb, hh, ww, _ = h.shape
                h = interpolate_bilinear_align_corners(h, 2 * hh, 2 * ww)
                h = Conv(cfg.num_relative_features, (3, 3),
                         padding=((1, 1), (1, 1)), dtype=self.dtype,
                         name="conv2")(h)
                h = nn.relu(h)
                features = h
                h = Conv(1, (1, 1), dtype=self.dtype, name="conv3")(h)
                h = nn.relu(h)
                return h[..., 0], features

        relative_depth, rel_features = _RelativeHead(
            dtype=self.dtype, name="relative_head"
        )(fused_states[-1])

        # --- metric head (single bin configuration, softplus centers) ---
        class _MetricHead(nn.Module):
            dtype: jnp.dtype = jnp.float32

            @nn.compact
            def __call__(self, outconv, bottleneck, feature_blocks,
                         relative_depth):
                x = Conv(cfg.bottleneck_features, (1, 1),
                         dtype=self.dtype, name="conv2")(bottleneck)
                seed = _Mlp1x1(
                    cfg.seed_mlp_dim, cfg.n_bins,
                    trailing="softplus", dtype=self.dtype,
                    name="seed_bin_regressor",
                )(x)
                prev_bin = seed  # softplus/unnormed: centers ARE the bins
                prev_embedding = _Mlp1x1(
                    cfg.projector_mlp_dim, cfg.bin_embedding_dim,
                    dtype=self.dtype, name="seed_projector",
                )(x)
                bin_centers = prev_bin
                for i, feature in enumerate(feature_blocks):
                    embedding = _Mlp1x1(
                        cfg.projector_mlp_dim, cfg.bin_embedding_dim,
                        dtype=self.dtype, name=f"projectors_{i}",
                    )(feature)

                    class _Attractor(nn.Module):
                        n_attr: int
                        dtype: jnp.dtype = jnp.float32

                        @nn.compact
                        def __call__(self, emb, prev_bin, prev_emb):
                            bb, hh, ww, _ = emb.shape
                            prev_emb = interpolate_bilinear_align_corners(
                                prev_emb, hh, ww
                            )
                            z = emb + prev_emb
                            z = Conv(cfg.bin_embedding_dim, (1, 1),
                                     dtype=self.dtype, name="conv1")(z)
                            z = nn.relu(z)
                            z = Conv(self.n_attr, (1, 1),
                                     dtype=self.dtype, name="conv2")(z)
                            attractors = nn.softplus(
                                z.astype(jnp.float32)
                            )
                            centers = interpolate_bilinear_align_corners(
                                prev_bin.astype(jnp.float32), hh, ww
                            )
                            # upstream calls inv_attractor with its
                            # DEFAULTS (alpha=300, gamma=2) — the config
                            # fields are unused there
                            dx = (attractors[..., None] -
                                  centers[..., None, :])
                            # attractor_kind "mean": average the per-
                            # attractor contractions
                            delta = jnp.mean(
                                dx / (1.0 + 300.0 * dx * dx), axis=-2
                            )
                            new_centers = centers + delta
                            return new_centers, new_centers

                    prev_bin, bin_centers = _Attractor(
                        cfg.num_attractors[i], dtype=self.dtype,
                        name=f"attractors_{i}",
                    )(embedding, prev_bin, prev_embedding)
                    prev_embedding = embedding

                rel = relative_depth[..., None]
                bb, hh, ww, _ = outconv.shape
                rel = interpolate_bilinear_align_corners(rel, hh, ww)
                last = jnp.concatenate([outconv, rel.astype(outconv.dtype)],
                                       axis=-1)
                embedding = interpolate_bilinear_align_corners(
                    prev_embedding, hh, ww
                )
                probs = _ConditionalLogBinomial(
                    cfg, dtype=self.dtype, name="conditional_log_binomial"
                )(last, embedding)
                centers = interpolate_bilinear_align_corners(
                    bin_centers, hh, ww
                )
                return jnp.sum(probs * centers, axis=-1)

        return _MetricHead(dtype=self.dtype, name="metric_head")(
            rel_features, bottleneck, fused_states, relative_depth
        )
