"""T5 text encoder (encoder-only), flax.linen — Flux's context encoder.

Reference context: Flux pipelines carry `max_sequence_length` 256/512 T5
tokens (reference swarm/test.py:259,283); the reference loads the encoder
through diffusers. This is the architecture rebuilt for XLA: pre-RMSNorm
blocks, relative-position-bucket attention bias computed once and shared
across layers (T5 semantics: only layer 0 owns the embedding table), and
gated-GELU FFN. Module names mirror the HF graph section-for-section so
conversion is a mechanical rename (models/conversion.py convert_t5).
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax.numpy as jnp
import numpy as np

from .layers import DeclaredParams, Dense, Embed


@dataclasses.dataclass(frozen=True)
class T5Config:
    vocab_size: int = 32128
    d_model: int = 4096
    d_kv: int = 64
    num_heads: int = 64
    d_ff: int = 10240
    num_layers: int = 24
    relative_attention_num_buckets: int = 32
    relative_attention_max_distance: int = 128
    layer_norm_epsilon: float = 1e-6


# Flux uses T5-XXL; the tiny config keeps every structural feature
TINY_T5 = T5Config(
    vocab_size=1000, d_model=32, d_kv=8, num_heads=4, d_ff=64, num_layers=2
)


def t5_config_from_json(cj: dict | None) -> T5Config:
    """Geometry from a transformers T5/UL2 config.json (Kandinsky 3 rides
    FLAN-UL2's encoder: d_model 4096, 32 layers, d_ff 16384, 16x256
    heads — different from Flux's XXL defaults)."""
    cj = cj or {}
    base = T5Config()
    return T5Config(
        vocab_size=int(cj.get("vocab_size", base.vocab_size)),
        d_model=int(cj.get("d_model", base.d_model)),
        d_kv=int(cj.get("d_kv", base.d_kv)),
        num_heads=int(cj.get("num_heads", base.num_heads)),
        d_ff=int(cj.get("d_ff", base.d_ff)),
        num_layers=int(cj.get("num_layers", base.num_layers)),
        relative_attention_num_buckets=int(
            cj.get("relative_attention_num_buckets",
                   base.relative_attention_num_buckets)
        ),
        relative_attention_max_distance=int(
            cj.get("relative_attention_max_distance",
                   base.relative_attention_max_distance)
        ),
        layer_norm_epsilon=float(
            cj.get("layer_norm_epsilon", base.layer_norm_epsilon)
        ),
    )


class RMSNorm(DeclaredParams, nn.Module):
    epsilon: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        x = x * (var + self.epsilon) ** -0.5
        return (x * scale).astype(self.dtype)


def relative_position_buckets(qlen: int, klen: int, num_buckets: int,
                              max_distance: int) -> np.ndarray:
    """T5's log-bucketed relative positions (bidirectional encoder form).

    Computed host-side with numpy — it depends only on static lengths, so
    it constant-folds into the compiled program.
    """
    context = np.arange(qlen)[:, None]
    memory = np.arange(klen)[None, :]
    rel = memory - context
    buckets = np.zeros_like(rel)
    half = num_buckets // 2
    buckets += (rel > 0).astype(np.int64) * half
    rel = np.abs(rel)
    max_exact = half // 2
    is_small = rel < max_exact
    large = max_exact + (
        np.log(np.maximum(rel, 1) / max_exact)
        / np.log(max_distance / max_exact)
        * (half - max_exact)
    ).astype(np.int64)
    large = np.minimum(large, half - 1)
    buckets += np.where(is_small, rel, large)
    return buckets


class T5Attention(DeclaredParams, nn.Module):
    config: T5Config
    dtype: jnp.dtype = jnp.float32
    has_relative_bias: bool = False

    @nn.compact
    def __call__(self, x, position_bias=None, attention_mask=None):
        cfg = self.config
        b, s, _ = x.shape
        inner = cfg.num_heads * cfg.d_kv
        # T5 projections carry no bias and no 1/sqrt(d) scaling (folded into
        # the stored weights at training time)
        q = Dense(inner, use_bias=False, dtype=self.dtype, name="q")(x)
        k = Dense(inner, use_bias=False, dtype=self.dtype, name="k")(x)
        v = Dense(inner, use_bias=False, dtype=self.dtype, name="v")(x)
        q = q.reshape(b, s, cfg.num_heads, cfg.d_kv)
        k = k.reshape(b, s, cfg.num_heads, cfg.d_kv)
        v = v.reshape(b, s, cfg.num_heads, cfg.d_kv)

        if self.has_relative_bias:
            table = self.param(
                "relative_attention_bias",
                nn.initializers.normal(1.0),
                (cfg.relative_attention_num_buckets, cfg.num_heads),
            )
            buckets = relative_position_buckets(
                s, s, cfg.relative_attention_num_buckets,
                cfg.relative_attention_max_distance,
            )
            position_bias = jnp.transpose(
                jnp.asarray(table)[jnp.asarray(buckets)], (2, 0, 1)
            )[None]  # [1, H, S, S]

        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        if position_bias is not None:
            logits = logits + position_bias.astype(jnp.float32)
        if attention_mask is not None:
            # [B, S] 1-keep mask over keys (transformers' extended-mask
            # additive form: masked keys get a large negative)
            logits = jnp.where(
                attention_mask[:, None, None, :].astype(bool),
                logits,
                jnp.asarray(-1e9, jnp.float32),
            )
        weights = nn.softmax(logits, axis=-1).astype(self.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", weights, v).reshape(b, s, inner)
        return Dense(
            cfg.d_model, use_bias=False, dtype=self.dtype, name="o"
        )(out), position_bias


class T5Block(nn.Module):
    config: T5Config
    dtype: jnp.dtype = jnp.float32
    has_relative_bias: bool = False

    @nn.compact
    def __call__(self, x, position_bias=None, attention_mask=None):
        cfg = self.config
        y = RMSNorm(cfg.layer_norm_epsilon, dtype=self.dtype, name="attn_norm")(x)
        y, position_bias = T5Attention(
            cfg, dtype=self.dtype, has_relative_bias=self.has_relative_bias,
            name="attention",
        )(y, position_bias, attention_mask)
        x = x + y
        y = RMSNorm(cfg.layer_norm_epsilon, dtype=self.dtype, name="ff_norm")(x)
        # gated-GELU FFN (T5 v1.1 / XXL): gelu(wi_0(x)) * wi_1(x) -> wo
        gate = Dense(cfg.d_ff, use_bias=False, dtype=self.dtype, name="wi_0")(y)
        value = Dense(cfg.d_ff, use_bias=False, dtype=self.dtype, name="wi_1")(y)
        y = nn.gelu(gate, approximate=True) * value
        y = Dense(cfg.d_model, use_bias=False, dtype=self.dtype, name="wo")(y)
        return x + y, position_bias


class T5Encoder(nn.Module):
    config: T5Config
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, input_ids, attention_mask=None):
        """[B, S] int32 (+ [B, S] 1-keep mask) -> [B, S, d_model]."""
        cfg = self.config
        x = Embed(
            cfg.vocab_size, cfg.d_model, dtype=self.dtype, name="token_embedding"
        )(input_ids)
        position_bias = None
        for i in range(cfg.num_layers):
            x, position_bias = T5Block(
                cfg, dtype=self.dtype, has_relative_bias=(i == 0),
                name=f"block_{i}",
            )(x, position_bias, attention_mask)
        return RMSNorm(cfg.layer_norm_epsilon, dtype=self.dtype,
                       name="final_norm")(x)
