"""GPT-2 trunk (transformers GPT2Model) — AudioLDM2's "language model".

Reference behavior replaced: the reference can serve AudioLDM2 jobs via
`parameters.pipeline_type = "AudioLDM2Pipeline"` (swarm/job_arguments.py
get_type resolves any diffusers class; the shipped callback is the same
txt2audio path, swarm/audio/audioldm.py:12-21). AudioLDM2 uses GPT-2
purely as an embedding-space sequence model: the projected CLAP+T5
sequence goes in as `inputs_embeds`, and generation appends the LAST
HIDDEN STATE eight times (no sampling, no vocabulary) — so this module
carries no token embedding at all (wte is dead weight for serving, like
the MoVQ codebook).

transformers stores the attention/MLP projections as Conv1D with (in,
out)-shaped weights — exactly flax Dense's kernel layout, so conversion
(models/conversion.py convert_gpt2) copies them UNtransposed.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax.numpy as jnp

from .layers import DeclaredParams, Dense, LayerNorm


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    n_positions: int = 1024
    layer_norm_epsilon: float = 1e-5


TINY_GPT2 = GPT2Config(hidden_size=32, num_layers=2, num_heads=4,
                       n_positions=64)


class _Block(nn.Module):
    config: GPT2Config
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, bias):
        cfg = self.config
        b, s, d = x.shape
        heads = cfg.num_heads
        hd = d // heads
        h = LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=self.dtype,
                      name="ln_1")(x)
        qkv = Dense(3 * d, dtype=self.dtype, name="c_attn")(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, s, heads, hd)
        k = k.reshape(b, s, heads, hd)
        v = v.reshape(b, s, heads, hd)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        logits = logits * (hd ** -0.5) + bias
        weights = nn.softmax(logits, axis=-1).astype(self.dtype)
        attn = jnp.einsum("bhqk,bkhd->bqhd", weights, v).reshape(b, s, d)
        x = x + Dense(d, dtype=self.dtype, name="c_proj")(attn)
        h = LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=self.dtype,
                      name="ln_2")(x)
        h = Dense(4 * d, dtype=self.dtype, name="c_fc")(h)
        h = nn.gelu(h, approximate=True)  # gelu_new
        return x + Dense(d, dtype=self.dtype, name="mlp_c_proj")(h)


class GPT2Model(DeclaredParams, nn.Module):
    """[B, S, hidden] input embeddings (+ optional [B, S] 1-keep padding
    mask) -> [B, S, hidden] final hidden states (causal)."""

    config: GPT2Config
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, inputs_embeds, attention_mask=None):
        cfg = self.config
        b, s, d = inputs_embeds.shape
        wpe = self.param(
            "wpe", nn.initializers.normal(0.02), (cfg.n_positions, d)
        )
        x = jnp.asarray(inputs_embeds, self.dtype) + jnp.asarray(
            wpe[:s], self.dtype
        )
        causal = jnp.tril(jnp.ones((s, s), bool))
        bias = jnp.where(causal[None, None], 0.0, -1e9)
        if attention_mask is not None:
            bias = bias + jnp.where(
                attention_mask[:, None, None, :].astype(bool), 0.0, -1e9
            )
        for i in range(cfg.num_layers):
            x = _Block(cfg, dtype=self.dtype, name=f"h_{i}")(x, bias)
        return LayerNorm(
            epsilon=cfg.layer_norm_epsilon, dtype=self.dtype, name="ln_f"
        )(x)
