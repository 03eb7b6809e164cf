"""CLAP text encoder (the AudioLDM prompt-conditioning tower).

Reference behavior replaced: diffusers' AudioLDMPipeline embeds prompts
with `ClapTextModelWithProjection` (the reference just calls the pipeline,
swarm/audio/audioldm.py:23-29). This flax module mirrors the transformers
graph — a RoBERTa-style post-LN encoder (learned positions offset past the
padding id, token-type embeddings), a tanh pooler over the CLS token, and
the two-layer CLAP projection into the 512-d joint audio-text space — so
checkpoints convert mechanically (conversion.convert_clap).
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from .layers import Dense, Embed, LayerNorm


@dataclasses.dataclass(frozen=True)
class ClapTextConfig:
    vocab_size: int = 50265  # roberta-base vocabulary
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_positions: int = 514
    type_vocab_size: int = 1
    pad_token_id: int = 1
    projection_dim: int = 512
    layer_norm_eps: float = 1e-12


TINY_CLAP = ClapTextConfig(
    vocab_size=1000, hidden_size=32, num_layers=2, num_heads=4,
    intermediate_size=64, max_positions=80, projection_dim=32,
)


class _SelfAttention(nn.Module):
    config: ClapTextConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, mask):
        cfg = self.config
        h = cfg.num_heads
        d = cfg.hidden_size // h

        def heads(t):
            return t.reshape(t.shape[0], t.shape[1], h, d)

        q = heads(Dense(cfg.hidden_size, dtype=self.dtype, name="query")(x))
        k = heads(Dense(cfg.hidden_size, dtype=self.dtype, name="key")(x))
        v = heads(Dense(cfg.hidden_size, dtype=self.dtype, name="value")(x))
        att = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d**-0.5)
        att = att + (1.0 - mask[:, None, None, :]) * -1e9
        att = jax.nn.softmax(att.astype(jnp.float32), axis=-1).astype(q.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", att, v)
        return out.reshape(x.shape)


class _Layer(nn.Module):
    """Post-LN transformer layer (BERT/RoBERTa convention)."""

    config: ClapTextConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, mask):
        cfg = self.config
        att = _SelfAttention(cfg, dtype=self.dtype, name="self_attn")(x, mask)
        att = Dense(cfg.hidden_size, dtype=self.dtype, name="attn_out")(att)
        x = LayerNorm(
            epsilon=cfg.layer_norm_eps, dtype=self.dtype, name="attn_norm"
        )(x + att)
        h = Dense(
            cfg.intermediate_size, dtype=self.dtype, name="intermediate"
        )(x)
        h = nn.gelu(h, approximate=False)
        h = Dense(cfg.hidden_size, dtype=self.dtype, name="output")(h)
        return LayerNorm(
            epsilon=cfg.layer_norm_eps, dtype=self.dtype, name="output_norm"
        )(x + h)


class ClapTextEncoder(nn.Module):
    config: ClapTextConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, input_ids, attention_mask=None):
        """[B, S] int32 -> {"hidden_states": [B,S,D], "pooled": [B,P]}.

        `pooled` is the CLAP text embedding (tanh pooler -> 2-layer
        projection), the conditioning vector AudioLDM's UNet consumes.
        """
        cfg = self.config
        if attention_mask is None:
            attention_mask = (input_ids != cfg.pad_token_id).astype(jnp.float32)
        # RoBERTa position ids: cumulative index over non-pad tokens,
        # offset past the padding id
        positions = (
            jnp.cumsum(attention_mask.astype(jnp.int32), axis=1)
            * attention_mask.astype(jnp.int32)
            + cfg.pad_token_id
        )
        x = (
            Embed(
                cfg.vocab_size, cfg.hidden_size, dtype=self.dtype,
                name="word_embeddings",
            )(input_ids)
            + Embed(
                cfg.max_positions, cfg.hidden_size, dtype=self.dtype,
                name="position_embeddings",
            )(positions)
            + Embed(
                cfg.type_vocab_size, cfg.hidden_size, dtype=self.dtype,
                name="token_type_embeddings",
            )(jnp.zeros_like(input_ids))
        )
        x = LayerNorm(
            epsilon=cfg.layer_norm_eps, dtype=self.dtype, name="embed_norm"
        )(x)
        for i in range(cfg.num_layers):
            x = _Layer(cfg, dtype=self.dtype, name=f"layers_{i}")(
                x, attention_mask
            )
        pooled = jnp.tanh(
            Dense(cfg.hidden_size, dtype=self.dtype, name="pooler")(x[:, 0])
        )
        # ClapProjectionLayer: linear -> relu -> linear
        p = Dense(cfg.projection_dim, dtype=self.dtype, name="proj_1")(pooled)
        p = nn.relu(p)
        p = Dense(cfg.projection_dim, dtype=self.dtype, name="proj_2")(p)
        return {"hidden_states": x, "pooled": p}
