"""Diffusion prior transformer: text -> CLIP image embedding (Kandinsky 2.x).

Reference behavior replaced: KandinskyV22PriorPipeline loaded fresh per job
and run before the main pipeline (swarm/diffusion/pipeline_steps.py:7-38,
including the split-embeds mode where `prior_timesteps` rides the job). The
prior denoises in CLIP *embedding* space: a transformer over
[text tokens | text embed | timestep | noisy image embed | learned query]
predicts the clean image embedding each step.

The graph matches diffusers' `PriorTransformer` (the module the K2.2 prior
checkpoint ships) parameter-for-parameter so conversion is mechanical:
sinusoidal time features at the INNER width -> 2-layer MLP, per-input
projections, learned positional + prd embeddings, pre-LN blocks with
biased qkv and exact-gelu FF, final LayerNorm + projection read from the
last (prd) token. When `attention_mask` is provided the blocks run CAUSAL
attention with padded text masked — PriorTransformer's behavior whenever
the pipeline passes the text mask.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax.numpy as jnp

from .layers import (
    DeclaredParams,
    Dense,
    LayerNorm,
    TimestepEmbedding,
    timestep_embedding,
)


@dataclasses.dataclass(frozen=True)
class PriorConfig:
    embed_dim: int = 1280  # CLIP image-embedding width (ViT-bigG)
    num_heads: int = 32
    head_dim: int = 64  # inner width = heads * head_dim = 2048
    num_layers: int = 20  # kandinsky-2-2-prior geometry
    text_seq: int = 77
    text_dim: int = 1280  # text-encoder hidden width
    additional_tokens: int = 4  # [text embed, time, sample, prd]

    @property
    def hidden_size(self) -> int:
        return self.num_heads * self.head_dim


TINY_PRIOR = PriorConfig(
    embed_dim=32, num_heads=4, head_dim=16, num_layers=2, text_seq=77,
    text_dim=32,
)


class PriorBlock(nn.Module):
    """Pre-LN transformer block matching PriorTransformer's
    BasicTransformerBlock(attention_bias=True, activation_fn='gelu'):
    norm1 -> biased multihead self-attention -> norm3 -> exact-gelu FF."""

    config: PriorConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, mask=None):
        cfg = self.config
        h, hd = cfg.num_heads, cfg.head_dim
        inner = cfg.hidden_size
        b, s, _ = x.shape
        y = LayerNorm(epsilon=1e-5, dtype=self.dtype, name="norm1")(x)
        proj = lambda name: Dense(inner, dtype=self.dtype, name=name)(
            y
        ).reshape(b, s, h, hd)
        q, k, v = proj("to_q"), proj("to_k"), proj("to_v")
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd**-0.5
        if mask is not None:
            logits = logits + mask
        w = nn.softmax(logits.astype(jnp.float32), axis=-1).astype(self.dtype)
        attn = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, s, inner)
        x = x + Dense(inner, dtype=self.dtype, name="to_out_0")(attn)
        y = LayerNorm(epsilon=1e-5, dtype=self.dtype, name="norm3")(x)
        y = Dense(4 * inner, dtype=self.dtype, name="ff_proj")(y)
        y = nn.gelu(y, approximate=False)
        return x + Dense(inner, dtype=self.dtype, name="ff_out")(y)


class DiffusionPrior(DeclaredParams, nn.Module):
    config: PriorConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, noisy_embed, timesteps, text_hiddens, text_embed,
                 attention_mask=None):
        """noisy_embed [B, E], timesteps [B], text_hiddens [B, S, Dt],
        text_embed [B, Dt], attention_mask [B, S] keep-mask or None ->
        predicted clean image embed [B, E]."""
        cfg = self.config
        inner = cfg.hidden_size
        b = noisy_embed.shape[0]
        t_feat = timestep_embedding(
            timesteps, inner, dtype=self.dtype
        )
        time_tok = TimestepEmbedding(inner, dtype=self.dtype,
                                     name="time_embedding")(t_feat)
        tokens = [
            Dense(inner, dtype=self.dtype,
                  name="encoder_hidden_states_proj")(
                text_hiddens.astype(self.dtype)
            ),
            Dense(inner, dtype=self.dtype, name="embed_proj")(
                text_embed.astype(self.dtype)
            )[:, None],
            time_tok[:, None],
            Dense(inner, dtype=self.dtype, name="proj_in")(
                noisy_embed.astype(self.dtype)
            )[:, None],
            jnp.broadcast_to(
                self.param(
                    "prd_embedding", nn.initializers.normal(0.02),
                    (1, 1, inner),
                ).astype(self.dtype),
                (b, 1, inner),
            ),
        ]
        x = jnp.concatenate(tokens, axis=1)
        seq = cfg.text_seq + cfg.additional_tokens
        pos = self.param(
            "positional_embedding", nn.initializers.normal(0.02),
            (1, seq, inner),
        ).astype(self.dtype)
        x = x + pos

        mask = None
        if attention_mask is not None:
            # PriorTransformer: pad mask over the text tokens (additional
            # tokens always attended) PLUS a causal triangle
            pad = (1.0 - attention_mask.astype(jnp.float32)) * -1e4
            pad = jnp.pad(pad, ((0, 0), (0, cfg.additional_tokens)))
            causal = jnp.triu(jnp.full((seq, seq), -1e4, jnp.float32), k=1)
            mask = (pad[:, None, :] + causal[None]).astype(self.dtype)[
                :, None, :, :
            ]

        for i in range(cfg.num_layers):
            x = PriorBlock(cfg, dtype=self.dtype,
                           name=f"transformer_blocks_{i}")(x, mask)
        x = LayerNorm(epsilon=1e-5, dtype=self.dtype, name="norm_out")(x)
        # the learned prd token carries the prediction
        return Dense(cfg.embed_dim, dtype=self.dtype,
                     name="proj_to_clip_embeddings")(x[:, -1])
