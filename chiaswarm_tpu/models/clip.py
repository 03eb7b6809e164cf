"""CLIP text encoders (SD1.x ViT-L, SD2.x OpenCLIP-H, SDXL dual encoders).

Config-driven flax transformer with causal masking; supports returning the
penultimate hidden state (SD2/SDXL use clip-skip style conditioning) and a
final text projection (SDXL's second encoder pools + projects).
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax.numpy as jnp

from .layers import DeclaredParams, Dense, Embed, LayerNorm


@dataclasses.dataclass(frozen=True)
class CLIPTextConfig:
    vocab_size: int = 49408
    hidden_size: int = 1024
    num_layers: int = 23
    num_heads: int = 16
    max_positions: int = 77
    intermediate_mult: int = 4
    hidden_act: str = "gelu"  # gelu (SD2/XL) | quick_gelu (SD1.x ViT-L)
    # output selection: -1 = final layer norm output; -2 = penultimate layer
    hidden_state_index: int = -1
    # False + index -1: the LAST layer's output BEFORE the final LayerNorm
    # (HF `hidden_states[-1]` — Stable Cascade's prior/decoder conditioning)
    apply_final_norm: bool = True
    projection_dim: int = 0  # >0: emit pooled projection (SDXL encoder 2)


def _act(name: str):
    if name == "quick_gelu":
        return lambda x: x * nn.sigmoid(1.702 * x)
    # exact erf gelu (transformers "gelu"); flax defaults to tanh approx
    return lambda x: nn.gelu(x, approximate=False)


class CLIPAttention(nn.Module):
    config: CLIPTextConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, hidden, mask):
        cfg = self.config
        head_dim = cfg.hidden_size // cfg.num_heads
        b, s, _ = hidden.shape

        def heads(name):
            return Dense(cfg.hidden_size, dtype=self.dtype, name=name)(
                hidden
            ).reshape(b, s, cfg.num_heads, head_dim)

        q, k, v = heads("q_proj"), heads("k_proj"), heads("v_proj")
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * head_dim**-0.5
        logits = logits + mask
        weights = nn.softmax(logits.astype(jnp.float32), axis=-1).astype(self.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", weights, v).reshape(b, s, cfg.hidden_size)
        return Dense(cfg.hidden_size, dtype=self.dtype, name="out_proj")(out)


class CLIPLayer(nn.Module):
    config: CLIPTextConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, hidden, mask):
        cfg = self.config
        hidden = hidden + CLIPAttention(cfg, dtype=self.dtype, name="self_attn")(
            LayerNorm(epsilon=1e-5, dtype=self.dtype, name="layer_norm1")(hidden),
            mask,
        )
        mlp_in = LayerNorm(epsilon=1e-5, dtype=self.dtype, name="layer_norm2")(hidden)
        h = Dense(
            cfg.hidden_size * cfg.intermediate_mult, dtype=self.dtype, name="fc1"
        )(mlp_in)
        h = _act(cfg.hidden_act)(h)
        return hidden + Dense(cfg.hidden_size, dtype=self.dtype, name="fc2")(h)


class CLIPTextEncoder(DeclaredParams, nn.Module):
    config: CLIPTextConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, input_ids, extra_embeddings=None, attention_mask=None):
        """input_ids [B, 77] -> dict with:
        - hidden_states: [B, 77, D] conditioning sequence (per config index)
        - pooled: [B, D or projection_dim] EOS-token pooled output

        `attention_mask` [B, S] (1 = attend) composes with the causal mask
        — Stable Cascade's pipelines mask padding (most SD-family callers
        don't pass one, matching diffusers).

        `extra_embeddings` [K, D] carries textual-inversion placeholder
        vectors: ids >= vocab_size index into it (id - vocab_size). Passed
        as data rather than grafted into the Embed table so the resident
        param tree (and its flax shape contract) never changes per job.
        """
        cfg = self.config
        b, s = input_ids.shape

        tok = Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=self.dtype, name="token_embedding"
        )(jnp.minimum(input_ids, cfg.vocab_size - 1))
        if extra_embeddings is not None:
            is_extra = input_ids >= cfg.vocab_size
            extra_idx = jnp.clip(
                input_ids - cfg.vocab_size, 0, extra_embeddings.shape[0] - 1
            )
            tok = jnp.where(
                is_extra[..., None],
                extra_embeddings.astype(tok.dtype)[extra_idx],
                tok,
            )
        pos = self.param(
            "position_embedding",
            nn.initializers.normal(0.01),
            (cfg.max_positions, cfg.hidden_size),
        ).astype(self.dtype)
        hidden = tok + pos[None, :s, :]

        causal = jnp.triu(jnp.full((s, s), -1e9, self.dtype), k=1)[None, None]
        if attention_mask is not None:
            pad = jnp.where(
                attention_mask[:, None, None, :].astype(bool), 0.0, -1e9
            ).astype(self.dtype)
            causal = causal + pad

        collected = []
        for i in range(cfg.num_layers):
            collected.append(hidden)
            hidden = CLIPLayer(cfg, dtype=self.dtype, name=f"layers_{i}")(hidden, causal)
        pre_ln = hidden
        final = LayerNorm(epsilon=1e-5, dtype=self.dtype, name="final_layer_norm")(
            hidden
        )
        collected.append(final)  # index -1

        # hidden_state_index -2 = input of the last layer (diffusers clip-skip)
        if cfg.hidden_state_index == -1:
            out_hidden = final if cfg.apply_final_norm else pre_ln
        else:
            out_hidden = collected[cfg.hidden_state_index]

        # pooled = final-LN state at each sequence's first EOS. EOS is the
        # highest id in the BASE vocab (both tokenizers), but textual-
        # inversion placeholder ids sit past it — match the id exactly
        # instead of argmax-ing raw ids
        eos_idx = jnp.argmax(
            (input_ids == cfg.vocab_size - 1).astype(jnp.int32), axis=-1
        )
        pooled = final[jnp.arange(b), eos_idx]
        if cfg.projection_dim:
            pooled = Dense(
                cfg.projection_dim, use_bias=False, dtype=self.dtype,
                name="text_projection",
            )(pooled)

        return {"hidden_states": out_hidden, "pooled": pooled}
