"""Multilingual CLIP text tower (Kandinsky 2.1's encoder).

diffusers' `MultilingualCLIP` = an XLM-RoBERTa-Large trunk + attention-
mask mean pooling + one Linear into the 768-d CLIP space; the decoder
UNet cross-attends to the raw 1024-wide hidden states while the pooled
projection feeds the additive TextImageTimeEmbedding branch (reference
serves it through KandinskyPipeline, swarm/test.py:85-107).

XLM-R is architecturally RoBERTa, which models/clap.py already implements
(same post-LN layers, pad-offset position ids), so the trunk reuses those
blocks and the conversion reuses clap_rename; only the head differs
(mean-pool + `LinearTransformation` instead of CLS-pool + 2-layer MLP).
Numeric parity vs transformers XLMRobertaModel is asserted in
tests/test_kandinsky_conversion.py.
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp

from .clap import ClapTextConfig, _Layer
from .layers import Dense, Embed, LayerNorm

# xlm-roberta-large geometry; serving reads the checkpoint config.json
MCLIP_XLMR_LARGE = ClapTextConfig(
    vocab_size=250_002,
    hidden_size=1024,
    num_layers=24,
    num_heads=16,
    intermediate_size=4096,
    max_positions=514,
    projection_dim=768,
    layer_norm_eps=1e-5,
)

TINY_MCLIP = ClapTextConfig(
    vocab_size=1000, hidden_size=32, num_layers=2, num_heads=4,
    intermediate_size=64, max_positions=80, projection_dim=16,
    layer_norm_eps=1e-5,
)


class MCLIPTextEncoder(nn.Module):
    config: ClapTextConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, input_ids, attention_mask=None):
        """[B, S] int32 -> {"hidden_states" [B,S,D], "pooled_proj" [B,P]}.

        `pooled_proj` = LinearTransformation(mean over non-pad tokens) —
        what the K2.1 UNet's text_embeds branch consumes; the hidden
        states cross-attend through the UNet's text_proj."""
        cfg = self.config
        if attention_mask is None:
            attention_mask = (input_ids != cfg.pad_token_id).astype(
                jnp.float32
            )
        positions = (
            jnp.cumsum(attention_mask.astype(jnp.int32), axis=1)
            * attention_mask.astype(jnp.int32)
            + cfg.pad_token_id
        )
        x = (
            Embed(cfg.vocab_size, cfg.hidden_size, dtype=self.dtype,
                  name="word_embeddings")(input_ids)
            + Embed(cfg.max_positions, cfg.hidden_size, dtype=self.dtype,
                    name="position_embeddings")(positions)
            + Embed(cfg.type_vocab_size, cfg.hidden_size, dtype=self.dtype,
                    name="token_type_embeddings")(
                jnp.zeros_like(input_ids))
        )
        x = LayerNorm(epsilon=cfg.layer_norm_eps, dtype=self.dtype,
                      name="embed_norm")(x)
        for i in range(cfg.num_layers):
            x = _Layer(cfg, dtype=self.dtype, name=f"layers_{i}")(
                x, attention_mask
            )
        denom = jnp.maximum(attention_mask.sum(axis=1, keepdims=True), 1.0)
        pooled = (x * attention_mask[..., None]).sum(axis=1) / denom.astype(
            x.dtype
        )
        proj = Dense(cfg.projection_dim, dtype=self.dtype,
                     name="transformation")(pooled)
        return {"hidden_states": x, "pooled_proj": proj}
