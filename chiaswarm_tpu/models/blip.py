"""BLIP-style captioner: ViT image encoder + cross-attending text decoder.

Reference swarm/captioning/caption_image.py:12-40 loads transformers BLIP
classes named in the job JSON. TPU rebuild: one flax module pair, greedy
decode as a fixed-length `lax.scan` (static shapes — no dynamic stopping
inside jit; EOS handling happens on host after the scan).
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from .layers import Conv, DeclaredParams, Dense, Embed, LayerNorm


@dataclasses.dataclass(frozen=True)
class BlipConfig:
    image_size: int = 384  # Salesforce/blip-image-captioning-* native size
    patch_size: int = 16
    vision_hidden: int = 768
    vision_layers: int = 12
    vision_heads: int = 12
    vocab_size: int = 30524  # bert-base vocab + [DEC]/[ENC] (BLIP's text side)
    text_hidden: int = 768
    text_layers: int = 12
    text_heads: int = 12
    max_positions: int = 512  # BERT absolute position table
    max_caption_len: int = 24
    bos_token_id: int = 30522  # [DEC]
    eos_token_id: int = 102  # bert [SEP]
    pad_token_id: int = 0


TINY_BLIP = BlipConfig(
    image_size=64, patch_size=16, vision_hidden=32, vision_layers=2,
    vision_heads=4, vocab_size=1000, text_hidden=32, text_layers=2,
    text_heads=4, max_positions=64, max_caption_len=8, bos_token_id=998,
    eos_token_id=999,
)


class _MHA(nn.Module):
    heads: int
    dim: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, q_in, kv_in, mask=None):
        head_dim = self.dim // self.heads
        b, sq, _ = q_in.shape
        sk = kv_in.shape[1]
        proj = lambda x, s, name: Dense(self.dim, dtype=self.dtype, name=name)(
            x
        ).reshape(b, s, self.heads, head_dim)
        q, k, v = proj(q_in, sq, "q"), proj(kv_in, sk, "k"), proj(kv_in, sk, "v")
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * head_dim**-0.5
        if mask is not None:
            logits = logits + mask
        weights = nn.softmax(logits.astype(jnp.float32), axis=-1).astype(self.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", weights, v).reshape(b, sq, self.dim)
        return Dense(self.dim, dtype=self.dtype, name="out")(out)


class VisionEncoder(DeclaredParams, nn.Module):
    """BLIP ViT (pre-LN). Module names line up with the HF checkpoint graph
    (vision_model.*) so convert_blip is a mechanical rename + qkv split."""

    config: BlipConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, pixels):
        """[B, H, W, 3] normalized -> [B, patches+1, D]."""
        cfg = self.config
        x = Conv(
            cfg.vision_hidden, (cfg.patch_size, cfg.patch_size),
            strides=(cfg.patch_size, cfg.patch_size), dtype=self.dtype,
            name="patch_embed",
        )(pixels)
        b, h, w, c = x.shape
        x = x.reshape(b, h * w, c)
        cls = self.param(
            "cls_token", nn.initializers.normal(0.02), (1, 1, cfg.vision_hidden)
        ).astype(self.dtype)
        x = jnp.concatenate([jnp.broadcast_to(cls, (b, 1, c)), x], axis=1)
        pos = self.param(
            "pos_embed", nn.initializers.normal(0.02),
            (1, x.shape[1], cfg.vision_hidden),
        ).astype(self.dtype)
        x = x + pos
        eps = 1e-5  # HF BlipVisionConfig.layer_norm_eps
        for i in range(cfg.vision_layers):
            y = LayerNorm(epsilon=eps, dtype=self.dtype, name=f"ln1_{i}")(x)
            x = x + _MHA(cfg.vision_heads, cfg.vision_hidden, dtype=self.dtype,
                         name=f"attn_{i}")(y, y)
            y = LayerNorm(epsilon=eps, dtype=self.dtype, name=f"ln2_{i}")(x)
            y = Dense(cfg.vision_hidden * 4, dtype=self.dtype, name=f"fc1_{i}")(y)
            y = nn.gelu(y, approximate=False)
            x = x + Dense(cfg.vision_hidden, dtype=self.dtype, name=f"fc2_{i}")(y)
        return LayerNorm(epsilon=eps, dtype=self.dtype, name="ln_post")(x)


def _embed_text(module, cfg: BlipConfig, input_ids, dtype):
    """Word + learned-position embeddings with BERT embedding LN (shared by
    the decoder and the VQA question encoder; identical param names)."""
    s = input_ids.shape[1]
    x = Embed(
        cfg.vocab_size, cfg.text_hidden, dtype=dtype, name="word_embeddings"
    )(input_ids)
    pos = module.param(
        "position_embeddings", nn.initializers.normal(0.02),
        (cfg.max_positions, cfg.text_hidden),
    ).astype(dtype)
    x = x + pos[None, :s]
    return LayerNorm(epsilon=1e-12, dtype=dtype, name="embed_ln")(x)


def _bert_layer(cfg: BlipConfig, dtype, i: int, x, context,
                self_mask=None, context_mask=None):
    """One post-LN BERT layer [self-attn + LN, cross-attn + LN, FFN + LN]
    — the block both TextDecoder and TextEncoder run, differing only in
    the masks. Must be called inside the owner's @nn.compact so the param
    names (self_{i}, cross_{i}, fc1_{i}, ...) land identically whichever
    module runs it."""
    eps = 1e-12  # BERT layer_norm_eps
    y = _MHA(cfg.text_heads, cfg.text_hidden, dtype=dtype,
             name=f"self_{i}")(x, x, self_mask)
    x = LayerNorm(epsilon=eps, dtype=dtype, name=f"self_ln_{i}")(x + y)
    y = _MHA(cfg.text_heads, cfg.text_hidden, dtype=dtype,
             name=f"cross_{i}")(x, context, context_mask)
    x = LayerNorm(epsilon=eps, dtype=dtype, name=f"cross_ln_{i}")(x + y)
    y = Dense(cfg.text_hidden * 4, dtype=dtype, name=f"fc1_{i}")(x)
    y = nn.gelu(y, approximate=False)
    y = Dense(cfg.text_hidden, dtype=dtype, name=f"fc2_{i}")(y)
    return LayerNorm(epsilon=eps, dtype=dtype, name=f"ffn_ln_{i}")(x + y)


def _additive_mask(attention_mask, dtype):
    """[B, K] 1/0 keep-mask -> [B, 1, 1, K] additive logits mask."""
    return ((1.0 - attention_mask.astype(jnp.float32)) * -1e9).astype(dtype)[
        :, None, None, :
    ]


class TextDecoder(nn.Module):
    """BERT-style post-LN causal decoder mirroring HF BLIP's text_decoder
    (BlipTextLMHeadModel): embedding LN, per-layer [self-attn + LN,
    cross-attn over the context + LN, FFN + LN], prediction-head
    transform (dense -> gelu -> LN) before the vocab projection. Post-LN
    ordering and 1e-12 epsilons are load-bearing for converted weights.
    The cross-attention context is the vision embeds for captioning or the
    encoded question for VQA; `context_mask` [B, K] excludes padded
    context positions.
    """

    config: BlipConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, input_ids, image_embeds, context_mask=None):
        """[B, L] ids + [B, K, Dc] -> [B, L, vocab] logits (causal)."""
        cfg = self.config
        s = input_ids.shape[1]
        eps = 1e-12
        x = _embed_text(self, cfg, input_ids, self.dtype)
        causal = jnp.triu(jnp.full((s, s), -1e9, self.dtype), k=1)[None, None]
        ctx = image_embeds.astype(self.dtype)
        ctx_mask = (
            _additive_mask(context_mask, self.dtype)
            if context_mask is not None
            else None
        )
        for i in range(cfg.text_layers):
            x = _bert_layer(cfg, self.dtype, i, x, ctx, causal, ctx_mask)
        y = Dense(cfg.text_hidden, dtype=self.dtype, name="head_dense")(x)
        y = nn.gelu(y, approximate=False)
        y = LayerNorm(epsilon=eps, dtype=self.dtype, name="head_ln")(y)
        return Dense(cfg.vocab_size, dtype=self.dtype, name="lm_head")(y)


def greedy_decode(decoder_apply, params, image_embeds, config: BlipConfig,
                  prefix_ids=None):
    """Fixed-length greedy decode under jit; returns [B, max_len] int32 ids.

    The buffer starts as [BOS, prefix..., EOS-pad]; each scan step writes
    the argmax for the next position. EOS truncation happens host-side.
    """
    b = image_embeds.shape[0]
    max_len = config.max_caption_len
    ids = jnp.full((b, max_len), config.eos_token_id, jnp.int32)
    ids = ids.at[:, 0].set(config.bos_token_id)
    start = 1
    if prefix_ids is not None:
        plen = prefix_ids.shape[1]
        ids = jax.lax.dynamic_update_slice(ids, prefix_ids.astype(jnp.int32), (0, 1))
        start = 1 + plen

    def body(ids, t):
        logits = decoder_apply(params, ids, image_embeds)  # [B, L, V]
        next_id = jnp.argmax(logits[:, t - 1, :], axis=-1).astype(jnp.int32)
        write = t >= start  # keep BOS/prefix intact
        current = jax.lax.dynamic_slice_in_dim(ids, t, 1, axis=1)[:, 0]
        next_id = jnp.where(write, next_id, current)
        ids = jax.lax.dynamic_update_slice_in_dim(
            ids, next_id[:, None], t, axis=1
        )
        return ids, ()

    ids, _ = jax.lax.scan(body, ids, jnp.arange(1, max_len))
    return ids


class TextEncoder(nn.Module):
    """BERT-style post-LN BIDIRECTIONAL encoder with cross-attention over
    vision embeds — HF BlipTextModel as BlipForQuestionAnswering uses it to
    encode the question against the image. Same block as TextDecoder
    (shared `_bert_layer`, identical param names) minus the causal mask
    and the LM head; returns hidden states for the answer decoder to
    cross-attend. `attention_mask` [B, L] excludes padded question
    positions from self-attention.

    Note on [ENC]: the original Salesforce BLIP swaps the question's
    leading [CLS] for a dedicated [ENC] token (id 30523); HF transformers'
    BlipForQuestionAnswering.generate — the stack the reference serves
    with — passes the tokenizer output ([CLS] q [SEP]) through UNCHANGED
    (verified against transformers 4.57). This encoder follows HF, and the
    torch-parity test in tests/test_captioning.py pins that choice."""

    config: BlipConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, input_ids, image_embeds, attention_mask=None):
        """[B, L] ids + [B, P, Dv] -> [B, L, D] question states."""
        cfg = self.config
        x = _embed_text(self, cfg, input_ids, self.dtype)
        img = image_embeds.astype(self.dtype)
        self_mask = (
            _additive_mask(attention_mask, self.dtype)
            if attention_mask is not None
            else None
        )
        for i in range(cfg.text_layers):
            x = _bert_layer(cfg, self.dtype, i, x, img, self_mask, None)
        return x
