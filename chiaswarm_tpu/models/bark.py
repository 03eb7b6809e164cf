"""Bark-style TTS transformer stack: causal GPTs + codec decoder.

The reference delegates Bark entirely to the `bark` package
(swarm/audio/bark.py:16-21: preload_models + generate_audio). This module
rebuilds the architecture TPU-first as three flax transformers over the
suno/bark token scheme — text -> semantic tokens (causal AR), semantic ->
coarse acoustic codebooks (causal AR, 2 codebooks interleaved), coarse ->
fine codebooks (non-causal, per-codebook refinement) — plus a SEANet-style
transposed-conv codec decoder from quantized codebooks to waveform.

TPU design notes: autoregressive decoding runs as ONE `lax.scan` over a
static token budget with an explicit KV cache in the scan carry (cache
writes via `dynamic_update_slice`, attention masked to `pos`) — no
Python-loop decoding, no dynamic shapes, one compiled program per (prompt
budget, generation budget). The fine stage and the codec are plain batched
forward passes that ride the MXU.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from .layers import Dense, Embed, LayerNorm


@dataclasses.dataclass(frozen=True)
class BarkGPTConfig:
    input_vocab: int
    output_vocab: int
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    block_size: int = 1024
    causal: bool = True
    # fine stage (transformers BarkFineModel): one embedding table per
    # codebook (summed over books 0..codebook_idx) and one LM head per
    # predicted book. 0 = single-table causal stages (semantic/coarse).
    n_codes_total: int = 0
    n_codes_given: int = 1


# suno/bark token scheme constants (semantic rate ~50 Hz, EnCodec 75 Hz)
SEMANTIC_VOCAB = 10_000
CODEBOOK_SIZE = 1024
N_COARSE_BOOKS = 2
N_FINE_BOOKS = 8
SEMANTIC_RATE = 50
CODEC_RATE = 75


def bark_small(stage: str) -> BarkGPTConfig:
    """suno/bark vocab structure (transformers Bark*Config); real serving
    reads the per-stage config.json from the checkpoint instead."""
    if stage == "semantic":
        return BarkGPTConfig(
            input_vocab=129_600,  # text ids at 10_048.., specials at top
            output_vocab=10_048,
        )
    if stage == "coarse":
        # coarse codes live at 10_000 + book*1024 INSIDE the shared vocab
        return BarkGPTConfig(input_vocab=12_096, output_vocab=12_096)
    return BarkGPTConfig(  # fine: per-book tables, pad id = CODEBOOK_SIZE
        input_vocab=1056,
        output_vocab=1056,
        causal=False,
        n_codes_total=N_FINE_BOOKS,
    )


def bark_tiny(stage: str) -> BarkGPTConfig:
    """Same vocab STRUCTURE as the real scheme at test scale
    (pipelines.bark.TINY_SCHEME): semantic ids 0..999, text above 1048,
    coarse codes at 1000 + book*64 in a shared in/out vocab."""
    kw = dict(n_layer=2, n_head=2, d_model=32, block_size=128)
    if stage == "semantic":
        return BarkGPTConfig(input_vocab=1200, output_vocab=1000, **kw)
    if stage == "coarse":
        return BarkGPTConfig(input_vocab=1136, output_vocab=1136, **kw)
    return BarkGPTConfig(
        input_vocab=64 + 1, output_vocab=64, causal=False,
        n_codes_total=N_FINE_BOOKS, **kw
    )


class _Block(nn.Module):
    config: BarkGPTConfig
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        cfg = self.config
        self.ln1 = LayerNorm(epsilon=1e-5, dtype=self.dtype)
        self.ln2 = LayerNorm(epsilon=1e-5, dtype=self.dtype)
        self.qkv = Dense(3 * cfg.d_model, dtype=self.dtype)
        self.proj = Dense(cfg.d_model, dtype=self.dtype)
        self.fc = Dense(4 * cfg.d_model, dtype=self.dtype)
        self.fc_out = Dense(cfg.d_model, dtype=self.dtype)

    def _heads(self, x):
        b = x.shape[0]
        h = self.config.n_head
        return x.reshape(b, -1, h, self.config.d_model // h)

    def _mlp(self, x):
        # transformers BarkMLP uses exact (erf) GELU, not the tanh approx
        return self.fc_out(nn.gelu(self.fc(x), approximate=False))

    def __call__(self, x, mask=None):
        """Full-sequence pass. x [B,T,D]; mask [T,T] additive or None."""
        h = self.ln1(x)
        q, k, v = jnp.split(self.qkv(h), 3, axis=-1)
        q, k, v = (self._heads(t) for t in (q, k, v))
        scale = (q.shape[-1]) ** -0.5
        att = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        if mask is not None:
            att = att + mask
        att = jax.nn.softmax(att.astype(jnp.float32), axis=-1).astype(q.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", att, v)
        x = x + self.proj(out.reshape(x.shape))
        return x + self._mlp(self.ln2(x))

    def step(self, x, pos, cache_k, cache_v):
        """One decode step. x [B,D]; caches [B,T_max,H,dh]; pos scalar.
        -> (x, cache_k, cache_v)."""
        h = self.ln1(x)
        q, k, v = jnp.split(self.qkv(h), 3, axis=-1)
        b = x.shape[0]
        hd = self.config.d_model // self.config.n_head
        q = q.reshape(b, self.config.n_head, hd)
        k = k.reshape(b, 1, self.config.n_head, hd)
        v = v.reshape(b, 1, self.config.n_head, hd)
        cache_k = jax.lax.dynamic_update_slice(cache_k, k, (0, pos, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(cache_v, v, (0, pos, 0, 0))
        scale = hd**-0.5
        att = jnp.einsum("bhd,bkhd->bhk", q, cache_k) * scale
        t_max = cache_k.shape[1]
        valid = jnp.arange(t_max) <= pos
        att = jnp.where(valid[None, None, :], att, -1e9)
        att = jax.nn.softmax(att.astype(jnp.float32), axis=-1).astype(q.dtype)
        out = jnp.einsum("bhk,bkhd->bhd", att, cache_v).reshape(b, -1)
        x = x + self.proj(out)
        return x + self._mlp(self.ln2(x)), cache_k, cache_v


class BarkGPT(nn.Module):
    """Causal (or bidirectional) transformer with an explicit-KV decode
    path for scan-based AR generation."""

    config: BarkGPTConfig
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        cfg = self.config
        if cfg.n_codes_total:
            self.tok_embeds = [
                Embed(cfg.input_vocab, cfg.d_model, dtype=self.dtype,
                      name=f"tok_embed_{i}")
                for i in range(cfg.n_codes_total)
            ]
            self.heads = [
                Dense(cfg.output_vocab, use_bias=False, dtype=self.dtype,
                      name=f"head_{i}")
                for i in range(cfg.n_codes_total - cfg.n_codes_given)
            ]
        else:
            self.tok_embed = Embed(
                cfg.input_vocab, cfg.d_model, dtype=self.dtype
            )
            self.head = Dense(
                cfg.output_vocab, use_bias=False, dtype=self.dtype
            )
        self.pos_embed = Embed(cfg.block_size, cfg.d_model, dtype=self.dtype)
        self.blocks = [
            _Block(cfg, dtype=self.dtype, name=f"block_{i}")
            for i in range(cfg.n_layer)
        ]
        self.ln_f = LayerNorm(epsilon=1e-5, dtype=self.dtype)

    def _trunk(self, x):
        t = x.shape[1]
        x = x + self.pos_embed(jnp.arange(t))[None]
        mask = None
        if self.config.causal:
            mask = jnp.where(
                jnp.tril(jnp.ones((t, t), bool)), 0.0, -1e9
            ).astype(self.dtype)
        for block in self.blocks:
            x = block(x, mask)
        return self.ln_f(x)

    def __call__(self, tokens, codebook_idx: int | None = None):
        """Single-table stages: [B,T] -> logits [B,T,output_vocab] (causal
        iff config.causal). Fine stage (n_codes_total): tokens [B,K,T],
        `codebook_idx` a static int — embeddings sum over books
        0..codebook_idx (transformers BarkFineModel semantics; unpredicted
        books carry the pad id = codebook size) and the logits come from
        that book's own head."""
        if self.config.n_codes_total:
            assert codebook_idx is not None, "fine stage needs codebook_idx"
            x = sum(
                self.tok_embeds[i](tokens[:, i])
                for i in range(codebook_idx + 1)
            )
            x = self._trunk(x)
            return self.heads[codebook_idx - self.config.n_codes_given](x)
        x = self.tok_embed(tokens)
        return self.head(self._trunk(x))

    def init_all(self, tokens):
        """Init-only entry touching every per-book table and head so a
        single `init` materialises the full fine-stage parameter tree."""
        cfg = self.config
        if not cfg.n_codes_total:
            return self(tokens)
        x = sum(emb(tokens[:, i]) for i, emb in enumerate(self.tok_embeds))
        x = self._trunk(x)
        return sum(head(x) for head in self.heads)

    def embed_step(self, token, pos):
        """[B] int32, pos scalar -> [B,D] (decode-path embedding)."""
        return self.tok_embed(token) + self.pos_embed(jnp.asarray(pos))[None]

    def step(self, token, pos, caches):
        """One AR step. caches: list of (k, v) [B,T_max,H,dh] per layer.
        -> (logits [B,V], caches)."""
        x = self.embed_step(token, pos)
        new = []
        for block, (ck, cv) in zip(self.blocks, caches):
            x, ck, cv = block.step(x, pos, ck, cv)
            new.append((ck, cv))
        return self.head(self.ln_f(x)), new

    def init_cache(self, batch: int, t_max: int):
        cfg = self.config
        hd = cfg.d_model // cfg.n_head
        z = jnp.zeros((batch, t_max, cfg.n_head, hd), self.dtype)
        return [(z, z) for _ in range(cfg.n_layer)]


def generate(model: BarkGPT, params, prompt, n_new: int, rng,
             temperature: float = 0.7, top_k: int = 50,
             input_offset: int = 0, range_fn=None):
    """Scan-based AR sampling: one compiled loop over prompt+generation.

    prompt [B, Tp] int32 feeds teacher-forced; then n_new tokens sample
    from top-k at `temperature`. `range_fn(gen_idx) -> (lo, hi)` (jax-
    traceable) restricts sampling to a logit slice per generated index
    (codebook parity constraints). Sampled ids live in the OUTPUT vocab;
    `input_offset` maps them back into the input embedding space when fed
    as the next token (e.g. coarse ids ride above the semantic ids).
    Returns [B, n_new] sampled OUTPUT-vocab ids.
    """
    b, t_prompt = prompt.shape
    total = t_prompt + n_new
    caches = model.init_cache(b, total)
    k = min(top_k, model.config.output_vocab)

    def sample(logits, key, gen_idx):
        logits = logits.astype(jnp.float32)
        if range_fn is not None:
            lo, hi = range_fn(gen_idx)
            idx = jnp.arange(logits.shape[-1])
            logits = jnp.where((idx >= lo) & (idx < hi), logits, -1e9)
        top, _ = jax.lax.top_k(logits, k)
        logits = jnp.where(logits < top[..., -1:], -1e9, logits)
        # temperature may be a traced scalar (kept out of jit cache keys)
        temp = jnp.maximum(jnp.asarray(temperature, jnp.float32), 1e-4)
        return jax.random.categorical(key, logits / temp)

    def body(carry, i):
        token, caches = carry
        logits, caches = model.apply(
            {"params": params}, token, i, caches, method=BarkGPT.step
        )
        sampled = sample(logits, jax.random.fold_in(rng, i), i - (t_prompt - 1))
        next_prompt = prompt[:, jnp.minimum(i + 1, t_prompt - 1)]
        token = jnp.where(
            i + 1 < t_prompt, next_prompt, sampled + input_offset
        ).astype(prompt.dtype)
        return (token, caches), sampled

    (_, _), out = jax.lax.scan(
        body, (prompt[:, 0], caches), jnp.arange(total - 1)
    )
    # out[i] is the sample made AFTER consuming position i; generation
    # begins once the prompt is exhausted
    return jnp.moveaxis(out, 0, 1)[:, t_prompt - 1:]
