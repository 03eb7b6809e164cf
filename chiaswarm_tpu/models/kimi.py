"""Kimi-K2's language model (`model_type` `kimi_k2`): latent attention with
a latent cache, sigmoid routing over sparse experts, as pure functions over
a parameter tree.

The block, with `h = RMSNorm(x)` (benchmark/reference/mla_moe.py is the
plain float32 statement of the same equations):

- latent attention: `c_q = RMSNorm(h W_qa)`, `q = c_q W_qb` a head
  `[q_nope | q_rope]`; `[c_kv | k_rope] = h W_kva`, `c_kv = RMSNorm(c_kv)`;
  `[k_nope | v]` a head `= c_kv W_kvb`; rotary with YaRN-scaled frequencies
  on `q_rope` and on the one `k_rope` all heads share; scores scaled by
  `(nope + rope)^-1/2 * mscale^2`. The cache holds `[c_kv | k_rope]` a
  position and nothing else. Prefill expands keys and values
  (`ops.attention`, causal, values narrower than keys); a decode step
  absorbs `W_kvb` into the query and the context
  (`ops.latent_attention`): the same function.
- experts: models/experts.py (every text family's): sigmoid
  scores over ALL the model's experts, the layer told which it holds
  (`KimiConfig.experts_held`), one grouped matmul over the held pairs.
- the leading `first_k_dense_replace` layers have a dense SwiGLU instead.

Rotary pairs: the checkpoint interleaves the two halves of each rotary
pair and the published code permutes them apart before rotating; the
weights here are taken as already permuted (a converter would permute the
columns once), so rotation is over the two halves of the rotary width.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import dot_product_attention
from ..ops.latent_attention import (
    column_block,
    kernel_taken,
    latent_decode_attention,
    rows_a_step,
)
from .experts import (  # noqa: F401  (the benchmark takes three from here)
    dot,
    empty_load,
    feed_forward,
    held_experts,
    init_leaves,
    leaf_rule,
    logits_of,
    rms_norm,
    tally,
)
from .prefill_chunks import chunk_account, chunk_widths, prefill_by_length
from .text_model import apply_rope, decode_mask


@dataclasses.dataclass(frozen=True)
class KimiConfig:
    """The published sizes (huggingface.co/moonshotai/Kimi-K2.6
    config.json), and which share of them is held here."""

    hidden_size: int = 7168
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    num_attention_heads: int = 64
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 384  # the router's width, whatever is held
    num_experts_per_tok: int = 8
    scoring_func: str = "sigmoid"
    routed_scaling_factor: float = 2.827
    n_shared_experts: int = 1
    first_k_dense_replace: int = 1
    num_hidden_layers: int = 61
    vocab_size: int = 163840  # rows of the vocabulary held here
    rms_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    rope_factor: float = 64.0
    rope_original_positions: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    # (first, count): the routed experts this chip holds of every layer
    experts_held: tuple[int, int] = (0, 384)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        """Values the cache holds a position a layer: `c_kv | k_rope`."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5 * yarn_mscale(
            self.rope_factor, self.rope_mscale_all_dim) ** 2


# one chip's share of a 32-chip expert-parallel deployment: the leading
# dense layer and six expert layers (the other 54 would lie on further
# pipeline stages), experts 0-11 of each layer's 384 (rank 0 of the 32
# chips that share a layer), rows 0-20479 of the vocabulary (an eighth)
KIMI_K2_EP32 = KimiConfig(num_hidden_layers=7, experts_held=(0, 12),
                          vocab_size=20480)
KIMI_TINY = KimiConfig(
    hidden_size=64, q_lora_rank=32, kv_lora_rank=16, num_attention_heads=4,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=128, moe_intermediate_size=32, n_routed_experts=32,
    num_experts_per_tok=4, num_hidden_layers=3, vocab_size=128,
    rope_original_positions=16, experts_held=(0, 8))


def config_for(model_name: str) -> KimiConfig:
    return KIMI_TINY if "tiny" in model_name.lower() else KIMI_K2_EP32


def cache_bytes(cfg: KimiConfig, rows: int, positions: int,
                itemsize: int) -> tuple[int, int, int]:
    """(bytes of a pass's cache, the part of it that is rings of a window,
    the part that is recurrent state: none of either here, every layer
    keeps every position)."""
    return (rows * positions * cfg.cache_width * itemsize
            * cfg.num_hidden_layers, 0, 0)


# --- the parameter tree ------------------------------------------------------


def param_shapes(cfg: KimiConfig, dtype) -> dict:
    """The tree as `jax.ShapeDtypeStruct`s: `embed`, `layers` (a list: each
    `attn`, two norms, and `mlp` or `moe`), `final_norm`, `head`. Matrices
    are `[in, out]`; the held experts' are stacked `[held, in, out]`."""
    h, heads = cfg.hidden_size, cfg.num_attention_heads

    def s(*dims):
        return jax.ShapeDtypeStruct(dims, dtype)

    def swiglu(width, *lead):
        return {"gate": s(*lead, h, width), "up": s(*lead, h, width),
                "down": s(*lead, width, h)}

    layers = []
    for index in range(cfg.num_hidden_layers):
        layer = {
            "input_norm": s(h), "post_norm": s(h),
            "attn": {
                "q_a": s(h, cfg.q_lora_rank), "q_norm": s(cfg.q_lora_rank),
                "q_b": s(cfg.q_lora_rank, heads * cfg.qk_head_dim),
                "kv_a": s(h, cfg.cache_width),
                "kv_norm": s(cfg.kv_lora_rank),
                "kv_b": s(cfg.kv_lora_rank,
                          heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                "o": s(heads * cfg.v_head_dim, h)}}
        if index < cfg.first_k_dense_replace:
            layer["mlp"] = swiglu(cfg.intermediate_size)
        else:
            layer["moe"] = {
                "router": s(h, cfg.n_routed_experts),
                "router_bias": s(cfg.n_routed_experts),
                "experts": swiglu(cfg.moe_intermediate_size,
                                  cfg.experts_held[1]),
                "shared": swiglu(
                    cfg.moe_intermediate_size * cfg.n_shared_experts)}
        layers.append(layer)
    return {"embed": s(cfg.vocab_size, h), "layers": layers,
            "final_norm": s(h), "head": s(h, cfg.vocab_size)}


def init_params(cfg: KimiConfig, key, dtype) -> dict:
    return init_leaves(param_shapes(cfg, dtype), key)


# --- rotary ------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: KimiConfig):
    """YaRN's frequencies: the pairs that turn more than `beta_fast` times
    over the original context keep theirs, those that turn less than
    `beta_slow` times are slowed by `factor`, a linear ramp between."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    exponent = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    extra = 1.0 / base ** exponent
    inter = extra / cfg.rope_factor

    def correction(turns: float) -> float:
        return dim * math.log(cfg.rope_original_positions
                              / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction(cfg.rope_beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def rope_tables(cfg: KimiConfig, positions):
    """cos and sin `[..., rope / 2]` of whole-number `positions`."""
    angles = positions.astype(jnp.float32)[..., None] * yarn_inv_freq(cfg)
    scale = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
             / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


# --- the block's parts -------------------------------------------------------


def _queries(p, cfg: KimiConfig, h):
    c_q = rms_norm(dot(h, p["q_a"]), p["q_norm"], cfg.rms_norm_eps)
    q = dot(c_q, p["q_b"]).reshape(
        *h.shape[:-1], cfg.num_attention_heads, cfg.qk_head_dim)
    return q[..., :cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:]


def _latents(p, cfg: KimiConfig, h, cos, sin):
    """What the cache holds of `h`'s positions: `c_kv | k_rope`, the key
    already rotated."""
    kv = dot(h, p["kv_a"])
    c_kv = rms_norm(kv[..., :cfg.kv_lora_rank], p["kv_norm"],
                    cfg.rms_norm_eps)
    k_rope = apply_rope(kv[..., cfg.kv_lora_rank:], cos, sin)
    return jnp.concatenate([c_kv, k_rope], axis=-1)


def _kv_up(p, cfg: KimiConfig):
    """`W_kvb` as `[latent, heads, nope | v]`."""
    return p["kv_b"].reshape(cfg.kv_lora_rank, cfg.num_attention_heads,
                             cfg.qk_nope_head_dim + cfg.v_head_dim)


def attention_prefill(p, cfg: KimiConfig, h, positions):
    """Causal latent attention over whole rows `h` [R, S, hidden]; returns
    the output and the rows' cache entries [R, S, cache_width]."""
    cos, sin = rope_tables(cfg, positions)
    q_nope, q_rope = _queries(p, cfg, h)
    q_rope = apply_rope(q_rope, cos[..., None, :], sin[..., None, :])
    entry = _latents(p, cfg, h, cos, sin)
    heads = cfg.num_attention_heads
    kv = jnp.einsum("rsc,chd->rshd", entry[..., :cfg.kv_lora_rank],
                    _kv_up(p, cfg),
                    preferred_element_type=jnp.float32).astype(h.dtype)
    k_rope = jnp.broadcast_to(
        entry[..., None, cfg.kv_lora_rank:],
        (*entry.shape[:-1], heads, cfg.qk_rope_head_dim))
    out = dot_product_attention(
        jnp.concatenate([q_nope, q_rope], axis=-1),
        jnp.concatenate([kv[..., :cfg.qk_nope_head_dim], k_rope], axis=-1),
        kv[..., cfg.qk_nope_head_dim:], scale=cfg.softmax_scale, causal=True)
    return dot(out.reshape(*h.shape[:-1], -1), p["o"]), entry


def attention_decode(p, cfg: KimiConfig, h, positions, cache, column, mask,
                     absorb: bool = True, interpret: bool = False):
    """One new token a row: `h` [R, hidden] at rotary `positions` [R],
    written to `cache` [R, S, cache_width] at `column`; `mask` [R, S] are
    the positions each row may see, its own included. `absorb` false
    expands the cache's keys and values instead (tests: the same
    function)."""
    cos, sin = rope_tables(cfg, positions)
    q_nope, q_rope = _queries(p, cfg, h)
    q_rope = apply_rope(q_rope, cos[:, None, :], sin[:, None, :])
    entry = _latents(p, cfg, h, cos, sin)
    cache = jax.lax.dynamic_update_slice(
        cache, entry[:, None, :].astype(cache.dtype), (0, column, 0))
    up = _kv_up(p, cfg)
    w_k, w_v = up[..., :cfg.qk_nope_head_dim], up[..., cfg.qk_nope_head_dim:]
    if absorb:
        q_lat = jnp.einsum("rhd,chd->rhc", q_nope, w_k,
                           preferred_element_type=jnp.float32).astype(h.dtype)
        context = latent_decode_attention(q_lat, q_rope, cache, mask,
                                          cfg.softmax_scale,
                                          interpret=interpret)
        out = jnp.einsum("rhc,chd->rhd", context, w_v,
                         preferred_element_type=jnp.float32).astype(h.dtype)
    else:
        latent = cache[..., :cfg.kv_lora_rank]
        k_nope = jnp.einsum("rsc,chd->rshd", latent, w_k)
        values = jnp.einsum("rsc,chd->rshd", latent, w_v)
        scores = (jnp.einsum("rhd,rshd->rhs", q_nope, k_nope)
                  + jnp.einsum("rhd,rsd->rhs", q_rope,
                               cache[..., cfg.kv_lora_rank:]))
        scores = jnp.where(mask[:, None, :],
                           scores.astype(jnp.float32) * cfg.softmax_scale,
                           -jnp.inf)
        out = jnp.einsum("rhs,rshd->rhd",
                         jax.nn.softmax(scores, -1).astype(h.dtype), values)
    return dot(out.reshape(h.shape[0], -1), p["o"]), cache


# --- prefill and decode ------------------------------------------------------


def new_cache(cfg: KimiConfig, rows: int, positions: int, dtype):
    return tuple(jnp.zeros((rows, positions, cfg.cache_width), dtype)
                 for _ in range(cfg.num_hidden_layers))


def prefill_rows(params, cfg: KimiConfig, ids, lengths, load,
                 interpret: bool = False):
    """Whole rows `ids` [R, S] (a row's prompt first, padding after: with
    a causal mask no real token sees padding) through every layer. Returns
    the hidden state of each row's last prompt token [R, hidden], each
    layer's cache entries [R, S, cache_width], and the tally."""
    rows, slots = ids.shape
    x = params["embed"][ids]
    positions = jnp.broadcast_to(jnp.arange(slots), (rows, slots))
    valid = (positions < lengths[:, None]).reshape(-1)
    entries = []

    # `prefill` traces this once a width: under a `jit` of its own, layers
    # of one shape are traced once and not once each (the compiler inlines
    # the calls: the same program, a second less of tracing a width)
    @jax.jit
    def through(layer, x, positions, valid):
        h = rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
        out, entry = attention_prefill(layer["attn"], cfg, h, positions)
        x = x + out
        h = rms_norm(x, layer["post_norm"], cfg.rms_norm_eps)
        out, told = feed_forward(layer, cfg, h.reshape(rows * slots, -1),
                                 valid, interpret)
        return x + out.reshape(x.shape), entry, told

    for index, layer in enumerate(params["layers"]):
        x, entry, told = through(layer, x, positions, valid)
        entries.append(entry)
        load = tally(load, index, cfg, told)
    last = jnp.take_along_axis(
        x, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)[:, 0]
    return last, entries, load


# a prefill chunk is whole rows: latent attention's prefill against what
# earlier positions cached is not written, so a long row goes through in one
POSITION_CHUNKS = False


def prefill_widths(slots: int, chunk_slots: int | None = None):
    """The widths a chunk of `prefill` may have, widest first: the bucket
    and its halvings (two more traced copies of the layers: a worker's
    start is ~4 s longer and every pass of ragged rows 0.8 s shorter;
    PERF.md section 6, PR 43)."""
    return chunk_widths(slots, chunk_slots)


def prefill_account(lengths, slots: int, chunk_rows: int, chunk_slots: int):
    """The host's account of what `prefill` ran (models/text_model.py):
    whole rows, a chunk at its rows' width."""
    return chunk_account(lengths, slots, chunk_rows, chunk_slots,
                         prefill_widths(slots, chunk_slots))


def prefill(params, cfg: KimiConfig, ids, lengths, positions: int,
            chunk_rows: int, chunk_slots: int | None = None,
            interpret: bool = False):
    """`ids` [R, S] in chunks of `chunk_rows` whole rows (what bounds the
    widest layer's activations; `chunk_slots` is S), a row at the narrowest
    width that holds it (models/prefill_chunks.py). Returns the last prompt
    position's logits [R, vocab], the cache (a `[R, positions,
    cache_width]` a layer, the first S columns written) and the tally."""
    rows, slots = ids.shape
    assert chunk_slots in (None, slots), (chunk_slots, slots)
    dtype = params["embed"].dtype

    def run(ids, lengths, load):
        last, entries, load = prefill_rows(params, cfg, ids, lengths, load,
                                           interpret)
        return (last, tuple(entries)), load

    (last, cache), load = prefill_by_length(
        ids, lengths, chunk_rows, prefill_widths(slots), run,
        (jnp.zeros((rows, cfg.hidden_size), dtype),
         new_cache(cfg, rows, positions, dtype)), empty_load(cfg))
    return logits_of(params, cfg, last), cache, load


def decode_step(params, cfg: KimiConfig, tokens, positions, cache, column,
                mask, load, valid=None, absorb: bool = True,
                interpret: bool = False):
    """One token a row through every layer and the cache: `tokens` [R] at
    rotary `positions` [R], cached at `column` (`valid` [R]: a row that
    only pads the pass is routed nowhere). Returns the logits [R, vocab]
    (float32), the cache and the tally."""
    x = params["embed"][tokens]
    cache = list(cache)
    for index, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
        out, cache[index] = attention_decode(
            layer["attn"], cfg, h, positions, cache[index], column, mask,
            absorb, interpret)
        x = x + out
        h = rms_norm(x, layer["post_norm"], cfg.rms_norm_eps)
        out, told = feed_forward(layer, cfg, h, valid, interpret)
        x = x + out
        load = tally(load, index, cfg, told)
    return logits_of(params, cfg, x), tuple(cache), load


def step(params, cfg: KimiConfig, tokens, lengths, number, slots: int, cache,
         load, valid=None):
    """`decode_step` for every row's generated token `number`: cached at
    column `slots + number`, at rotary position `lengths + number`."""
    column = slots + number
    return decode_step(
        params, cfg, tokens, lengths + number, cache, column,
        decode_mask(lengths, slots, cache[0].shape[1], number, column), load,
        valid=valid)


def decode_cache_blocks(cfg: KimiConfig, lengths, slots: int, positions: int,
                        steps: int) -> tuple[int, int]:
    """The host's account of the cache's column blocks a decode of `steps`
    steps went over (models/text_model.py), without jax: (walked, rows x
    the blocks of the cache's width), summed over rows, layers and steps.
    A row at generated token `number` sees its prompt and the columns
    `slots .. slots + number` (`decode_mask`), and the attention kernel
    walks, for the rows of a grid step (the pass's order, `rows_a_step` at
    a time), the blocks in which one of them sees a position
    (`ops.latent_attention.live_blocks`, which tests/test_latent_attention.py
    holds this count equal to at every step); the plain form walks them
    all."""
    lengths = np.asarray(lengths, np.int64)
    block = column_block(positions)
    bucket = lengths.size * -(-positions // block) * steps
    if not kernel_taken(cfg.kv_lora_rank, lengths.size):
        return cfg.num_hidden_layers * bucket, cfg.num_hidden_layers * bucket
    # a step's rows walk what its longest prompt needs
    together = rows_a_step(lengths.size)
    prompt = -(-lengths.reshape(-1, together).max(axis=1) // block)
    first = slots // block
    generated = (slots + np.arange(steps)) // block - first + 1
    # the block the generated columns start in may hold a prompt's end
    walked = together * steps * int((prompt - (prompt > first)).sum()) + (
        lengths.size * int(generated.sum()))
    return cfg.num_hidden_layers * walked, cfg.num_hidden_layers * bucket
