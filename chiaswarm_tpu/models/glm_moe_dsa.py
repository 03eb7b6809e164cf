"""GLM-5's language model (`model_type` `glm_moe_dsa`): latent attention
over the keys a lightning indexer selects, sigmoid-routed sparse experts,
as pure functions over a parameter tree.

The block, with `h = RMSNorm(x)` (benchmark/reference/dsa_mla_moe.py is the
plain float32 statement of the same equations):

- latent attention: `c_q = RMSNorm(h W_qa)`, `q = c_q W_qb` a head
  `[q_nope | q_rope]`; `[c_kv | k_rope] = h W_kva`, `c_kv = RMSNorm(c_kv)`;
  `[k_nope | v]` a head `= c_kv W_kvb`; plain rotary (no scaling) on
  `q_rope` and on the one `k_rope` all heads share; scores scaled by
  `(nope + rope)^-1/2`. The cache holds `[c_kv | k_rope]` a position.
- the lightning indexer: `q^I = c_q W^I_qb` (`index_n_heads` heads of
  `index_head_dim`, from the SAME `c_q` as the main queries), `k^I =
  LayerNorm(h W^I_k)`, ONE key a position shared by the heads, rotary on
  the first `qk_rope_head_dim` dims of each; `w = h W^I_w * heads^-1/2 *
  dim^-1/2`; `I[t, s] = sum_j w[t, j] ReLU(q^I[t, j] . k^I[s])` for every
  visible `s <= t` (`ops.lightning_indexer`). The cache holds `k^I` too.
- the selection: `S_t`, the positions of the `min(index_topk, t + 1)`
  largest `I[t, .]`, exact, ties to the lower position
  (`ops.lightning_indexer.index_select`). Attention's softmax runs over
  `S_t` alone; up to `index_topk` visible positions it is dense attention.
- experts: models/experts.py (every text family's), the leading
  `first_k_dense_replace` layers a dense SwiGLU instead.

Two caches a layer side by side (`new_cache`): the latents `[rows,
positions, kv_lora_rank + qk_rope_head_dim]` and the index keys `[rows,
positions, index_head_dim]`, the prompt's in the first `prompt slots`
columns and generated token `n` at column `slots + n`.

Prefill goes in spans of positions (`prefill`): a span writes its latents
and index keys into the row's two caches, its queries score the index keys
cached so far and its own, select, and attend to keys and values EXPANDED
from the cached latents under the selection's mask
(`ops.sparse_latent_attention`: every visible pair is computed, none of the
selected keys is gathered). The spans of a row are ONE traced body in a
loop, the span's first position a number the loop carries, so a row of
eight spans compiles five layers and not forty; the span's END (`start +
span`, data too) bounds the four of a span's key side (`attention_prefill`):
the index scores' key blocks, the selection's counts, the expansion's row
blocks and the attention kernel's key blocks all stop at it, so a row's
first span does an eighth of the key-side work of its last. What the two
caches, the scores, the mask and the expanded keys and values hold at or
past a span's end is read by none of the four (a later span writes the
caches there; the scores and the expansion are left unwritten). The keys
are expanded through one matrix `[c_kv | k_rope] -> [k_nope | k_rope]` a
head (`_key_up`: `W_kvb`'s key half over
an identity for the rotary key), so the kernel's 256-wide keys are written
once and never put together from two arrays. A decode step scores the
row's whole index-key cache, picks `index_topk` columns and reads ONLY
those rows of the latent cache, in the absorbed form (`W_kvb` folded into
the query and the context): the same function.

The selection's tally rides beside the routing's (`empty_load`: a third
leaf, `[4, 2]` int32: the positions the real queries saw, those attention
read for them, the key positions the prefill's spans walked (rows x the
span's end, a layer) and spans x the bucket's width, each as (multiples of
2^20, the rest), so that a pass of long rows does not overflow 32 bits);
`selection_counts` reads it.

Rotary pairs: the checkpoint interleaves the two halves of each rotary
pair (`rope_interleave`, `indexer_rope_interleave`); the weights here are
taken as already permuted (a fixed permutation of both sides of a dot
product), so rotation is over the two halves of the rotary width.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ..ops.lightning_indexer import index_select, lightning_indexer
from ..ops.sparse_latent_attention import (
    expand_latents,
    sparse_decode_attention,
    sparse_prefill_attention,
)
from . import experts, prefill_chunks
from .experts import (
    dot,
    feed_forward,
    init_leaves,
    logits_of,
    rms_norm,
)
from .text_model import apply_rope, decode_mask, rope_tables


@dataclasses.dataclass(frozen=True)
class GlmMoeDsaConfig:
    """The published sizes (huggingface.co/zai-org/GLM-5 config.json), and
    which share of them is held here."""

    hidden_size: int = 6144
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    num_attention_heads: int = 64
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256  # the router's width, whatever is held
    num_experts_per_tok: int = 8
    scoring_func: str = "sigmoid"
    routed_scaling_factor: float = 2.5
    n_shared_experts: int = 1
    first_k_dense_replace: int = 3
    num_hidden_layers: int = 78
    vocab_size: int = 154880  # rows of the vocabulary held here
    rms_norm_eps: float = 1e-5
    index_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    # (first, count): the routed experts this chip holds of every layer
    experts_held: tuple[int, int] = (0, 256)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        """Values the latent cache holds a position a layer: `c_kv |
        k_rope`."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5

    @property
    def index_scale(self) -> float:
        return self.index_n_heads ** -0.5 * self.index_head_dim ** -0.5


# one of 16 chips that share each layer: one leading dense layer (the
# published three count once) and four expert layers (the other 73 would
# lie on further pipeline stages), experts 0-15 of each layer's 256 (rank 0
# of the 16), rows 0-19359 of the vocabulary (an eighth)
GLM5_EP16 = GlmMoeDsaConfig(num_hidden_layers=5, first_k_dense_replace=1,
                            experts_held=(0, 16), vocab_size=19360)
GLM5_TINY = GlmMoeDsaConfig(
    hidden_size=64, q_lora_rank=32, kv_lora_rank=16, num_attention_heads=4,
    qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32, index_n_heads=4,
    index_head_dim=16, index_topk=8, intermediate_size=128,
    moe_intermediate_size=32, n_routed_experts=32, num_experts_per_tok=4,
    first_k_dense_replace=1, num_hidden_layers=3, vocab_size=128,
    experts_held=(0, 8))


def config_for(model_name: str) -> GlmMoeDsaConfig:
    return GLM5_TINY if "tiny" in model_name.lower() else GLM5_EP16


def index_cache_bytes(cfg: GlmMoeDsaConfig, rows: int, positions: int,
                      itemsize: int) -> int:
    """The index keys' part of a pass's cache."""
    return (rows * positions * cfg.index_head_dim * itemsize
            * cfg.num_hidden_layers)


def cache_bytes(cfg: GlmMoeDsaConfig, rows: int, positions: int,
                itemsize: int) -> tuple[int, int, int]:
    """(bytes of a pass's cache, latents and index keys, the part of it
    that is rings of a window, the part that is recurrent state: none of
    either, every layer keeps every position of both)."""
    latents = (rows * positions * cfg.cache_width * itemsize
               * cfg.num_hidden_layers)
    return latents + index_cache_bytes(cfg, rows, positions, itemsize), 0, 0


# --- the parameter tree ------------------------------------------------------


def param_shapes(cfg: GlmMoeDsaConfig, dtype) -> dict:
    """The tree as `jax.ShapeDtypeStruct`s: `embed`, `layers` (a list: each
    `attn`, which holds the indexer's leaves too, two norms, and `mlp` or
    `moe`), `final_norm`, `head`. Matrices are `[in, out]`; the held
    experts' are stacked `[held, in, out]`."""
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
                "o": s(heads * cfg.v_head_dim, h),
                "index_q": s(cfg.q_lora_rank,
                             cfg.index_n_heads * cfg.index_head_dim),
                "index_k": s(h, cfg.index_head_dim),
                "index_k_norm": s(cfg.index_head_dim),
                "index_k_norm_bias": s(cfg.index_head_dim),
                "index_w": s(h, cfg.index_n_heads)}}
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


def init_params(cfg: GlmMoeDsaConfig, key, dtype) -> dict:
    return init_leaves(param_shapes(cfg, dtype), key)


# --- the tally ----------------------------------------------------------------

_LOW = 20  # bits of a count's second part


def empty_load(cfg: GlmMoeDsaConfig):
    """The routing's tally (models/experts.py) and the selection's beside
    it: `[4, 2]` int32, (visible, selected, key positions the prefill's
    spans walked, spans x the bucket's width) x (multiples of 2^20, the
    rest), all zero."""
    return (*experts.empty_load(cfg), jnp.zeros((4, 2), jnp.int32))


def tally(load, index: int, cfg: GlmMoeDsaConfig, told, seen):
    """`load` with a layer's routing `told` and its selection `seen` ((the
    positions the real queries saw, those attention read, the key
    positions a prefill span walked, the bucket's): int32 [4], under 2^30
    a call) added."""
    low = load[2][:, 1] + seen
    counts = jnp.stack([load[2][:, 0] + (low >> _LOW),
                        low & ((1 << _LOW) - 1)], axis=1)
    return (*experts.tally(load[:2], index, cfg, told), counts)


def selection_counts(counts) -> tuple[int, int, int, int]:
    """(visible, selected, walked, bucket) as whole numbers, on the host,
    of a tally's third leaf: `walked / bucket` is the share of the
    bucket's width the prefill's key side went over (a row of eight spans:
    36 / 64; 1 says no span was bounded by its end)."""
    return tuple(int(high) * (1 << _LOW) + int(low) for high, low in counts)


# --- the block's parts -------------------------------------------------------


def layer_norm(x, weight, bias, eps: float):
    x32 = x.astype(jnp.float32)
    centred = x32 - jnp.mean(x32, -1, keepdims=True)
    scaled = centred * jax.lax.rsqrt(
        jnp.mean(centred * centred, -1, keepdims=True) + eps)
    return (scaled * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _rope_head(x, cos, sin, rope: int):
    """Rotary on the first `rope` dims of the last axis."""
    return jnp.concatenate(
        [apply_rope(x[..., :rope], cos, sin), x[..., rope:]], axis=-1)


def _projections(p, cfg: GlmMoeDsaConfig, h, positions):
    """Everything a layer's attention makes of `h` [..., hidden] at
    `positions` [...]: the main queries `q_nope`, `q_rope` [..., heads, .]
    (rotated), the latent cache's entry `c_kv | k_rope` [..., cache_width]
    (the key rotated), the index queries [..., index heads, index dim]
    (rotated), their weights [..., index heads] float32 and the index
    cache's entry [..., index dim] (normed, rotated)."""
    rope = cfg.qk_rope_head_dim
    cos, sin = rope_tables(rope, cfg.rope_theta, positions)
    head_cos, head_sin = cos[..., None, :], sin[..., None, :]
    c_q = rms_norm(dot(h, p["q_a"]), p["q_norm"], cfg.rms_norm_eps)
    q = dot(c_q, p["q_b"]).reshape(
        *h.shape[:-1], cfg.num_attention_heads, cfg.qk_head_dim)
    q_nope = q[..., :cfg.qk_nope_head_dim]
    q_rope = apply_rope(q[..., cfg.qk_nope_head_dim:], head_cos, head_sin)
    kv = dot(h, p["kv_a"])
    entry = jnp.concatenate([
        rms_norm(kv[..., :cfg.kv_lora_rank], p["kv_norm"],
                 cfg.rms_norm_eps),
        apply_rope(kv[..., cfg.kv_lora_rank:], cos, sin)], axis=-1)
    index_q = _rope_head(
        dot(c_q, p["index_q"]).reshape(
            *h.shape[:-1], cfg.index_n_heads, cfg.index_head_dim),
        head_cos, head_sin, rope)
    index_k = _rope_head(
        layer_norm(dot(h, p["index_k"]), p["index_k_norm"],
                   p["index_k_norm_bias"], cfg.index_norm_eps),
        cos, sin, rope)
    index_w = jnp.dot(h, p["index_w"],
                      preferred_element_type=jnp.float32) * cfg.index_scale
    return q_nope, q_rope, entry, index_q, index_w, index_k


def _kv_up(p, cfg: GlmMoeDsaConfig):
    """`W_kvb` as `[latent, heads, nope | v]`."""
    return p["kv_b"].reshape(cfg.kv_lora_rank, cfg.num_attention_heads,
                             cfg.qk_nope_head_dim + cfg.v_head_dim)


def _key_up(p, cfg: GlmMoeDsaConfig):
    """`[cache_width, heads, nope + rope]`: a cache entry `c_kv | k_rope`
    to every head's key `k_nope | k_rope` in one matrix: `W_kvb`'s key half
    over an identity that hands the shared rotary key to each head (exact:
    a product with one and zeros)."""
    up = _kv_up(p, cfg)[..., :cfg.qk_nope_head_dim]
    rope, heads = cfg.qk_rope_head_dim, cfg.num_attention_heads
    share = jnp.broadcast_to(jnp.eye(rope, dtype=up.dtype)[:, None, :],
                             (rope, heads, rope))
    return jnp.concatenate([
        jnp.concatenate([up, jnp.zeros((cfg.kv_lora_rank, heads, rope),
                                       up.dtype)], axis=-1),
        jnp.concatenate([jnp.zeros((rope, heads, cfg.qk_nope_head_dim),
                                   up.dtype), share], axis=-1)], axis=0)


def attention_prefill(p, cfg: GlmMoeDsaConfig, h, start, cache, real,
                      interpret: bool = False):
    """A span's latent attention under the selection: `h` [R, C, hidden]
    at the positions `start .. start + C` (`start` a number or a traced
    scalar), `cache` (latents [R, S, cache_width], index keys [R, S, index
    dim]: the rows' slots, the spans before this one written), `real` [R,
    C] the positions that hold a prompt's id. Returns the output, the two
    caches with the span written and (positions the real queries saw,
    those attention read for them, the key positions the span walked: rows
    x its end, the bucket's: rows x S) int32 [4]."""
    rows, span = h.shape[:2]
    positions = jnp.broadcast_to(start + jnp.arange(span), (rows, span))
    q_nope, q_rope, entry, index_q, index_w, index_k = _projections(
        p, cfg, h, positions)
    latents, keys = (
        jax.lax.dynamic_update_slice(whole, new.astype(whole.dtype),
                                     (0, start, 0))
        for whole, new in zip(cache, (entry, index_k)))
    # the span's end bounds all four of the key side: nothing below reads
    # a column of either cache, of the scores or of the mask at or past it
    end = start + span
    scores = lightning_indexer(index_q, index_w, keys, offset=start, end=end,
                               interpret=interpret)
    mask, selected = index_select(scores, cfg.index_topk, end=end,
                                  interpret=interpret)
    # every head's keys and values as columns, as one matmul leaves them
    k, v = expand_latents(
        latents, _key_up(p, cfg).reshape(cfg.cache_width, -1),
        _kv_up(p, cfg)[..., cfg.qk_nope_head_dim:].reshape(
            cfg.kv_lora_rank, -1), end, interpret=interpret)
    out = sparse_prefill_attention(
        jnp.concatenate([q_nope, q_rope], axis=-1).reshape(rows, span, -1),
        k, v, mask, cfg.softmax_scale, cfg.num_attention_heads, offset=start,
        interpret=interpret)
    seen = jnp.stack([jnp.sum(jnp.where(real, positions + 1, 0)),
                      jnp.sum(jnp.where(real, selected, 0)),
                      rows * end, rows * latents.shape[1]])
    return dot(out, p["o"]), (latents, keys), seen.astype(jnp.int32)


def attention_decode(p, cfg: GlmMoeDsaConfig, h, positions, cache, column,
                     mask, valid=None):
    """One new token a row: `h` [R, hidden] at rotary `positions` [R],
    written to both of `cache` (latents [R, S, cache_width], index keys
    [R, S, index dim]) at `column`; `mask` [R, S] are the positions each
    row may see, its own included. The row's index scores over its whole
    key cache pick `index_topk` columns, and attention reads those rows of
    the latent cache alone."""
    q_nope, q_rope, entry, index_q, index_w, index_k = _projections(
        p, cfg, h, positions)
    latents, keys = (
        jax.lax.dynamic_update_slice(
            whole, new[:, None, :].astype(whole.dtype), (0, column, 0))
        for whole, new in zip(cache, (entry, index_k)))
    scores = lightning_indexer(index_q[:, None], index_w[:, None], keys,
                               visible=mask)
    columns, chosen = index_select(scores, cfg.index_topk, "indices")
    up = _kv_up(p, cfg)
    q_lat = jnp.einsum("rhd,chd->rhc", q_nope,
                       up[..., :cfg.qk_nope_head_dim],
                       preferred_element_type=jnp.float32).astype(h.dtype)
    context, _ = sparse_decode_attention(
        q_lat, q_rope, latents, columns[:, 0], chosen[:, 0],
        cfg.softmax_scale)
    out = jnp.einsum("rhc,chd->rhd", context,
                     up[..., cfg.qk_nope_head_dim:],
                     preferred_element_type=jnp.float32).astype(h.dtype)
    rows = jnp.ones(h.shape[:1], bool) if valid is None else valid
    # (a step walks the row's whole cache: no span, no extent)
    seen = jnp.stack([jnp.sum(mask & rows[:, None]),
                      jnp.sum(chosen[:, 0] & rows[:, None]), 0, 0])
    return (dot(out.reshape(h.shape[0], -1), p["o"]), (latents, keys),
            seen.astype(jnp.int32))


# --- prefill and decode ------------------------------------------------------


def new_cache(cfg: GlmMoeDsaConfig, rows: int, positions: int, dtype):
    """(latents [rows, positions, cache_width], index keys [rows,
    positions, index_head_dim]) a layer."""
    return tuple(
        (jnp.zeros((rows, positions, cfg.cache_width), dtype),
         jnp.zeros((rows, positions, cfg.index_head_dim), dtype))
        for _ in range(cfg.num_hidden_layers))


# a prefill chunk may be a span of one row's positions: a span attends to
# the latents and index keys the spans before it cached, plus its own
POSITION_CHUNKS = True


def prefill_account(lengths, slots: int, chunk_rows: int, chunk_slots: int):
    """The host's account of what `prefill` ran (models/text_model.py):
    `chunk_rows` rows a chunk whatever their lengths, of its spans those
    some row of it reaches."""
    return prefill_chunks.chunk_account(
        lengths, slots, chunk_rows, chunk_slots,
        runs=prefill_chunks.span_runs)


def prefill_rows(params, cfg: GlmMoeDsaConfig, ids, lengths,
                 chunk_slots: int, load, interpret: bool = False):
    """Rows `ids` [R, S] (a row's prompt first, padding after: the mask is
    causal and the selection picks among visible keys, so no real token
    sees padding) through every layer, `chunk_slots` positions at a time;
    a span no row reaches is not run (`prefill_chunks.span_runs`: a
    conditional on the device, the one program whatever the lengths).
    Returns the hidden state of each row's last prompt token [R, hidden],
    a layer's cache entries ((latents, index keys), `[R, S, .]` each) and
    the tally."""
    rows, slots = ids.shape
    assert slots % chunk_slots == 0, (slots, chunk_slots)
    dtype = params["embed"].dtype

    def run(start, cache, last, load):
        """The span from `start` through every layer, written into the
        rows' caches."""
        cache = list(cache)
        real = (start + jnp.arange(chunk_slots))[None, :] < lengths[:, None]
        x = params["embed"][jax.lax.dynamic_slice_in_dim(
            ids, start, chunk_slots, axis=1)]
        for index, layer in enumerate(params["layers"]):
            h = rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
            out, cache[index], seen = attention_prefill(
                layer["attn"], cfg, h, start, cache[index], real, interpret)
            x = x + out
            h = rms_norm(x, layer["post_norm"], cfg.rms_norm_eps)
            out, told = feed_forward(
                layer, cfg, h.reshape(rows * chunk_slots, -1),
                real.reshape(-1), interpret)
            x = x + out.reshape(x.shape)
            load = tally(load, index, cfg, told, seen)
        at = lengths - 1 - start
        mine = (at >= 0) & (at < chunk_slots)
        picked = jnp.take_along_axis(
            x, jnp.clip(at, 0, chunk_slots - 1)[:, None, None], axis=1)[:, 0]
        return tuple(cache), jnp.where(mine[:, None], picked, last), load

    def span(number, carry):
        """A span no row reaches is not run: `last` and the tally as they
        came, its columns of the caches the zeros they began as (columns
        past every row's length, which `decode_mask` shows to nobody)."""
        start = number * chunk_slots
        return jax.lax.cond(
            prefill_chunks.span_runs(lengths, start),
            functools.partial(run, start), lambda *carry: carry, *carry)

    cache, last, load = jax.lax.fori_loop(
        0, slots // chunk_slots, span,
        (new_cache(cfg, rows, slots, dtype),
         jnp.zeros((rows, cfg.hidden_size), dtype), load))
    return last, cache, load


def prefill(params, cfg: GlmMoeDsaConfig, ids, lengths, positions: int,
            chunk_rows: int, chunk_slots: int | None = None,
            interpret: bool = False):
    """`ids` [R, S] in chunks of `chunk_rows` rows x `chunk_slots`
    positions (whole rows where rows are short, a span of one row's
    positions where a row is longer). Returns the last prompt position's
    logits [R, vocab], the cache (`new_cache`, the first S columns of both
    kinds written) and the tally."""
    rows, slots = ids.shape
    dtype = params["embed"].dtype
    chunk_slots = slots if chunk_slots is None else chunk_slots
    assert rows % chunk_rows == 0, (rows, chunk_rows)

    def chunk(number, carry):
        last, cache, load = carry
        at = number * chunk_rows
        x, entries, load = prefill_rows(
            params, cfg,
            jax.lax.dynamic_slice(ids, (at, 0), (chunk_rows, slots)),
            jax.lax.dynamic_slice(lengths, (at,), (chunk_rows,)),
            chunk_slots, load, interpret)
        # whole rows: the loop writes every element of the cache, so what
        # the buffer held before does not matter (`whole_rows`)
        cache = tuple(
            tuple(jax.lax.dynamic_update_slice(
                whole, prefill_chunks.whole_rows(entry.astype(dtype),
                                                 whole.shape[1]),
                (at, 0, 0))
                  for whole, entry in zip(layer, written))
            for layer, written in zip(cache, entries))
        return (jax.lax.dynamic_update_slice(last, x, (at, 0)), cache, load)

    last, cache, load = jax.lax.fori_loop(
        0, rows // chunk_rows, chunk,
        (jnp.zeros((rows, cfg.hidden_size), dtype),
         new_cache(cfg, rows, positions, dtype), empty_load(cfg)))
    return logits_of(params, cfg, last), cache, load


def step(params, cfg: GlmMoeDsaConfig, tokens, lengths, number, slots: int,
         cache, load, valid=None, interpret: bool = False):
    """Every row's generated token `number` through every layer and both
    caches: `tokens` [R], at rotary position `lengths + number`, cached at
    column `slots + number` (`valid` [R]: a row that only pads the pass is
    routed nowhere and counted nowhere). Returns the logits [R, vocab]
    (float32), the cache and the tally."""
    x = params["embed"][tokens]
    column = slots + number
    mask = decode_mask(lengths, slots, cache[0][0].shape[1], number, column)
    cache = list(cache)
    for index, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
        out, cache[index], seen = attention_decode(
            layer["attn"], cfg, h, lengths + number, cache[index], column,
            mask, valid)
        x = x + out
        h = rms_norm(x, layer["post_norm"], cfg.rms_norm_eps)
        out, told = feed_forward(layer, cfg, h, valid, interpret)
        x = x + out
        load = tally(load, index, cfg, told, seen)
    return logits_of(params, cfg, x), tuple(cache), load
