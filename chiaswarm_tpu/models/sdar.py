"""SDAR's language model (`model_type` `sdar_moe`): grouped-query attention
under a mask that is causal between blocks and bidirectional inside one,
softmax-routed sparse experts on every layer, and generation a block at a
time, as pure functions over a parameter tree.

The layer, with `h = RMSNorm(x)` (benchmark/reference/block_diffusion_moe.py
is the plain float32 statement of the same equations):

- attention: `q = h W_q` (heads x head_dim), `k = h W_k`, `v = h W_v` (key
  heads x head_dim; query head `j` reads key head `j // G`); `q` and `k`
  RMS-normed over the head's dims with a learned weight, then rotary over
  all the head's dims at the token's own position in its row, on every
  layer. With block length `B` the token at position `t` sees key `u` iff
  `u // B <= t // B`: every earlier block whole, its own block in both
  directions. `x += softmax(q . k / sqrt(head_dim)) v W_o`.
- the second half is models/experts.py's under the softmax rule:
  `x += sum_chosen w_i E_i(RMSNorm(x))`, no dense layer, no shared expert,
  no correction bias.

Generation is by blocks of `B` positions counted in the row's own positions
(`first_block`, `unmask`; the loop is pipelines/text_generation.py's): a
row's whole prompt blocks `[0, L // B * B)` are prefilled under the mask
and cached; the prompt's last `L mod B` ids open the first generated block
as given, the rest of it and every later block start as the mask id; a
block is run through `block_step` against the cache, which is not written,
until no position of it is masked. Its keys and values are written by the
forward that first needs them: the first forward of block `g + 1` is a
**fused** one (`block_step(finished=)`), `2B` positions a row, block `g`'s
final ids in front of block `g + 1`'s. Under the block mask the first `B`
see the cache and each other, which is all a forward of block `g` alone
sees, so their keys and values are that forward's; the last `B` see the
cache and all `2B`, which is the cache as it stands once block `g` is in
it. `block_step(commit=True)` is the forward of a block alone that writes
its own keys and values: what the comparison with the reference runs, and
what a fused forward is held against.

The cache (`new_cache`): keys and values a layer `[rows, positions, key
heads, head_dim]`, as attention reads them (normed and rotated); a row's
whole prompt blocks in the first columns, generated block `g` at columns
`slots + g * B` on, whatever the row's length, so a commit is one slice a
layer. The columns between a row's whole blocks and `slots` (its prompt's
tail and the padding) are written by prefill and shown to nobody.

Positions whose logits nobody reads (a prefill's, the finished block's in
a fused forward) stop at the last layer's keys and values: their
attention output, their experts and the head are not run, and the
routing's tally does not count them; the head and the sampler see a fused
forward's last `B` positions alone.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import dot_product_attention
from .experts import (
    dot,
    empty_load,
    feed_forward,
    init_leaves,
    logits_of,
    rms_norm,
    tally,
)
from .prefill_chunks import chunk_account, prefill_by_length, span_runs
from .text_model import apply_rope, rope_tables


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    """The published sizes (huggingface.co/JetLM/SDAR-30B-A3B-Chat
    config.json), what the family's published `generate` adds to them
    (`block_length`, `mask_token_id`), and which share is held here."""

    hidden_size: int = 2048
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128  # the router's width, whatever is held
    num_experts_per_tok: int = 8
    scoring_func: str = "softmax"
    routed_scaling_factor: float = 1.0  # `norm_topk_prob`, and no scale
    first_k_dense_replace: int = 0
    num_hidden_layers: int = 48
    vocab_size: int = 151936
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    block_length: int = 4
    mask_token_id: int = 151669  # the tokenizer's <|MASK|>
    # (first, count): the routed experts this chip holds of every layer
    experts_held: tuple[int, int] = (0, 128)

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def position_bytes(self) -> int:
        """Values a layer caches a position: a key and a value a key
        head."""
        return 2 * self.num_key_value_heads * self.head_dim


# one stage of an 8-stage pipeline: 6 whole layers of the 48 (the other 42
# on the further stages), every expert of each, the whole vocabulary
# (embedding and head both here, which the first and the last stage hold
# one each)
SDAR_30B_PP8 = SdarConfig(num_hidden_layers=6)
SDAR_TINY = SdarConfig(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, moe_intermediate_size=32, num_experts=16,
    num_experts_per_tok=4, num_hidden_layers=3, vocab_size=128,
    mask_token_id=127, experts_held=(0, 16))


def config_for(model_name: str) -> SdarConfig:
    return SDAR_TINY if "tiny" in model_name.lower() else SDAR_30B_PP8


# --- the parameter tree ------------------------------------------------------


def param_shapes(cfg: SdarConfig, dtype) -> dict:
    """The tree as `jax.ShapeDtypeStruct`s: `embed`, `layers` (a list: each
    `attn`, two norms and `moe`: `router` and the held `experts`, no
    `router_bias`, no `shared`), `final_norm`, `head`. Matrices are `[in,
    out]`; the held experts' are stacked `[held, in, out]`."""
    h, d, width = cfg.hidden_size, cfg.head_dim, cfg.moe_intermediate_size
    heads, kv_heads = cfg.num_attention_heads, cfg.num_key_value_heads
    held = cfg.experts_held[1]

    def s(*dims):
        return jax.ShapeDtypeStruct(dims, dtype)

    def layer():
        return {
            "input_norm": s(h), "post_norm": s(h),
            "attn": {"q": s(h, heads * d), "k": s(h, kv_heads * d),
                     "v": s(h, kv_heads * d), "o": s(heads * d, h),
                     "q_norm": s(d), "k_norm": s(d)},
            "moe": {"router": s(h, cfg.num_experts),
                    "experts": {"gate": s(held, h, width),
                                "up": s(held, h, width),
                                "down": s(held, width, h)}}}

    return {"embed": s(cfg.vocab_size, h),
            "layers": [layer() for _ in range(cfg.num_hidden_layers)],
            "final_norm": s(h), "head": s(h, cfg.vocab_size)}


def init_params(cfg: SdarConfig, key, dtype) -> dict:
    return init_leaves(param_shapes(cfg, dtype), key)


# --- a forward ---------------------------------------------------------------


def _rotated(x, weight, cfg: SdarConfig, positions):
    """A head's dims `x` [..., heads, head_dim] RMS-normed under `weight`
    and rotated at `positions` [...]."""
    cos, sin = rope_tables(cfg.head_dim, cfg.rope_theta, positions)
    return apply_rope(rms_norm(x, weight, cfg.rms_norm_eps),
                      cos[..., None, :], sin[..., None, :])


def _queries(p, cfg: SdarConfig, h, positions):
    """`h` [..., hidden] at `positions` [...] as queries [..., heads,
    head_dim], normed and rotated."""
    q = dot(h, p["q"]).reshape(*h.shape[:-1], cfg.num_attention_heads,
                               cfg.head_dim)
    return _rotated(q, p["q_norm"], cfg, positions)


def _keys_values(p, cfg: SdarConfig, h, positions):
    """The keys and values [..., key heads, head_dim] the cache holds of
    `h` [..., hidden] at `positions` [...]: the keys normed and rotated."""
    k, v = (dot(h, p[name]).reshape(*h.shape[:-1], cfg.num_key_value_heads,
                                    cfg.head_dim) for name in ("k", "v"))
    return _rotated(k, p["k_norm"], cfg, positions), v


def _forward(params, cfg: SdarConfig, ids, positions, attend, load, valid,
             read: int, interpret: bool):
    """Tokens `ids` [R, C] at `positions` [R, C] through every layer;
    `attend(index, q, k, v)` is the layer's attention over whatever the
    caller keeps, [R, C, heads, head_dim]; `valid` [R, C]: the tokens that
    are routed. `read`: the last positions of a row whose hidden state
    somebody reads. The others stop at the last layer's keys and values:
    there `attend` gets the queries of the last `read` positions alone
    (and every position's keys and values), and only those are routed.
    Returns the last hidden state [R, read, hidden] (None where `read` is
    0), a layer's keys and values of all the tokens, and the tally."""
    x = params["embed"][ids]
    entries = []
    for index, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
        k, v = _keys_values(layer["attn"], cfg, h, positions)
        entries.append((k, v))
        if index == len(params["layers"]) - 1 and read < ids.shape[1]:
            if not read:
                return None, entries, load
            x, h, positions, valid = (
                None if part is None else part[:, -read:]
                for part in (x, h, positions, valid))
        out = attend(index, _queries(layer["attn"], cfg, h, positions), k, v)
        x = x + dot(out.reshape(*x.shape[:-1], -1), layer["attn"]["o"])
        h = rms_norm(x, layer["post_norm"], cfg.rms_norm_eps)
        out, told = feed_forward(
            layer, cfg, h.reshape(-1, cfg.hidden_size),
            None if valid is None else valid.reshape(-1), interpret)
        x = x + out.reshape(x.shape)
        load = tally(load, index, cfg, told)
    return x, entries, load


# --- the cache and prefill ---------------------------------------------------


def blocks_of(cfg: SdarConfig, new_tokens: int) -> int:
    """Blocks a pass runs for `new_tokens` ids a row: the first block of a
    row may hold `block_length - 1` given ids of its prompt."""
    return -(-(cfg.block_length - 1 + new_tokens) // cfg.block_length)


def cache_positions(cfg: SdarConfig, slots: int, new_tokens: int) -> int:
    """Columns of a pass's cache: the prompt's slots and every block but
    the last, which nobody reads after it and is not committed."""
    return slots + (blocks_of(cfg, new_tokens) - 1) * cfg.block_length


def new_cache(cfg: SdarConfig, rows: int, positions: int, dtype):
    """(keys, values) a layer, `[rows, positions, key heads, head_dim]`."""
    return tuple(
        tuple(jnp.zeros((rows, positions, cfg.num_key_value_heads,
                         cfg.head_dim), dtype) for _ in range(2))
        for _ in range(cfg.num_hidden_layers))


def cache_bytes(cfg: SdarConfig, rows: int, positions: int,
                itemsize: int) -> tuple[int, int, int]:
    """(bytes of a pass's cache, the part of it that is rings of a window,
    the part that is recurrent state: none of either)."""
    return (cfg.num_hidden_layers * rows * positions * cfg.position_bytes
            * itemsize, 0, 0)


# a prefill chunk may be a span of one row's positions (a chunk's edge is
# a multiple of the block length: `prefill_rows` asserts it)
POSITION_CHUNKS = True


def whole_blocks(cfg: SdarConfig, lengths):
    """The positions of a row's prompt that are whole blocks, [R]."""
    return lengths // cfg.block_length * cfg.block_length


def prefill_rows(params, cfg: SdarConfig, ids, lengths, chunk_slots: int,
                 load, interpret: bool = False):
    """Rows `ids` [R, S] (a row's prompt first, padding after) through the
    layers under the block mask, `chunk_slots` positions at a time, each
    span attending to what the spans before it cached; a span no row
    reaches is not run (`span_runs`, as K-EXAONE's). A token of a whole
    prompt block sees whole prompt blocks only, so what lies behind them
    (the prompt's tail, padding) is computed, routed nowhere and never
    read. Returns a layer's keys and values `[R, S, ...]` and the tally."""
    rows, slots = ids.shape
    assert slots % chunk_slots == 0 and chunk_slots % cfg.block_length == 0, (
        slots, chunk_slots, cfg.block_length)
    dtype = params["embed"].dtype
    scale = cfg.head_dim ** -0.5
    whole = whole_blocks(cfg, lengths)

    def run(start, kept, load):
        positions = jnp.broadcast_to(
            start + jnp.arange(chunk_slots), (rows, chunk_slots))

        def attend(index, q, k, v):
            keys, values = (jnp.concatenate([*old, entry], 1)
                            for old, entry in zip(kept[index], (k, v)))
            return dot_product_attention(q, keys, values, scale=scale,
                                         causal=True, span=cfg.block_length)

        _, added, load = _forward(
            params, cfg, ids[:, start:start + chunk_slots], positions,
            attend, load, positions < whole[:, None], 0, interpret)
        return added, load

    def skip(start, kept, load):
        """What `run` gives where no row reaches `start`: zeros, written
        and not left to the buffer (models/prefill_chunks.py `whole_rows`)."""
        zeros = jnp.zeros((rows, chunk_slots, cfg.num_key_value_heads,
                           cfg.head_dim), dtype)
        return [(zeros, zeros)] * cfg.num_hidden_layers, load

    kept = [([], []) for _ in range(cfg.num_hidden_layers)]
    for start in range(0, slots, chunk_slots):
        added, load = jax.lax.cond(
            span_runs(lengths, start), functools.partial(run, start),
            functools.partial(skip, start), kept, load)
        kept = [tuple(old + [new] for old, new in zip(before, after))
                for before, after in zip(kept, added)]
    return [tuple(jnp.concatenate(part, 1) for part in layer)
            for layer in kept], load


def prefill_widths(slots: int, chunk_slots: int | None = None):
    """The widths a chunk of `prefill` may have: the bucket alone. A
    narrower width is one more traced copy of the layers, and two of them
    made a worker's start 8 s (13 %) longer where a pass got 0.18 s
    shorter (PERF.md section 6, PR 43)."""
    return (slots,)


def prefill_account(lengths, slots: int, chunk_rows: int, chunk_slots: int):
    """The host's account of what `prefill` ran (models/text_model.py):
    the rows that have a length, and of a chunk's spans those some row of
    it reaches."""
    return chunk_account(lengths, slots, chunk_rows, chunk_slots,
                         prefill_widths(slots, chunk_slots), span_runs)


def prefill(params, cfg: SdarConfig, ids, lengths, positions: int,
            chunk_rows: int, chunk_slots: int | None = None,
            interpret: bool = False):
    """`ids` [R, S] in chunks of `chunk_rows` rows x `chunk_slots`
    positions, rows of no length left out (models/prefill_chunks.py).
    Returns the cache (`new_cache`: the first S columns written) and the
    tally. No logits: the last prompt position's predict nothing here (a
    generated position's own logits predict its token)."""
    rows, slots = ids.shape
    dtype = params["embed"].dtype
    chunk_slots = slots if chunk_slots is None else chunk_slots

    def run(ids, lengths, load):
        entries, load = prefill_rows(params, cfg, ids, lengths, chunk_slots,
                                     load, interpret)
        return tuple(entries), load

    return prefill_by_length(
        ids, lengths, chunk_rows, prefill_widths(slots, chunk_slots),
        run, new_cache(cfg, rows, positions, dtype), empty_load(cfg))


# --- a block -----------------------------------------------------------------


def block_attention(q, k, v, keys, values, seen, scale: float, span: int):
    """A row's queries of its own positions against the cache and those
    positions' own keys: `k` / `v` [R, C, key heads, D] the forward's own,
    `q` [R, Q, heads, D] the queries of the last `Q` of those `C`
    positions, `keys` / `values` [R, S, key heads, D] the cache, `seen`
    [R, S] its columns the row may see. Own position `t` sees own key `u`
    iff `u // span <= t // span`: the block mask between the two blocks of
    a fused forward, and no mask where every query sees every own key (one
    block; the last block's queries alone). A group's query heads meet
    their one cached head in one batched matmul where the cache lies; one
    softmax over both parts, which are never laid side by side (the
    cache's columns stay a whole number of lane tiles): each leaves as
    `exp(score - the largest of both)`, rounded as the values are, and the
    sum of both divides the output."""
    rows, block, heads, d = q.shape
    kv_heads, own_keys = k.shape[2], k.shape[1]
    q = q.reshape(rows, block, kv_heads, heads // kv_heads, d)
    past = jnp.einsum("rbhgd,rshd->rhgbs", q, keys,
                      preferred_element_type=jnp.float32) * scale
    past = jnp.where(seen[:, None, None, None, :], past, -jnp.inf)
    own = jnp.einsum("rbhgd,rchd->rhgbc", q, k,
                     preferred_element_type=jnp.float32) * scale
    at = np.arange(own_keys - block, own_keys)[:, None] // span
    visible = np.arange(own_keys)[None, :] // span <= at
    if not visible.all():
        own = jnp.where(visible, own, -jnp.inf)
    top = jnp.maximum(jnp.max(past, -1), jnp.max(own, -1))[..., None]
    past, own = jnp.exp(past - top), jnp.exp(own - top)
    total = jnp.sum(past, -1) + jnp.sum(own, -1)
    out = jnp.einsum("rhgbs,rshd->rbhgd", past.astype(values.dtype), values,
                     preferred_element_type=jnp.float32) + jnp.einsum(
        "rhgbc,rchd->rbhgd", own.astype(values.dtype),
        v.astype(values.dtype), preferred_element_type=jnp.float32)
    out = out / jnp.transpose(total, (0, 3, 1, 2))[..., None]
    return out.astype(values.dtype).reshape(rows, block, heads, d)


def block_step(params, cfg: SdarConfig, ids, lengths, block, slots: int,
               cache, load, valid=None, commit: bool = False, finished=None,
               interpret: bool = False):
    """Every row's block number `block` through every layer: `ids` [R, B]
    at the row's positions `L // B * B + block * B` on, against the row's
    whole prompt blocks and the committed blocks before this one (`valid`
    [R]: a row that only pads the pass is routed nowhere). The cache is
    written only under `commit`, at columns `slots + block * B` on, and
    with `finished`.

    `finished` [R, B] makes the forward a **fused** one: the final ids of
    block `block - 1`, which the cache does not hold yet, run in front of
    `ids` as eight positions a row under the block mask (the first four
    see the cache and each other, the last four the cache and all eight),
    and the first four's keys and values of every layer are written at
    columns `slots + (block - 1) * B` on: what a forward of that block
    alone under `commit` writes. Their positions yield nothing else: in
    the last layer they stop at their keys and values, and the head never
    sees them.

    Returns the logits [R, B, vocab] of `ids`' positions (float32;
    position `j`'s predict position `j`'s own token), the cache and the
    tally."""
    rows, length = ids.shape
    given = 0 if finished is None else length
    first = slots + block * length - given
    start = whole_blocks(cfg, lengths)
    tokens = ids if finished is None else jnp.concatenate([finished, ids], 1)
    positions = (start + block * length - given)[:, None] + jnp.arange(
        given + length)
    columns = jnp.arange(cache[0][0].shape[1])[None, :]
    seen = (columns < start[:, None]) | (
        (columns >= slots) & (columns < first))
    scale = cfg.head_dim ** -0.5

    def attend(index, q, k, v):
        return block_attention(q, k, v, *cache[index], seen, scale, length)

    x, entries, load = _forward(
        params, cfg, tokens, positions, attend, load,
        None if valid is None else jnp.broadcast_to(
            valid[:, None], tokens.shape), length, interpret)
    written = given + (length if commit else 0)
    if written:
        # the writes wait for the forward's last read of the cache: left
        # to itself the chip's compiler copies a whole layer's keys
        # before a fused forward's write (two copies a block, PR 41)
        x, cache = jax.lax.optimization_barrier((x, cache))
        cache = tuple(
            tuple(jax.lax.dynamic_update_slice(
                whole, entry[:, :written].astype(whole.dtype),
                (0, first, 0, 0))
                  for whole, entry in zip(layer, added))
            for layer, added in zip(cache, entries))
    return logits_of(params, cfg, x), cache, load


# --- the generation's rule ---------------------------------------------------


def first_block(cfg: SdarConfig, ids, lengths):
    """The first generated block of every row, (ids [R, B], masked [R,
    B]): the prompt's last `L mod B` ids as given, the mask id behind
    them. Any later block is the mask id throughout."""
    start = whole_blocks(cfg, lengths)
    at = start[:, None] + jnp.arange(cfg.block_length)
    masked = at >= lengths[:, None]
    given = jnp.take_along_axis(
        ids, jnp.minimum(at, ids.shape[1] - 1), axis=1)
    return jnp.where(masked, cfg.mask_token_id, given), masked


def unmask(ids, masked, drawn, confidence, count: int, threshold=None):
    """A block after one denoise forward: `ids`, `masked` [R, B] before
    it, `drawn` [R, B] the ids the forward drew and `confidence` [R, B]
    each one's probability. The `count` masked positions of highest
    confidence take their drawn id (all of them where fewer are masked;
    ties to the earlier position); with a `threshold`, every masked
    position over it instead where those are `count` at least. "Masked" is
    the state kept here, not `id == mask id`. Returns (ids, masked)."""
    held = jnp.where(masked, confidence, -jnp.inf)
    order = jnp.argsort(-held, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    take = masked & (rank < count)
    if threshold is not None:
        high = masked & (confidence > threshold)
        take = jnp.where(
            jnp.sum(high, -1, keepdims=True) >= count, high, take)
    return jnp.where(take, drawn, ids), masked & ~take
