"""K-EXAONE's language model (`model_type` `exaone_moe`): grouped-query
attention on window and full layers side by side, sigmoid-routed sparse
experts, as pure functions over a parameter tree.

The block, with `h = RMSNorm(x)` (benchmark/reference/gqa_window_moe.py is
the plain float32 statement of the same equations):

- attention: `q = h W_q` (heads x head_dim), `k = h W_k`, `v = h W_v`
  (key heads x head_dim; query head `j` reads key head `j // G`); `q` and
  `k` RMS-normed over the head's dims with a learned weight; on a
  `sliding_attention` layer rotary (all the head's dims, the position's
  own angle) on `q` and `k` and a token sees the `sliding_window` keys up
  to its own, on a `full_attention` layer no rotary and every key up to
  its own. `x += softmax(q . k / sqrt(head_dim)) v W_o`.
- `layer_types` is `sliding_window_pattern` repeated (`LLLG`: three
  sliding layers, then a full one).
- the second half is models/experts.py's: `x += ffn(RMSNorm(x))`, the
  leading `first_k_dense_replace` layers a dense SwiGLU, the others the
  held experts' part and the shared expert's.

Two kinds of cache in one pass (`new_cache`): a full layer keeps keys and
values `[rows, positions, key heads, head_dim]`, the prompt's in the first
`prompt slots` columns and generated token `n` at column `slots + n`; a
sliding layer keeps a ring of `sliding_window` columns, position `p` at
column `p mod window`, so it holds the window's keys whatever the length.
A key is cached as attention reads it: normed and, on a sliding layer,
rotated. What a row sees of either follows from its own length and the
step (models/text_model.py `decode_mask`, `ring_mask`).

Prefill goes in chunks of positions (`prefill`): a chunk's queries attend
to the keys cached so far and its own through `ops.attention` (causal,
with the layer's window: the banded kernel on a TPU), so no score matrix
is ever longer than a chunk on one side. A decode step's attention over
either cache is plain XLA, the group's query heads against their one
cached head in one batched matmul.

Rotary pairs are the two halves of the head's dims (`rope_type`
`default`), as the published code rotates them.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ..ops import dot_product_attention
from . import prefill_chunks
from .experts import (
    dot,
    empty_load,
    feed_forward,
    init_leaves,
    logits_of,
    rms_norm,
    tally,
)
from .text_model import (
    apply_rope,
    cached_attention,
    decode_mask,
    ring_fill,
    ring_mask,
    rope_tables,
)


@dataclasses.dataclass(frozen=True)
class ExaoneConfig:
    """The published sizes (huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B
    config.json), and which share of them is held here."""

    hidden_size: int = 6144
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_experts: int = 128  # the router's width, whatever is held
    num_experts_per_tok: int = 8
    scoring_func: str = "sigmoid"
    routed_scaling_factor: float = 2.5
    num_shared_experts: int = 1
    first_k_dense_replace: int = 1
    num_hidden_layers: int = 48
    vocab_size: int = 153600  # rows of the vocabulary held here
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    sliding_window: int = 128
    sliding_window_pattern: str = "LLLG"
    # (first, count): the routed experts this chip holds of every layer
    experts_held: tuple[int, int] = (0, 128)

    @property
    def windows(self) -> tuple[int, ...]:
        """A layer's window: `sliding_window` keys, or 0 for every key."""
        pattern = self.sliding_window_pattern
        return tuple(
            self.sliding_window if pattern[n % len(pattern)] == "L" else 0
            for n in range(self.num_hidden_layers))

    @property
    def layer_types(self) -> tuple[str, ...]:
        return tuple("sliding_attention" if window else "full_attention"
                     for window in self.windows)

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def position_bytes(self) -> int:
        """Values a layer caches a position: a key and a value a key
        head."""
        return 2 * self.num_key_value_heads * self.head_dim


# one chip's share of an 8-chip expert-parallel deployment: layers 0-4 of
# the 48 (sliding and dense, sliding, sliding, full, sliding: the dense
# layer once and four expert layers in the published 3 : 1; the other 43
# would lie on further pipeline stages), experts 0-15 of each layer's 128
# (rank 0 of the 8 chips that share a layer), rows 0-19199 of the
# vocabulary (an eighth)
EXAONE_236B_EP8 = ExaoneConfig(num_hidden_layers=5, experts_held=(0, 16),
                               vocab_size=19200)
EXAONE_TINY = ExaoneConfig(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, intermediate_size=128, moe_intermediate_size=32,
    num_experts=32, num_experts_per_tok=4, num_hidden_layers=5,
    vocab_size=128, sliding_window=4, experts_held=(0, 8))


def config_for(model_name: str) -> ExaoneConfig:
    return EXAONE_TINY if "tiny" in model_name.lower() else EXAONE_236B_EP8


# --- the parameter tree ------------------------------------------------------


def param_shapes(cfg: ExaoneConfig, dtype) -> dict:
    """The tree as `jax.ShapeDtypeStruct`s: `embed`, `layers` (a list: each
    `attn`, two norms, and `mlp` or `moe`), `final_norm`, `head`. Matrices
    are `[in, out]`; the held experts' are stacked `[held, in, out]`."""
    h, d = cfg.hidden_size, cfg.head_dim
    heads, kv_heads = cfg.num_attention_heads, cfg.num_key_value_heads

    def s(*dims):
        return jax.ShapeDtypeStruct(dims, dtype)

    def swiglu(width, *lead):
        return {"gate": s(*lead, h, width), "up": s(*lead, h, width),
                "down": s(*lead, width, h)}

    layers = []
    for index in range(cfg.num_hidden_layers):
        layer = {
            "input_norm": s(h), "post_norm": s(h),
            "attn": {"q": s(h, heads * d), "k": s(h, kv_heads * d),
                     "v": s(h, kv_heads * d), "o": s(heads * d, h),
                     "q_norm": s(d), "k_norm": s(d)}}
        if index < cfg.first_k_dense_replace:
            layer["mlp"] = swiglu(cfg.intermediate_size)
        else:
            layer["moe"] = {
                "router": s(h, cfg.num_experts),
                "router_bias": s(cfg.num_experts),
                "experts": swiglu(cfg.moe_intermediate_size,
                                  cfg.experts_held[1]),
                "shared": swiglu(
                    cfg.moe_intermediate_size * cfg.num_shared_experts)}
        layers.append(layer)
    return {"embed": s(cfg.vocab_size, h), "layers": layers,
            "final_norm": s(h), "head": s(h, cfg.vocab_size)}


def init_params(cfg: ExaoneConfig, key, dtype) -> dict:
    return init_leaves(param_shapes(cfg, dtype), key)


# --- attention ---------------------------------------------------------------


def _heads(p, cfg: ExaoneConfig, h, positions, window: int):
    """`h` [..., hidden] at `positions` [...] as queries [..., heads,
    head_dim] and the keys and values [..., key heads, head_dim] the cache
    holds of them: normed, and on a sliding layer rotated."""
    d = cfg.head_dim
    q = dot(h, p["q"]).reshape(*h.shape[:-1], cfg.num_attention_heads, d)
    k = dot(h, p["k"]).reshape(*h.shape[:-1], cfg.num_key_value_heads, d)
    v = dot(h, p["v"]).reshape(*h.shape[:-1], cfg.num_key_value_heads, d)
    q = rms_norm(q, p["q_norm"], cfg.rms_norm_eps)
    k = rms_norm(k, p["k_norm"], cfg.rms_norm_eps)
    if window:
        cos, sin = rope_tables(cfg.head_dim, cfg.rope_theta, positions)
        q = apply_rope(q, cos[..., None, :], sin[..., None, :])
        k = apply_rope(k, cos[..., None, :], sin[..., None, :])
    return q, k, v


# --- prefill and decode ------------------------------------------------------


def new_cache(cfg: ExaoneConfig, rows: int, positions: int, dtype):
    """(keys, values) a layer: `[rows, positions, key heads, head_dim]` on
    a full layer, `[rows, sliding_window, ...]` on a sliding one."""
    return tuple(
        tuple(jnp.zeros((rows, window or positions, cfg.num_key_value_heads,
                         cfg.head_dim), dtype) for _ in range(2))
        for window in cfg.windows)


def cache_bytes(cfg: ExaoneConfig, rows: int, positions: int,
                itemsize: int) -> tuple[int, int, int]:
    """(bytes of a pass's cache, the part of it that is rings of a window,
    the part that is recurrent state: none)."""
    rings = sum(rows * window * cfg.position_bytes * itemsize
                for window in cfg.windows if window)
    whole = sum(rows * positions * cfg.position_bytes * itemsize
                for window in cfg.windows if not window)
    return whole + rings, rings, 0


# a prefill chunk may be a span of one row's positions
POSITION_CHUNKS = True


def prefill_account(lengths, slots: int, chunk_rows: int, chunk_slots: int):
    """The host's account of what `prefill` ran (models/text_model.py):
    `chunk_rows` rows a chunk whatever their lengths, of its spans those
    some row of it reaches."""
    return prefill_chunks.chunk_account(
        lengths, slots, chunk_rows, chunk_slots,
        runs=prefill_chunks.span_runs)


def prefill_rows(params, cfg: ExaoneConfig, ids, lengths, chunk_slots: int,
                 load, interpret: bool = False):
    """Rows `ids` [R, S] (a row's prompt first, padding after: under a
    causal mask no real token sees padding) through every layer,
    `chunk_slots` positions at a time; a span no row reaches is not run
    (`prefill_chunks.span_runs`, asked through its module so that a test
    can put another rule in its place: a conditional on the device, the one
    program whatever the lengths). Returns the hidden state of each row's
    last prompt token [R, hidden], a layer's cache entries ((keys, values):
    `[R, S, ...]` on a full layer, the ring `[R, window, ...]` on a sliding
    one) and the tally."""
    rows, slots = ids.shape
    assert slots % chunk_slots == 0, (slots, chunk_slots)
    dtype = params["embed"].dtype
    scale = cfg.head_dim ** -0.5

    def zeros(columns):
        return jnp.zeros((rows, columns, cfg.num_key_value_heads,
                          cfg.head_dim), dtype)

    def run(start, rings, kept, last, load):
        """The span from `start` through every layer. `kept` is read only;
        what the span adds to it comes back a layer: a full layer's keys
        and values of these positions, a sliding layer's last `window`."""
        rings, added = list(rings), []
        positions = jnp.broadcast_to(
            start + jnp.arange(chunk_slots), (rows, chunk_slots))
        valid = (positions < lengths[:, None]).reshape(-1)
        x = params["embed"][ids[:, start:start + chunk_slots]]
        for index, (layer, window) in enumerate(
                zip(params["layers"], cfg.windows)):
            h = rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
            q, k, v = _heads(layer["attn"], cfg, h, positions, window)
            keys, values = (jnp.concatenate([*old, entry], 1)
                            for old, entry in zip(kept[index], (k, v)))
            if window:
                rings[index] = tuple(
                    ring_fill(ring, entry, start, lengths)
                    for ring, entry in zip(rings[index], (k, v)))
                added.append((keys[:, -window:], values[:, -window:]))
            else:
                added.append((k, v))
            out = dot_product_attention(q, keys, values, scale=scale,
                                        causal=True, window=window)
            x = x + dot(out.reshape(rows, chunk_slots, -1),
                        layer["attn"]["o"])
            h = rms_norm(x, layer["post_norm"], cfg.rms_norm_eps)
            out, told = feed_forward(
                layer, cfg, h.reshape(rows * chunk_slots, -1), valid,
                interpret)
            x = x + out.reshape(x.shape)
            load = tally(load, index, cfg, told)
        at = lengths - 1 - start
        mine = (at >= 0) & (at < chunk_slots)
        picked = jnp.take_along_axis(
            x, jnp.clip(at, 0, chunk_slots - 1)[:, None, None], axis=1)[:, 0]
        return (rings, added, jnp.where(mine[:, None], picked, last), load)

    def skip(start, rings, kept, last, load):
        """What `run` gives where no row reaches `start`, wherever anything
        reads it. Rings, `last` and the tally as they came: `run` finds no
        position of its own for them. A full layer's keys and values
        zero: columns past every row's length, which `decode_mask` shows
        to nobody, written and not left to the buffer (`whole_rows`). A
        sliding layer's tail zero too: only a later span would read it,
        and none runs."""
        added = [(zeros(min(start + chunk_slots, window)
                        if window else chunk_slots),) * 2
                 for window in cfg.windows]
        return rings, added, last, load

    # what the earlier spans left, (keys, values) a layer: a sliding
    # layer's ring, and in `kept` its last `window` and a full layer's
    # whole, span by span
    rings = [(zeros(window),) * 2 if window else None
             for window in cfg.windows]
    kept = [([], []) for _ in cfg.windows]
    last = jnp.zeros((rows, cfg.hidden_size), dtype)
    for start in range(0, slots, chunk_slots):
        # a branch is handed what it reads and gives back what the span
        # adds: the full layer's keys so far go in and do not come out
        rings, added, last, load = jax.lax.cond(
            prefill_chunks.span_runs(lengths, start),
            functools.partial(run, start),
            functools.partial(skip, start), rings, kept, last, load)
        kept = [tuple(([] if window else old) + [new]
                      for old, new in zip(before, after))
                for window, before, after in zip(cfg.windows, kept, added)]
    entries = [
        ring if window else tuple(jnp.concatenate(part, 1) for part in whole)
        for window, ring, whole in zip(cfg.windows, rings, kept)]
    return last, entries, load


def prefill(params, cfg: ExaoneConfig, ids, lengths, positions: int,
            chunk_rows: int, chunk_slots: int | None = None,
            interpret: bool = False):
    """`ids` [R, S] in chunks of `chunk_rows` rows x `chunk_slots`
    positions (what bounds the widest layer's activations: whole rows
    where rows are short, a span of one row's positions where a row is
    longer). Returns the last prompt position's logits [R, vocab], the
    cache (`new_cache`: a full layer's first S columns written, a sliding
    layer's ring) and the tally."""
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
                (at, 0, 0, 0))
                  for whole, entry in zip(layer, written))
            for layer, written in zip(cache, entries))
        return (jax.lax.dynamic_update_slice(last, x, (at, 0)), cache, load)

    last, cache, load = jax.lax.fori_loop(
        0, rows // chunk_rows, chunk,
        (jnp.zeros((rows, cfg.hidden_size), dtype),
         new_cache(cfg, rows, positions, dtype), empty_load(cfg)))
    return logits_of(params, cfg, last), cache, load


def step(params, cfg: ExaoneConfig, tokens, lengths, number, slots: int,
         cache, load, valid=None, interpret: bool = False):
    """Every row's generated token `number` through every layer and both
    kinds of cache: `tokens` [R], at position `lengths + number`, cached at
    column `slots + number` of a full layer and `position mod window` of a
    ring (`valid` [R]: a row that only pads the pass is routed nowhere).
    Returns the logits [R, vocab] (float32), the cache and the tally."""
    x = params["embed"][tokens]
    rows = tokens.shape[0]
    at = lengths + number
    scale = cfg.head_dim ** -0.5
    full_positions = next(
        (layer[0].shape[1] for layer, window in zip(cache, cfg.windows)
         if not window), slots + 1)
    see_full = decode_mask(lengths, slots, full_positions, number)
    see_ring = ring_mask(cfg.sliding_window, lengths, number)
    cache = list(cache)
    for index, (layer, window) in enumerate(zip(params["layers"],
                                                cfg.windows)):
        h = rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
        q, k, v = _heads(layer["attn"], cfg, h, at, window)
        if window:
            column = jnp.mod(at, window)
            keys, values = (
                whole.at[jnp.arange(rows), column].set(
                    entry.astype(whole.dtype))
                for whole, entry in zip(cache[index], (k, v)))
        else:
            keys, values = (
                jax.lax.dynamic_update_slice(
                    whole, entry[:, None].astype(whole.dtype),
                    (0, slots + number, 0, 0))
                for whole, entry in zip(cache[index], (k, v)))
        cache[index] = (keys, values)
        out = cached_attention(q, keys, values,
                               see_ring if window else see_full, scale)
        x = x + dot(out, layer["attn"]["o"])
        h = rms_norm(x, layer["post_norm"], cfg.rms_norm_eps)
        out, told = feed_forward(layer, cfg, h, valid, interpret)
        x = x + out
        load = tally(load, index, cfg, told)
    return logits_of(params, cfg, x), tuple(cache), load
