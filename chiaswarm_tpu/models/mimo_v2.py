"""MiMo-V2's language model (`model_type` `mimo_v2`): grouped-query
attention whose keys are wider than its values, on window layers with a
learned sink in their softmax and full layers side by side, sigmoid-routed
sparse experts with no shared one, as pure functions over a parameter tree.

The block, with `h = RMSNorm(x)` (benchmark/reference/gqa_sink_moe.py is the
plain float32 statement of the same equations):

- projections, one fused matrix: `[q | k | v] = h W_qkv`, `q` heads x
  `head_dim` (192), `k` key heads x `head_dim`, `v` key heads x
  `v_head_dim` (128) times `attention_value_scale`; the key heads are
  `num_key_value_heads` (4) on a full layer and `swa_num_key_value_heads`
  (8) on a window layer; query head `j` reads key head `j // G`. No bias,
  no query / key norm.
- rotary on the first `int(head_dim x partial_rotary_factor)` (64) dims of
  every query and key head, the two halves of those as the pairs, the rest
  unrotated; base `rope_theta` on a full layer, `swa_rope_theta` on a
  window layer.
- a full layer (`hybrid_layer_pattern` 0): every key up to the token's own,
  `softmax(q . k / sqrt(head_dim)) v`.
- a window layer (pattern 1): the `sliding_window` keys up to its own, and
  a learned logit `sink` a query head in the softmax's denominator, with no
  value: a row's weights add up to less than one
  (`ops.wide_key_attention`).
- `x += concat(heads) W_o`; the second half is models/experts.py's: `x +=
  ffn(RMSNorm(x))`, a layer whose `moe_layer_freq` is 0 a dense SwiGLU (the
  leading one), the others the held experts' part (no shared expert: the
  layer's tree has none).

Two cache geometries in one pass (`new_cache`): a full layer keeps keys
`[rows, positions, 4, 192]` and values `[rows, positions, 4, 128]`, the
prompt's in the first `prompt slots` columns and generated token `n` at
column `slots + n`; a window layer keeps a ring of `sliding_window` columns
of `[8, 192]` and `[8, 128]`, position `p` at column `p mod window`. A key
is cached as attention reads it (rotated), a value scaled. What a row sees
of either follows from its own length and the step (models/text_model.py
`decode_mask`, `ring_mask`).

Prefill goes in spans of positions (`prefill`), the spans of a row ONE
traced body in a loop, the span's first position a number the loop carries
(a row of eight spans compiles seven layers and not fifty-six). A full
layer writes the span's keys and values into the row's cache and its
queries attend to the cache up to the span's END (`ops.wide_key_attention`,
`offset=`: the key blocks past it are no grid step, so a row's first span
does an eighth of the key-side work of its last). A window layer's queries
attend to the `sliding_window` keys the span before left (the `tail` the
loop carries) and the span's own; before a row's first span the tail holds
no position and is masked out (`floor=`). A decode step's attention over
either cache is plain XLA (models/text_model.py `cached_attention`, with
the layer's sink).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.wide_key_attention import wide_key_attention
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

# the published pattern: layer 0 full, four window layers and a full one,
# then five window layers to a full one seven times (9 full, 39 window)
_PATTERN = (0, 1, 1, 1, 1, 0) + (1, 1, 1, 1, 1, 0) * 7


@dataclasses.dataclass(frozen=True)
class MimoV2Config:
    """The published sizes (huggingface.co/XiaomiMiMo/MiMo-V2.5
    config.json), and which share of them is held here."""

    hidden_size: int = 4096
    num_attention_heads: int = 64
    num_key_value_heads: int = 4       # a full layer's
    swa_num_key_value_heads: int = 8   # a window layer's
    head_dim: int = 192
    v_head_dim: int = 128
    partial_rotary_factor: float = 0.334
    rope_theta: float = 1e7            # a full layer's
    swa_rope_theta: float = 1e4        # a window layer's
    attention_value_scale: float = 0.707
    sliding_window: int = 128
    # a layer's kind: 0 full, 1 window (with the sink)
    hybrid_layer_pattern: tuple[int, ...] = _PATTERN
    # a layer's second half: 0 a dense SwiGLU, 1 the experts
    moe_layer_freq: tuple[int, ...] = (0,) + (1,) * 47
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256  # the router's width, whatever is held
    num_experts_per_tok: int = 8
    scoring_func: str = "sigmoid"
    routed_scaling_factor: float = 1.0  # published as null
    num_hidden_layers: int = 48
    vocab_size: int = 152576  # rows of the vocabulary held here
    rms_norm_eps: float = 1e-5
    # (first, count): the routed experts this chip holds of every layer
    experts_held: tuple[int, int] = (0, 256)

    def __post_init__(self):
        layers = self.num_hidden_layers
        assert len(self.hybrid_layer_pattern) == layers == len(
            self.moe_layer_freq), (layers, self.hybrid_layer_pattern,
                                   self.moe_layer_freq)
        # the dense layers lead (models/experts.py counts an expert layer
        # from `first_k_dense_replace`)
        dense = self.first_k_dense_replace
        assert not any(self.moe_layer_freq[:dense]) and all(
            self.moe_layer_freq[dense:]), self.moe_layer_freq

    @property
    def windows(self) -> tuple[int, ...]:
        """A layer's window: `sliding_window` keys, or 0 for every key."""
        return tuple(self.sliding_window if kind else 0
                     for kind in self.hybrid_layer_pattern)

    @property
    def first_k_dense_replace(self) -> int:
        return self.moe_layer_freq.index(1) if any(
            self.moe_layer_freq) else self.num_hidden_layers

    @property
    def expert_layers(self) -> int:
        return sum(self.moe_layer_freq)

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    def key_heads(self, window: int) -> int:
        return (self.swa_num_key_value_heads if window
                else self.num_key_value_heads)

    def position_bytes(self, window: int) -> int:
        """Values a layer of the kind caches a position: a key of
        `head_dim` and a value of `v_head_dim` a key head."""
        return self.key_heads(window) * (self.head_dim + self.v_head_dim)


# one of 16 chips that share each layer: layer 0 (full, dense) and one whole
# period behind it (five window layers, one full; the other 41 would lie on
# further pipeline stages), experts 0-15 of each layer's 256 (rank 0 of the
# 16), rows 0-19071 of the vocabulary (an eighth)
MIMO_V25_EP16 = MimoV2Config(
    num_hidden_layers=7, hybrid_layer_pattern=(0, 1, 1, 1, 1, 1, 0),
    moe_layer_freq=(0, 1, 1, 1, 1, 1, 1), experts_held=(0, 16),
    vocab_size=19072)
MIMO_TINY = MimoV2Config(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=1,
    swa_num_key_value_heads=2, head_dim=24, v_head_dim=16,
    sliding_window=4, hybrid_layer_pattern=(0, 1, 1, 1, 1, 1, 0),
    moe_layer_freq=(0, 1, 1, 1, 1, 1, 1), intermediate_size=128,
    moe_intermediate_size=32, n_routed_experts=32, num_experts_per_tok=4,
    num_hidden_layers=7, vocab_size=128, experts_held=(0, 8))


def config_for(model_name: str) -> MimoV2Config:
    return MIMO_TINY if "tiny" in model_name.lower() else MIMO_V25_EP16


# --- the parameter tree ------------------------------------------------------


def param_shapes(cfg: MimoV2Config, dtype) -> dict:
    """The tree as `jax.ShapeDtypeStruct`s: `embed`, `layers` (a list: each
    `attn` (`qkv`, `o` and on a window layer `sink`), two norms, and `mlp`
    or `moe`), `final_norm`, `head`. Matrices are `[in, out]`; the held
    experts' are stacked `[held, in, out]`."""
    h, heads = cfg.hidden_size, cfg.num_attention_heads

    def s(*dims):
        return jax.ShapeDtypeStruct(dims, dtype)

    def swiglu(width, *lead):
        return {"gate": s(*lead, h, width), "up": s(*lead, h, width),
                "down": s(*lead, width, h)}

    layers = []
    for window, sparse in zip(cfg.windows, cfg.moe_layer_freq):
        attn = {"qkv": s(h, heads * cfg.head_dim
                         + cfg.position_bytes(window)),
                "o": s(heads * cfg.v_head_dim, h)}
        if window:
            attn["sink"] = s(heads)
        layer = {"input_norm": s(h), "post_norm": s(h), "attn": attn}
        if sparse:
            layer["moe"] = {
                "router": s(h, cfg.n_routed_experts),
                "router_bias": s(cfg.n_routed_experts),
                "experts": swiglu(cfg.moe_intermediate_size,
                                  cfg.experts_held[1])}
        else:
            layer["mlp"] = swiglu(cfg.intermediate_size)
        layers.append(layer)
    return {"embed": s(cfg.vocab_size, h), "layers": layers,
            "final_norm": s(h), "head": s(h, cfg.vocab_size)}


def init_params(cfg: MimoV2Config, key, dtype) -> dict:
    return init_leaves(param_shapes(cfg, dtype), key)


# --- attention ---------------------------------------------------------------


def _heads(p, cfg: MimoV2Config, h, positions, window: int):
    """`h` [..., hidden] at `positions` [...] as queries [..., heads,
    head_dim] and the keys [..., key heads, head_dim] and values [..., key
    heads, v_head_dim] the cache holds of them: the keys rotated at the
    layer kind's base, the values scaled."""
    heads, kv_heads = cfg.num_attention_heads, cfg.key_heads(window)
    d, dv, rotary = cfg.head_dim, cfg.v_head_dim, cfg.rotary_dim
    qkv = dot(h, p["qkv"])
    q = qkv[..., :heads * d].reshape(*h.shape[:-1], heads, d)
    k = qkv[..., heads * d:(heads + kv_heads) * d].reshape(
        *h.shape[:-1], kv_heads, d)
    v = qkv[..., (heads + kv_heads) * d:].reshape(
        *h.shape[:-1], kv_heads, dv)
    v = (v.astype(jnp.float32) * cfg.attention_value_scale).astype(v.dtype)
    cos, sin = rope_tables(
        rotary, cfg.swa_rope_theta if window else cfg.rope_theta, positions)
    cos, sin = cos[..., None, :], sin[..., None, :]
    q, k = (jnp.concatenate([apply_rope(x[..., :rotary], cos, sin),
                             x[..., rotary:]], axis=-1) for x in (q, k))
    return q, k, v


# --- prefill and decode ------------------------------------------------------


def new_cache(cfg: MimoV2Config, rows: int, positions: int, dtype):
    """(keys, values) a layer: `[rows, positions, 4, 192]` and `[rows,
    positions, 4, 128]` on a full layer, a ring `[rows, sliding_window, 8,
    .]` of each on a window layer."""
    return tuple(
        tuple(jnp.zeros((rows, window or positions, cfg.key_heads(window),
                         width), dtype)
              for width in (cfg.head_dim, cfg.v_head_dim))
        for window in cfg.windows)


def cache_bytes(cfg: MimoV2Config, rows: int, positions: int,
                itemsize: int) -> tuple[int, int, int]:
    """(bytes of a pass's cache, the part of it that is rings of a window,
    the part that is recurrent state: none), each kind of layer at its own
    key heads."""
    rings = sum(rows * window * cfg.position_bytes(window) * itemsize
                for window in cfg.windows if window)
    whole = sum(rows * positions * cfg.position_bytes(0) * itemsize
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


def prefill_key_extent(cfg: MimoV2Config, lengths, slots: int,
                       chunk_rows: int, chunk_slots: int) -> tuple[int, int]:
    """The host's account of the key positions the spans' full attention
    went over (models/text_model.py): a span that ran walks its rows'
    caches up to its own end on each full layer, where the bucket's width
    would be `slots`. (walked, bucket): a row of eight spans reads 36 /
    64."""
    lengths = np.asarray(lengths)
    full = sum(1 for window in cfg.windows if not window)
    walked = bucket = 0
    for at in range(0, len(lengths), chunk_rows):
        for start in range(0, slots, chunk_slots):
            if prefill_chunks.span_runs(lengths[at:at + chunk_rows], start):
                walked += chunk_rows * (start + chunk_slots) * full
                bucket += chunk_rows * slots * full
    return walked, bucket


def prefill_rows(params, cfg: MimoV2Config, ids, lengths, chunk_slots: int,
                 load, interpret: bool = False):
    """Rows `ids` [R, S] (a row's prompt first, padding after: under a
    causal mask no real token sees padding) through every layer,
    `chunk_slots` positions at a time, the spans one traced body in a loop;
    a span no row reaches is not run (`prefill_chunks.span_runs`: a
    conditional on the device, the one program whatever the lengths).
    Returns the hidden state of each row's last prompt token [R, hidden],
    a layer's cache entries ((keys, values): `[R, S, ...]` on a full layer,
    the ring `[R, window, ...]` on a window layer) and the tally."""
    rows, slots = ids.shape
    assert slots % chunk_slots == 0, (slots, chunk_slots)
    dtype = params["embed"].dtype
    scale = cfg.head_dim ** -0.5

    def run(start, cache, tails, last, load):
        """The span from `start` through every layer: a full layer's keys
        and values written into the rows' caches, a window layer's ring
        filled and its last `window` positions left as the next span's
        tail."""
        cache, tails = list(cache), list(tails)
        positions = jnp.broadcast_to(
            start + jnp.arange(chunk_slots), (rows, chunk_slots))
        real = positions < lengths[:, None]
        x = params["embed"][jax.lax.dynamic_slice_in_dim(
            ids, start, chunk_slots, axis=1)]
        for index, (layer, window) in enumerate(
                zip(params["layers"], cfg.windows)):
            attn = layer["attn"]
            h = rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
            q, k, v = _heads(attn, cfg, h, positions, window)
            if window:
                keys, values = (jnp.concatenate([old, new], axis=1)
                                for old, new in zip(tails[index], (k, v)))
                tails[index] = (keys[:, -window:], values[:, -window:])
                cache[index] = tuple(
                    ring_fill(ring, entry, start, lengths)
                    for ring, entry in zip(cache[index], (k, v)))
                # the tail's column `u` is position `start - window + u`:
                # before a row's first span it holds none
                out = wide_key_attention(
                    q, keys, values, scale, window, attn["sink"],
                    floor=jnp.maximum(window - start, 0),
                    interpret=interpret)
            else:
                keys, values = cache[index] = tuple(
                    jax.lax.dynamic_update_slice(
                        whole, new.astype(whole.dtype), (0, start, 0, 0))
                    for whole, new in zip(cache[index], (k, v)))
                # up to the span's end and no further: what the cache
                # holds past it (a later span writes it) is not fetched
                out = wide_key_attention(q, keys, values, scale,
                                         offset=start, interpret=interpret)
            x = x + dot(out.reshape(rows, chunk_slots, -1), attn["o"])
            h = rms_norm(x, layer["post_norm"], cfg.rms_norm_eps)
            out, told = feed_forward(
                layer, cfg, h.reshape(rows * chunk_slots, -1),
                real.reshape(-1), interpret)
            x = x + out.reshape(x.shape)
            load = tally(load, index, cfg, told)
        at = lengths - 1 - start
        mine = (at >= 0) & (at < chunk_slots)
        picked = jnp.take_along_axis(
            x, jnp.clip(at, 0, chunk_slots - 1)[:, None, None], axis=1)[:, 0]
        return (tuple(cache), tuple(tails),
                jnp.where(mine[:, None], picked, last), load)

    def span(number, carry):
        """A span no row reaches is not run: rings, tails, `last` and the
        tally as they came, a full layer's columns the zeros they began as
        (columns past every row's length, which `decode_mask` shows to
        nobody)."""
        start = number * chunk_slots
        return jax.lax.cond(
            prefill_chunks.span_runs(lengths, start),
            functools.partial(run, start), lambda *carry: carry, *carry)

    # a full layer's whole cache and a window layer's ring, and beside the
    # rings the tails: a window layer's last `window` keys and values
    cache = new_cache(cfg, rows, slots, dtype)
    tails = tuple(layer if window else None
                  for layer, window in zip(cache, cfg.windows))
    cache, _, last, load = jax.lax.fori_loop(
        0, slots // chunk_slots, span,
        (cache, tails, jnp.zeros((rows, cfg.hidden_size), dtype), load))
    return last, cache, load


def prefill(params, cfg: MimoV2Config, ids, lengths, positions: int,
            chunk_rows: int, chunk_slots: int | None = None,
            interpret: bool = False):
    """`ids` [R, S] in chunks of `chunk_rows` rows x `chunk_slots`
    positions (whole rows where rows are short, a span of one row's
    positions where a row is longer). Returns the last prompt position's
    logits [R, vocab], the cache (`new_cache`: a full layer's first S
    columns written, a window layer's ring) and the tally."""
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


def step(params, cfg: MimoV2Config, tokens, lengths, number, slots: int,
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
        attn = layer["attn"]
        h = rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
        q, k, v = _heads(attn, cfg, h, at, window)
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
                               see_ring if window else see_full, scale,
                               sink=attn.get("sink"))
        x = x + dot(out, attn["o"])
        h = rms_norm(x, layer["post_norm"], cfg.rms_norm_eps)
        out, told = feed_forward(layer, cfg, h, valid, interpret)
        x = x + out
        load = tally(load, index, cfg, told)
    return logits_of(params, cfg, x), tuple(cache), load
