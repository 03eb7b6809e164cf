"""Qwen3-Next's language model (`model_type` `qwen3_next`): three layers in
four are Gated DeltaNet linear attention, which keeps a recurrent state a
row and no keys, every fourth is gated softmax attention; every layer is
followed by softmax-routed sparse experts and one shared expert behind a
sigmoid gate. Pure functions over a parameter tree.

Norms are zero-centred: `norm(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)` in
float32 (input, post-attention, final, and the per-head query / key norms).
A layer is `x += mixer(norm(x)); x += moe(norm(x))`
(benchmark/reference/gated_delta_moe.py is the plain float32 statement of
the same equations):

- Gated DeltaNet (layer `i` with `(i + 1) % full_attention_interval != 0`):
  `q | k | v | z = h W_qkvz`, `b | a = h W_ba`; `q | k | v` through a
  depthwise causal convolution of width 4 over the positions (no bias) and
  SiLU; a value head `beta = sigmoid(b)`, `g = -exp(A_log) * softplus(a +
  dt_bias)` in float32; `q`, `k` L2-normalised over the head's dims, `q`
  scaled by `key dim^-1/2`, each key head serving `value heads / key
  heads` consecutive value heads; the gated delta rule
  (ops/gated_delta_rule.py) over a float32 state `[value heads, key dim,
  value dim]` a row; `y = (o * rsqrt(mean(o^2) + eps) * w_n) * silu(z)` a
  head (`w_n` a plain weight), then `y W_o`.
- gated attention (every `full_attention_interval`-th layer): `h W_q` gives
  a query and a gate a head; `q` and `k` normed a head; rotary (halves
  convention) on the first `partial_rotary_factor` of the head's dims;
  causal softmax attention at `head_dim^-1/2`, query head `j` on key head
  `j // G`; the output times `sigmoid(gate)`; `W_o`. No bias anywhere.
- the second half is models/experts.py's under the softmax rule (SDAR's),
  plus `sigmoid(h w_s) * SwiGLU_shared(h)`.

The leaves' layout is this module's own: `qkvz` has the columns `[q heads |
k heads | v heads | z heads]` and `ba` `[b | a]`, where the checkpoint
interleaves them a key head (`q, k, 2 x v, 2 x z`; `b, a`), and `q_gate`
has `[queries | gates]` where the checkpoint interleaves them a head: a
converter would permute the columns once.

Two kinds of cache in one pass, with nothing in common (`new_cache`): a
full layer keeps keys and values `[rows, positions, key heads, head_dim]`
as K-EXAONE's full layers do (the prompt's in the first `prompt slots`
columns, generated token `n` at column `slots + n`; a key cached normed
and rotated); a linear layer keeps the rule's state, float32 `[rows, value
heads, key dim, value dim]`, and the convolution's tail, the last three
inputs of its `q | k | v` channels, whatever the row's length. Prefill
leaves each row's state and tail as they stood at the row's OWN last
prompt id and not at the bucket's last slot: a padded slot changes neither
(ops/gated_delta_rule.py `gated_delta_chunks` under the rows' lengths; the
tail is gathered at each row's length). A row longer than a prefill span
goes through in spans of positions, state, tail and keys carried from
span to span.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..ops import dot_product_attention
from ..ops.gated_delta_rule import gated_delta_chunks, gated_delta_step
from .experts import (
    dot,
    empty_load,
    feed_forward,
    init_leaves,
    logits_of,
    rms_norm,
    tally,
)
from .prefill_chunks import chunk_account, prefill_by_length
from .text_model import apply_rope, cached_attention, decode_mask, rope_tables


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """The published sizes (huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct
    config.json), and which share of them is held here."""

    hidden_size: int = 2048
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    full_attention_interval: int = 4
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512  # the router's width, whatever is held
    num_experts_per_tok: int = 10
    scoring_func: str = "softmax"
    routed_scaling_factor: float = 1.0  # `norm_topk_prob`, and no scale
    first_k_dense_replace: int = 0
    num_hidden_layers: int = 48
    vocab_size: int = 151936  # rows of the vocabulary held here
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    # the norms multiply by `1 + weight` (models/experts.py reads it)
    zero_centred_norms: bool = True
    # (first, count): the routed experts this chip holds of every layer
    experts_held: tuple[int, int] = (0, 512)

    @property
    def linear_layers(self) -> tuple[bool, ...]:
        """A layer's kind: True for Gated DeltaNet, False for attention."""
        return tuple((n + 1) % self.full_attention_interval != 0
                     for n in range(self.num_hidden_layers))

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def key_width(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_width(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_width(self) -> int:
        """Channels of the convolution: `q | k | v`."""
        return 2 * self.key_width + self.value_width

    @property
    def position_bytes(self) -> int:
        """Values a full layer caches a position: a key and a value a key
        head."""
        return 2 * self.num_key_value_heads * self.head_dim


# one of 4 chips that share each layer, in a pipeline of 6 such hosts: layers
# 0-7 of the 48 (two whole periods, L L L F L L L F; the other 40 on the
# further stages), experts 0-127 of each layer's 512 (rank 0 of the 4 chips),
# rows 0-37983 of the vocabulary (a quarter)
QWEN3_NEXT_80B_EP4 = Qwen3NextConfig(
    num_hidden_layers=8, experts_held=(0, 128), vocab_size=37984)
# the cut in small: one period, two value heads a key head, a rotary on a
# quarter of the head, a quarter of the experts held
QWEN3_NEXT_TINY = Qwen3NextConfig(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=8, linear_value_head_dim=8, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, num_experts=32,
    num_experts_per_tok=4, num_hidden_layers=4, vocab_size=128,
    experts_held=(0, 8))


def config_for(model_name: str) -> Qwen3NextConfig:
    return (QWEN3_NEXT_TINY if "tiny" in model_name.lower()
            else QWEN3_NEXT_80B_EP4)


# --- the parameter tree ------------------------------------------------------


def param_shapes(cfg: Qwen3NextConfig, dtype) -> dict:
    """The tree as `jax.ShapeDtypeStruct`s: `embed`, `layers` (a list: each
    `mixer` (a linear layer's) or `attn` (a full layer's), two norm offsets
    and `moe`: `router`, the held `experts`, `shared`, `shared_gate`),
    `final_norm_offset`, `head`. Matrices are `[in, out]`; the held
    experts' are stacked `[held, in, out]`; `A_log`, `dt_bias` and the
    rule's output norm are float32."""
    h, d, width = cfg.hidden_size, cfg.head_dim, cfg.moe_intermediate_size
    heads, kv_heads = cfg.num_attention_heads, cfg.num_key_value_heads
    held = cfg.experts_held[1]
    value_heads = cfg.linear_num_value_heads

    def s(*dims, kind=dtype):
        return jax.ShapeDtypeStruct(dims, kind)

    def swiglu(width, *lead):
        return {"gate": s(*lead, h, width), "up": s(*lead, h, width),
                "down": s(*lead, width, h)}

    def layer(linear: bool):
        out = {"input_norm_offset": s(h), "post_norm_offset": s(h),
               "moe": {"router": s(h, cfg.num_experts),
                       "experts": swiglu(width, held),
                       "shared": swiglu(cfg.shared_expert_intermediate_size),
                       "shared_gate": s(h, 1)}}
        if linear:
            out["mixer"] = {
                "qkvz": s(h, cfg.conv_width + cfg.value_width),
                "ba": s(h, 2 * value_heads),
                "conv": s(cfg.linear_conv_kernel_dim, cfg.conv_width),
                "A_log": s(value_heads, kind=jnp.float32),
                "dt_bias": s(value_heads, kind=jnp.float32),
                "norm": s(cfg.linear_value_head_dim, kind=jnp.float32),
                "o": s(cfg.value_width, h)}
        else:
            out["attn"] = {
                "q_gate": s(h, 2 * heads * d), "k": s(h, kv_heads * d),
                "v": s(h, kv_heads * d), "o": s(heads * d, h),
                "q_norm_offset": s(d), "k_norm_offset": s(d)}
        return out

    return {"embed": s(cfg.vocab_size, h),
            "layers": [layer(linear) for linear in cfg.linear_layers],
            "final_norm_offset": s(h), "head": s(h, cfg.vocab_size)}


def init_params(cfg: Qwen3NextConfig, key, dtype) -> dict:
    return init_leaves(param_shapes(cfg, dtype), key)


def _norm(x, offset, cfg: Qwen3NextConfig):
    return rms_norm(x, offset, cfg.rms_norm_eps, zero_centred=True)


# --- gated attention ---------------------------------------------------------


def _rotated(x, offset, cfg: Qwen3NextConfig, positions):
    """A head's dims `x` [..., heads, head_dim] normed and its first
    `rotary_dim` rotated at `positions` [...]."""
    x = _norm(x, offset, cfg)
    cos, sin = rope_tables(cfg.rotary_dim, cfg.rope_theta, positions)
    turned = apply_rope(x[..., :cfg.rotary_dim], cos[..., None, :],
                        sin[..., None, :])
    return jnp.concatenate([turned, x[..., cfg.rotary_dim:]], axis=-1)


def _heads(p, cfg: Qwen3NextConfig, h, positions):
    """`h` [..., hidden] at `positions` [...] as queries [..., heads,
    head_dim], the output's gate [..., heads x head_dim], and the keys and
    values [..., key heads, head_dim] the cache holds of them."""
    d, heads = cfg.head_dim, cfg.num_attention_heads
    q_gate = dot(h, p["q_gate"])
    q = q_gate[..., :heads * d].reshape(*h.shape[:-1], heads, d)
    k, v = (dot(h, p[name]).reshape(*h.shape[:-1], cfg.num_key_value_heads,
                                    d) for name in ("k", "v"))
    return (_rotated(q, p["q_norm_offset"], cfg, positions),
            q_gate[..., heads * d:],
            _rotated(k, p["k_norm_offset"], cfg, positions), v)


def _gated(out, gate):
    """Attention's output [..., heads x head_dim] times `sigmoid(gate)`."""
    return (out.astype(jnp.float32)
            * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(out.dtype)


# --- Gated DeltaNet ----------------------------------------------------------


def _projections(p, cfg: Qwen3NextConfig, h):
    """`h` [..., hidden] as the convolution's input `q | k | v` [...,
    conv_width], the output's gate `z` [..., value heads, value dim], and
    a value head's `beta` and log decay `g` [..., value heads], float32."""
    qkvz = dot(h, p["qkvz"])
    ba = jnp.dot(h, p["ba"], preferred_element_type=jnp.float32)
    heads = cfg.linear_num_value_heads
    beta = jax.nn.sigmoid(ba[..., :heads])
    g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[..., heads:] + p["dt_bias"].astype(jnp.float32))
    z = qkvz[..., cfg.conv_width:].reshape(
        *h.shape[:-1], heads, cfg.linear_value_head_dim)
    return qkvz[..., :cfg.conv_width], z, beta, g


def _rule_inputs(cfg: Qwen3NextConfig, mixed):
    """The convolution's output [..., conv_width] (after SiLU) as the
    rule's `q`, `k` [..., value heads, key dim] (L2-normalised, `q`
    scaled, a key head repeated for the value heads it serves) and `v`
    [..., value heads, value dim]."""
    lead = mixed.shape[:-1]
    key_heads, dim = cfg.linear_num_key_heads, cfg.linear_key_head_dim

    def unit(x):
        x = x.astype(jnp.float32).reshape(*lead, key_heads, dim)
        x = x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
        return jnp.repeat(x, cfg.linear_num_value_heads // key_heads,
                          axis=-2)

    q = unit(mixed[..., :cfg.key_width]) * dim ** -0.5
    k = unit(mixed[..., cfg.key_width:2 * cfg.key_width])
    v = mixed[..., 2 * cfg.key_width:].reshape(
        *lead, cfg.linear_num_value_heads, cfg.linear_value_head_dim)
    return q, k, v


def _rule_output(p, cfg: Qwen3NextConfig, o, z, dtype):
    """The rule's `o` [..., value heads, value dim] (float32) normed a
    head under the plain weight, gated by `silu(z)`, through `W_o`."""
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg.rms_norm_eps) * p["norm"]
    y = (o * jax.nn.silu(z.astype(jnp.float32))).astype(dtype)
    return dot(y.reshape(*y.shape[:-2], -1), p["o"])


def linear_prefill(p, cfg: Qwen3NextConfig, h, lengths, start: int, state,
                   tail):
    """A linear layer over the rows' slots `start .. start + C`: `h` [R,
    C, hidden], `state` and `tail` as the rows stood before `start`.
    Returns the layer's output [R, C, hidden], and state and tail after
    each row's last real position of these slots (a row that has none
    keeps what it came with)."""
    rows, slots, _ = h.shape
    taps = cfg.linear_conv_kernel_dim
    x, z, beta, g = _projections(p, cfg, h)
    behind = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    mixed = sum(behind[:, tap:tap + slots].astype(jnp.float32)
                * p["conv"][tap].astype(jnp.float32) for tap in range(taps))
    mixed = jax.nn.silu(mixed).astype(h.dtype)
    # the last `taps - 1` real inputs: slot `j` is `behind`'s `j + taps - 1`
    end = jnp.clip(lengths - start, 0, slots)
    tail = jnp.take_along_axis(
        behind, (end[:, None] + jnp.arange(taps - 1))[:, :, None], axis=1)
    o, state = gated_delta_chunks(*_rule_inputs(cfg, mixed), g, beta,
                                  lengths, state, start)
    return _rule_output(p, cfg, o, z, h.dtype), state, tail


def linear_step(p, cfg: Qwen3NextConfig, h, state, tail,
                interpret: bool = False):
    """A linear layer for one new token a row: `h` [R, hidden]. Returns
    the layer's output [R, hidden], the state and the tail."""
    x, z, beta, g = _projections(p, cfg, h)
    behind = jnp.concatenate([tail, x[:, None].astype(tail.dtype)], axis=1)
    mixed = jnp.sum(behind.astype(jnp.float32)
                    * p["conv"].astype(jnp.float32), axis=1)
    mixed = jax.nn.silu(mixed).astype(h.dtype)
    o, state = gated_delta_step(*_rule_inputs(cfg, mixed), g, beta, state,
                                interpret=interpret)
    return _rule_output(p, cfg, o, z, h.dtype), state, behind[:, 1:]


# --- the cache, prefill and decode -------------------------------------------


def new_cache(cfg: Qwen3NextConfig, rows: int, positions: int, dtype):
    """A layer's cache: (state float32 `[rows, value heads, key dim, value
    dim]`, tail `[rows, 3, conv_width]`) on a linear layer, (keys, values)
    `[rows, positions, key heads, head_dim]` on a full one."""
    def layer(linear: bool):
        if linear:
            return (jnp.zeros((rows, cfg.linear_num_value_heads,
                               cfg.linear_key_head_dim,
                               cfg.linear_value_head_dim), jnp.float32),
                    jnp.zeros((rows, cfg.linear_conv_kernel_dim - 1,
                               cfg.conv_width), dtype))
        return tuple(jnp.zeros((rows, positions, cfg.num_key_value_heads,
                                cfg.head_dim), dtype) for _ in range(2))

    return tuple(layer(linear) for linear in cfg.linear_layers)


def state_row_bytes(cfg: Qwen3NextConfig, itemsize: int) -> int:
    """What one row's recurrent state and convolution tail take on one
    linear layer: the float32 matrices and the tail in the cache's
    dtype."""
    return (4 * cfg.linear_num_value_heads * cfg.linear_key_head_dim
            * cfg.linear_value_head_dim
            + itemsize * (cfg.linear_conv_kernel_dim - 1) * cfg.conv_width)


def cache_bytes(cfg: Qwen3NextConfig, rows: int, positions: int,
                itemsize: int) -> tuple[int, int, int]:
    """(bytes of a pass's cache, the part of it that is rings of a window:
    none, the part that is the linear layers' state and tail: it does not
    grow with the positions)."""
    linear = sum(cfg.linear_layers)
    state = rows * linear * state_row_bytes(cfg, itemsize)
    keys = (rows * positions * cfg.position_bytes * itemsize
            * (cfg.num_hidden_layers - linear))
    return state + keys, 0, state


# a prefill chunk may be a span of one row's positions
POSITION_CHUNKS = True


def prefill_rows(params, cfg: Qwen3NextConfig, ids, lengths, chunk_slots: int,
                 load, interpret: bool = False):
    """Rows `ids` [R, S] (a row's prompt first, padding after: under a
    causal mask no real token sees padding, and a padded slot leaves
    state and tail alone) through every layer, `chunk_slots` positions at
    a time: a span's linear layers start from the state and tail the
    spans before it left, its full layers attend to the keys cached so
    far and its own. Returns the hidden state of each row's last prompt
    token [R, hidden], a layer's cache entries ((state, tail), or (keys,
    values) `[R, S, ...]`) and the tally."""
    rows, slots = ids.shape
    assert slots % chunk_slots == 0, (slots, chunk_slots)
    dtype = params["embed"].dtype
    scale = cfg.head_dim ** -0.5
    # what the spans so far left: zero state and tail, no keys yet
    entries = list(new_cache(cfg, rows, 0, dtype))
    last = jnp.zeros((rows, cfg.hidden_size), dtype)
    for start in range(0, slots, chunk_slots):
        positions = jnp.broadcast_to(
            start + jnp.arange(chunk_slots), (rows, chunk_slots))
        valid = (positions < lengths[:, None]).reshape(-1)
        x = params["embed"][ids[:, start:start + chunk_slots]]
        for index, (layer, linear) in enumerate(
                zip(params["layers"], cfg.linear_layers)):
            h = _norm(x, layer["input_norm_offset"], cfg)
            if linear:
                out, state, tail = linear_prefill(
                    layer["mixer"], cfg, h, lengths, start, *entries[index])
                entries[index] = (state, tail)
            else:
                q, gate, k, v = _heads(layer["attn"], cfg, h, positions)
                keys, values = entries[index] = tuple(
                    jnp.concatenate([old, new], 1)
                    for old, new in zip(entries[index], (k, v)))
                out = dot_product_attention(q, keys, values, scale=scale,
                                            causal=True)
                out = dot(_gated(out.reshape(rows, chunk_slots, -1), gate),
                          layer["attn"]["o"])
            x = x + out
            h = _norm(x, layer["post_norm_offset"], cfg)
            out, told = feed_forward(
                layer, cfg, h.reshape(rows * chunk_slots, -1), valid,
                interpret)
            x = x + out.reshape(x.shape)
            load = tally(load, index, cfg, told)
        at = lengths - 1 - start
        mine = (at >= 0) & (at < chunk_slots)
        picked = jnp.take_along_axis(
            x, jnp.clip(at, 0, chunk_slots - 1)[:, None, None], axis=1)[:, 0]
        last = jnp.where(mine[:, None], picked, last)
    return last, entries, load


def prefill_widths(slots: int, chunk_slots: int | None = None):
    """The widths a chunk of `prefill` may have: the bucket alone. A
    narrower width is one more traced copy of the layers, and two of them
    made a worker's start 12 s (15 %) longer and the program's temporaries
    0.05 GB larger (PERF.md section 6, PR 43)."""
    return (slots,)


def prefill_account(lengths, slots: int, chunk_rows: int, chunk_slots: int):
    """The host's account of what `prefill` ran (models/text_model.py):
    the rows that have a length, every span of them (a span no row
    reaches is run all the same: the state and the tail pass through)."""
    return chunk_account(lengths, slots, chunk_rows, chunk_slots,
                         prefill_widths(slots, chunk_slots))


def prefill(params, cfg: Qwen3NextConfig, ids, lengths, positions: int,
            chunk_rows: int, chunk_slots: int | None = None,
            interpret: bool = False):
    """`ids` [R, S] in chunks of `chunk_rows` rows x `chunk_slots`
    positions (whole rows where rows are short, a span of one row's
    positions where a row is longer), rows of no length left out
    (models/prefill_chunks.py). Returns the last prompt position's
    logits [R, vocab], the cache (`new_cache`: a linear layer's state and
    tail at each row's own length, a full layer's first S columns
    written) and the tally."""
    rows, slots = ids.shape
    dtype = params["embed"].dtype
    chunk_slots = slots if chunk_slots is None else chunk_slots

    def run(ids, lengths, load):
        last, entries, load = prefill_rows(params, cfg, ids, lengths,
                                           chunk_slots, load, interpret)
        return (last, tuple(entries)), load

    (last, cache), load = prefill_by_length(
        ids, lengths, chunk_rows, prefill_widths(slots, chunk_slots),
        run, (jnp.zeros((rows, cfg.hidden_size), dtype),
              new_cache(cfg, rows, positions, dtype)), empty_load(cfg))
    return logits_of(params, cfg, last), cache, load


def step(params, cfg: Qwen3NextConfig, tokens, lengths, number, slots: int,
         cache, load, valid=None, interpret: bool = False):
    """Every row's generated token `number` through every layer and both
    kinds of cache: `tokens` [R], at position `lengths + number`; a full
    layer caches it at column `slots + number`, a linear layer moves its
    state and tail on by one position (`valid` [R]: a row that only pads
    the pass is routed nowhere). Returns the logits [R, vocab] (float32),
    the cache and the tally."""
    x = params["embed"][tokens]
    at = lengths + number
    scale = cfg.head_dim ** -0.5
    full_positions = next(
        (layer[0].shape[1] for layer, linear in zip(cache, cfg.linear_layers)
         if not linear), slots + 1)
    seen = decode_mask(lengths, slots, full_positions, number)
    cache = list(cache)
    for index, (layer, linear) in enumerate(zip(params["layers"],
                                                cfg.linear_layers)):
        h = _norm(x, layer["input_norm_offset"], cfg)
        if linear:
            out, state, tail = linear_step(
                layer["mixer"], cfg, h, *cache[index], interpret=interpret)
            cache[index] = (state, tail)
        else:
            q, gate, k, v = _heads(layer["attn"], cfg, h, at)
            keys, values = cache[index] = tuple(
                jax.lax.dynamic_update_slice(
                    whole, entry[:, None].astype(whole.dtype),
                    (0, slots + number, 0, 0))
                for whole, entry in zip(cache[index], (k, v)))
            out = dot(_gated(cached_attention(q, keys, values, seen, scale),
                             gate), layer["attn"]["o"])
        x = x + out
        h = _norm(x, layer["post_norm_offset"], cfg)
        out, told = feed_forward(layer, cfg, h, valid, interpret)
        x = x + out
        load = tally(load, index, cfg, told)
    return logits_of(params, cfg, x), tuple(cache), load
