"""Falcon-H1's language model (`model_type` `falcon_h1`): every layer runs a
Mamba-2 state-space mixer and softmax attention side by side on one normed
input and sums them, then a dense SwiGLU; no expert anywhere. Fourteen
scalar multipliers (the published maximal-update parametrisation) are part
of the forward. Pure functions over a parameter tree.

Every norm is `x * rsqrt(mean(x^2) + eps) * w` in float32. With `h` the
residual stream (benchmark/reference/ssd_hybrid.py is the plain float32
statement of the same equations):

- `h0 = embed[ids] * embedding_multiplier`;
- a layer: `u = norm_in(h)`; `h += ssm(u) * ssm_out_multiplier + attn(u *
  attention_in_multiplier) * attention_out_multiplier`; `h += mlp(
  norm_ff(h))`;
- `attn`: `q = x W_q`, `k = (x W_k) * key_multiplier`, `v = x W_v`, rotary
  over the whole head (the two halves as the pairs), causal softmax at
  `head_dim^-1/2`, query head `j` on key head `j // G`, `W_o`; no bias;
- `ssm`: `p = ((x * ssm_in_multiplier) W_in) * m`, the columns `z | x | B
  | C | dt` and `m` the five `ssm_multipliers` on those segments; `x | B |
  C` through a depthwise causal convolution of `mamba_d_conv` taps with a
  bias, and SiLU; `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)` a
  head; the recurrence of ops/ssd.py over a float32 state `[heads, state,
  head dim]` a row, `B` and `C` shared by the heads of a group, the skip
  `D x` in it; `y = norm_g(y * silu(z))`, the norm over each group's
  channels; `W_out`;
- `mlp`: `down(silu(gate(x) * mlp_multipliers[0]) * up(x)) *
  mlp_multipliers[1]`;
- `logits = (norm_f(h) W_head) * lm_head_multiplier`.

The leaves' layout is this module's own: the in-projection is two leaves,
`zxbc` `[hidden, z | x | B | C]` and `dt` `[hidden, heads]`, where the
checkpoint has one matrix with the `dt` columns last (a split of columns:
the first is a whole number of lanes wide).

Two kinds of cache on every layer (`new_cache`): the recurrence's state,
float32 `[rows, heads, state, head dim]`, with the convolution's tail, the
last `taps - 1` inputs of its `x | B | C` channels, whatever the row's
length; and keys and values `[rows, positions, key heads, head_dim]` (the
prompt's in the first `prompt slots` columns, generated token `n` at
column `slots + n`; a key cached multiplied and rotated). Prefill leaves
each row's state and tail as they stood at the row's OWN last prompt id: a
padded slot changes neither (ops/ssd.py `ssd_chunks` under the rows'
lengths; the tail is gathered at each row's length). A row longer than a
prefill span goes through in spans of positions, state, tail and keys
carried from span to span.

The family has no experts: `expert_layers` is 0 and `experts_held` (0, 0),
so the routing's tally (models/experts.py `empty_load`) has no row, and
goes through `prefill` and `step` as it came.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..ops import dot_product_attention
from ..ops.ssd import causal_conv, ssd_chunks, ssd_step
from .experts import dot, empty_load, init_leaves, logits_of, rms_norm
from .prefill_chunks import chunk_account, prefill_by_length
from .text_model import apply_rope, cached_attention, decode_mask, rope_tables


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    """The published sizes and multipliers
    (huggingface.co/tiiuae/Falcon-H1-34B-Instruct config.json)."""

    hidden_size: int = 5120
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    intermediate_size: int = 21504
    mamba_d_ssm: int = 4096  # the mixer's width, whatever `mamba_expand`
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_n_groups: int = 2
    mamba_d_state: int = 256
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    num_hidden_layers: int = 72
    vocab_size: int = 261120
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    # on the in-projection's segments z, x, B, C, dt
    ssm_multipliers: tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738)
    # on the feed-forward's gate and on its output
    mlp_multipliers: tuple[float, ...] = (
        0.1767766952966369, 0.011160714285714284)
    # no experts: what models/text_model.py's interface reads of them
    expert_layers: int = 0
    experts_held: tuple[int, int] = (0, 0)

    @property
    def state_width(self) -> int:
        """Values of `B`, and of `C`: a state's size a group."""
        return self.mamba_n_groups * self.mamba_d_state

    @property
    def conv_width(self) -> int:
        """Channels of the convolution: `x | B | C`."""
        return self.mamba_d_ssm + 2 * self.state_width

    @property
    def position_bytes(self) -> int:
        """Values a layer caches a position: a key and a value a key
        head."""
        return 2 * self.num_key_value_heads * self.head_dim


# one stage of an 18-stage pipeline, four whole layers a stage (the other 68
# on seventeen further stages), every width and the whole vocabulary
FALCON_H1_34B_PP18 = FalconH1Config(num_hidden_layers=4)
# the cut in small: two groups of two heads, no multiplier at one
FALCON_H1_TINY = FalconH1Config(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, intermediate_size=96, mamba_d_ssm=32, mamba_n_heads=4,
    mamba_d_head=8, mamba_n_groups=2, mamba_d_state=16, mamba_chunk_size=8,
    num_hidden_layers=2, vocab_size=128, rope_theta=1e4,
    embedding_multiplier=1.5, lm_head_multiplier=0.5,
    attention_in_multiplier=0.75, attention_out_multiplier=0.6,
    key_multiplier=0.4, ssm_out_multiplier=0.7, mlp_multipliers=(0.6, 0.8))


def config_for(model_name: str) -> FalconH1Config:
    return (FALCON_H1_TINY if "tiny" in model_name.lower()
            else FALCON_H1_34B_PP18)


# --- the parameter tree ------------------------------------------------------


def param_shapes(cfg: FalconH1Config, dtype) -> dict:
    """The tree as `jax.ShapeDtypeStruct`s: `embed`, `layers` (a list: each
    `mixer`, `attn`, `mlp` and two norms), `final_norm`, `head`. Matrices
    are `[in, out]`; `A_log`, `D`, `dt_bias` and the mixer's gated norm are
    float32."""
    h, d = cfg.hidden_size, cfg.head_dim
    heads, kv_heads = cfg.num_attention_heads, cfg.num_key_value_heads
    width, ssm_heads = cfg.intermediate_size, cfg.mamba_n_heads

    def s(*dims, kind=dtype):
        return jax.ShapeDtypeStruct(dims, kind)

    def layer():
        return {
            "input_norm": s(h), "ff_norm": s(h),
            "mixer": {
                "zxbc": s(h, cfg.mamba_d_ssm + cfg.conv_width),
                "dt": s(h, ssm_heads),
                "conv": s(cfg.mamba_d_conv, cfg.conv_width),
                "conv_bias": s(cfg.conv_width),
                "A_log": s(ssm_heads, kind=jnp.float32),
                "D": s(ssm_heads, kind=jnp.float32),
                "dt_bias": s(ssm_heads, kind=jnp.float32),
                "norm": s(cfg.mamba_d_ssm, kind=jnp.float32),
                "out": s(cfg.mamba_d_ssm, h)},
            "attn": {"q": s(h, heads * d), "k": s(h, kv_heads * d),
                     "v": s(h, kv_heads * d), "o": s(heads * d, h)},
            "mlp": {"gate": s(h, width), "up": s(h, width),
                    "down": s(width, h)}}

    return {"embed": s(cfg.vocab_size, h),
            "layers": [layer() for _ in range(cfg.num_hidden_layers)],
            "final_norm": s(h), "head": s(h, cfg.vocab_size)}


def init_params(cfg: FalconH1Config, key, dtype) -> dict:
    return init_leaves(param_shapes(cfg, dtype), key)


def _times(x, multiplier: float):
    """`x` times a multiplier of the parametrisation, in float32."""
    return (x.astype(jnp.float32) * multiplier).astype(x.dtype)


# --- attention ---------------------------------------------------------------


def _heads(p, cfg: FalconH1Config, u, positions):
    """`u` [..., hidden] (the layer's normed input) at `positions` [...]
    as queries [..., heads, head_dim] and the keys and values [..., key
    heads, head_dim] the cache holds of them: the key multiplied and
    rotated."""
    x = _times(u, cfg.attention_in_multiplier)
    d = cfg.head_dim
    cos, sin = (t[..., None, :] for t in rope_tables(
        d, cfg.rope_theta, positions))
    q = dot(x, p["q"]).reshape(*x.shape[:-1], cfg.num_attention_heads, d)
    k = jnp.dot(x, p["k"], preferred_element_type=jnp.float32)
    k = (k * cfg.key_multiplier).astype(x.dtype).reshape(
        *x.shape[:-1], cfg.num_key_value_heads, d)
    v = dot(x, p["v"]).reshape(k.shape)
    return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v


def attention_prefill(p, cfg: FalconH1Config, u, positions, keys, values):
    """Attention over a span of the rows' slots: `u` [R, C, hidden] at
    `positions` [R, C], `keys` / `values` [R, S, key heads, head_dim] what
    the spans before it cached. Returns the output [R, C, hidden] (before
    its multiplier) and the keys and values with the span's behind them."""
    q, k, v = _heads(p, cfg, u, positions)
    keys, values = (jnp.concatenate([old, new], 1)
                    for old, new in ((keys, k), (values, v)))
    out = dot_product_attention(q, keys, values, scale=cfg.head_dim ** -0.5,
                                causal=True)
    return dot(out.reshape(*u.shape[:2], -1), p["o"]), keys, values


# --- the state-space mixer ---------------------------------------------------


def _projections(p, cfg: FalconH1Config, u):
    """`u` [..., hidden] as the output's gate `z` [..., d_ssm], the
    convolution's input `x | B | C` [..., conv_width] and the step `dt`
    [..., heads] (float32, after its bias and softplus): the in-projection
    of `u * ssm_in_multiplier`, each segment times its multiplier."""
    z_m, x_m, b_m, c_m, dt_m = cfg.ssm_multipliers
    x = _times(u, cfg.ssm_in_multiplier)
    m = jnp.concatenate([
        jnp.full((width,), value, jnp.float32) for width, value in (
            (cfg.mamba_d_ssm, z_m), (cfg.mamba_d_ssm, x_m),
            (cfg.state_width, b_m), (cfg.state_width, c_m))])
    zxbc = (jnp.dot(x, p["zxbc"], preferred_element_type=jnp.float32)
            * m).astype(u.dtype)
    dt = jnp.dot(x, p["dt"], preferred_element_type=jnp.float32) * dt_m
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    return zxbc[..., :cfg.mamba_d_ssm], zxbc[..., cfg.mamba_d_ssm:], dt


def _ssd_inputs(cfg: FalconH1Config, mixed):
    """The convolution's output [..., conv_width] as the recurrence's `x`
    [..., heads, head dim] and `B`, `C` [..., groups, state]."""
    lead = mixed.shape[:-1]
    x = mixed[..., :cfg.mamba_d_ssm].reshape(
        *lead, cfg.mamba_n_heads, cfg.mamba_d_head)
    b, c = (mixed[..., at:at + cfg.state_width].reshape(
        *lead, cfg.mamba_n_groups, cfg.mamba_d_state)
        for at in (cfg.mamba_d_ssm, cfg.mamba_d_ssm + cfg.state_width))
    return x, b, c


def _gated_out(p, cfg: FalconH1Config, y, z, dtype):
    """The recurrence's `y` [..., heads, head dim] (float32) times
    `silu(z)`, normed over each group's channels under the weight, through
    `W_out`."""
    lead = y.shape[:-2]
    z = z.astype(jnp.float32)
    y = (y.reshape(*lead, -1) * jax.nn.silu(z)).reshape(
        *lead, cfg.mamba_n_groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                          + cfg.rms_norm_eps)
    y = y.reshape(*lead, -1) * p["norm"]
    return dot(y.astype(dtype), p["out"])


def _rate(p):
    return -jnp.exp(p["A_log"].astype(jnp.float32))


def ssm_prefill(p, cfg: FalconH1Config, u, lengths, start: int, state, tail):
    """The mixer over the rows' slots `start .. start + C`: `u` [R, C,
    hidden], `state` and `tail` as the rows stood before `start`. Returns
    the mixer's output [R, C, hidden], and state and tail after each row's
    last real position of these slots (a row that has none keeps what it
    came with)."""
    slots, taps = u.shape[1], cfg.mamba_d_conv
    z, xbc, dt = _projections(p, cfg, u)
    mixed, behind = causal_conv(xbc, tail, p["conv"], p["conv_bias"])
    # the last `taps - 1` real inputs: slot `j` is `behind`'s `j + taps - 1`
    end = jnp.clip(lengths - start, 0, slots)
    tail = jnp.take_along_axis(
        behind, (end[:, None] + jnp.arange(taps - 1))[:, :, None], axis=1)
    x, b, c = _ssd_inputs(cfg, mixed)
    y, state = ssd_chunks(x, dt, _rate(p), b, c, p["D"], lengths, state,
                          start, cfg.mamba_chunk_size)
    return _gated_out(p, cfg, y, z, u.dtype), state, tail


def ssm_step(p, cfg: FalconH1Config, u, state, tail, interpret: bool = False):
    """The mixer for one new token a row: `u` [R, hidden]. Returns the
    mixer's output [R, hidden], the state and the tail."""
    z, xbc, dt = _projections(p, cfg, u)
    mixed, behind = causal_conv(xbc[:, None], tail, p["conv"],
                                p["conv_bias"])
    x, b, c = _ssd_inputs(cfg, mixed[:, 0])
    y, state = ssd_step(x, dt, _rate(p), b, c, p["D"], state,
                        interpret=interpret)
    return _gated_out(p, cfg, y, z, u.dtype), state, behind[:, 1:]


def feed_forward(layer, cfg: FalconH1Config, h):
    """A layer's second half for tokens `h` [T, hidden]: the dense SwiGLU
    under its two multipliers."""
    p = layer["mlp"]
    gate_m, down_m = cfg.mlp_multipliers
    gate = jnp.dot(h, p["gate"], preferred_element_type=jnp.float32)
    up = jnp.dot(h, p["up"], preferred_element_type=jnp.float32)
    inner = (jax.nn.silu(gate * gate_m) * up).astype(h.dtype)
    return _times(dot(inner, p["down"]), down_m)


def _mixed(cfg: FalconH1Config, ssm, attn):
    """The two mixers' outputs, each times its multiplier, summed in
    float32: what the layer adds to the residual stream."""
    return (ssm.astype(jnp.float32) * cfg.ssm_out_multiplier
            + attn.astype(jnp.float32) * cfg.attention_out_multiplier
            ).astype(ssm.dtype)


def _logits(params, cfg: FalconH1Config, x):
    return logits_of(params, cfg, x) * cfg.lm_head_multiplier


# --- the cache, prefill and decode -------------------------------------------


def new_cache(cfg: FalconH1Config, rows: int, positions: int, dtype):
    """A layer's cache: (state float32 `[rows, heads, state, head dim]`,
    tail `[rows, taps - 1, conv_width]`, keys, values `[rows, positions,
    key heads, head_dim]`)."""
    def layer():
        kv = (rows, positions, cfg.num_key_value_heads, cfg.head_dim)
        return (jnp.zeros((rows, cfg.mamba_n_heads, cfg.mamba_d_state,
                           cfg.mamba_d_head), jnp.float32),
                jnp.zeros((rows, cfg.mamba_d_conv - 1, cfg.conv_width),
                          dtype),
                jnp.zeros(kv, dtype), jnp.zeros(kv, dtype))

    return tuple(layer() for _ in range(cfg.num_hidden_layers))


def state_row_bytes(cfg: FalconH1Config, itemsize: int) -> int:
    """What one row's recurrent state and convolution tail take on one
    layer: the float32 matrices and the tail in the cache's dtype."""
    return (4 * cfg.mamba_n_heads * cfg.mamba_d_state * cfg.mamba_d_head
            + itemsize * (cfg.mamba_d_conv - 1) * cfg.conv_width)


def cache_bytes(cfg: FalconH1Config, rows: int, positions: int,
                itemsize: int) -> tuple[int, int, int]:
    """(bytes of a pass's cache, the part of it that is rings of a window:
    none, the part that is the layers' state and tail: it does not grow
    with the positions)."""
    state = rows * cfg.num_hidden_layers * state_row_bytes(cfg, itemsize)
    keys = (rows * positions * cfg.position_bytes * itemsize
            * cfg.num_hidden_layers)
    return state + keys, 0, state


# a prefill chunk may be a span of one row's positions
POSITION_CHUNKS = True


def prefill_rows(params, cfg: FalconH1Config, ids, lengths,
                 chunk_slots: int):
    """Rows `ids` [R, S] (a row's prompt first, padding after: under a
    causal mask no real token sees padding, and a padded slot leaves
    state and tail alone) through every layer, `chunk_slots` positions at
    a time: a span's mixers start from the state and tail the spans before
    it left, its attention reads the keys cached so far and its own.
    Returns the hidden state of each row's last prompt token [R, hidden]
    and a layer's cache entries (state, tail, keys, values `[R, S,
    ...]`)."""
    rows, slots = ids.shape
    assert slots % chunk_slots == 0, (slots, chunk_slots)
    dtype = params["embed"].dtype
    # what the spans so far left: zero state and tail, no keys yet
    entries = list(new_cache(cfg, rows, 0, dtype))
    last = jnp.zeros((rows, cfg.hidden_size), dtype)
    for start in range(0, slots, chunk_slots):
        positions = jnp.broadcast_to(
            start + jnp.arange(chunk_slots), (rows, chunk_slots))
        x = _times(params["embed"][ids[:, start:start + chunk_slots]],
                   cfg.embedding_multiplier)
        for index, layer in enumerate(params["layers"]):
            u = rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
            state, tail, keys, values = entries[index]
            ssm, state, tail = ssm_prefill(layer["mixer"], cfg, u, lengths,
                                           start, state, tail)
            attn, keys, values = attention_prefill(
                layer["attn"], cfg, u, positions, keys, values)
            entries[index] = (state, tail, keys, values)
            x = x + _mixed(cfg, ssm, attn)
            h = rms_norm(x, layer["ff_norm"], cfg.rms_norm_eps)
            x = x + feed_forward(layer, cfg, h.reshape(
                rows * chunk_slots, -1)).reshape(x.shape)
        at = lengths - 1 - start
        mine = (at >= 0) & (at < chunk_slots)
        picked = jnp.take_along_axis(
            x, jnp.clip(at, 0, chunk_slots - 1)[:, None, None], axis=1)[:, 0]
        last = jnp.where(mine[:, None], picked, last)
    return last, entries


def prefill_widths(slots: int, chunk_slots: int | None = None):
    """The widths a chunk of `prefill` may have: the bucket alone (a
    narrower width is one more traced copy of the layers:
    models/prefill_chunks.py)."""
    return (slots,)


def prefill_account(lengths, slots: int, chunk_rows: int, chunk_slots: int):
    """The host's account of what `prefill` ran (models/text_model.py):
    the rows that have a length, every span of them (a span no row
    reaches is run all the same: the state and the tail pass through)."""
    return chunk_account(lengths, slots, chunk_rows, chunk_slots,
                         prefill_widths(slots, chunk_slots))


def prefill(params, cfg: FalconH1Config, ids, lengths, positions: int,
            chunk_rows: int, chunk_slots: int | None = None):
    """`ids` [R, S] in chunks of `chunk_rows` rows x `chunk_slots`
    positions (whole rows where rows are short, a span of one row's
    positions where a row is longer), rows of no length left out
    (models/prefill_chunks.py). Returns the last prompt position's
    logits [R, vocab], the cache (`new_cache`: a layer's state and tail at
    each row's own length, its first S columns of keys written) and the
    tally, empty."""
    rows, slots = ids.shape
    dtype = params["embed"].dtype
    chunk_slots = slots if chunk_slots is None else chunk_slots

    def run(ids, lengths, load):
        last, entries = prefill_rows(params, cfg, ids, lengths, chunk_slots)
        return (last, tuple(entries)), load

    (last, cache), load = prefill_by_length(
        ids, lengths, chunk_rows, prefill_widths(slots, chunk_slots),
        run, (jnp.zeros((rows, cfg.hidden_size), dtype),
              new_cache(cfg, rows, positions, dtype)), empty_load(cfg))
    return _logits(params, cfg, last), cache, load


def step(params, cfg: FalconH1Config, tokens, lengths, number, slots: int,
         cache, load, valid=None, interpret: bool = False):
    """Every row's generated token `number` through every layer and both
    kinds of cache: `tokens` [R], at position `lengths + number`; a layer
    caches its key and value at column `slots + number` and moves its
    state and tail on by one position (`valid` is the interface's: no
    expert, so nothing is routed). Returns the logits [R, vocab]
    (float32), the cache and the tally as it came."""
    x = _times(params["embed"][tokens], cfg.embedding_multiplier)
    at = lengths + number
    scale = cfg.head_dim ** -0.5
    seen = decode_mask(lengths, slots, cache[0][2].shape[1], number)
    cache = list(cache)
    for index, layer in enumerate(params["layers"]):
        u = rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
        state, tail, keys, values = cache[index]
        ssm, state, tail = ssm_step(layer["mixer"], cfg, u, state, tail,
                                    interpret=interpret)
        q, k, v = _heads(layer["attn"], cfg, u, at)
        keys, values = (
            jax.lax.dynamic_update_slice(
                whole, entry[:, None].astype(whole.dtype),
                (0, slots + number, 0, 0))
            for whole, entry in ((keys, k), (values, v)))
        cache[index] = (state, tail, keys, values)
        attn = dot(cached_attention(q, keys, values, seen, scale),
                   layer["attn"]["o"])
        x = x + _mixed(cfg, ssm, attn)
        x = x + feed_forward(layer, cfg, rms_norm(
            x, layer["ff_norm"], cfg.rms_norm_eps))
    return _logits(params, cfg, x), tuple(cache), load
