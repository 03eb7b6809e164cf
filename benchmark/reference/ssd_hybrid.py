"""Plain reference for Falcon-H1's language model (`model_type`
`falcon_h1`: in every layer a Mamba-2 state-space mixer and softmax
attention side by side on one normed input, summed, then a dense SwiGLU;
fourteen scalar multipliers in the forward): one full forward pass over
prompt and given tokens, `jax.numpy` in float32 at the highest matmul
precision. No cache, no kernel, no chunk: the state-space mixer is the
position-by-position recurrence (one `lax.scan` over the positions, all
rows and heads at once), attention one explicit matrix of who sees whom.

`sizes` is the configuration's own keys (the published `config.json`
names: `hidden_size`, `num_attention_heads`, `num_key_value_heads`,
`head_dim`, `rope_theta`, `rms_norm_eps`, `mamba_d_ssm`, `mamba_n_heads`,
`mamba_d_head`, `mamba_n_groups`, `mamba_d_state`, `mamba_d_conv`, and the
multipliers `embedding_multiplier`, `lm_head_multiplier`,
`attention_in_multiplier`, `attention_out_multiplier`, `key_multiplier`,
`ssm_in_multiplier`, `ssm_out_multiplier`, `ssm_multipliers` (five: on
`z`, `x`, `B`, `C`, `dt`), `mlp_multipliers` (two: on the gate, on the
output)). `weights` is indexed as the program's tree is (`embed`,
`layers[i]` with `input_norm`, `ff_norm`, `mixer`, `attn`, `mlp`,
`final_norm`, `head`; matrices `[in, out]`), a layer at a time, so a caller
can convert one layer's weights to float32 as it is asked for.

Every norm is `x rsqrt(mean(x^2) + eps) w`. With `h` the residual stream:

- `h0 = embed[ids] * embedding_multiplier`;
- a layer: `u = norm_in(h)`; `h += ssm(u) * ssm_out_multiplier + attn(u *
  attention_in_multiplier) * attention_out_multiplier`; `h += mlp(
  norm_ff(h))`: the two mixers read the same `u` and are summed before
  the one residual add;
- `attn`: `q = x W_q`, `k = (x W_k) * key_multiplier`, `v = x W_v`; rotary
  (`rope_theta`, the two halves of the head as the pairs) over the whole
  head; token `t` sees `s <= t`; query head `j` reads key head `j // G`;
  `softmax(q . k / sqrt(head_dim)) v`, `W_o`;
- `ssm`: `p = ((x * ssm_in_multiplier) W_in) * m` with `W_in`'s columns `z
  | x | B | C | dt` and `m` holding `ssm_multipliers[0..4]` on those
  segments; `x | B | C` through a depthwise causal convolution of
  `mamba_d_conv` taps (`out_t = sum_i w_i in_{t-3+i} + b`, zeros before
  the row's start) and SiLU; `dt = softplus(dt + dt_bias)`, `A =
  -exp(A_log)` a head. With `S` [head dim, state] a head, zero at the
  row's start, head `j` of group `g = j // (heads / groups)`, for each
  position: `S <- exp(dt_t A_j) S + dt_t x_t (x) B_t^g`, `y_t = S C_t^g +
  D_j x_t`. Then `y = norm_g(y * silu(z))`, the norm over each group's
  `d_ssm / groups` channels, and `W_out`;
- `mlp`: `down(silu(gate(x) * mlp_multipliers[0]) * up(x)) *
  mlp_multipliers[1]`;
- `logits = (norm_f(h) W_head) * lm_head_multiplier`.

Departures from the published description, each the same function or
stated in the configuration: (1) the layers are the first
`len(weights["layers"])`. (2) The in-projection is two leaves, `zxbc`
`[hidden, z | x | B | C]` and `dt` `[hidden, heads]`, where the
checkpoint has one matrix with the `dt` columns last (a split of
columns); the convolution's weight is `[taps, channels]` where the
checkpoint's is `[channels, 1, taps]`. (3) Pre-norm residuals, the place
of each multiplier and the grouping of the gated norm are not keys of
`config.json`: the configuration lists them under `assumed`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def silu(x):
    return x * jax.nn.sigmoid(x)


def rotate(x, positions, theta):
    """`x` [N, T, heads, dim] at `positions` [T]: the whole head turned,
    its two halves as the pairs."""
    dim = x.shape[-1]
    # `rope_theta` 1e11 stands in the published config as a whole number
    # no 32-bit operand holds
    inv_freq = 1.0 / float(theta) ** (
        jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = positions.astype(jnp.float32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    a, b = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def attention(p, sizes, x):
    """Rows `x` [N, T, hidden] -> [N, T, hidden], causal."""
    rows, length, _ = x.shape
    heads, kv_heads = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dim = sizes["head_dim"]
    at = jnp.arange(length)
    q = (x @ p["q"]).reshape(rows, length, heads, dim)
    k = ((x @ p["k"]) * sizes["key_multiplier"]).reshape(
        rows, length, kv_heads, dim)
    v = (x @ p["v"]).reshape(rows, length, kv_heads, dim)
    q = rotate(q, at, sizes["rope_theta"])
    k = rotate(k, at, sizes["rope_theta"])
    # query head j reads key head j // group
    q = q.reshape(rows, length, kv_heads, heads // kv_heads, dim)
    scores = jnp.einsum("nqhgd,nkhd->nhgqk", q, k) * dim ** -0.5
    seen = at[None, :] <= at[:, None]
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("nhgqk,nkhd->nqhgd", weights, v)
    return out.reshape(rows, length, heads * dim) @ p["o"]


def recurrence(x, dt, a, b, c, d, decay_bits=None, state_bits=None):
    """The state-space recurrence, position by position: `x` [N, T, H, P],
    `dt` [N, T, H], `a`, `d` [H], `b`, `c` [N, T, G, S]; `S` [P, S] a
    head starts at zero. Returns `y` [N, T, H, P]. `decay_bits` /
    `state_bits`: a control's, the decays or the state rounded to that many
    mantissa bits at every position (7: bfloat16's); never set by a run."""
    rows, _, heads, dim = x.shape
    per = heads // b.shape[2]

    def position(state, xs):
        x, dt, b, c = xs
        b, c = (jnp.repeat(v, per, axis=1) for v in (b, c))  # [N, H, S]
        decay = jnp.exp(dt * a)
        if decay_bits is not None:
            decay = jax.lax.reduce_precision(decay, 8, decay_bits)
        state = (state * decay[..., None, None]
                 + (dt[..., None] * x)[..., :, None] * b[..., None, :])
        if state_bits is not None:
            state = jax.lax.reduce_precision(state, 8, state_bits)
        y = jnp.einsum("nhps,nhs->nhp", state, c) + d[:, None] * x
        return state, y

    _, y = jax.lax.scan(
        position, jnp.zeros((rows, heads, dim, b.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


def mixer(p, sizes, u, control=None):
    """Rows `u` [N, T, hidden] -> [N, T, hidden]: the state-space mixer
    (`control`: `recurrence`'s `decay_bits` / `state_bits`, a control's)."""
    rows, length, _ = u.shape
    width, heads = sizes["mamba_d_ssm"], sizes["mamba_n_heads"]
    groups, size = sizes["mamba_n_groups"], sizes["mamba_d_state"]
    taps = sizes["mamba_d_conv"]
    z_m, x_m, b_m, c_m, dt_m = sizes["ssm_multipliers"]
    x = u * sizes["ssm_in_multiplier"]
    zxbc = x @ p["zxbc"]
    edges = (width, 2 * width, 2 * width + groups * size)
    z = zxbc[..., :edges[0]] * z_m
    into = jnp.concatenate([zxbc[..., edges[0]:edges[1]] * x_m,
                            zxbc[..., edges[1]:edges[2]] * b_m,
                            zxbc[..., edges[2]:] * c_m], axis=-1)
    dt = jax.nn.softplus((x @ p["dt"]) * dt_m + p["dt_bias"])
    behind = jnp.pad(into, ((0, 0), (taps - 1, 0), (0, 0)))
    mixed = silu(sum(behind[:, tap:tap + length] * p["conv"][tap]
                     for tap in range(taps)) + p["conv_bias"])
    xs = mixed[..., :width].reshape(rows, length, heads, width // heads)
    b = mixed[..., width:width + groups * size].reshape(
        rows, length, groups, size)
    c = mixed[..., width + groups * size:].reshape(rows, length, groups, size)
    y = recurrence(xs, dt, -jnp.exp(p["A_log"]), b, c, p["D"],
                   **(control or {}))
    y = (y.reshape(rows, length, width) * silu(z)).reshape(
        rows, length, groups, width // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                          + sizes["rms_norm_eps"])
    return (y.reshape(rows, length, width) * p["norm"]) @ p["out"]


def mlp(p, sizes, x):
    gate_m, down_m = sizes["mlp_multipliers"]
    return ((silu((x @ p["gate"]) * gate_m) * (x @ p["up"])) @ p["down"]
            ) * down_m


def layer_forward(layer, sizes, h, control=None):
    """One layer over rows `h` [N, T, hidden]."""
    eps = sizes["rms_norm_eps"]
    u = norm(h, layer["input_norm"], eps)
    h = h + (mixer(layer["mixer"], sizes, u, control) * sizes["ssm_out_multiplier"]
             + attention(layer["attn"], sizes,
                         u * sizes["attention_in_multiplier"])
             * sizes["attention_out_multiplier"])
    return h + mlp(layer["mlp"], sizes, norm(h, layer["ff_norm"], eps))


def forward_rows(weights, sizes, rows, device=None, positions=None,
                 control=None):
    """One full forward pass for several sequences side by side: every
    sequence is lengthened to the longest with id 0 behind it (no position
    sees a later one, in either mixer, so no logit of its own changes),
    and a layer is one operation over `[rows, positions]`, its weights
    converted once. `positions[i]` picks the positions of sequence `i`
    whose logits are returned: the head runs over those alone. Every layer
    is waited for before the next is converted: the host holds one layer's
    float32 weights at a time. `control`: a lower-precision control's
    `decay_bits` / `state_bits` for `recurrence`; never set by a run."""
    import numpy as np

    device = device or jax.local_devices(backend="cpu")[0]

    def f32(tree):
        return jax.tree_util.tree_map(
            lambda w: jnp.asarray(jax.device_put(w, device), jnp.float32),
            tree)

    with jax.default_device(device), \
            jax.default_matmul_precision("highest"):
        longest = max(len(ids) for ids in rows)
        ids = np.stack([np.pad(np.asarray(ids), (0, longest - len(ids)))
                        for ids in rows])
        # the rows of the embedding that are read, not the whole of it
        h = f32(np.asarray(weights["embed"])[ids]) \
            * sizes["embedding_multiplier"]
        layers = weights["layers"]
        for index in range(len(layers)):
            layer = f32(layers[index])
            h = jax.block_until_ready(layer_forward(layer, sizes, h,
                                                       control))
            del layer
        hs = [h[n, :len(row)] if positions is None
              else h[n, jnp.asarray(positions[n])]
              for n, row in enumerate(rows)]
        weight, head = f32(weights["final_norm"]), f32(weights["head"])
        return jax.block_until_ready(
            [(norm(h, weight, sizes["rms_norm_eps"]) @ head)
             * sizes["lm_head_multiplier"] for h in hs])
