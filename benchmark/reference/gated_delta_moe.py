"""Plain reference for Qwen3-Next's language model (`model_type`
`qwen3_next`: Gated DeltaNet linear attention on three layers in four, gated
softmax attention on every fourth, softmax-routed sparse experts and a
gated shared expert behind every one): one full forward pass over prompt
and given tokens, `jax.numpy` in float32 at the highest matmul precision.
No cache, no kernel, no chunk: the delta rule is the position-by-position
recurrence (one `lax.scan` over the positions, all rows and heads at
once), attention one explicit matrix of who sees whom, and each expert
runs on the tokens that chose it.

`sizes` is the configuration's own keys (the published `config.json` names:
`hidden_size`, `num_attention_heads`, `num_key_value_heads`, `head_dim`,
`partial_rotary_factor`, `rope_theta`, `full_attention_interval`,
`linear_num_key_heads`, `linear_num_value_heads`, `linear_key_head_dim`,
`linear_value_head_dim`, `linear_conv_kernel_dim`, `num_experts` (the
router's width), `num_experts_per_tok`, `rms_norm_eps`). `weights` is
indexed as the program's tree is (`embed`, `layers[i]` with `mixer` or
`attn`, the two norm offsets and `moe`, `final_norm_offset`, `head`;
matrices `[in, out]`, experts stacked), a layer at a time, so a caller can
convert one layer's weights to float32 as it is asked for.

Every norm is zero-centred: `norm(x) = x * rsqrt(mean(x^2) + eps) * (1 +
w)`. A layer is `x += mixer(norm(x))`, `x += moe(norm(x))`:

- Gated DeltaNet (layer `i`, `(i + 1) % full_attention_interval != 0`):
  `q | k | v | z = h W_qkvz`, `b | a = h W_ba`; `q | k | v` through a
  depthwise causal convolution of width 4 (`out_t = sum_i w_i x_{t-3+i}`,
  zeros before the row's start, no bias) and SiLU; a value head `beta =
  sigmoid(b)`, `g = -exp(A_log) softplus(a + dt_bias)`; `q`, `k` divided by
  `sqrt(sum x^2 + 1e-6)` over the head's 128, `q` times `128^-1/2`, key
  head `j // 2` serving value head `j`. With `S` [128 keys, 128 values] a
  head, zero at the row's start, for each position: `S <- S exp(g_t)`,
  `m = S^T k_t`, `d = (v_t - m) beta_t`, `S <- S + k_t (x) d`, `o_t = S^T
  q_t`. Then `y = (o rsqrt(mean(o^2) + eps) w_n) silu(z)` a head (`w_n` a
  plain weight) and `y W_o`.
- gated attention: `h W_q` is a query and a gate a head; `q`, `k` normed a
  head; rotary (`rope_theta`, the two halves of the rotary dims as the
  pairs) on the first `partial_rotary_factor x head_dim` dims; token `t`
  sees `u <= t`; query head `j` reads key head `j // G`; `softmax(q . k /
  sqrt(head_dim)) v`, times `sigmoid(gate)`, `W_o`.
- experts: `s = softmax(h W_g)` over all the experts, the `k` largest,
  weights `s_i / sum_chosen s`; `sum_{chosen and held} w_i E_i(h) +
  sigmoid(h w_s) E_shared(h)`, every `E` a SwiGLU.

Departures from the published description, each the same function or
stated in the configuration: (1) `held = (first, count)` names the routed
experts whose part is computed, one chip's share of an expert-parallel
deployment; the others' part is left out, as it is in the program; the
router still scores all of them and the weights are normalised over all the
chosen. (2) The weights stack only the held experts. (3) The vocabulary is
the rows held. (4) The layers are the first `len(weights["layers"])`. (5)
The leaves' columns are `[q | k | v | z]`, `[b | a]` and `[queries |
gates]` where the checkpoint interleaves them a head (a permutation of
columns). (6) Pre-norm residuals are not a key of `config.json`: the
configuration lists them under `assumed`. (7) The multi-token-prediction
head is left out: no logit depends on it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def norm(x, offset, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + offset)


def swiglu(p, x):
    hidden = x @ p["gate"]
    return ((hidden * jax.nn.sigmoid(hidden)) * (x @ p["up"])) @ p["down"]


def rotate(x, positions, theta, rotary: int):
    """`x` [N, T, heads, dim] at `positions` [T]: the first `rotary` dims
    turned, the two halves of them as the pairs."""
    inv_freq = 1.0 / theta ** (
        jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
    angles = positions.astype(jnp.float32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    a, b = x[..., :rotary // 2], x[..., rotary // 2:rotary]
    return jnp.concatenate(
        [a * cos - b * sin, a * sin + b * cos, x[..., rotary:]], axis=-1)


def attention(p, sizes, h):
    """Rows `h` [N, T, hidden] -> [N, T, hidden], causal."""
    rows, length, _ = h.shape
    heads, kv_heads = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dim, eps = sizes["head_dim"], sizes["rms_norm_eps"]
    rotary = int(dim * sizes["partial_rotary_factor"])
    q_gate = h @ p["q_gate"]
    q = q_gate[..., :heads * dim].reshape(rows, length, heads, dim)
    gate = q_gate[..., heads * dim:]
    k = (h @ p["k"]).reshape(rows, length, kv_heads, dim)
    v = (h @ p["v"]).reshape(rows, length, kv_heads, dim)
    at = jnp.arange(length)
    q = rotate(norm(q, p["q_norm_offset"], eps), at, sizes["rope_theta"],
               rotary)
    k = rotate(norm(k, p["k_norm_offset"], eps), at, sizes["rope_theta"],
               rotary)
    # query head j reads key head j // group
    q = q.reshape(rows, length, kv_heads, heads // kv_heads, dim)
    scores = jnp.einsum("nqhgd,nkhd->nhgqk", q, k) * dim ** -0.5
    seen = at[None, :] <= at[:, None]
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("nhgqk,nkhd->nqhgd", weights, v).reshape(
        rows, length, heads * dim)
    return (out * jax.nn.sigmoid(gate)) @ p["o"]


def delta_rule(q, k, v, g, beta):
    """The recurrence, position by position: `q`, `k` [N, T, H, K], `v`
    [N, T, H, V], `g`, `beta` [N, T, H]; `S` starts at zero. Returns `o`
    [N, T, H, V]."""
    rows, _, heads, keys = q.shape

    def position(state, xs):
        q, k, v, g, beta = xs
        state = state * jnp.exp(g)[..., None, None]
        m = jnp.einsum("nhkv,nhk->nhv", state, k)
        d = (v - m) * beta[..., None]
        state = state + k[..., :, None] * d[..., None, :]
        return state, jnp.einsum("nhkv,nhk->nhv", state, q)

    _, o = jax.lax.scan(
        position, jnp.zeros((rows, heads, keys, v.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def delta_net(p, sizes, h):
    """Rows `h` [N, T, hidden] -> [N, T, hidden]."""
    rows, length, _ = h.shape
    key_heads, heads = (sizes["linear_num_key_heads"],
                        sizes["linear_num_value_heads"])
    key_dim, value_dim = (sizes["linear_key_head_dim"],
                          sizes["linear_value_head_dim"])
    taps = sizes["linear_conv_kernel_dim"]
    key_width, value_width = key_heads * key_dim, heads * value_dim
    qkvz = h @ p["qkvz"]
    ba = h @ p["ba"]
    beta = jax.nn.sigmoid(ba[..., :heads])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., heads:] + p["dt_bias"])
    x = qkvz[..., :2 * key_width + value_width]
    z = qkvz[..., 2 * key_width + value_width:].reshape(
        rows, length, heads, value_dim)
    behind = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    mixed = sum(behind[:, tap:tap + length] * p["conv"][tap]
                for tap in range(taps))
    mixed = mixed * jax.nn.sigmoid(mixed)

    def unit(x):
        x = x.reshape(rows, length, key_heads, key_dim)
        x = x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
        return jnp.repeat(x, heads // key_heads, axis=2)

    q = unit(mixed[..., :key_width]) * key_dim ** -0.5
    k = unit(mixed[..., key_width:2 * key_width])
    v = mixed[..., 2 * key_width:].reshape(rows, length, heads, value_dim)
    o = delta_rule(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + sizes["rms_norm_eps"]) * p["norm"]
    y = o * (z * jax.nn.sigmoid(z))
    return y.reshape(rows, length, value_width) @ p["o"]


def scores(p, h):
    """The router's scores [S, all the experts]: a softmax over them."""
    return jax.nn.softmax(h @ p["router"], axis=-1)


def routing(p, sizes, h):
    """Chosen experts [S, k] and their weights [S, k]."""
    picked, chosen = jax.lax.top_k(scores(p, h), sizes["num_experts_per_tok"])
    return chosen, picked / picked.sum(-1, keepdims=True)


def held_margin(p, sizes, h, held):
    """[S]: how far each token's routing is from changing what the held
    experts compute: the least distance, over the held experts, between the
    expert's score and the boundary it would have to cross (the `k+1`-th
    largest for a chosen one, the `k`-th for another). Top-k is the one
    discontinuity of the network (`mla_moe.held_margin` says more); a flip
    between two experts that are both absent changes nothing here but the
    normalisation, which is continuous."""
    first, count = held
    k = sizes["num_experts_per_tok"]
    s = scores(p, h)
    top = jax.lax.top_k(s, k + 1)[0]
    mine = s[:, first:first + count]
    chosen = mine >= top[:, k - 1:k]
    return jnp.where(chosen, mine - top[:, k:k + 1],
                     top[:, k - 1:k] - mine).min(axis=-1)


# an expert's tokens are made up to a multiple of this with a row of
# zeros at weight zero, so that a few shapes compile and not one a count
TOKEN_BLOCK = 64


def experts(p, sizes, h, held):
    """The held experts' part and the gated shared expert's, [S, hidden];
    an expert's tokens of all the rows in one product. The stacked expert
    `j` is the model's expert `held[0] + j`."""
    import numpy as np

    first, count = held
    tokens = h.shape[0]
    chosen, weights = routing(p, sizes, h)
    chosen, weights = np.asarray(chosen), np.asarray(weights)
    shared = jax.nn.sigmoid(h @ p["shared_gate"]) * swiglu(p["shared"], h)
    # one more row, of zeros: where the made-up tokens read and add
    h = jnp.concatenate([h, jnp.zeros_like(h[:1])])
    out = jnp.zeros_like(h)
    for j in range(count):
        token, slot = np.nonzero(chosen == first + j)
        if token.size == 0:
            continue
        size = -(-token.size // TOKEN_BLOCK) * TOKEN_BLOCK
        index = np.full((size,), tokens)
        index[:token.size] = token
        weight = np.zeros((size,), np.float32)
        weight[:token.size] = weights[token, slot]
        one = {name: p["experts"][name][j] for name in ("gate", "up", "down")}
        out = out.at[index].add(swiglu(one, h[index]) * weight[:, None])
    return shared + out[:tokens]


def layer_forward(layer, sizes, x, held, linear: bool):
    """One layer over rows `x` [N, T, hidden]; returns the rows after it
    and the expert layer's input [N x T, hidden]."""
    eps = sizes["rms_norm_eps"]
    h = norm(x, layer["input_norm_offset"], eps)
    x = x + (delta_net(layer["mixer"], sizes, h) if linear
             else attention(layer["attn"], sizes, h))
    h = norm(x, layer["post_norm_offset"], eps).reshape(-1, x.shape[-1])
    return x + experts(layer["moe"], sizes, h, held).reshape(x.shape), h


def forward_rows(weights, sizes, rows, held=None, device=None,
                 positions=None, margins=None):
    """One full forward pass for several sequences side by side: every
    sequence is lengthened to the longest with id 0 behind it (no position
    sees a later one, on either kind of layer, so no logit of its own
    changes), and a layer is one operation over `[rows, positions]`, its
    weights converted once. `positions[i]` picks the positions of sequence `i`
    whose logits are returned. Every layer is waited for before the next
    is converted: the host holds one layer's float32 weights at a time. A
    list given as `margins` receives, a sequence, the least `held_margin`
    of each of its positions over the layers."""
    import numpy as np

    device = device or jax.local_devices(backend="cpu")[0]
    held = held or (0, sizes["num_experts"])
    interval = sizes["full_attention_interval"]

    def f32(tree):
        return jax.tree_util.tree_map(
            lambda w: jnp.asarray(jax.device_put(w, device), jnp.float32),
            tree)

    with jax.default_device(device), \
            jax.default_matmul_precision("highest"):
        longest = max(len(ids) for ids in rows)
        ids = np.stack([np.pad(np.asarray(ids), (0, longest - len(ids)))
                        for ids in rows])
        x = f32(weights["embed"])[jnp.asarray(ids)]
        least = jnp.full(ids.shape, jnp.inf)
        layers = weights["layers"]
        for index in range(len(layers)):
            layer = f32(layers[index])
            x, h = layer_forward(layer, sizes, x, held,
                                 (index + 1) % interval != 0)
            if margins is not None:
                least = jnp.minimum(least, held_margin(
                    layer["moe"], sizes, h, held).reshape(ids.shape))
            jax.block_until_ready(x)
            del layer
        if margins is not None:
            margins.extend(least[n, :len(row)] for n, row in enumerate(rows))
        xs = [x[n, :len(row)] if positions is None
              else x[n, jnp.asarray(positions[n])]
              for n, row in enumerate(rows)]
        offset, head = f32(weights["final_norm_offset"]), f32(weights["head"])
        return jax.block_until_ready(
            [norm(x, offset, sizes["rms_norm_eps"]) @ head for x in xs])
