"""Plain reference for Kimi-K2's language model (`model_type` `kimi_k2`:
latent attention, sigmoid-routed sparse experts): one sequence, one full
forward pass, `jax.numpy` in float32 at the highest matmul precision. No
cache, no kernels, no batching: every position's keys and values are
expanded from the latents, and each expert runs on the tokens that chose it.

`sizes` is the configuration's own keys (the published `config.json` names:
`hidden_size`, `q_lora_rank`, `kv_lora_rank`, `num_attention_heads`,
`qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim`, `n_routed_experts`,
`num_experts_per_tok`, `routed_scaling_factor`, `rms_norm_eps`,
`rope_theta`, `rope_scaling`). `weights` is indexed as the program's tree is
(`embed`, `layers[i]` with `attn`, the two norms and `mlp` or `moe`,
`final_norm`, `head`; matrices `[in, out]`, experts stacked), a layer at a
time, so a caller can convert one layer's weights to float32 as it is asked
for.

With `h = RMSNorm(x)`, a layer is `x += attention(h)`, `x += ffn(RMSNorm(x))`:

- attention: `c_q = RMSNorm(h W_qa)`; `q = c_q W_qb`, a head `[q_n | q_r]`;
  `[c_kv | k_r] = h W_kva`, `c_kv = RMSNorm(c_kv)`; a head's `[k_n | v] =
  c_kv W_kvb`; rotary on `q_r` and on the one `k_r` every head shares, at
  YaRN's frequencies; scores `(q_n.k_n + q_r.k_r) (nope + rope)^-1/2 m^2`,
  `m = 0.1 ln(factor) + 1`; causal softmax; `concat(heads . v) W_o`.
- experts: `s = sigmoid(h W_g)`; the `k` largest of `s + b` chosen; weights
  `s_i / sum_chosen s * routed_scaling_factor`; `E(h) = W_d (silu(W_g' h) *
  W_u h)`; `ffn = sum_{i chosen and held} w_i E_i(h) + E_shared(h)`.
- a layer with `mlp` instead of `moe`: one SwiGLU.

Departures from the published code, each the same function or stated in
the configuration: (1) `held = (first, count)` names the routed experts
whose part is computed, one chip's share of an expert-parallel deployment;
the others' part is left out, as it is in the program (`held = (0,
n_routed_experts)` is the uncut layer); the router still scores all of
them and the weights are normalised over all the chosen. (2) The weights
stack only the held experts. (3) Rotary pairs are the two halves of the
rotary width: the checkpoint interleaves them and the published code
permutes them apart at run time; the weights are taken as already permuted.
(4) `n_group` 1 and `topk_group` 1: the group-limited choice is the plain
one. (5) The vocabulary is the rows held.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def silu(x):
    return x * jax.nn.sigmoid(x)


def swiglu(p, x):
    return (silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_angles(sizes, positions):
    """[S, rope / 2] angles at YaRN's frequencies."""
    dim, base = sizes["qk_rope_head_dim"], sizes["rope_theta"]
    yarn = sizes["rope_scaling"]
    exponent = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    extra = 1.0 / base ** exponent  # frequencies kept
    inter = extra / yarn["factor"]  # frequencies slowed

    def correction(turns):
        return dim * math.log(yarn["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction(yarn["beta_fast"])), 0)
    high = min(math.ceil(correction(yarn["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    inv_freq = inter * ramp + extra * (1.0 - ramp)
    return positions.astype(jnp.float32)[:, None] * inv_freq[None, :]


def rotate(x, angles, scale):
    """`x` [..., S, rope], the two halves of the last axis as the pairs."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    cos, sin = jnp.cos(angles) * scale, jnp.sin(angles) * scale
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def attention(p, sizes, h):
    """[S, hidden] -> [S, hidden], causal."""
    s = h.shape[0]
    heads = sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    latent, eps = sizes["kv_lora_rank"], sizes["rms_norm_eps"]
    yarn = sizes["rope_scaling"]
    c_q = rms_norm(h @ p["q_a"], p["q_norm"], eps)
    q = (c_q @ p["q_b"]).reshape(s, heads, nope + rope)
    kv = h @ p["kv_a"]
    c_kv = rms_norm(kv[:, :latent], p["kv_norm"], eps)
    up = (c_kv @ p["kv_b"]).reshape(s, heads, nope + sizes["v_head_dim"])
    k_n, v = up[..., :nope], up[..., nope:]
    angles = rope_angles(sizes, jnp.arange(s))
    table_scale = (yarn_mscale(yarn["factor"], yarn["mscale"])
                   / yarn_mscale(yarn["factor"], yarn["mscale_all_dim"]))
    q_r = rotate(q[..., nope:].transpose(1, 0, 2), angles, table_scale)
    k_r = rotate(kv[:, latent:], angles, table_scale)
    scale = (nope + rope) ** -0.5 * yarn_mscale(
        yarn["factor"], yarn["mscale_all_dim"]) ** 2
    scores = (jnp.einsum("qhd,khd->hqk", q[..., :nope], k_n)
              + jnp.einsum("hqd,kd->hqk", q_r, k_r)) * scale
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", weights, v).reshape(s, -1) @ p["o"]


def routing(p, sizes, h):
    """Chosen experts [S, k] and their weights [S, k]."""
    scores = jax.nn.sigmoid(h @ p["router"])
    _, chosen = jax.lax.top_k(scores + p["router_bias"],
                              sizes["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / picked.sum(-1, keepdims=True)
    return chosen, weights * sizes["routed_scaling_factor"]


def held_margin(p, sizes, h, held):
    """[S]: how far each token's routing is from changing what the held
    experts compute: the least distance, over the held experts, between the
    expert's biased score and the boundary it would have to cross (the
    `k+1`-th largest for a chosen one, the `k`-th for another). Top-k is
    the one discontinuity of the network: a token whose margin is under a
    rounding's reach may flip an expert in or out, which moves its output
    by a whole expert's part and is no measure of the arithmetic."""
    first, count = held
    k = sizes["num_experts_per_tok"]
    biased = jax.nn.sigmoid(h @ p["router"]) + p["router_bias"]
    top = jax.lax.top_k(biased, k + 1)[0]
    mine = biased[:, first:first + count]
    chosen = mine >= top[:, k - 1:k]
    return jnp.where(chosen, mine - top[:, k:k + 1],
                     top[:, k - 1:k] - mine).min(axis=-1)


def experts(p, sizes, h, held):
    """The held experts' part and the shared expert's, [S, hidden]. The
    stacked expert `j` is the model's expert `held[0] + j`."""
    import numpy as np

    first, count = held
    chosen, weights = routing(p, sizes, h)
    chosen, weights = np.asarray(chosen), np.asarray(weights)
    out = swiglu(p["shared"], h)
    for j in range(count):
        token, slot = np.nonzero(chosen == first + j)
        if token.size == 0:
            continue
        one = {name: p["experts"][name][j] for name in ("gate", "up", "down")}
        part = swiglu(one, h[token]) * weights[token, slot][:, None]
        out = out.at[token].add(part)
    return out


def forward_rows(weights, sizes, rows, held=None, device=None,
                 positions=None, margins=None):
    """`forward` for several sequences: the layers in turn, each
    sequence through a layer on its own (no batch: a sequence never meets
    another), so a layer's weights are converted once. `positions[i]`
    picks the positions of sequence `i` whose logits are returned. Every
    layer is waited for before the next is converted: the host holds one
    layer's float32 weights at a time. A list given as `margins` receives,
    a sequence, the least `held_margin` of each position over the expert
    layers."""
    device = device or jax.local_devices(backend="cpu")[0]
    held = held or (0, sizes["n_routed_experts"])
    eps = sizes["rms_norm_eps"]

    def f32(tree):
        return jax.tree_util.tree_map(
            lambda w: jnp.asarray(jax.device_put(w, device), jnp.float32),
            tree)

    with jax.default_device(device), \
            jax.default_matmul_precision("highest"):
        embed = f32(weights["embed"])
        xs = [embed[jnp.asarray(ids)] for ids in rows]
        del embed
        least = None if margins is None else [
            jnp.full((len(ids),), jnp.inf) for ids in rows]
        layers = weights["layers"]
        for index in range(len(layers)):
            layer = f32(layers[index])
            for n, x in enumerate(xs):
                x = x + attention(layer["attn"], sizes,
                                  rms_norm(x, layer["input_norm"], eps))
                h = rms_norm(x, layer["post_norm"], eps)
                xs[n] = x + (swiglu(layer["mlp"], h) if "mlp" in layer
                             else experts(layer["moe"], sizes, h, held))
                if least is not None and "moe" in layer:
                    least[n] = jnp.minimum(least[n], held_margin(
                        layer["moe"], sizes, h, held))
            jax.block_until_ready(xs)
            del layer
        if margins is not None:
            margins.extend(least)
        if positions is not None:
            xs = [x[jnp.asarray(at)] for x, at in zip(xs, positions)]
        norm, head = f32(weights["final_norm"]), f32(weights["head"])
        return jax.block_until_ready(
            [rms_norm(x, norm, eps) @ head for x in xs])


def forward(weights, sizes, ids, held=None, device=None, positions=None):
    """Logits [S, vocab] (or at `positions` only) of one sequence `ids`
    [S], float32, on `device` (the host CPU where none is given)."""
    return forward_rows(weights, sizes, [ids], held, device,
                        None if positions is None else [positions])[0]
