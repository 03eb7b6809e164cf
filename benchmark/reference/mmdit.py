"""Plain reference: the FLUX.1 MMDiT forward (Black Forest Labs, `flux`
repository, `model.py` / `modules/layers.py`; Esser et al. 2024 for the
double-stream block) in straightforward float32 `jax.numpy`.

No kernels, no flax modules, no sharding. Written from the published
description:

- `img_in` / `txt_in` project the 2x2-patchified latents and the T5 states
  to the hidden width; three embedders (sinusoidal timestep x 1000, the
  distilled guidance likewise, CLIP's pooled vector), each Linear - SiLU -
  Linear, are summed into `vec`;
- a double-stream block: per stream, `vec` -> SiLU -> Linear gives two sets
  of (shift, scale, gate); LayerNorm without affine, `(1 + scale) * x +
  shift`, a fused qkv projection split as (3, heads, head width), RMS norm
  of q and k over the head width with a learnt scale, RoPE; ONE softmax
  attention over text then image tokens; per stream the output projection
  and a tanh-GELU MLP, each added through its gate;
- a single-stream block over the joined sequence: one modulation, `linear1`
  gives `[q | k | v | mlp]`, attention as above, `linear2` over `[attn |
  gelu(mlp)]`, added through the gate;
- the last layer: `vec` -> SiLU -> Linear gives (shift, scale), LayerNorm,
  modulate, project to the patch channels;
- RoPE by axis: position ids `[.., 3]`, axis a rotates `axes_dim[a] / 2`
  consecutive pairs `(2i, 2i + 1)` by `pos_a * theta ** (-2i / axes_dim[a])`.

It reads the program's parameter tree by the flax modules' own names
(`img_attn_qkv`, `linear1`, ...), with the fused kernels in the checkpoint's
column order. `params` is anything indexable by those names: the benchmark
hands it a view that pulls one block at a time from the chips, so the
reference never holds a second copy of the model (a double block is 1.36 GB
in float32). Leaves are upcast block by block (`_f32`); every product runs
under `default_matmul_precision("highest")`; `device` is where it computes
(the benchmark gives the host CPU).

Departures from the published code, none of the mathematics: (1) rows are
`[B, S, C]` and heads `[B, S, H, D]` where BFL's attention works on
`[B, H, S, D]`, a layout; (2) the rotation is applied as two real products
on the even and odd halves of each pair instead of BFL's stacked 2x2
matrices, the same numbers; (3) scores are formed whole, `[B, H, S, S]` in
float32 (2 GB a row at 4608 tokens), where torch's fused attention never
materialises them.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _f32(tree, device=None):
    """`tree` as float32 on `device` (None: where it is)."""
    def leaf(x):
        if device is not None:
            x = jax.device_put(x, device)
        return jnp.asarray(x, jnp.float32)

    return jax.tree_util.tree_map(leaf, tree)


def _block(*static):
    """One jitted block, float32 leaves, highest matmul precision; it
    computes where its first activation is."""
    def wrap(fn):
        jitted = jax.jit(fn, static_argnames=static)

        @functools.wraps(fn)
        def call(params, *args, **kwargs):
            device = next(iter(args[0].devices()))
            with jax.default_matmul_precision("highest"):
                return jitted(_f32(params, device), *args, **kwargs)

        return call

    return wrap


def dense(p, x):
    return x @ p["kernel"] + p["bias"]


def layer_norm(x, eps=1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps)


def rms_norm(x, scale, eps=1e-6):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def timestep_features(t, dim=256, max_period=10000.0, time_factor=1000.0):
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    args = (time_factor * jnp.asarray(t, jnp.float32))[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


def rope_angles(ids, axes_dim, theta):
    """[B, S, 3] positions -> [B, S, head width / 2] angles, axis by axis."""
    parts = []
    for axis, dim in enumerate(axes_dim):
        omega = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
        parts.append(ids[..., axis].astype(jnp.float32)[..., None] * omega)
    return jnp.concatenate(parts, axis=-1)


def rotate(x, angles):
    """x [B, S, H, D]: pair (2i, 2i+1) turned by angles[..., i]."""
    cos, sin = jnp.cos(angles)[:, :, None], jnp.sin(angles)[:, :, None]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def attention(q, k, v, angles):
    """Softmax attention over all tokens; [B, S, H, D] -> [B, S, H * D]."""
    q, k = rotate(q, angles), rotate(k, angles)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", weights, v)
    return out.reshape(out.shape[0], out.shape[1], -1)


def modulation(p, vec, n):
    return jnp.split(dense(p["lin"], silu(vec))[:, None, :], n, axis=-1)


def embedder(p, x):
    return dense(p["out_layer"], silu(dense(p["in_layer"], x)))


def split_heads(qkv, heads):
    """[B, S, 3 * H * D] in the order (3, H, D) -> q, k, v [B, S, H, D]."""
    b, s, width = qkv.shape
    qkv = qkv.reshape(b, s, 3, heads, width // (3 * heads))
    return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]


@_block("guidance_embed")
def head(p, img, txt, timesteps, pooled, guidance, guidance_embed):
    vec = embedder(p["time_in"], timestep_features(timesteps))
    if guidance_embed:
        vec = vec + embedder(p["guidance_in"], timestep_features(guidance))
    vec = vec + embedder(p["vector_in"], pooled)
    return dense(p["img_in"], img), dense(p["txt_in"], txt), vec


@_block("heads")
def double_block(p, img, txt, vec, angles, heads):
    mods = {s: modulation(p[f"{s}_mod"], vec, 6) for s in ("img", "txt")}
    streams = {"img": img, "txt": txt}
    qkv = {}
    for s, x in streams.items():
        shift, scale = mods[s][0], mods[s][1]
        q, k, v = split_heads(
            dense(p[f"{s}_attn_qkv"], (1 + scale) * layer_norm(x) + shift),
            heads)
        norm = p[f"{s}_attn_norm"]
        qkv[s] = (rms_norm(q, norm["query_scale"]),
                  rms_norm(k, norm["key_scale"]), v)
    q, k, v = (jnp.concatenate([qkv["txt"][i], qkv["img"][i]], axis=1)
               for i in range(3))
    attn = attention(q, k, v, angles)
    n_txt = txt.shape[1]
    parts = {"txt": attn[:, :n_txt], "img": attn[:, n_txt:]}
    out = {}
    for s, x in streams.items():
        _, _, gate1, shift2, scale2, gate2 = mods[s]
        x = x + gate1 * dense(p[f"{s}_attn_proj"], parts[s])
        y = (1 + scale2) * layer_norm(x) + shift2
        y = dense(p[f"{s}_mlp_2"], gelu_tanh(dense(p[f"{s}_mlp_0"], y)))
        out[s] = x + gate2 * y
    return out["img"], out["txt"]


@_block("heads")
def single_block(p, x, vec, angles, heads):
    shift, scale, gate = modulation(p["modulation"], vec, 3)
    fused = dense(p["linear1"], (1 + scale) * layer_norm(x) + shift)
    hidden = x.shape[-1]
    q, k, v = split_heads(fused[..., :3 * hidden], heads)
    q = rms_norm(q, p["norm"]["query_scale"])
    k = rms_norm(k, p["norm"]["key_scale"])
    attn = attention(q, k, v, angles)
    out = dense(p["linear2"], jnp.concatenate(
        [attn, gelu_tanh(fused[..., 3 * hidden:])], axis=-1))
    return x + gate * out


@_block()
def final(p, x, vec):
    shift, scale = jnp.split(
        dense(p["final_layer_mod"], silu(vec))[:, None, :], 2, axis=-1)
    return dense(p["final_layer_linear"],
                 (1 + scale) * layer_norm(x) + shift)


HEAD_NAMES = ("img_in", "txt_in", "time_in", "guidance_in", "vector_in")
FINAL_NAMES = ("final_layer_mod", "final_layer_linear")


def mmdit_forward(params, config, img, img_ids, txt, txt_ids, timesteps,
                  pooled, guidance=None, device=None):
    """The velocity `[B, S_img, in_channels]` for patchified latents `img`
    `[B, S_img, in_channels]`, T5 states `txt` `[B, S_txt, context_dim]`,
    positions `*_ids` `[B, S, 3]`, flow times `timesteps` `[B]`, CLIP's
    pooled vector `[B, pooled_dim]` and `guidance` `[B]` (dev checkpoints).
    `config` gives `num_heads`, `depth_double`, `depth_single`,
    `guidance_embed`, `axes_dims_rope`, `theta`; `params[name]` is asked for
    once per block, in order."""
    def put(x):
        x = jnp.asarray(x)
        return x if device is None else jax.device_put(x, device)

    img, txt, timesteps, pooled = (
        put(x).astype(jnp.float32) for x in (img, txt, timesteps, pooled))
    guidance = (jnp.ones_like(timesteps) if guidance is None
                else put(guidance).astype(jnp.float32))
    angles = rope_angles(
        jnp.concatenate([put(txt_ids), put(img_ids)], axis=1),
        config.axes_dims_rope, float(config.theta))
    names = [n for n in HEAD_NAMES
             if n != "guidance_in" or config.guidance_embed]
    img, txt, vec = head({n: params[n] for n in names}, img, txt, timesteps,
                         pooled, guidance,
                         guidance_embed=bool(config.guidance_embed))
    for i in range(config.depth_double):
        img, txt = double_block(params[f"double_blocks_{i}"], img, txt, vec,
                                angles, heads=config.num_heads)
    x = jnp.concatenate([txt, img], axis=1)
    for i in range(config.depth_single):
        x = single_block(params[f"single_blocks_{i}"], x, vec, angles,
                         heads=config.num_heads)
    return final({n: params[n] for n in FINAL_NAMES}, x[:, txt.shape[1]:],
                 vec)
