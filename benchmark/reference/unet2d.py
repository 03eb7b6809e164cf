"""Plain reference: the UNet2DCondition forward of the SD family (SD1.x,
SD2.x, SDXL) in straightforward float32 `jax.numpy`.

No kernels, no flax modules, no batching tricks. It reads the program's
parameter tree by the flax modules' own names (`to_q`, `proj_in`,
`time_emb_proj`, ...) and follows the published architecture (Rombach et
al. 2022; Podell et al. 2023 for SDXL's added conditioning; the diffusers
`UNet2DConditionModel` graph that the public checkpoints are stored in).
`tests/torch_unet_ref.py` states the same equations independently in torch.

Leaves are upcast to float32 one block at a time (`_f32`), so the
reference never holds a second copy of the model. Each block is one jitted
function keyed by its shapes; nothing bigger is ever compiled. Every matrix
product runs under `default_matmul_precision("highest")` — on a TPU a
float32 product is otherwise rounded to bfloat16 passes. `unet_forward`
takes the device to compute on: the benchmark gives it the host CPU, whose
float32 is exact and whose compiler takes seconds where the TPU's took 13
minutes for these ~35 float32 blocks (PERF.md, PR 23).

NHWC throughout, as the program stores its convolution kernels
(`[kh, kw, in, out]`); that is a layout, not a departure.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

GROUPS = 32


def _f32(tree, device=None):
    """`tree` as float32 on `device` (None: where it is)."""
    def leaf(x):
        if device is not None:
            x = jax.device_put(x, device)
        return jnp.asarray(x, jnp.float32)

    return jax.tree_util.tree_map(leaf, tree)


def _block(*static):
    """One jitted block, float32 leaves, highest matmul precision."""
    def wrap(fn):
        jitted = jax.jit(fn, static_argnames=static)

        @functools.wraps(fn)
        def call(params, *args, **kwargs):
            # the block computes where its activations are
            device = next(iter(args[0].devices())) if args else None
            with jax.default_matmul_precision("highest"):
                return jitted(_f32(params, device), *args, **kwargs)

        return call

    return wrap


def timestep_features(timesteps, dim, flip_sin_to_cos=True, freq_shift=0.0):
    half = dim // 2
    exponent = -math.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
    freqs = jnp.exp(exponent / (half - freq_shift))
    args = jnp.asarray(timesteps, jnp.float32)[:, None] * freqs[None, :]
    emb = jnp.concatenate([jnp.sin(args), jnp.cos(args)], axis=-1)
    if flip_sin_to_cos:
        emb = jnp.concatenate([emb[:, half:], emb[:, :half]], axis=-1)
    return emb


def dense(p, x):
    y = x @ p["kernel"]
    return y + p["bias"] if "bias" in p else y


def conv(p, x, stride=1, padding=1):
    y = jax.lax.conv_general_dilated(
        x, p["kernel"], (stride, stride), ((padding, padding),) * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["bias"]


def group_norm(p, x, eps, silu=False):
    b, h, w, c = x.shape
    g = x.reshape(b, h * w, GROUPS, c // GROUPS)
    mean = g.mean(axis=(1, 3), keepdims=True)
    var = ((g - mean) ** 2).mean(axis=(1, 3), keepdims=True)
    y = ((g - mean) / jnp.sqrt(var + eps)).reshape(b, h, w, c)
    y = y * p["scale"] + p["bias"]
    return y * jax.nn.sigmoid(y) if silu else y


def layer_norm(p, x, eps=1e-5):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def attention(p, x, context, heads):
    q, k, v = dense(p["to_q"], x), dense(p["to_k"], context), dense(p["to_v"], context)
    b, sq, inner = q.shape
    d = inner // heads
    q = q.reshape(b, sq, heads, d)
    k = k.reshape(b, -1, heads, d)
    v = v.reshape(b, -1, heads, d)

    def one_row(qkv):  # a batch row at a time: [H, Sq, Sk] scores in f32
        q, k, v = qkv
        logits = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(logits, axis=-1), v)

    out = jax.lax.map(one_row, (q, k, v))
    return dense(p["to_out_0"], out.reshape(b, sq, inner))


@_block()
def time_mlp(p, feat):
    return dense(p["linear_2"], jax.nn.silu(dense(p["linear_1"], feat)))


@_block("stride", "up")
def conv_block(p, x, stride=1, up=False):
    if up:  # nearest-neighbour 2x, then the convolution
        x = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
    return conv(p, x, stride=stride)


@_block()
def resnet(p, x, temb):
    eps = 1e-5
    h = conv(p["conv1"], group_norm(p["norm1"], x, eps, silu=True))
    h = h + dense(p["time_emb_proj"], jax.nn.silu(temb))[:, None, None, :]
    h = conv(p["conv2"], group_norm(p["norm2"], h, eps, silu=True))
    if "conv_shortcut" in p:
        x = conv(p["conv_shortcut"], x, padding=0)
    return x + h


@_block("heads")
def transformer_block(p, hidden, context, heads=1):
    normed = layer_norm(p["norm1"], hidden)
    hidden = hidden + attention(p["attn1"], normed, normed, heads)
    hidden = hidden + attention(
        p["attn2"], layer_norm(p["norm2"], hidden), context, heads)
    h = dense(p["ff"]["net_0"]["proj"], layer_norm(p["norm3"], hidden))
    h, gate = jnp.split(h, 2, axis=-1)
    h = h * jax.nn.gelu(gate, approximate=False)
    return hidden + dense(p["ff"]["net_2"], h)


@_block()
def transformer_in(p, x):
    b, h, w, c = x.shape
    hidden = group_norm(p["norm"], x, 1e-6).reshape(b, h * w, c)
    return dense(p["proj_in"], hidden)


@_block()
def transformer_out(p, hidden, residual):
    return dense(p["proj_out"], hidden).reshape(residual.shape) + residual


@_block()
def head(p, x):
    return conv(p["conv_out"], group_norm(p["conv_norm_out"], x, 1e-5, silu=True))


def spatial_transformer(p, x, context, heads):
    hidden = transformer_in({"norm": p["norm"], "proj_in": p["proj_in"]}, x)
    n = 0
    while f"transformer_blocks_{n}" in p:
        hidden = transformer_block(
            p[f"transformer_blocks_{n}"], hidden, context, heads=heads)
        n += 1
    return transformer_out({"proj_out": p["proj_out"]}, hidden, x)


def _stage(p, x, temb, context, heads, skips=None):
    """The resnets (+ transformers) of one down/mid/up block. For an up
    block `skips` is popped and concatenated before every resnet."""
    outs = []
    i = 0
    while f"resnets_{i}" in p:
        if skips is not None:
            x = jnp.concatenate([x, skips.pop()], axis=-1)
        x = resnet(p[f"resnets_{i}"], x, temb)
        if f"attentions_{i}" in p:
            x = spatial_transformer(p[f"attentions_{i}"], x, context, heads)
        outs.append(x)
        i += 1
    return x, outs


def unet_forward(params, config, sample, timesteps, context, added_cond=None,
                 device=None):
    """Predicted noise (or v) for `sample` [B, H, W, C_in] at `timesteps`
    [B] under text `context` [B, S, D]; `added_cond` is SDXL's
    {"text_embeds": [B, P], "time_ids": [B, 6]}. `config` is the program's
    `UNet2DConfig` (sizes only: heads per block, flip/shift of the
    sinusoid, the added-conditioning width). Computes on `device` (None:
    the default device)."""
    f32 = functools.partial(_f32, device=device)
    sample, context, timesteps = f32(sample), f32(context), f32(timesteps)
    widths = config.block_out_channels
    heads = config.heads_per_block()

    feat = timestep_features(timesteps, widths[0], config.flip_sin_to_cos,
                             config.freq_shift)
    temb = time_mlp(params["time_embedding"], feat)
    if config.addition_embed_dim:
        ids = timestep_features(
            f32(added_cond["time_ids"]).reshape(-1),
            config.addition_time_embed_dim, config.flip_sin_to_cos,
            config.freq_shift).reshape(sample.shape[0], -1)
        added = jnp.concatenate([f32(added_cond["text_embeds"]), ids], axis=-1)
        temb = temb + time_mlp(params["add_embedding"], added)

    x = conv_block(params["conv_in"], sample)
    skips = [x]
    for b in range(len(widths)):
        p = params[f"down_blocks_{b}"]
        x, outs = _stage(p, x, temb, context, heads[b])
        skips.extend(outs)
        if "downsamplers_0" in p:
            x = conv_block(p["downsamplers_0"]["conv"], x, stride=2)
            skips.append(x)

    mid = params["mid_block"]
    x = resnet(mid["resnets_0"], x, temb)
    x = spatial_transformer(mid["attentions_0"], x, context, heads[-1])
    x = resnet(mid["resnets_1"], x, temb)

    for b in range(len(widths)):
        p = params[f"up_blocks_{b}"]
        x, _ = _stage(p, x, temb, context, heads[len(widths) - 1 - b], skips)
        if "upsamplers_0" in p:
            x = conv_block(p["upsamplers_0"]["conv"], x, up=True)

    return head({"conv_norm_out": params["conv_norm_out"],
                 "conv_out": params["conv_out"]}, x)
