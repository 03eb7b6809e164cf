"""Flux MMDiT transformer, flax.linen — the rectified-flow flagship family.

Reference context: Flux dev/schnell jobs ride `FluxPipeline` wire names
with bf16 + sequential CPU offload on CUDA (reference swarm/test.py:
244-290, swarm/job_arguments.py large-model branches). TPU rebuild: the
whole transformer is one XLA program — no offload; memory scaling comes
from sharding (parallel/tensor.py) instead.

Architecture (Black Forest Labs Flux):
- 2x2-patchified 16-channel latents -> `img_in` linear; T5 context ->
  `txt_in` linear; sinusoidal timestep (+ guidance for dev) and CLIP
  pooled vector feed MLPs summed into the modulation vector `vec`.
- `depth_double` double-stream blocks: separate img/txt streams, each with
  adaLN modulation from `vec`, joint attention over the concatenated
  token sequence, per-head RMS qk-norm, 3D RoPE (text ids zero, image ids
  (y, x)).
- `depth_single` single-stream blocks over the fused sequence: one fused
  linear producing qkv + MLP-in, attention + gelu-MLP combined, one
  output linear.
- final adaLN + linear back to patch channels.

Module names follow the BFL checkpoint graph (double_blocks.N.img_attn.*)
so conversion is mechanical (models/conversion.py convert_flux).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable

import flax.linen as nn
import jax.numpy as jnp

from ..ops.platform import KERNEL_TRACES, active_mesh
from ..parallel import tensor
from .layers import DeclaredParams, Dense, LayerNorm


@dataclasses.dataclass(frozen=True)
class FluxConfig:
    in_channels: int = 64  # 16 latent channels x 2x2 patch
    hidden_size: int = 3072
    num_heads: int = 24
    depth_double: int = 19
    depth_single: int = 38
    mlp_ratio: float = 4.0
    context_dim: int = 4096  # T5-XXL d_model
    pooled_dim: int = 768  # CLIP-L pooled
    guidance_embed: bool = True  # flux-dev distilled guidance; schnell: False
    axes_dims_rope: tuple[int, ...] = (16, 56, 56)
    theta: int = 10_000

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


TINY_FLUX = FluxConfig(
    in_channels=16,  # 4 latent channels x 2x2 patch (tiny VAE)
    hidden_size=32,
    num_heads=2,
    depth_double=1,
    depth_single=1,
    context_dim=32,
    pooled_dim=32,
    guidance_embed=True,
    axes_dims_rope=(4, 6, 6),
)


def timestep_embedding(t, dim: int, max_period: float = 10_000.0,
                       time_factor: float = 1000.0):
    """Sinusoidal features of (scaled) flow time t in [0, 1] -> [B, dim]."""
    t = t * time_factor
    half = dim // 2
    freqs = jnp.exp(
        -math.log(max_period) * jnp.arange(half, dtype=jnp.float32) / half
    )
    args = t[:, None].astype(jnp.float32) * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


def rope_frequencies(ids, axes_dims: tuple[int, ...], theta: int):
    """[B, S, n_axes] integer positions -> complex-as-pair rotations
    [B, S, head_dim/2, 2] laid out axis-by-axis (Flux 3D RoPE)."""
    components = []
    for axis, dim in enumerate(axes_dims):
        pos = ids[..., axis].astype(jnp.float32)  # [B, S]
        scale = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
        omega = 1.0 / (theta**scale)  # [dim/2]
        angles = pos[..., None] * omega  # [B, S, dim/2]
        components.append(angles)
    angles = jnp.concatenate(components, axis=-1)  # [B, S, head_dim/2]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x, cos, sin):
    """x [B, S, H, D] with rotation pairs on the last dim; cos, sin
    [B, S, D/2], or [B, S, G, D/2] where each of G groups of heads has its
    tokens in an order of its own (`parallel.tensor.ring_rows`)."""
    x1 = x[..., 0::2]
    x2 = x[..., 1::2]
    if cos.ndim == 3:
        cos, sin = cos[:, :, None], sin[:, :, None]
    else:
        cos, sin = (jnp.repeat(t, x.shape[2] // t.shape[2], axis=2)
                    for t in (cos, sin))
    out1 = x1 * cos - x2 * sin
    out2 = x1 * sin + x2 * cos
    return jnp.stack([out1, out2], axis=-1).reshape(x.shape)



# --- fused-kernel layout under tensor parallelism ---------------------------
#
# The checkpoint orders a fused qkv kernel's columns (3, heads, head_dim),
# `linear1`'s as [q | k | v | mlp] and `linear2`'s rows as [attn | mlp]: a
# contiguous 1/T of those columns is not 1/T of the heads. Placement on a
# `tensor=T` mesh (pipelines/flux.py `_place`) permutes them ONCE into T
# groups, group g holding heads g*H/T ... (g+1)*H/T - 1 (q, k and v of them)
# and the matching 1/T of the MLP's hidden units, so that a plain
# `P(None, "tensor")` gives every chip whole heads. The blocks undo it in
# their split (`head_groups`); with `head_groups=1` the order is the
# checkpoint's, reshape for reshape.


def head_groups_for(config: "FluxConfig", tensor: int) -> int:
    """How many groups the fused kernels are laid out in on a `tensor`-way
    mesh: `tensor` when heads and MLP width divide, else 1 (the attention
    kernels then stay whole on every chip)."""
    mlp_dim = int(config.hidden_size * config.mlp_ratio)
    if tensor > 1 and config.num_heads % tensor == 0 \
            and mlp_dim % tensor == 0:
        return tensor
    return 1


def _qkv_order(heads: int, head_dim: int, groups: int):
    """Old column of each new column of a qkv kernel in group order, as
    [groups, 3 * heads/groups * head_dim]."""
    import numpy as np

    old = np.arange(3 * heads * head_dim).reshape(
        3, groups, heads // groups, head_dim)
    return old.transpose(1, 0, 2, 3).reshape(groups, -1)


def grouped_layout(flux_params: dict, config: "FluxConfig", groups: int,
                   inverse: bool = False) -> dict:
    """The transformer's tree with its fused kernels (and their biases) in
    group order, from the checkpoint's order; `inverse=True` goes back.
    Leaf by leaf on whatever device each leaf lives; other leaves are
    passed through untouched. `groups=1` is the identity."""
    import numpy as np

    if groups <= 1:
        return flux_params
    h, hd = config.num_heads, config.head_dim
    mlp_dim = int(config.hidden_size * config.mlp_ratio)
    qkv_groups = _qkv_order(h, hd, groups)
    qkv = qkv_groups.reshape(-1)
    mlp = 3 * h * hd + np.arange(mlp_dim).reshape(groups, -1)
    linear1 = np.concatenate([qkv_groups, mlp], axis=1).reshape(-1)
    linear2 = np.concatenate(
        [np.arange(h * hd).reshape(groups, -1), mlp - 2 * h * hd],
        axis=1).reshape(-1)
    if inverse:
        qkv, linear1, linear2 = (np.argsort(o) for o in (qkv, linear1, linear2))

    def columns(dense, order):
        return {k: v[..., order] for k, v in dense.items()}

    out = dict(flux_params)
    for name, block in flux_params.items():
        if name.startswith("double_blocks_"):
            block = dict(block)
            for stream in ("img", "txt"):
                key = f"{stream}_attn_qkv"
                block[key] = columns(block[key], qkv)
            out[name] = block
        elif name.startswith("single_blocks_"):
            block = dict(block)
            block["linear1"] = columns(block["linear1"], linear1)
            block["linear2"] = {
                k: (v[linear2] if k == "kernel" else v)
                for k, v in block["linear2"].items()}
            out[name] = block
    return out


def _whole(x):
    """Under a `mesh_scope`: `x` whole on every tensor shard (rows stay on
    `data` where they divide). The modulation kernels are column-parallel;
    their output, a few kilobytes a row, is gathered HERE, once — left to
    the partitioner, the six-way split travels by collective-permute and
    the residual stream it scales ends up sharded by features, with an
    activation-sized all-gather ahead of every matmul."""
    from ..ops.platform import active_mesh, batch_axis

    mesh = active_mesh()
    if mesh is None:
        return x
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = P(batch_axis(mesh, x.shape[0]), *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


class _ProductDense(DeclaredParams, nn.Module):
    """`nn.Dense`, its parameters under the same names, with `product` (one
    of `parallel.tensor`'s pair) as its matmul."""

    features: int
    product: Callable
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.linear.default_kernel_init,
                            (x.shape[-1], self.features))
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (self.features,))
        return self.product(*nn.dtypes.promote_dtype(
            x, kernel, bias, dtype=self.dtype))


def _parallel_dense(mesh, dtype, product, features: int, name: str):
    """A block's column-parallel (`tensor.gather_matmul`) or row-parallel
    (`tensor.matmul_scatter`) Dense. On the `mesh` that
    `tensor.overlap_mesh` found, the product itself: the residual stream
    between a row-parallel kernel and the next column-parallel one is then
    sharded by tokens, and with it the gate, the LayerNorm and the
    modulation; between a column-parallel kernel and the next row-parallel
    one each chip has its tokens in its ring order. With `mesh` None a
    plain Dense, behind whose row-parallel kernel XLA puts one all-reduce
    if the weights are sharded at all."""
    scope = active_mesh()
    if scope is not None and scope.shape[tensor.TENSOR_AXIS] > 1:
        KERNEL_TRACES.inc(op="tensor_matmul",
                          path="reduced" if mesh is None else "overlapped")
    if mesh is None:
        return Dense(features, dtype=dtype, name=name)
    return _ProductDense(features, functools.partial(product, mesh),
                         dtype=dtype, name=name)


def _split_qkv(out, heads: int, head_dim: int, groups: int):
    """[B, S, (groups, 3, heads/groups, head_dim)] -> q, k, v [B, S, H, D].
    Splitting the (tensor-sharded) column axis by its leading `groups`
    keeps every chip's heads on that chip."""
    b, s = out.shape[:2]
    out = out.reshape(b, s, groups, 3, heads // groups, head_dim)
    return tuple(out[:, :, :, i].reshape(b, s, heads, head_dim)
                 for i in range(3))


class QKNorm(DeclaredParams, nn.Module):
    """Per-head RMS normalization of q and k (Flux stabilization)."""

    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, q, k):
        def rms(x, name):
            scale = self.param(name, nn.initializers.ones, (x.shape[-1],))
            var = jnp.mean(
                jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True
            )
            return ((x * (var + 1e-6) ** -0.5) * scale).astype(self.dtype)

        return rms(q, "query_scale"), rms(k, "key_scale")


class MLPEmbedder(nn.Module):
    hidden: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = Dense(self.hidden, dtype=self.dtype, name="in_layer")(x)
        x = nn.silu(x)
        return Dense(self.hidden, dtype=self.dtype, name="out_layer")(x)


class Modulation(nn.Module):
    """vec -> (shift, scale, gate) x n chunks."""

    hidden: int
    n: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, vec):
        out = _whole(Dense(
            self.n * self.hidden, dtype=self.dtype, name="lin")(nn.silu(vec)))
        return jnp.split(out[:, None, :], self.n, axis=-1)


def _attention(q, k, v, cos, sin):
    """Joint attention with RoPE; [B, S, H, D] -> [B, S, H*D]."""
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    from ..ops import dot_product_attention

    out = dot_product_attention(q, k, v)
    b, s, h, d = out.shape
    return out.reshape(b, s, h * d)


class DoubleStreamBlock(nn.Module):
    config: FluxConfig
    dtype: jnp.dtype = jnp.float32
    head_groups: int = 1  # layout of the fused kernels (grouped_layout)

    @nn.compact
    def __call__(self, img, txt, vec, cos, sin):
        cfg = self.config
        h, hd = cfg.num_heads, cfg.head_dim
        mlp_dim = int(cfg.hidden_size * cfg.mlp_ratio)
        mesh = tensor.overlap_mesh(self.head_groups, img.shape[1], txt.shape[1])
        dense = functools.partial(_parallel_dense, mesh, self.dtype)
        if mesh is not None:  # q, k, v come in each chip's ring order
            cos, sin = (tensor.ring_rows(mesh, t, (txt.shape[1], img.shape[1]))
                        for t in (cos, sin))

        def stream(name):
            mod = Modulation(cfg.hidden_size, 6, dtype=self.dtype,
                             name=f"{name}_mod")
            return mod

        img_mod = stream("img")(vec)
        txt_mod = stream("txt")(vec)

        def norm(x):
            return LayerNorm(
                use_bias=False, use_scale=False, epsilon=1e-6, dtype=self.dtype
            )(x)

        def qkv(x, name):
            out = dense(tensor.gather_matmul, 3 * h * hd,
                        f"{name}_attn_qkv")(x)
            q, k, v = _split_qkv(out, h, hd, self.head_groups)
            q, k = QKNorm(dtype=self.dtype, name=f"{name}_attn_norm")(q, k)
            return q, k, v

        # modulated pre-norm + qkv per stream
        img_n = norm(img) * (1 + img_mod[1]) + img_mod[0]
        txt_n = norm(txt) * (1 + txt_mod[1]) + txt_mod[0]
        iq, ik, iv = qkv(img_n, "img")
        tq, tk, tv = qkv(txt_n, "txt")

        # joint attention: text tokens first (matches ids layout)
        q = jnp.concatenate([tq, iq], axis=1)
        k = jnp.concatenate([tk, ik], axis=1)
        v = jnp.concatenate([tv, iv], axis=1)
        attn = _attention(q, k, v, cos, sin)
        txt_len = txt.shape[1]
        txt_attn, img_attn = attn[:, :txt_len], attn[:, txt_len:]

        img = img + img_mod[2] * dense(
            tensor.matmul_scatter, cfg.hidden_size, "img_attn_proj")(img_attn)
        txt = txt + txt_mod[2] * dense(
            tensor.matmul_scatter, cfg.hidden_size, "txt_attn_proj")(txt_attn)

        def mlp(x, mod_shift, mod_scale, mod_gate, name):
            y = norm(x) * (1 + mod_scale) + mod_shift
            y = dense(tensor.gather_matmul, mlp_dim, f"{name}_mlp_0")(y)
            y = nn.gelu(y, approximate=True)
            y = dense(tensor.matmul_scatter, cfg.hidden_size,
                      f"{name}_mlp_2")(y)
            return x + mod_gate * y

        img = mlp(img, img_mod[3], img_mod[4], img_mod[5], "img")
        txt = mlp(txt, txt_mod[3], txt_mod[4], txt_mod[5], "txt")
        return img, txt


class SingleStreamBlock(nn.Module):
    config: FluxConfig
    dtype: jnp.dtype = jnp.float32
    head_groups: int = 1  # layout of linear1 / linear2 (grouped_layout)

    @nn.compact
    def __call__(self, x, vec, cos, sin):
        cfg = self.config
        h, hd = cfg.num_heads, cfg.head_dim
        mlp_dim = int(cfg.hidden_size * cfg.mlp_ratio)
        shift, scale, gate = Modulation(
            cfg.hidden_size, 3, dtype=self.dtype, name="modulation"
        )(vec)
        y = LayerNorm(
            use_bias=False, use_scale=False, epsilon=1e-6, dtype=self.dtype
        )(x)
        y = y * (1 + scale) + shift
        b, s, _ = y.shape
        mesh = tensor.overlap_mesh(self.head_groups, s)
        dense = functools.partial(_parallel_dense, mesh, self.dtype)
        if mesh is not None:  # q, k, v come in each chip's ring order
            cos, sin = (tensor.ring_rows(mesh, t, (s,)) for t in (cos, sin))
        fused = dense(tensor.gather_matmul, 3 * h * hd + mlp_dim,
                      "linear1")(y)
        # columns: `head_groups` groups of [q | k | v | mlp], each a 1/groups
        # of the heads and of the MLP's hidden units
        g = self.head_groups
        qkv_part, mlp_part = jnp.split(
            fused.reshape(b, s, g, -1), [3 * h * hd // g], axis=-1)
        q, k, v = _split_qkv(qkv_part.reshape(b, s, -1), h, hd, g)
        q, k = QKNorm(dtype=self.dtype, name="norm")(q, k)
        attn = _attention(q, k, v, cos, sin)
        # rows of linear2 in the same groups: [attn | mlp] of each
        out = dense(tensor.matmul_scatter, cfg.hidden_size, "linear2")(
            jnp.concatenate(
                [attn.reshape(b, s, g, -1),
                 nn.gelu(mlp_part, approximate=True)], axis=-1,
            ).reshape(b, s, -1)
        )
        return x + gate * out


class FluxTransformer(nn.Module):
    config: FluxConfig
    dtype: jnp.dtype = jnp.float32
    head_groups: int = 1  # layout of the blocks' fused kernels

    @nn.compact
    def __call__(self, img, img_ids, txt, txt_ids, timesteps, pooled,
                 guidance=None):
        """img [B, S_img, in_channels] patchified latents; txt [B, S_txt,
        context_dim]; ids [B, S, 3]; -> [B, S_img, in_channels]."""
        cfg = self.config
        img = Dense(cfg.hidden_size, dtype=self.dtype, name="img_in")(img)
        txt = Dense(cfg.hidden_size, dtype=self.dtype, name="txt_in")(txt)

        vec = MLPEmbedder(cfg.hidden_size, dtype=self.dtype, name="time_in")(
            timestep_embedding(timesteps, 256).astype(self.dtype)
        )
        if cfg.guidance_embed:
            g = guidance if guidance is not None else jnp.ones_like(timesteps)
            vec = vec + MLPEmbedder(
                cfg.hidden_size, dtype=self.dtype, name="guidance_in"
            )(timestep_embedding(g, 256).astype(self.dtype))
        vec = vec + MLPEmbedder(
            cfg.hidden_size, dtype=self.dtype, name="vector_in"
        )(pooled.astype(self.dtype))

        ids = jnp.concatenate([txt_ids, img_ids], axis=1)
        cos, sin = rope_frequencies(ids, cfg.axes_dims_rope, cfg.theta)
        cos = cos.astype(self.dtype)
        sin = sin.astype(self.dtype)

        for i in range(cfg.depth_double):
            img, txt = DoubleStreamBlock(
                cfg, dtype=self.dtype, head_groups=self.head_groups,
                name=f"double_blocks_{i}"
            )(img, txt, vec, cos, sin)

        # the blocks leave both streams sharded by tokens where their
        # matmuls overlap their collectives: each chip then keeps its chunk
        # of `txt` beside its chunk of `img`, and RoPE follows that order
        mesh = tensor.overlap_mesh(self.head_groups, txt.shape[1],
                                   img.shape[1])
        if mesh is None:
            x = jnp.concatenate([txt, img], axis=1)
        else:
            x = tensor.join_token_shards(mesh, txt, img)
            order = tensor.token_shard_order(
                mesh.shape[tensor.TENSOR_AXIS], txt.shape[1], img.shape[1])
            cos, sin = cos[:, order], sin[:, order]
        for i in range(cfg.depth_single):
            x = SingleStreamBlock(
                cfg, dtype=self.dtype, head_groups=self.head_groups,
                name=f"single_blocks_{i}"
            )(x, vec, cos, sin)
        if mesh is None:
            x = x[:, txt.shape[1]:]
        else:
            x = tensor.last_token_shards(mesh, x, img.shape[1])

        shift, scale = jnp.split(
            _whole(Dense(2 * cfg.hidden_size, dtype=self.dtype,
                         name="final_layer_mod")(nn.silu(vec)))[:, None, :],
            2, axis=-1,
        )
        x = LayerNorm(
            use_bias=False, use_scale=False, epsilon=1e-6, dtype=self.dtype
        )(x)
        x = x * (1 + scale) + shift
        out = Dense(
            cfg.in_channels, dtype=self.dtype, name="final_layer_linear"
        )(x)
        # from each chip's quarter of the image tokens: kilobytes
        return out if mesh is None else _whole(out)


class FluxHead(nn.Module):
    """The pre-block section of FluxTransformer as a standalone module.

    Param names (img_in/txt_in/time_in/guidance_in/vector_in) match the
    monolith exactly, so the weight-streaming runner applies it against
    the SAME converted tree (a subset of params['flux']) — parity between
    the streamed and resident paths is asserted in tests/test_flux_stream.
    """

    config: FluxConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, img, txt, timesteps, pooled, guidance=None):
        cfg = self.config
        img = Dense(cfg.hidden_size, dtype=self.dtype, name="img_in")(img)
        txt = Dense(cfg.hidden_size, dtype=self.dtype, name="txt_in")(txt)
        vec = MLPEmbedder(cfg.hidden_size, dtype=self.dtype, name="time_in")(
            timestep_embedding(timesteps, 256).astype(self.dtype)
        )
        if cfg.guidance_embed:
            g = guidance if guidance is not None else jnp.ones_like(timesteps)
            vec = vec + MLPEmbedder(
                cfg.hidden_size, dtype=self.dtype, name="guidance_in"
            )(timestep_embedding(g, 256).astype(self.dtype))
        vec = vec + MLPEmbedder(
            cfg.hidden_size, dtype=self.dtype, name="vector_in"
        )(pooled.astype(self.dtype))
        return img, txt, vec


class FluxFinal(nn.Module):
    """The post-block section of FluxTransformer (modulated output proj),
    standalone for the streaming runner; names match the monolith."""

    config: FluxConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, vec):
        cfg = self.config
        shift, scale = jnp.split(
            _whole(Dense(2 * cfg.hidden_size, dtype=self.dtype,
                         name="final_layer_mod")(nn.silu(vec)))[:, None, :],
            2, axis=-1,
        )
        x = LayerNorm(
            use_bias=False, use_scale=False, epsilon=1e-6, dtype=self.dtype
        )(x)
        x = x * (1 + scale) + shift
        return Dense(
            cfg.in_channels, dtype=self.dtype, name="final_layer_linear"
        )(x)


# params['flux'] keys consumed by FluxHead / FluxFinal (the rest are the
# double_blocks_i / single_blocks_i trees the streaming runner pages in)
HEAD_KEYS = ("img_in", "txt_in", "time_in", "guidance_in", "vector_in")
FINAL_KEYS = ("final_layer_mod", "final_layer_linear")


def patchify(latents):
    """[B, H, W, C] -> ([B, H/2*W/2, 4C], ids [B, S, 3])."""
    b, h, w, c = latents.shape
    x = latents.reshape(b, h // 2, 2, w // 2, 2, c)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, (h // 2) * (w // 2), 4 * c)
    ys, xs = jnp.meshgrid(
        jnp.arange(h // 2), jnp.arange(w // 2), indexing="ij"
    )
    ids = jnp.stack(
        [jnp.zeros_like(ys), ys, xs], axis=-1
    ).reshape(1, -1, 3)
    return x, jnp.broadcast_to(ids, (b, ids.shape[1], 3)).astype(jnp.int32)


def unpatchify(x, h: int, w: int):
    """[B, H/2*W/2, 4C] -> [B, H, W, C]."""
    b, s, c4 = x.shape
    c = c4 // 4
    x = x.reshape(b, h // 2, w // 2, 2, 2, c)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, h, w, c)
    return x
