"""Tensor-parallel partition rules for the diffusion model families, and
the two products of a column / row-parallel pair.

Megatron-style sharding expressed as jax PartitionSpecs over flax param
trees: attention QKV + MLP-in are column-parallel (shard the output
feature dim over ``tensor``), attention-out + MLP-out are row-parallel
(shard the input dim). Convolutions and norms are small — they stay
replicated. The reference scales big models by CPU offload instead
(swarm/diffusion/diffusion_func.py:134-146); on TPU we shard.

The sum a row-parallel matmul owes takes one of two forms. Left to XLA it
is one blocking all-reduce of the whole activation between two matmuls
(T5, the UNets). Where a model keeps its residual stream sharded by
tokens between the pair (models/flux.py, when `overlap_mesh` finds the
shapes for it), the pair is `matmul_scatter` and `gather_matmul`: the
reduce-scatter and the all-gather that make up that all-reduce, each
broken into ring steps that travel while the next chunk of the same
matmul runs.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.platform import active_mesh, batch_axis
from .mesh import TENSOR_AXIS


def column_parallel() -> P:
    """Kernel [in, out] sharded on out -> each device computes a head/ffn slice."""
    return P(None, TENSOR_AXIS)


def row_parallel() -> P:
    """Kernel [in, out] sharded on in -> psum over tensor axis after matmul."""
    return P(TENSOR_AXIS, None)


# (regex over "/"-joined param path) -> spec, first match wins.
# Matches the module names in models/layers.py Transformer2DModel /
# FeedForward and models/clip.py CLIPAttention.
_UNET_RULES: tuple[tuple[str, P], ...] = (
    (r".*(to_q|to_k|to_v|q_proj|k_proj|v_proj)/kernel$", column_parallel()),
    (r".*(to_out_0|out_proj)/kernel$", row_parallel()),
    (r".*net_0_proj/kernel$", column_parallel()),  # geglu in (gate+value)
    (r".*net_2/kernel$", row_parallel()),  # ffn out
    (r".*fc1/kernel$", column_parallel()),  # CLIP MLP in
    (r".*fc2/kernel$", row_parallel()),  # CLIP MLP out
    # biases (incl. row-parallel layers') fall through to the replicated
    # default in _spec_for — added once after the psum
)


def unet_partition_rules():
    return _UNET_RULES


def _column(pattern: str) -> tuple[tuple[str, P], ...]:
    """A column-parallel Dense: its kernel's columns and its bias."""
    return ((rf".*{pattern}/kernel$", column_parallel()),
            (rf".*{pattern}/bias$", P(TENSOR_AXIS)))


def flux_partition_rules(head_aligned: bool = True):
    """The MMDiT (models/flux.py), Megatron-style. Column-parallel: the
    fused qkv kernels and `linear1`, the MLPs' first layer, and the
    modulation kernels (a quarter of the transformer's parameters; their
    output, a few kilobytes a row, is gathered). Row-parallel, one
    all-reduce each: `*_attn_proj`, `*_mlp_2`, `linear2`. `img_in`,
    `txt_in`, the embedders, the qk-norm scales and the last projection
    stay whole on every chip.

    The fused kernels' columns must be in group order
    (`models.flux.grouped_layout`) for a contiguous share to be whole heads;
    with `head_aligned=False` (a head count the tensor axis does not
    divide) the attention-side kernels stay replicated."""
    rules = (
        *_column(r"(img|txt)_mlp_0"),
        (r".*(img|txt)_mlp_2/kernel$", row_parallel()),
        *_column(r"((img|txt)_mod|modulation)/lin"),
        *_column(r"final_layer_mod"),
    )
    if head_aligned:
        rules += (
            *_column(r"(img|txt)_attn_qkv"),
            (r".*(img|txt)_attn_proj/kernel$", row_parallel()),
            *_column(r"single_blocks_\d+/linear1"),
            (r".*single_blocks_\d+/linear2/kernel$", row_parallel()),
        )
    return rules


def t5_partition_rules():
    """The T5 encoder (models/t5.py): q / k / v and the gated FFN's two
    input kernels by columns (heads are contiguous there), `o` and `wo` by
    rows. The embedding table, the norms and the relative-position table
    stay whole."""
    return (
        (r".*attention/(q|k|v)/kernel$", column_parallel()),
        (r".*attention/o/kernel$", row_parallel()),
        (r".*wi_[01]/kernel$", column_parallel()),
        (r".*wo/kernel$", row_parallel()),
    )


def _spec_for(path: str, rules) -> P:
    for pattern, spec in rules:
        if re.fullmatch(pattern, path):
            return spec
    return P()


def partition_spec_tree(params, rules=_UNET_RULES):
    """Map a param pytree to PartitionSpecs by path."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]

    def path_str(kp):
        return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)

    specs = {path_str(kp): _spec_for(path_str(kp), rules) for kp, _ in flat}

    def lookup(kp, leaf):
        spec = specs[path_str(kp)]
        # never shard a dim the leaf doesn't have or that doesn't divide
        if len(spec) > leaf.ndim:
            return P()
        return spec

    return jax.tree_util.tree_map_with_path(lookup, params)


def sharding_tree(mesh: Mesh, params, rules=_UNET_RULES):
    """The tree of `NamedSharding`s the rules give `params` (arrays or
    shapes) on `mesh`.

    A leaf whose dim doesn't divide the mesh axis falls back to replication
    (e.g. head dims not divisible by the tensor axis) instead of erroring
    deep inside device_put.
    """
    specs = partition_spec_tree(params, rules)

    def sharding(x, spec):
        for d, axis in enumerate(spec):
            if axis is not None and x.shape[d] % mesh.shape[axis] != 0:
                spec = P()
                break
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map(sharding, params, specs)


def shard_params(mesh: Mesh, params, rules=_UNET_RULES):
    """Place a param tree on the mesh per the partition rules."""
    return jax.device_put(params, sharding_tree(mesh, params, rules))


def largest_device_bytes(params) -> int:
    """Bytes of a placed tree on the chip that holds most of it (this
    process's shards): a tree whose rules fell through to replicated reads
    its whole size here."""
    held: dict = {}
    for leaf in jax.tree_util.tree_leaves(params):
        for shard in getattr(leaf, "addressable_shards", ()):
            held[shard.device] = held.get(shard.device, 0) + shard.data.nbytes
    return max(held.values(), default=0)


# --- the pair's products with their collectives under the matmuls -----------
#
# Between `gather_matmul` and `matmul_scatter` every chip has every token,
# for its own columns, in ITS ring order: its own chunk first, then the
# chunk of the chip one place along the ring, and so on round (`ring_chunks`
# has the table). Each product's pieces then sit where the ring step that
# made or needs them finds them, at offsets known when the program is
# traced, so a piece's matmul writes it in place and nothing is copied into
# order. What lies between the pair must take tokens one by one, or be told
# the order: attention sees q, k and v in one order a chip, and RoPE's
# tables are put in it by `ring_rows`.


def overlap_mesh(head_groups: int, *token_counts: int) -> Mesh | None:
    """The mesh on which a block's column / row-parallel pairs run as
    `gather_matmul` / `matmul_scatter`, or None for plain matmuls (and
    XLA's all-reduce, if a mesh is active at all). Decided by what a trace
    can see: an active `mesh_scope` whose `tensor` axis is T > 1, kernels
    that really are sharded by heads (`head_groups` > 1), and token counts
    that split into T chunks."""
    mesh = active_mesh()
    if mesh is None or head_groups <= 1:
        return None
    parts = mesh.shape[TENSOR_AXIS]
    if parts == 1 or any(n % parts for n in token_counts):
        return None
    return mesh


def _ring_order(mesh: Mesh) -> list[int]:
    """The tensor axis' indices in an order in which neighbours are
    neighbours on the chips' interconnect, as far as the devices say where
    they are (`coords`; a v5e 2x2 lists its chips row by row, so 0-1-2-3
    crosses a diagonal twice and 0-1-3-2 never): nearest first, from index
    0. Devices that say nothing keep their order."""
    devices = np.moveaxis(
        mesh.devices, mesh.axis_names.index(TENSOR_AXIS), 0
    ).reshape(mesh.shape[TENSOR_AXIS], -1)[:, 0]
    coords = [getattr(d, "coords", None) for d in devices]
    if any(c is None for c in coords):
        return list(range(len(devices)))
    order = [0]
    while len(order) < len(devices):
        here = coords[order[-1]]
        order.append(min(
            (i for i in range(len(devices)) if i not in order),
            key=lambda i: sum(abs(a - b) for a, b in zip(here, coords[i]))))
    return order


def ring_chunks(mesh: Mesh) -> np.ndarray:
    """[T, T]: row i is the order in which the chip at tensor index i holds
    the T token chunks between the pair (chunk c is the tokens that chip c
    owns while they are sharded)."""
    order = _ring_order(mesh)
    place = np.argsort(order)
    return np.asarray([[order[(place[i] + j) % len(order)]
                        for j in range(len(order))]
                       for i in range(len(order))])


def _send(mesh: Mesh, x, way: int):
    """`x` to the next (`way` +1) or the previous (-1) chip of the ring."""
    order = _ring_order(mesh)
    return jax.lax.ppermute(x, TENSOR_AXIS, [
        (order[p], order[(p + way) % len(order)])
        for p in range(len(order))])


# a chunk this long travels in two halves, one each way round the ring, so
# that both of a chip's links carry as under XLA's own all-reduce; a shorter
# one (the 512 T5 tokens: 128 a chip) goes one way whole, in half as many
# matmuls, each still a full MXU tile of rows
_TWO_WAY_TOKENS = 256


def _pieces(chunk: int):
    """(way round the ring, offset in a chunk, tokens) of the pieces a
    chunk travels in: its halves forward (+1) and backward (-1), or all of
    it forward."""
    if chunk < _TWO_WAY_TOKENS:
        return ((+1, 0, chunk),)
    return (+1, 0, chunk // 2), (-1, chunk // 2, chunk - chunk // 2)


def _gather_matmul(mesh, x, w, b):
    """One chip's part: x [B, S/T, K] its tokens, w [K, N/T] and b [N/T]
    its columns -> [B, S, N/T] in ring order. While a piece multiplies it
    is already on its way to the next chip; after T - 1 hops every chip
    has multiplied every chunk."""
    parts = mesh.shape[TENSOR_AXIS]
    pieces = _pieces(x.shape[1])
    held = [x[:, at:at + n] for _, at, n in pieces]
    # by places along the ring: the chip's own chunk, then what arrives
    chunks = [[x @ w + b]] + [[None] * len(pieces) for _ in range(1, parts)]
    for step in range(1, parts):
        for i, (way, _, _) in enumerate(pieces):
            held[i] = _send(mesh, held[i], way)
            # after `step` hops `way`: from that many places against it
            chunks[-way * step % parts][i] = held[i] @ w + b
    return jnp.concatenate([made for chunk in chunks for made in chunk],
                           axis=1)


def _matmul_scatter(mesh, x, w, b):
    """One chip's part: x [B, S, K/T] its features of every token in ring
    order, w [K/T, N] its rows, b [N] -> [B, S/T, N], the finished sum for
    its own tokens. A chunk's sum starts one chip past its owner and goes
    round the other T - 1, each adding its partial product to what arrives
    (float32 products, rounded to the activations' dtype for the wire as
    an all-reduce's are) while the previous sum is still travelling. The
    order of a token's T terms is fixed by the ring, whatever else is in
    the batch."""
    parts = mesh.shape[TENSOR_AXIS]
    chunk = x.shape[1] // parts
    pieces = _pieces(chunk)

    def product(place, at, n):
        start = place % parts * chunk + at
        return jnp.matmul(x[:, start:start + n], w,
                          preferred_element_type=jnp.float32)

    arrived = [0.0] * len(pieces)
    for step in range(1, parts):
        for i, (way, at, n) in enumerate(pieces):
            # the sum passing now is owed to the chip `step` places against
            # `way`: T - 1 hops from where it started, it is home
            part = product(-way * step, at, n) + arrived[i]
            arrived[i] = _send(mesh, part.astype(x.dtype), way)
    own = product(0, 0, chunk) + jnp.concatenate(arrived, axis=1) + b
    return own.astype(x.dtype)


def _ring_rows(mesh, lengths, x):
    """One chip's part: each stream of x [B, sum(lengths), C] with its
    chunks in this chip's ring order, under a new axis for the chips."""
    parts = mesh.shape[TENSOR_AXIS]
    chunks = jnp.asarray(ring_chunks(mesh))[jax.lax.axis_index(TENSOR_AXIS)]
    streams = jnp.split(x, np.cumsum(lengths)[:-1], axis=1)
    return jnp.concatenate([
        jnp.take(s.reshape(s.shape[0], parts, -1, s.shape[-1]), chunks,
                 axis=1).reshape(s.shape)
        for s in streams], axis=1)[:, :, None]


def _token_sharded(mesh: Mesh, rows: int) -> P:
    return P(batch_axis(mesh, rows), TENSOR_AXIS, None)


def _feature_sharded(mesh: Mesh, rows: int) -> P:
    return P(batch_axis(mesh, rows), None, TENSOR_AXIS)


def _on_each_chip(mesh, fn, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# jitted by mesh and shapes: a model of 57 blocks traces each product once


@functools.partial(jax.jit, static_argnums=0)
def gather_matmul(mesh: Mesh, x, w, b):
    """`x @ w + b` for a column-parallel `w` [K, N], `b` [N] and an `x`
    [B, S, K] sharded by tokens: the all-gather of `x` goes round the ring
    under the matmul. Out: [B, S, N] sharded by columns, every token on
    every chip, each chip's in its ring order."""
    rows = x.shape[0]
    return _on_each_chip(
        mesh, functools.partial(_gather_matmul, mesh),
        (_token_sharded(mesh, rows), column_parallel(), P(TENSOR_AXIS)),
        _feature_sharded(mesh, rows))(x, w, b)


@functools.partial(jax.jit, static_argnums=0)
def matmul_scatter(mesh: Mesh, x, w, b):
    """`x @ w + b` for a row-parallel `w` [K, N], a whole `b` [N] and an
    `x` [B, S, K] sharded by features, each chip's tokens in its ring
    order: the reduce-scatter of the chips' partial products goes round
    the ring under the matmul. Out: [B, S, N] sharded by tokens, in
    order."""
    rows = x.shape[0]
    return _on_each_chip(
        mesh, functools.partial(_matmul_scatter, mesh),
        (_feature_sharded(mesh, rows), row_parallel(), P()),
        _token_sharded(mesh, rows))(x, w, b)


@functools.partial(jax.jit, static_argnums=(0, 2))
def ring_rows(mesh: Mesh, x, lengths: tuple[int, ...]):
    """A whole `x` [B, S, C] that goes with the tokens between the pair
    (RoPE's tables), in every chip's ring order: [B, S, T, C], sharded
    over T. `lengths` are the streams that were gathered one by one and
    laid end to end."""
    rows = x.shape[0]
    data = batch_axis(mesh, rows)
    return _on_each_chip(
        mesh, functools.partial(_ring_rows, mesh, lengths),
        (P(data, None, None),), P(data, None, TENSOR_AXIS, None))(x)


def token_shard_order(parts: int, *stream_lengths: int):
    """Where each token of `join_token_shards`' result comes from in the
    plain concatenation of the streams: chip by chip, its chunk of each
    stream in turn."""
    starts = np.cumsum((0, *stream_lengths[:-1]))
    return np.concatenate([
        start + np.arange(n).reshape(parts, -1)[chip]
        for chip in range(parts)
        for start, n in zip(starts, stream_lengths)])


def join_token_shards(mesh: Mesh, *streams):
    """Token-sharded streams [B, S_i, C] as one token-sharded stream with
    nothing moved: each chip's chunks side by side, so the joint order is
    `token_shard_order`'s and not the concatenation's."""
    spec = _token_sharded(mesh, streams[0].shape[0])
    return _on_each_chip(
        mesh, lambda *chunks: jnp.concatenate(chunks, axis=1),
        (spec,) * len(streams), spec)(*streams)


def last_token_shards(mesh: Mesh, x, length: int):
    """The last stream, `length` tokens, of a `join_token_shards` result:
    token-sharded and in its own order again."""
    keep = length // mesh.shape[TENSOR_AXIS]
    spec = _token_sharded(mesh, x.shape[0])
    return _on_each_chip(
        mesh, lambda chunk: chunk[:, chunk.shape[1] - keep:], (spec,), spec)(x)
