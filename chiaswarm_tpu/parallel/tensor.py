"""Tensor-parallel partition rules for the diffusion model families.

Megatron-style sharding expressed as jax PartitionSpecs over flax param
trees: attention QKV + MLP-in are column-parallel (shard the output
feature dim over ``tensor``), attention-out + MLP-out are row-parallel
(shard the input dim); XLA inserts the psum where the row-parallel matmul
contracts over the sharded dim. Convolutions and norms are small — they
stay replicated. The reference scales big models by CPU offload instead
(swarm/diffusion/diffusion_func.py:134-146); on TPU we shard.
"""

from __future__ import annotations

import re

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

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
