"""Mesh construction and batch-sharding helpers.

Axis convention (scaling-book style): ``data`` shards the image batch /
CFG pair, ``tensor`` shards attention heads + MLP inner dims, ``seq``
shards sequence blocks for ring attention. Any axis may be size 1; the
same pipeline code runs single-chip and multi-chip by changing only the
mesh shape.
"""

from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
TENSOR_AXIS = "tensor"
SEQ_AXIS = "seq"


def make_mesh(
    devices: list | None = None,
    data: int | None = None,
    tensor: int = 1,
    seq: int = 1,
) -> Mesh:
    """Mesh over `devices` (default: all local) as [data, tensor, seq].

    `data` defaults to whatever is left after tensor/seq. Device order is
    kept as given — callers that care about ICI adjacency (ring attention)
    should pass devices in torus order; `jax.devices()` already is for a
    single slice.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if data is None:
        if n % (tensor * seq):
            raise ValueError(f"{n} devices not divisible by tensor*seq={tensor * seq}")
        data = n // (tensor * seq)
    if data * tensor * seq != n:
        raise ValueError(
            f"mesh {data}x{tensor}x{seq} != {n} devices"
        )
    arr = np.asarray(devices).reshape(data, tensor, seq)
    return Mesh(arr, (DATA_AXIS, TENSOR_AXIS, SEQ_AXIS))


def host_local_mesh(**kw) -> Mesh:
    """Mesh over this process's addressable devices (multi-host: one worker
    process per host serves jobs on its local chips; cross-host scale-out
    stays at the hive-job level, matching the reference's topology)."""
    return make_mesh(jax.local_devices(), **kw)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    """Shard dim 0 (batch) over `data`, replicate the rest."""
    return NamedSharding(mesh, P(DATA_AXIS, *([None] * (ndim - 1))))


def pad_batch(n: int, parts: int) -> int:
    """Batch size padded up so it divides over `parts` devices."""
    return math.ceil(n / parts) * parts


def shard_batch(mesh: Mesh, tree):
    """Device_put a host pytree with dim-0 sharded over `data`.

    Arrays whose batch dim doesn't divide the data axis must be padded by
    the caller first (`pad_batch`); scalars/rank-0 leaves are replicated.
    """

    def place(x):
        x = np.asarray(x)
        if x.ndim == 0:
            return jax.device_put(x, replicated(mesh))
        return jax.device_put(x, batch_sharding(mesh, x.ndim))

    return jax.tree_util.tree_map(place, tree)
