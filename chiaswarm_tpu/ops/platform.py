"""What a kernel choice may depend on besides shape: the platform and the
mesh the program is being traced for, plus the record of which way each
choice went.

Kernel dispatch (ops/attention.py, ops/group_norm.py) is a trace-time
branch on ``trace_platform() == "tpu"`` and the operand shapes, nothing
else: no environment toggle, no try-and-catch. Every branch taken bumps
``swarm_kernel_traces_total{op, path}`` — once per traced call site, so a
program that compiled for the TPU but traced the reference path shows up
in the worker's ``/metrics`` (chip_smoke.py fails on it).
"""

from __future__ import annotations

import contextlib
import threading

import jax

from .. import telemetry

KERNEL_TRACES = telemetry.counter(
    "swarm_kernel_traces_total",
    "Kernel dispatch decisions taken while tracing, by op (attention | "
    "group_norm | expert_matmul | latent_attention | tensor_matmul | "
    "gated_delta_step | ssd_step | sampler | lightning_indexer | "
    "index_select | latent_expansion | sparse_latent_attention) and path (flash | banded | wide_key | "
    "ring | fused | grouped | absorbed | overlapped | reduced | pallas | "
    "einsum | gathered | reference); a traced latent_attention call bumps "
    "absorbed whichever way it is computed (the form, which both ways "
    "share and a deployment's expected paths name) and pallas beside it "
    "where the kernel is what was traced",
    ("op", "path"),
)


def trace_platform() -> str:
    """The platform the current trace will run on: the device of an active
    ``jax.default_device(...)`` scope (params initialise on the host CPU
    while the process's backend is the TPU), else the default backend."""
    override = jax.config.jax_default_device
    if override is None:
        return jax.default_backend()
    return override if isinstance(override, str) else override.platform


_SCOPE = threading.local()


@contextlib.contextmanager
def mesh_scope(mesh):
    """Tell the ops traced under this scope which multi-chip mesh their
    program runs on. A Pallas call is one opaque custom call to the SPMD
    partitioner ("Mosaic kernels cannot be automatically partitioned"), so
    under a mesh each kernel is split by hand in shard_map; long
    self-attention additionally rides the seq axis as ring attention.

    Pipelines wrap their jitted-program *invocation* in this scope: jit
    traces lazily on the first call, so the routing decision (a trace-time
    branch) lands in the compiled program; cached invocations are
    unaffected. `mesh=None` or a one-chip mesh makes the scope a no-op, so
    call sites never need their own guard.
    """
    prev = active_mesh()
    _SCOPE.mesh = (
        mesh if mesh is not None and mesh.devices.size > 1 else None
    )
    try:
        yield
    finally:
        _SCOPE.mesh = prev


def active_mesh():
    """The multi-chip mesh of the enclosing mesh_scope, or None."""
    return getattr(_SCOPE, "mesh", None)


def batch_axis(mesh, batch: int):
    """The mesh axis a kernel's batch dim splits over: `data` when the
    batch divides it, else None (every chip computes every row)."""
    from ..parallel.mesh import DATA_AXIS

    return DATA_AXIS if batch % mesh.shape[DATA_AXIS] == 0 else None
