"""Runtime per-row LoRA deltas inside the jitted denoise program.

The ISSUE 13 tentpole: instead of merging each adapter into a full COPY
of the base UNet tree (per-adapter HBM residency, no coalescing across
tenants), the padded batched program carries up to N adapters as STACKED
low-rank factors and computes, per batch row b with adapter slot s(b):

    y_b = W·x_b + gain_b · B[s(b)] · (A[s(b)] · x_b)

- ``A`` stacks are ``[N, r, in]`` and ``B`` stacks ``[N, out, r]`` per
  Dense module path, zero-padded in both the slot dim (slot 0 is always
  the zero adapter — adapter-free rows compute an exact zero delta) and
  the rank dim (every adapter pads to one shared power-of-two rank
  bucket; zero rows/cols keep B@A exact), so ONE compiled program serves
  any mix of adapters with those bucket dims — adapter identity is data,
  not program structure, and swapping adapters never recompiles.
- ``gain`` carries ``scale * (alpha/rank)`` per row (0 for no-adapter
  rows), so per-module alphas and per-job lora_scale ride per row too.

Injection uses flax's method interceptor (`nn.intercept_methods`) scoped
to the UNet apply alone: every `nn.Dense.__call__` whose module path has
a factor stack gets the low-rank correction added to its output. The
base model's params and HLO are untouched — a pass with an empty operand
dict traces to the identical program (pinned bitwise by tests).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry import counter as telemetry_counter

# image rows through SD denoise passes by adapter mode: "delta" rows had
# a runtime per-row delta applied, "merged" rows ran on a merged-tree
# param copy (the fallback path), "none" rows carried no adapter. The
# multi-tenant refactor's whole point is delta >> merged at scale.
LORA_ROWS = telemetry_counter(
    "swarm_lora_rows_total",
    "Image rows through denoise passes by adapter mode "
    "(delta | merged | none)",
    ("mode",),
)

# slot-count and rank buckets: each distinct (slots, rank) pair is one
# compiled program variant per shape bucket, so both snap to powers of
# two. MIN_RANK keeps trivial adapters from fragmenting the space; it
# AND the bucketing function are shared with the jax-free coalesce
# vocabulary so the rank buckets that gang jobs together are exactly the
# ones that compile together.
from ..coalesce import LORA_MIN_RANK as MIN_RANK
from ..coalesce import _pow2_bucket as pow2_bucket


class DeltaIneligibleError(ValueError):
    """A coalesced group carries adapters the runtime delta cannot
    express (conv/LoCon modules, rank past lora_rank_max). Carries the
    affected member job ids so the worker can RE-BATCH the eligible
    majority and route only these members through the solo merged-tree
    fallback — one slow adapter must not serialize its batchmates.
    Subclasses ValueError so callers without per-member identity (direct
    run_batched users) still get the classic whole-group solo fallback.
    """

    def __init__(self, job_ids):
        self.job_ids = [j for j in job_ids if j is not None]
        super().__init__(
            f"adapter(s) for jobs {self.job_ids or list(job_ids)} are not "
            "delta-eligible; merged-tree fallback")


def adapter_rank(factors: dict[str, tuple]) -> int:
    """The largest rank across an adapter's matched modules."""
    return max((np.asarray(a).shape[0] for a, _b, _al in factors.values()),
               default=0)


def stacks_sig(adapters: list[dict]) -> tuple:
    """The operand signature — (n_slot_bucket, rank_bucket,
    targeted-module-paths) — computed host-side WITHOUT assembling or
    uploading anything, so the operand-residency cache (lora_operands.py)
    can be consulted before any stacking work. The path set is part of
    the sig because it is the operand dict's PYTREE STRUCTURE: two
    adapters hitting different Dense subsets would otherwise silently
    retrace inside one cached jit wrapper."""
    n_slots = pow2_bucket(1 + len(adapters))
    ranks = [adapter_rank(f) for f in adapters]
    r_bucket = pow2_bucket(max([MIN_RANK] + ranks))
    paths = sorted({p for f in adapters for p in f})
    return (n_slots, r_bucket, tuple(paths))


def build_stacks(adapters: list[dict], dtype,
                 sig: tuple | None = None) -> tuple[dict, dict, int]:
    """Assemble + upload the per-path A/B stacks — the expensive leg
    (host numpy assembly then `jnp.asarray` device transfer). Returns
    (a_map, b_map, nbytes) where nbytes is the device footprint the
    residency cache charges for the pair. Scale-INDEPENDENT by
    construction: the per-module ``alpha/rank`` folds into A here
    (adapter-intrinsic), while the job's ``lora_scale`` rides the
    per-row gain vector (row_operands), so one resident stack serves
    the same adapter at any scale."""
    if sig is None:
        sig = stacks_sig(adapters)
    n_slots, r_bucket, paths = sig
    a_map: dict[str, jnp.ndarray] = {}
    b_map: dict[str, jnp.ndarray] = {}
    nbytes = 0
    for path in paths:
        a_stack = b_stack = None
        for slot, factors in enumerate(adapters, start=1):
            entry = factors.get(path)
            if entry is None:
                continue
            a, b, alpha = entry
            a = np.asarray(a, np.float32)
            b = np.asarray(b, np.float32)
            rank = a.shape[0]
            if a_stack is None:
                a_stack = np.zeros((n_slots, r_bucket, a.shape[1]), np.float32)
                b_stack = np.zeros((n_slots, b.shape[0], r_bucket), np.float32)
            # per-module alpha/rank folds into A so one per-row gain
            # (the job's lora_scale) serves modules with distinct alphas
            eff = (alpha / rank) if alpha is not None else 1.0
            a_stack[slot, :rank, :] = eff * a
            b_stack[slot, :, :rank] = b
        a_map[path] = jnp.asarray(a_stack, dtype)
        b_map[path] = jnp.asarray(b_stack, dtype)
        nbytes += a_map[path].nbytes + b_map[path].nbytes
    return a_map, b_map, nbytes


def row_operands(a_map: dict, b_map: dict, row_slots: list[int],
                 row_gains: list[float]) -> dict:
    """Join (possibly cache-resident) stacks with the pass's tiny
    per-row slot/gain vectors into the jitted program's lora operand.
    ``row_slots``/``row_gains`` are per BATCH ROW (pre-CFG; the step
    body tiles them over the CFG rows)."""
    return {
        "a": a_map,
        "b": b_map,
        "slot": jnp.asarray(np.asarray(row_slots, np.int32)),
        "gain": jnp.asarray(np.asarray(row_gains, np.float32)),
    }


def build_operands(adapters: list[dict], row_slots: list[int],
                   row_gains: list[float], dtype) -> tuple[dict, tuple]:
    """Stack per-slot factors into the jitted program's lora operand.

    ``adapters``: matched factor dicts ({path: (A, B, alpha)}), one per
    occupied slot, slot numbers 1..len(adapters) — slot 0 is the
    implicit zero adapter. Returns (operands, sig); same sig => same
    compiled program, any adapters. The uncached composition of
    stacks_sig + build_stacks + row_operands — the residency-aware path
    (SDPipeline._lora_operands) calls the legs separately so a repeat
    gang skips build_stacks entirely.
    """
    sig = stacks_sig(adapters)
    a_map, b_map, _nbytes = build_stacks(adapters, dtype, sig)
    return row_operands(a_map, b_map, row_slots, row_gains), sig


def _path_interceptor(a_map: dict, b_map: dict, slots, gains, prefix: str):
    """The shared Dense-call interceptor body: every `nn.Dense.__call__`
    whose (prefixed) module path has a factor stack gets the per-row
    low-rank correction added to its output. Dense calls whose leading
    dim is not the expected batch pass through untouched."""
    rows = slots.shape[0]

    def interceptor(next_fun, args, kwargs, context):
        if (context.method_name != "__call__"
                or not isinstance(context.module, nn.Dense)):
            return next_fun(*args, **kwargs)
        key = prefix + "/".join(context.module.path)
        stack_a = a_map.get(key)
        if stack_a is None:
            return next_fun(*args, **kwargs)
        x = args[0]
        if getattr(x, "ndim", 0) < 2 or x.shape[0] != rows:
            return next_fun(*args, **kwargs)
        y = next_fun(*args, **kwargs)
        stack_b = b_map[key]
        # the delta is no flax module: it gets its name in a device
        # trace here, under the path of the Dense it corrects
        with jax.named_scope("lora_row_delta"):
            a = jnp.take(stack_a, slots, axis=0)  # [rows, r, in]
            b = jnp.take(stack_b, slots, axis=0)  # [rows, out, r]
            if x.ndim == 2:
                low = jnp.einsum("bi,bri->br", x, a)
                delta = jnp.einsum("br,bor->bo", low, b)
                delta = delta * gains[:, None]
            else:
                low = jnp.einsum("bsi,bri->bsr", x, a)
                delta = jnp.einsum("bsr,bor->bso", low, b)
                delta = delta * gains[:, None, None]
            return y + delta.astype(y.dtype)

    return interceptor


def make_interceptor(operands: dict, cfg_rows: int):
    """Flax method interceptor applying the stacked per-row deltas to
    every targeted Dense inside ONE unet apply. ``operands['slot']`` /
    ``['gain']`` are per batch row; the UNet sees the CFG-tiled batch
    (uncond rows first), so both tile by ``cfg_rows`` here. Text-encoder
    paths in the stacks carry a ``te{i}:`` prefix, which can never equal
    a flax module path (':' is not a module-name character), so one
    shared stack map serves both interceptors without cross-matching."""
    slots = jnp.tile(operands["slot"], (cfg_rows,))
    gains = jnp.tile(operands["gain"], (cfg_rows,)).astype(jnp.float32)
    return _path_interceptor(operands["a"], operands["b"], slots, gains, "")


def make_te_interceptor(operands: dict, enc_index: int):
    """Interceptor for ONE text-encoder apply (ISSUE 16 tentpole part
    2): stacks are looked up under the ``te{enc_index}:`` namespace the
    TE-aware matcher emits, and ``operands['slot']``/``['gain']`` are
    already per TEXT ROW (the encoder batch is the text batch — no CFG
    tiling; callers lay slots out to match their negs+prompts rows)."""
    slots = operands["slot"]
    gains = operands["gain"].astype(jnp.float32)
    return _path_interceptor(operands["a"], operands["b"], slots, gains,
                             f"te{enc_index}:")
