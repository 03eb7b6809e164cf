"""The `qwen3_next` pipeline family: everything the benchmark knows of
Qwen3-Next's language model as one chip of a four-chip host that shares
each layer. What a job of token ids carries, how its JSON artifact is
judged, the serving half of `correct` 5 and the 8-bit control are the
`kimi` family's, used from there (a traffic file reads the same:
`families/kimi.py`, "What a family of token ids reads from a traffic
file"); here are the seeded weights (Kimi's pool, `A_log` through the
program's own `finish_leaf`), the operations this family brings, the
reference's half of `correct` 5 and the compile check's operands (README,
"A family").

It reads the program through public names only:
`pipelines.text_generation.TextGenerationPipeline(..., weights=)`,
`param_shapes()` / `param_shardings()`, `prefill_program`, `step_program`,
`decode_program`, the attributes `params`, `config`, `dtype`, `mesh`,
`models.qwen3_next` (`new_cache`), `models.experts` (`leaf_rule`,
`finish_leaf`, `held_experts`, `empty_load`), the operations
`ops.gated_delta_rule` (`gated_delta_step`, `gated_delta_chunks`) and
`ops.dot_product_attention(causal=)`. A program that has no
`models/qwen3_next.py` (the parent of PR 42) fails `register` with a
`RunFailure`, before anything is built.

**`correct` 4**, at the configuration's `kernel_shapes`:
`gated_delta_step` as the program dispatches it at the cell's shape (256
rows x 32 heads of `[128, 128]`), eight positions one after another from
a zero state, and `gated_delta_chunks` at a prefill chunk's shape (16 rows
of 256 slots, ragged lengths), each against the float32 recurrence
(`reference/gated_delta_moe.py` `delta_rule`); the grouped matmul at
`[256 and 4096 tokens, 2048, 512]` over 128 groups against
`reference/moe_kernels.py`; causal attention at head width 256, 16 query
heads on 2 key heads, on the path the cell takes (XLA's) against
`reference/banded_kernels.py`.

**`correct` 5** is the serving path at the timed shapes, compared by
logits and never by sampled ids: the resident pipeline's own prefill
program (the configuration's `denoiser`: 256 rows, 256 prompt slots, 512
cached positions, lengths 16-256: the program the window ran) leaves
every row's state and tail at the row's own length and writes the full
layers' keys, then `given_tokens` decode steps with given tokens go
through state, tail and keys, and for `compared_rows` of the rows the
logits of the last prompt position and of every step are held against the
plain reference's ONE full forward pass over prompt + given tokens
(`reference/gated_delta_moe.py`: float32 on the host CPU, the DeltaNet as
the position-by-position recurrence, no cache, the rows side by side, a
layer's weights pulled from the chip and converted at a time). A position
whose routing the reference finds within `ROUTING_MARGIN` of flipping is
left out on both sides (the constant says why, and where its number was
read).
"""

from __future__ import annotations

import math

from .kimi import (  # noqa: F401  (the contract's names, as they are there)
    HostWeights,
    check_artifact,
    denoiser_serve,
    int8_control,
    job_fields,
    seeded_leaves,
)

FAMILY = "qwen3_next"
# the wire name the registry resolves this family by
PIPELINE_TYPE = "Qwen3NextForCausalLM"

# `correct` 4, max abs error against the float32 references; the inputs
# are drawn from fixed keys, so a sound program reads the same number
# every run (my chip runs, PR 42). A limit lies between the sound reading
# and the reading of a lower precision (`low_precision_controls`, not part
# of a run), with room on both sides.
# gated_delta_step (256 rows x 32 heads, eight positions from a zero
# state; unit keys and queries, unit-normal values rounded to bfloat16,
# log decays -U(0, 0.2), strengths U(0, 1); outputs of rms ~0.1): the
# kernel reads 0.0 on the chip (float32 products and sums in the order
# XLA's own reduction takes them) and 4e-8 interpreted on the CPU; the
# same steps with the state rounded to bfloat16 between positions read
# 2.23e-4. The limit is a twentieth of that.
GATED_DELTA_STEP_TOL = 1e-5
# gated_delta_chunks (16 rows of 256 slots, lengths ragged over 16-256,
# the same distributions): the chunk form at the highest matmul precision
# reads 6.59e-7 against the recurrence (sixteen products of 64-wide
# float32 matrices where the recurrence has sums; 6.52e-7 while the
# operands were drawn op by op and not in one program); with the state
# rounded to bfloat16 between chunks 1.21e-4. The limit is 4.6 times the
# first and a fortieth of the second.
GATED_DELTA_CHUNKS_TOL = 3e-6
# expert_matmul (gate and up, SiLU, down through the grouped kernel at
# hidden 2048 and width 512, 128 groups of 512 scored, on outputs of rms
# 1.18): 0.0210 at a decode step's 256 tokens (16-row tiles) and 0.0248 at
# a prefill chunk's 4096 (128-row tiles); the 256 tokens' pairs through
# matrices rounded to 8 bits an expert read 0.0813 (float32 arithmetic):
# the limit is 1.6 times the larger of the first and half the second.
EXPERT_MATMUL_TOL = 0.04
# causal attention at head width 256 (16 rows of 256 queries and keys, 16
# query heads on 2 key heads, unit-normal operands, scores of standard
# deviation 1) on XLA's path, as a share of the reference output's rms
# (0.212; `families/exaone.py` says why a share): 0.0161, 0.0757 of the
# rms (that path's scores are a bfloat16 matmul's output and its softmax
# weights are rounded to bfloat16, for every family: SDAR's 128-wide heads
# read 0.078); keys and values rounded to 8 bits a tensor 0.177 of the rms.
CAUSAL_ATTENTION_TOL = 0.11
# How far a position's routing has to be from changing before its logits
# are compared (`gated_delta_moe.held_margin`: the least distance, over
# the 128 held experts and the 8 layers, between a held expert's SOFTMAX
# score and the choice's boundary; the scores of 512 experts sum to one
# and the tenth largest is ~0.005, so this is no number of a sigmoid
# router's). Read on this network, my chip runs, PR 42, three weight seeds,
# every compared position against the reference with its margin beside it
# (216, 776 and 776 positions). The margin is 0.00008 at the median, 0.00015
# at the upper quartile and 0.00047 at the 99th percentile. A position
# that no flip touches reads 0.012 to 0.020 against the reference, one that
# a flip touches 0.03 to 0.11, and **no margin parts the two**: of the
# positions under 0.0001, 44 % read over 0.03; between 0.0002 and 0.0003,
# 9 to 31 %; over 0.0005 (12 positions in all) still one at 0.044. A flip
# does not stay at its position here: the token's keys, values and its
# write into the recurrent state carry a whole expert's difference on to
# every later position of its row. So the margin only thins the flips out
# (all positions read 0.0316 to 0.0358; over 0.0001 0.0241 to 0.0282; over
# 0.0002 0.0223 to 0.0281; over 0.0003 0.0203 to 0.0235; over 0.0005 0.016
# to 0.027 on 2 to 6 positions, which is noise), and what keeps the
# reading steady is the number of positions: the configuration compares 8
# rows of 1 + 96 positions, 776, of which 0.0002 keeps one in seven (109
# and 130 on the two seeds of that size).
ROUTING_MARGIN = 0.0002
# Logits against the plain reference's full forward pass, relative L2 over
# the compared positions whose routing is not within `ROUTING_MARGIN` of
# changing. My chip runs, PR 42: the three seeds of the study above read
# 0.0223, 0.0228 and 0.0281 (the last over 25 positions only), nine runs
# of the cell, each its own weights and inputs, 0.0213 to 0.0271, with
# bf16 weights, activations and keys, a float32 state, float32
# accumulation and a float32 router; the same network from weights rounded
# to 8 bits a tensor (`int8_control`, two seeds) read 0.0845 over the same
# positions (0.0913 and 0.0927 over all of them, no position under 0.053).
# The limit is 1.6 times the largest of the first and 0.53 of the second.
DENOISER_REL_L2_TOL = 0.045


def register(seed: int, record: dict) -> None:
    """Re-register the `qwen3_next` family in this process with a factory
    whose pipelines take their weights from `seeded_leaves`, every leaf
    then through the program's own `finish_leaf` (`A_log`: the published
    `log U(0, 16)`, which no scaled normal says)."""
    import time

    from ..harness import RunFailure

    try:
        import chiaswarm_tpu.models.qwen3_next  # noqa: F401
        from chiaswarm_tpu.models.experts import finish_leaf
        from chiaswarm_tpu.pipelines.text_generation import (
            TextGenerationPipeline,
        )
    except ImportError:
        raise RunFailure(
            "this program has no models/qwen3_next.py: it cannot serve "
            "Qwen3-Next (the parent of PR 42)") from None
    import jax

    from chiaswarm_tpu import registry

    def weights(shapes, shardings, phases):
        tree = seeded_leaves(shapes, shardings, int(seed), phases)
        return jax.tree_util.tree_map_with_path(finish_leaf, tree)

    def factory(model_name, chipset, **variant):
        started = time.perf_counter()
        phases: dict = {}
        pipe = TextGenerationPipeline(
            model_name, chipset, **variant,
            weights=lambda shapes, shardings: weights(
                shapes, shardings, phases))
        record.setdefault("weights_ready_s", {})[model_name] = (
            time.perf_counter() - started)
        record.setdefault("weights_phases", {})[model_name] = phases
        return pipe

    registry.register_family(FAMILY)(factory)


# --- `correct` 4: the operations this family brings --------------------------


def rule_operands(key, rows: int, slots: int, heads: int, keys: int,
                  values: int):
    """Seeded operands of the delta rule over `[rows, slots]`: unit `k`
    and `q` (the latter scaled by `keys^-1/2`), unit-normal `v` rounded to
    bfloat16 as the convolution's output is, log decays `-U(0, 0.2)` (a
    state that remembers: the published init's forget within a position
    or two), strengths `U(0, 1)`."""
    import jax
    import jax.numpy as jnp

    def unit(key):
        x = jax.random.normal(key, (rows, slots, heads, keys), jnp.float32)
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    def draw(key):
        ks = jax.random.split(key, 5)
        v = jax.random.normal(ks[2], (rows, slots, heads, values),
                              jnp.bfloat16)
        g = -jax.random.uniform(ks[3], (rows, slots, heads), maxval=0.2)
        beta = jax.random.uniform(ks[4], (rows, slots, heads))
        return (unit(ks[0]) * keys ** -0.5, unit(ks[1]),
                v.astype(jnp.float32), g, beta)

    # one program and not a dozen: a cold run compiles each
    return jax.jit(draw)(key)


def steps_of(q, k, v, g, beta, interpret: bool, mantissa_bits=None):
    """`gated_delta_step` as the program dispatches it, a position after
    another from a zero state: `o` [R, T, H, V]. `mantissa_bits`: the
    control's, the state rounded to that many between positions (7:
    bfloat16's; `reduce_precision`, which the compiler may not take for
    excess precision and drop as it does a pair of converts)."""
    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu.ops.gated_delta_rule import gated_delta_step

    rows, _, heads, keys = q.shape

    def position(state, xs):
        o, state = gated_delta_step(*xs, state, interpret=interpret)
        if mantissa_bits is not None:
            state = jax.lax.reduce_precision(state, 8, mantissa_bits)
        return state, o

    def run(q, k, v, g, beta):
        _, o = jax.lax.scan(
            position,
            jnp.zeros((rows, heads, keys, v.shape[-1]), jnp.float32),
            tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
        return jnp.moveaxis(o, 0, 1)

    return jax.jit(run)(q, k, v, g, beta)


def ragged_lengths(key, rows: int, slots: int):
    """Lengths over `[slots / 16, slots]`, the first row a full one and
    the second the shortest."""
    import jax

    lengths = jax.random.randint(key, (rows,), max(slots // 16, 1),
                                 slots + 1)
    return lengths.at[0].set(slots).at[1].set(max(slots // 16, 1))


def kernel_checks(config: dict, dtype, interpret: bool = False):
    """The delta rule's step and chunk form, the grouped matmul over held
    experts at this family's widths and causal attention at its head
    width, each as the program dispatches it, at the configuration's
    `kernel_shapes`, against the plain references. A reading is
    `{<kernel>: shape, "max_abs": number, "limit": its tolerance}`."""
    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu.models.experts import held_experts
    from chiaswarm_tpu.ops import dot_product_attention
    from chiaswarm_tpu.ops.gated_delta_rule import gated_delta_chunks

    from ..reference import banded_kernels, moe_kernels
    from ..reference.gated_delta_moe import delta_rule

    failures, readings = [], []
    shapes = config["kernel_shapes"]
    held = int(config["num_experts"])
    router = int(config["deployment_share"]["router_width"])
    choices = int(config["num_experts_per_tok"])

    @jax.jit
    def worst(got, want, mask):
        err = jnp.abs(got.astype(jnp.float32) - want)
        return jnp.max(err if mask is None else jnp.where(mask, err, 0.0))

    def note(kernel, shape, got, want, limit, mask=None):
        err = float(worst(got, want, mask))
        limit = float(limit)
        readings.append({kernel: list(shape), "max_abs": err, "limit": limit})
        if not err <= limit:
            failures.append(f"{kernel} {'x'.join(map(str, shape))}: max "
                            f"abs error {err:.3g} over {limit:.3g}")

    def recurrence(*operands):
        with jax.default_matmul_precision("highest"):
            return delta_rule(*operands)

    for n, shape in enumerate(shapes["gated_delta_step"]):
        rows, heads, keys, values, steps = shape
        operands = rule_operands(jax.random.key(900 + n), rows, steps, heads,
                                 keys, values)
        note("gated_delta_step", shape, steps_of(*operands, interpret),
             recurrence(*operands), GATED_DELTA_STEP_TOL)
    for n, shape in enumerate(shapes["gated_delta_chunks"]):
        rows, slots, heads, keys, values = shape
        operands = rule_operands(jax.random.key(950 + n), rows, slots, heads,
                                 keys, values)
        lengths = ragged_lengths(jax.random.key(960 + n), rows, slots)
        got, _ = jax.jit(gated_delta_chunks)(
            *operands, lengths,
            jnp.zeros((rows, heads, keys, values), jnp.float32))
        real = jnp.arange(slots)[None, :] < lengths[:, None]
        note("gated_delta_chunks", shape, got, recurrence(*operands),
             GATED_DELTA_CHUNKS_TOL, real[..., None, None])
    for n, (tokens, hidden, width) in enumerate(shapes["expert_matmul"]):
        ks = jax.random.split(jax.random.key(400 + n), 5)
        h = jax.random.normal(ks[0], (tokens, hidden), dtype)
        gate, up = (jax.random.normal(key, (held, hidden, width), dtype)
                    / math.sqrt(hidden) for key in ks[1:3])
        # outputs of unit scale, as the layer's are after its weights
        down = jax.random.normal(ks[3], (held, width, hidden), dtype) \
            * (2.0 / math.sqrt(width))
        # every token's distinct choices over the router's whole width,
        # uneven (the low experts drawn more often): a token holds 0 to
        # `choices` of the experts here, and some hold none
        scores = jax.random.gumbel(ks[4], (tokens, router)) \
            - 0.005 * jnp.arange(router)
        local = jax.lax.top_k(scores, choices)[1].astype(jnp.int32)
        experts = {"gate": gate, "up": up, "down": down}
        got, _ = jax.jit(lambda e, h, l: held_experts(
            e, h, l, interpret=False))(experts, h, local)
        note("expert_matmul", (tokens, hidden, width), got,
             moe_kernels.expert_ffn(h, local, gate, up, down),
             EXPERT_MATMUL_TOL)
    for n, shape in enumerate(shapes["causal_attention"]):
        rows, length, heads, kv_heads, dim = shape
        ks = jax.random.split(jax.random.key(600 + n), 3)
        q = jax.random.normal(ks[0], (rows, length, heads, dim), dtype)
        k = jax.random.normal(ks[1], (rows, length, kv_heads, dim), dtype)
        v = jax.random.normal(ks[2], (rows, length, kv_heads, dim), dtype)
        got = jax.jit(lambda q, k, v: dot_product_attention(
            q, k, v, scale=dim ** -0.5, causal=True))(q, k, v)
        want = banded_kernels.banded_attention(q, k, v, dim ** -0.5)
        note("causal_attention", shape, got, want,
             CAUSAL_ATTENTION_TOL * jnp.sqrt(jnp.mean(want * want)))
    return failures, readings


def low_precision_controls(config: dict) -> dict:
    """The limits' second readings (not part of a run): the step with its
    state rounded to bfloat16 between positions, the chunk form with its
    state rounded to bfloat16 between chunks of 64 (the chunk form run a
    chunk a call), each against the float32 recurrence; max abs error a
    shape."""
    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu.ops.gated_delta_rule import CHUNK, gated_delta_chunks

    from ..reference.gated_delta_moe import delta_rule

    out: dict = {"gated_delta_step": [], "gated_delta_chunks": []}
    shapes = config["kernel_shapes"]

    def worst(got, want, mask=None):
        err = jnp.abs(got - want)
        return float(jnp.max(err if mask is None
                             else jnp.where(mask, err, 0.0)))

    for n, (rows, heads, keys, values, steps) in enumerate(
            shapes["gated_delta_step"]):
        operands = rule_operands(jax.random.key(900 + n), rows, steps, heads,
                                 keys, values)
        with jax.default_matmul_precision("highest"):
            want = delta_rule(*operands)
        out["gated_delta_step"].append(worst(
            steps_of(*operands, False, 7), want))
    for n, (rows, slots, heads, keys, values) in enumerate(
            shapes["gated_delta_chunks"]):
        operands = rule_operands(jax.random.key(950 + n), rows, slots, heads,
                                 keys, values)
        lengths = ragged_lengths(jax.random.key(960 + n), rows, slots)
        with jax.default_matmul_precision("highest"):
            want = delta_rule(*operands)
        state = jnp.zeros((rows, heads, keys, values), jnp.float32)
        parts = []
        for start in range(0, slots, CHUNK):
            o, state = jax.jit(gated_delta_chunks, static_argnums=7)(
                *(x[:, start:start + CHUNK] for x in operands), lengths,
                state, start)
            state = jax.lax.reduce_precision(state, 8, 7)
            parts.append(o)
        real = jnp.arange(slots)[None, :] < lengths[:, None]
        out["gated_delta_chunks"].append(worst(
            jnp.concatenate(parts, 1), want, real[..., None, None]))
    return out


# --- the network's half of `correct` 5 ---------------------------------------

_SIZES = ("hidden_size", "num_attention_heads", "num_key_value_heads",
          "head_dim", "partial_rotary_factor", "rope_theta",
          "full_attention_interval", "linear_num_key_heads",
          "linear_num_value_heads", "linear_key_head_dim",
          "linear_value_head_dim", "linear_conv_kernel_dim",
          "num_experts_per_tok", "rms_norm_eps")


def denoiser_inputs(pipe, config: dict, seed: int) -> dict:
    """One seeded pass at the timed shapes (the configuration's
    `denoiser`): `rows` prompts with lengths log-uniform over the traffic's
    range and ids uniform over the held vocabulary, `given_tokens` given
    tokens a row, and the `compared_rows` rows whose logits are compared."""
    import numpy as np

    want = config["denoiser"]
    rng = np.random.default_rng(seed)
    rows, slots = int(want["rows"]), int(want["prompt_slots"])
    low, high = int(want["length_min"]), int(want["length_max"])
    vocabulary = int(config["vocab_size"])
    lengths = np.clip(np.exp(rng.uniform(
        math.log(low), math.log(high + 1), rows)).astype(np.int32), low, high)
    ids = np.zeros((rows, slots), np.int32)
    for row, length in enumerate(lengths):
        ids[row, :length] = rng.integers(0, vocabulary, length)
    return {"ids": ids, "lengths": lengths,
            "given": rng.integers(0, vocabulary, (
                rows, int(want["given_tokens"]))).astype(np.int32),
            "compared": np.sort(rng.choice(
                rows, int(want["compared_rows"]), replace=False)),
            "positions": int(want["positions"]),
            "sizes": {key: config[key] for key in _SIZES} | {
                "num_experts": int(
                    config["deployment_share"]["router_width"])},
            "held": tuple(config["deployment_share"]["experts_held"])}


def denoiser_reference(pipe, inputs: dict):
    """The plain reference's logits on the host CPU, one full forward pass
    over the compared rows side by side: `[kept positions, vocabulary]`,
    the positions of `[compared rows, 1 + given tokens]` whose routing
    margin is `ROUTING_MARGIN` at least (`inputs["kept"]`, for
    `denoiser_serve`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..reference.gated_delta_moe import forward_rows

    device = jax.local_devices(backend="cpu")[0]
    sequences, wanted = [], []
    for row in inputs["compared"]:
        length = int(inputs["lengths"][row])
        sequences.append(np.concatenate(
            [inputs["ids"][row, :length], inputs["given"][row]]))
        wanted.append(np.arange(length - 1, len(sequences[-1])))
    margins: list = []
    out = forward_rows(HostWeights(pipe.params), inputs["sizes"], sequences,
                       held=inputs["held"], device=device, positions=wanted,
                       margins=margins)
    # what `denoiser_serve` keeps too: [compared rows, 1 + given tokens];
    # the position farthest from flipping where none is far enough (a
    # rehearsal's few positions; never the chip's)
    least = np.stack([np.asarray(margin)[at]
                      for margin, at in zip(margins, wanted)])
    far = least >= ROUTING_MARGIN
    inputs["margins"] = least
    inputs["kept"] = far if far.any() else least == least.max()
    with jax.default_device(device):
        return jnp.stack(out)[inputs["kept"]]


# --- the compile check's operands --------------------------------------------


def compile_operands(spec: dict, devices):
    """The cell's decode program (the pass's longer half, and the one
    that holds the kernel this family brings) as the worker keys it, its
    arguments as shapes on the described `devices`, and its rows."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from chiaswarm_tpu.chips.device import ChipSet
    from chiaswarm_tpu.coalesce import prompt_slots
    from chiaswarm_tpu.models.experts import empty_load
    from chiaswarm_tpu.models.qwen3_next import new_cache
    from chiaswarm_tpu.pipelines.text_generation import (
        TextGenerationPipeline,
    )
    from chiaswarm_tpu.settings import load_settings

    config, traffic = spec["config"], spec["traffic"]
    job = {**config["job"], **traffic["job"]}
    pipe = TextGenerationPipeline(
        job["model_name"], ChipSet(list(devices)),
        dtype=jnp.dtype(config["kernel_dtype"]),
        weights=lambda shapes, shardings: jax.tree_util.tree_map(
            lambda s, place: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=place), shapes, shardings))
    jobs = min(int(traffic["clients"]),
               int(load_settings().hive_max_jobs_per_poll))
    rows = jobs * int(traffic["tokens"]["sequences"])
    slots = prompt_slots(int(traffic["tokens"]["length_max"]))
    new_tokens = int(job["max_new_tokens"])
    whole = NamedSharding(pipe.mesh, PartitionSpec())

    def shaped(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=whole),
            tree)

    cfg = pipe.config
    args = (
        pipe.params,
        shaped(jax.eval_shape(
            lambda: new_cache(cfg, rows, slots + new_tokens, pipe.dtype))),
        shaped(jax.ShapeDtypeStruct((rows, cfg.vocab_size), jnp.float32)),
        shaped(jax.ShapeDtypeStruct((rows,), jnp.int32)),
        shaped(jax.ShapeDtypeStruct((jobs, 2), jnp.uint32)),
        shaped(jax.ShapeDtypeStruct((rows,), jnp.int32)),
        shaped(jax.ShapeDtypeStruct((rows,), jnp.int32)),
        shaped(jax.ShapeDtypeStruct((), jnp.float32)),
        shaped(jax.eval_shape(lambda: empty_load(cfg))))
    return pipe.decode_program(rows, slots, new_tokens), args, rows
