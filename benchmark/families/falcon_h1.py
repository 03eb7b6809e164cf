"""The `falcon_h1` pipeline family: everything the benchmark knows of
Falcon-H1's language model as one stage of an 18-stage pipeline. What a
job of token ids carries and how its JSON artifact is judged are the `kimi`
family's, used from there (a traffic file reads the same:
`families/kimi.py`, "What a family of token ids reads from a traffic
file"); here are the seeded weights (a draw a leaf: `seeded_leaves` says
why not Kimi's pool; `A_log` through the program's own `finish_leaf`), the
operations this family brings, both halves of `correct` 5 (the serving
half is Kimi's with the compared logits taken to the host a step at a
time: `denoiser_serve` says why), the controls of the limits' second
readings and the compile check's operands (README, "A family").

It reads the program through public names only:
`pipelines.text_generation.TextGenerationPipeline(..., weights=)`,
`param_shapes()` / `param_shardings()`, `prefill_program`, `step_program`,
`decode_program`, the attributes `params`, `config`, `dtype`, `mesh`,
`models.falcon_h1` (`new_cache`), `models.experts` (`leaf_rule`,
`finish_leaf`, `empty_load`), the operations `ops.ssd` (`ssd_step`,
`ssd_chunks`) and `ops.dot_product_attention(causal=)`. A program that has
no `models/falcon_h1.py` (the parent of PR 46) fails `register` with a
`RunFailure`, before anything is built.

**`correct` 4**, at the configuration's `kernel_shapes`: `ssd_step` as the
program dispatches it at the cell's shape (256 rows x 32 heads of `[256,
128]` in 2 groups), eight positions one after another from a zero state,
and `ssd_chunks` at a prefill chunk's shape (16 rows of 256 slots, ragged
lengths), each against the float32 recurrence (`reference/ssd_hybrid.py`
`recurrence`, on the host); one seeded layer's mixer, attention and
feed-forward apart, each as the program computes it against the plain
reference's (`sublayers`: where the five multipliers that four seeded
layers' logits hardly feel each move what is compared); causal attention
at head width 128, 20 query heads on 4 key heads, on the path the cell
takes (XLA's) against `reference/banded_kernels.py`.

**`correct` 5** is the serving path at the timed shapes, compared by
logits and never by sampled ids: the resident pipeline's own prefill
program (the configuration's `denoiser`: 256 rows, 256 prompt slots, 512
cached positions, lengths 16-256: the program the window ran) leaves every
row's state and tail at the row's own length and writes every layer's
keys, then `given_tokens` decode steps with given tokens go through state,
tail and keys, and for `compared_rows` of the rows the logits of the last
prompt position and of every step are held against the plain reference's
ONE full forward pass over prompt + given tokens
(`reference/ssd_hybrid.py`: float32 on the host CPU, the state-space mixer
as the position-by-position recurrence, no cache, the rows side by side, a
layer's weights pulled from the chip and converted at a time, the head
over the compared positions alone). The model is dense: no router, no
routing margin, **every position is compared**.
"""

from __future__ import annotations

import dataclasses
import math

from .kimi import (  # noqa: F401  (the contract's names, as they are there)
    HostWeights,
    check_artifact,
    job_fields,
)
from .qwen3_next import ragged_lengths  # noqa: F401  (lengths of a chunk)

FAMILY = "falcon_h1"
# the wire name the registry resolves this family by
PIPELINE_TYPE = "FalconH1ForCausalLM"

# `correct` 4, max abs error against the float32 references; the inputs
# are drawn from fixed keys, so a sound program reads the same number
# every run (my chip runs, PR 46). A limit lies between the sound reading
# and the smallest reading of a lower precision (`low_precision_controls`,
# not part of a run), with room on both sides. Both recurrence references
# are computed on the host (`_recurrence` says why).
# ssd_step (256 rows x 32 heads in 2 groups, eight positions from a zero
# state; unit-normal `x`, `B`, `C` rounded to bfloat16, steps U(0.05, 0.5)
# and rates -U(0.05, 0.5): a state that remembers; outputs of rms 9.0):
# the kernel reads 6.56e-4 against the host's recurrence (1.9e-5 against
# the same recurrence run on the chip: what is left is the chip's `exp`
# in the decays, carried on for eight positions) and 9.5e-7 interpreted
# on the CPU at the tiny shape; the same steps with the state rounded to
# bfloat16 between positions read 0.337, the recurrence with its decays
# rounded to bfloat16 0.486. The limit is 7.6 times the first and a
# sixty-seventh of the second.
SSD_STEP_TOL = 5e-3
# ssd_chunks (16 rows of 256 slots, lengths ragged over 16-256, the same
# distributions; outputs of rms 14.4): the chunk form at the highest
# matmul precision reads 3.59e-4 against the host's recurrence (it takes
# `exp` of running sums and not a product of 256 of them, so less of the
# chip's `exp` reaches it than reaches the step); with the state rounded
# to bfloat16 between chunks of 128 0.199, with bfloat16 decays 1.61. The
# limit is 14 times the first and a fortieth of the second.
SSD_CHUNKS_TOL = 5e-3
# causal attention at head width 128 (16 rows of 256 queries and keys, 20
# query heads on 4 key heads, unit-normal operands, scores of standard
# deviation 1) on XLA's path, as a share of the reference output's rms
# (`families/exaone.py` says why a share): 0.0162, 0.076 of the rms 0.2126
# (that path's scores are a bfloat16 matmul's output and its softmax
# weights are rounded to bfloat16, for every family: SDAR's 128-wide
# heads read 0.078, Qwen3-Next's 256-wide 0.0757); keys and values
# rounded to 8 bits a tensor read 0.177 of the rms there
# (`families/qwen3_next.py`: the same path, the same limit).
CAUSAL_ATTENTION_TOL = 0.11
# One seeded layer's three parts (2 rows of 256 positions, unit-normal
# normed inputs, the program's own seeded init from a fixed key), max abs
# error as a share of the reference output's rms (0.62, 0.155, 0.00099):
# the sound readings are 0.0213 (mixer), 0.0817 (attention: XLA's causal
# path, bfloat16 scores and weights, as `CAUSAL_ATTENTION_TOL` reads it)
# and 0.0174 (feed-forward); the reference with one multiplier left out
# (`sublayer_controls`) reads, mixer: the step's 0.0965, `C`'s 1.48, `B`'s
# 1.71, `z`'s 1.75, `x`'s 3.15, `ssm_in_multiplier` 4.87; attention: the
# key's 6.66; feed-forward: the gate's 4.49, the output's 5.09 (my chip
# run, PR 46). Each limit lies between its sound reading and its smallest
# control: 2.1 times and 0.47 (the step's multiplier sits beside a
# `dt_bias` of one, so it moves the mixer least), 3.7 times and a
# twenty-second, 5.7 times and a forty-fifth.
SUBLAYER_TOLS = {"mixer": 0.045, "attention": 0.3, "feed_forward": 0.1}
# Logits against the plain reference's full forward pass, relative L2 over
# every compared position (8 rows of 1 + 96 positions: no router, so none
# is left out). My chip runs, PR 46: five runs, each its own weights and
# inputs, read 0.003718 to 0.003728 with bf16 weights, activations, keys
# and tail, a float32 state and float32 accumulation (a row 0.00371 to
# 0.00374, a position 0.0043 at most: the logits are `lm_head_multiplier`
# times a bfloat16 head product, whose rounding is most of the reading,
# and nothing in the network can flip); the same network from weights
# rounded to 8 bits a tensor (`int8_control`, two seeds) read 0.01788 (no
# row under 0.01785). The limit is 2.1 times the largest of the first and
# 0.45 of the second. Of the fourteen multipliers left out of the
# reference one at a time (`multiplier_controls`, one row), eight fail it
# (0.0101 `ssm_multipliers[0]` to 0.992 `lm_head_multiplier`), one is
# published at 1, and five do not move these logits (0.00373 to 0.00459):
# `SUBLAYER_TOLS` is theirs.
DENOISER_REL_L2_TOL = 0.008


def seeded_leaves(shapes, shardings, seed: int, phases: dict | None = None):
    """Every leaf of `shapes` from `seed`: a normal draw of its own (key:
    the seed folded with the leaf's index), scaled and shifted by the
    program's own rule for the leaf's name (`models.experts.leaf_rule`),
    made on the device in the leaf's dtype, one program a distinct shape
    (12 for this tree). Not Kimi's pool: that is one float32 draw twice
    the largest leaf, and the largest leaf here is the 1.34 B-parameter
    embedding: 10.7 GB beside 8.8 GB of weights on a chip of 16.9."""
    import time

    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu.models.experts import leaf_rule

    phases = {} if phases is None else phases
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    places = jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: isinstance(x, jax.sharding.Sharding))
    draws: dict = {}

    def draw(shape, dtype, sharding):
        key = (shape, str(dtype), sharding)
        if key not in draws:
            draws[key] = jax.jit(
                lambda key, std, shift: (jax.random.normal(
                    key, shape, jnp.float32) * std + shift).astype(dtype),
                out_shardings=sharding)
        return draws[key]

    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        started = time.perf_counter()
        root = jax.random.key(int(seed))
        out = []
        for index, ((path, leaf), place) in enumerate(zip(leaves, places)):
            std, shift = leaf_rule(path, leaf.shape)
            out.append(draw(tuple(leaf.shape), leaf.dtype, place)(
                jax.random.fold_in(root, index), std, shift))
        jax.block_until_ready(out)
        phases["leaves_s"] = time.perf_counter() - started
        phases["programs"] = len(draws)
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)
    return jax.tree_util.tree_unflatten(treedef, out)


def register(seed: int, record: dict) -> None:
    """Re-register the `falcon_h1` family in this process with a factory
    whose pipelines take their weights from `seeded_leaves`, every leaf
    then through the program's own `finish_leaf` (`A_log`: `log U(0, 16)`,
    which no scaled normal says)."""
    import time

    from ..harness import RunFailure

    try:
        import chiaswarm_tpu.models.falcon_h1  # noqa: F401
        from chiaswarm_tpu.models.experts import finish_leaf
        from chiaswarm_tpu.pipelines.text_generation import (
            TextGenerationPipeline,
        )
    except ImportError:
        raise RunFailure(
            "this program has no models/falcon_h1.py: it cannot serve "
            "Falcon-H1 (the parent of PR 46)") from None
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


def ssd_operands(key, rows: int, slots: int, heads: int, size: int, dim: int,
                 groups: int):
    """Seeded operands of the recurrence over `[rows, slots]`: unit-normal
    `x`, `B` and `C` rounded to bfloat16 as the convolution's output is,
    steps `U(0.05, 0.5)` and rates `-U(0.05, 0.5)` (a state that remembers
    tens of positions: the seeded weights' forget within a few), skips
    `N(1, 0.1^2)`: `(x, dt, a, b, c, d)`."""
    import jax
    import jax.numpy as jnp

    def draw(key):
        ks = jax.random.split(key, 6)
        f32 = jnp.float32
        x = jax.random.normal(ks[0], (rows, slots, heads, dim), jnp.bfloat16)
        b, c = (jax.random.normal(k, (rows, slots, groups, size),
                                  jnp.bfloat16) for k in ks[1:3])
        dt = jax.random.uniform(ks[3], (rows, slots, heads), f32, 0.05, 0.5)
        a = -jax.random.uniform(ks[4], (heads,), f32, 0.05, 0.5)
        d = 1.0 + 0.1 * jax.random.normal(ks[5], (heads,), f32)
        return x.astype(f32), dt, a, b.astype(f32), c.astype(f32), d

    # one program and not a dozen: a cold run compiles each
    return jax.jit(draw)(key)


def steps_of(x, dt, a, b, c, d, size: int, interpret: bool,
             mantissa_bits=None):
    """`ssd_step` as the program dispatches it, a position after another
    from a zero state: `y` [R, T, H, P]. `mantissa_bits`: the control's,
    the state rounded to that many between positions (7: bfloat16's;
    `reduce_precision`, which the compiler may not take for excess
    precision and drop as it does a pair of converts)."""
    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu.ops.ssd import ssd_step

    rows, _, heads, dim = x.shape

    def position(state, xs):
        x, dt, b, c = xs
        y, state = ssd_step(x, dt, a, b, c, d, state, interpret=interpret)
        if mantissa_bits is not None:
            state = jax.lax.reduce_precision(state, 8, mantissa_bits)
        return state, y

    def run(x, dt, b, c):
        _, y = jax.lax.scan(
            position, jnp.zeros((rows, heads, size, dim), jnp.float32),
            tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
        return jnp.moveaxis(y, 0, 1)

    return jax.jit(run)(x, dt, b, c)


def _recurrence(*operands, **control):
    """The plain recurrence on the host CPU, as `correct` 5's reference
    is: the chip's `exp` reads 5.2e-6 of relative error where float32 has
    6e-8 (my chip run, PR 46), and a recurrence that multiplies its state
    by `exp(dt A)` at every position carries that on: computed on the
    chip, this reference itself read 0.0069 against the host's after 256
    positions of these operands, the chunk form 0.0003. `control`: a
    control's `decay_bits` / `state_bits`."""
    import jax
    import numpy as np

    from ..reference.ssd_hybrid import recurrence

    host = jax.local_devices(backend="cpu")[0]
    with jax.default_device(host), jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lambda *xs: recurrence(*xs, **control))(
            *jax.device_put(operands, host)))


def _worst(got, want, mask=None) -> float:
    """The largest absolute difference, on the host (`want` may live
    there), over `mask` where given."""
    import numpy as np

    err = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    return float(np.max(err if mask is None
                        else np.where(np.asarray(mask), err, 0.0)))


def sublayers(config: dict, dtype, shape, key: int, sizes: dict | None = None):
    """One seeded layer's three parts at the configuration's widths, each
    as the program computes it over `shape` = [rows, slots] of unit-normal
    normed inputs from a zero state and no keys, and as the plain
    reference does on the host: `({name: got}, {name: want})` for `mixer`
    (`ssm_prefill`, before `ssm_out_multiplier`), `attention`
    (`attention_prefill`, before `attention_out_multiplier`) and
    `feed_forward`. The weights are the program's own seeded init of one
    layer (`models.experts.init_leaves`). Here a multiplier that the
    logits of four seeded layers hardly feel (the step's, `B`'s, `C`'s,
    the key's, the gate's) moves what is compared by a large share of
    itself. `sizes`: the reference's, where a control leaves one out."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chiaswarm_tpu.models import falcon_h1 as model
    from chiaswarm_tpu.models.experts import init_leaves

    from ..reference import ssd_hybrid as reference

    rows, slots = shape
    cfg = model.config_for(config["job"]["model_name"])
    sizes = sizes or {name: config[name] for name in _SIZES}
    ks = jax.random.split(jax.random.key(key), 2)
    layer = init_leaves(model.param_shapes(cfg, dtype)["layers"][0], ks[0])
    u = jax.random.normal(ks[1], (rows, slots, cfg.hidden_size), dtype)
    lengths = jnp.full((rows,), slots)
    positions = jnp.broadcast_to(jnp.arange(slots), (rows, slots))

    def program(layer, u):
        state, tail, keys, values = model.new_cache(
            dataclasses.replace(cfg, num_hidden_layers=1), rows, 0, dtype)[0]
        return {
            "mixer": model.ssm_prefill(layer["mixer"], cfg, u, lengths, 0,
                                       state, tail)[0],
            "attention": model.attention_prefill(
                layer["attn"], cfg, u, positions, keys, values)[0],
            "feed_forward": model.feed_forward(
                layer, cfg, u.reshape(rows * slots, -1)).reshape(u.shape)}

    got = jax.jit(program)(layer, u)
    host = jax.local_devices(backend="cpu")[0]
    with jax.default_device(host), jax.default_matmul_precision("highest"):
        p, x = jax.tree_util.tree_map(
            lambda w: jnp.asarray(jax.device_put(w, host), jnp.float32),
            (layer, u))
        want = {
            "mixer": reference.mixer(p["mixer"], sizes, x),
            "attention": reference.attention(
                p["attn"], sizes, x * sizes["attention_in_multiplier"]),
            "feed_forward": reference.mlp(p["mlp"], sizes, x)}
        return ({name: np.asarray(value, np.float32)
                 for name, value in got.items()},
                {name: np.asarray(value) for name, value in want.items()})


def kernel_checks(config: dict, dtype, interpret: bool = False):
    """The state-space recurrence's step and chunk form, a seeded
    layer's three parts and causal attention at this family's head width,
    each as the program dispatches it, at the configuration's
    `kernel_shapes`, against the plain references. A reading is `{<kernel>: shape, "max_abs": number,
    "limit": its tolerance}`."""
    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu.ops import dot_product_attention
    from chiaswarm_tpu.ops.ssd import ssd_chunks

    from ..reference import banded_kernels

    import numpy as np

    failures, readings = [], []
    shapes = config["kernel_shapes"]

    def note(kernel, shape, got, want, limit, mask=None):
        err = _worst(got, want, mask)
        limit = float(limit)
        readings.append({kernel: list(shape), "max_abs": err, "limit": limit})
        if not err <= limit:
            failures.append(f"{kernel} {'x'.join(map(str, shape))}: max "
                            f"abs error {err:.3g} over {limit:.3g}")

    for n, shape in enumerate(shapes["ssd_step"]):
        rows, heads, size, dim, groups, steps = shape
        operands = ssd_operands(jax.random.key(900 + n), rows, steps, heads,
                                size, dim, groups)
        note("ssd_step", shape, steps_of(*operands, size, interpret),
             _recurrence(*operands), SSD_STEP_TOL)
    for n, shape in enumerate(shapes["ssd_chunks"]):
        rows, slots, heads, size, dim, groups = shape
        operands = ssd_operands(jax.random.key(950 + n), rows, slots, heads,
                                size, dim, groups)
        lengths = ragged_lengths(jax.random.key(960 + n), rows, slots)
        chunk = int(config["mamba_chunk_size"])
        got, _ = jax.jit(lambda *xs: ssd_chunks(*xs, chunk=chunk))(
            *operands, lengths,
            jnp.zeros((rows, heads, size, dim), jnp.float32))
        real = jnp.arange(slots)[None, :] < lengths[:, None]
        note("ssd_chunks", shape, got, _recurrence(*operands),
             SSD_CHUNKS_TOL, real[..., None, None])
    for n, shape in enumerate(shapes["sublayers"]):
        got, want = sublayers(config, dtype, shape, 700 + n)
        for name, limit in SUBLAYER_TOLS.items():
            rms = float(np.sqrt(np.mean(want[name] ** 2)))
            note(name, shape, got[name], want[name], limit * rms)
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
    """The kernel limits' second readings (not part of a run), max abs
    error a shape against the float32 recurrence: `bfloat16_state`, the
    step with its state rounded to bfloat16 between positions and the
    chunk form with its state rounded between chunks (the chunk form run a
    chunk a call: the mildest way a program could keep such a state);
    `bfloat16_decays`, the recurrence itself with every position's decay
    rounded to bfloat16."""
    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu.ops.ssd import ssd_chunks

    out: dict = {kernel: {"bfloat16_state": [], "bfloat16_decays": []}
                 for kernel in ("ssd_step", "ssd_chunks")}
    shapes = config["kernel_shapes"]
    chunk = int(config["mamba_chunk_size"])

    def decays_rounded(*operands):
        return _recurrence(*operands, decay_bits=7)

    for n, (rows, heads, size, dim, groups, steps) in enumerate(
            shapes["ssd_step"]):
        operands = ssd_operands(jax.random.key(900 + n), rows, steps, heads,
                                size, dim, groups)
        want = _recurrence(*operands)
        out["ssd_step"]["bfloat16_state"].append(_worst(
            steps_of(*operands, size, False, 7), want))
        out["ssd_step"]["bfloat16_decays"].append(_worst(
            decays_rounded(*operands), want))
    for n, (rows, slots, heads, size, dim, groups) in enumerate(
            shapes["ssd_chunks"]):
        operands = ssd_operands(jax.random.key(950 + n), rows, slots, heads,
                                size, dim, groups)
        x, dt, a, b, c, d = operands
        lengths = ragged_lengths(jax.random.key(960 + n), rows, slots)
        real = (jnp.arange(slots)[None, :] < lengths[:, None])[
            ..., None, None]
        want = _recurrence(*operands)
        state = jnp.zeros((rows, heads, size, dim), jnp.float32)
        parts = []
        for start in range(0, slots, chunk):
            span = slice(start, start + chunk)
            y, state = jax.jit(ssd_chunks, static_argnums=(8, 9))(
                x[:, span], dt[:, span], a, b[:, span], c[:, span], d,
                lengths, state, start, chunk)
            state = jax.lax.reduce_precision(state, 8, 7)
            parts.append(y)
        out["ssd_chunks"]["bfloat16_state"].append(_worst(
            jnp.concatenate(parts, 1), want, real))
        out["ssd_chunks"]["bfloat16_decays"].append(_worst(
            decays_rounded(*operands), want, real))
    return out


# --- the network's half of `correct` 5 ---------------------------------------

_SIZES = ("hidden_size", "num_attention_heads", "num_key_value_heads",
          "head_dim", "rope_theta", "rms_norm_eps", "mamba_d_ssm",
          "mamba_n_heads", "mamba_n_groups", "mamba_d_state", "mamba_d_conv",
          "embedding_multiplier", "lm_head_multiplier",
          "attention_in_multiplier", "attention_out_multiplier",
          "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
          "ssm_multipliers", "mlp_multipliers")


def denoiser_inputs(pipe, config: dict, seed: int) -> dict:
    """One seeded pass at the timed shapes (the configuration's
    `denoiser`): `rows` prompts with lengths log-uniform over the traffic's
    range and ids uniform over the vocabulary, `given_tokens` given tokens
    a row, and the `compared_rows` rows whose logits are compared."""
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
            "sizes": {key: config[key] for key in _SIZES}}


def _compared(inputs: dict, rows: int | None = None):
    """The compared rows (the first `rows` of them) as the reference takes
    them: each row's prompt and given tokens in one sequence, and the
    positions whose logits are compared: the last prompt position and
    every given token's."""
    import numpy as np

    sequences, wanted = [], []
    for row in inputs["compared"][:rows]:
        length = int(inputs["lengths"][row])
        sequences.append(np.concatenate(
            [inputs["ids"][row, :length], inputs["given"][row]]))
        wanted.append(np.arange(length - 1, len(sequences[-1])))
    return sequences, wanted


def denoiser_reference(pipe, inputs: dict):
    """The plain reference's logits on the host CPU, one full forward pass
    over the compared rows side by side: `[compared rows, 1 + given
    tokens, vocabulary]`, every position of them."""
    import jax
    import jax.numpy as jnp

    from ..reference.ssd_hybrid import forward_rows

    device = jax.local_devices(backend="cpu")[0]
    sequences, wanted = _compared(inputs)
    out = forward_rows(HostWeights(pipe.params), inputs["sizes"], sequences,
                       device=device, positions=wanted)
    with jax.default_device(device):
        return jnp.stack(out)


def denoiser_serve(pipe, inputs: dict):
    """The resident pipeline's own prefill program, then its decode step
    with the given tokens through state, tail and keys, in the serving
    dtype, the operations as dispatched: the logits of `[compared rows, 1
    + given tokens, vocabulary]`, float32, on the host. Kimi's `_serve`
    with one difference: each step's compared logits leave the chip as
    they are made. Kept there and stacked at the end they are twice 0.81
    GB (97 slices of 8 rows x 261,120) beside 8.79 GB of weights, 5.40 of
    cache and a step's own logits, and the chip has 28 MB left for the
    stack (my chip run, PR 46)."""
    import jax.numpy as jnp
    import numpy as np

    rows, slots = inputs["ids"].shape
    positions = inputs["positions"]
    lengths = jnp.asarray(inputs["lengths"])
    logits, cache, _ = pipe.prefill_program(rows, slots, positions)(
        pipe.params, inputs["ids"], lengths)
    compared = jnp.asarray(inputs["compared"])
    out = [np.asarray(logits[compared])]
    del logits
    step = pipe.step_program(rows, slots, positions)
    for number in range(inputs["given"].shape[1]):
        logits, cache = step(pipe.params, cache, inputs["given"][:, number],
                             lengths, number)
        out.append(np.asarray(logits[compared]))
    return np.stack(out, axis=1)


def int8_control(pipe, inputs: dict):
    """The low-precision control of `DENOISER_REL_L2_TOL`'s second reading
    (not part of a run): the same evaluation from weights rounded to 8
    bits a tensor (symmetric, one scale a matrix), leaf by leaf and in
    place (the leaf is donated: the chip cannot hold the tree twice), so
    the pipeline serves rounded weights from here on. Kimi's, over this
    family's `denoiser_serve`."""
    import jax
    import jax.numpy as jnp

    def rounded(x):
        if x.ndim < 2:
            return x
        x32 = x.astype(jnp.float32)
        scale = jnp.max(jnp.abs(x32), axis=(-2, -1), keepdims=True) / 127.0
        return (jnp.round(x32 / scale) * scale).astype(x.dtype)

    pipe.params = jax.tree_util.tree_map(
        jax.jit(rounded, donate_argnums=0), pipe.params)
    return denoiser_serve(pipe, inputs)


def multiplier_controls(pipe, inputs: dict, got, rows: int = 1) -> dict:
    """The second reading of `DENOISER_REL_L2_TOL` a multiplier (not part
    of a run): the served logits `got` (`denoiser_serve`'s) against the
    reference with that one multiplier left out (set to 1), relative L2
    over the first `rows` compared rows. A multiplier published at 1
    (`attention_in_multiplier`) cannot be left out, and is."""
    import numpy as np

    from ..reference.ssd_hybrid import forward_rows

    sizes = inputs["sizes"]
    sequences, wanted = _compared(inputs, rows)
    got = np.asarray(got)[:rows]
    weights = HostWeights(pipe.params)
    readings = {}
    for name, value in sizes.items():
        if not name.endswith(("multiplier", "multipliers")):
            continue
        for index in range(len(value)) if isinstance(value, list) else (None,):
            if (value if index is None else value[index]) == 1:
                continue
            without = 1.0 if index is None else [
                1.0 if n == index else v for n, v in enumerate(value)]
            out = np.stack([np.asarray(x) for x in forward_rows(
                weights, {**sizes, name: without}, sequences,
                positions=wanted)])
            readings[name if index is None else f"{name}[{index}]"] = float(
                np.linalg.norm(got - out) / np.linalg.norm(out))
    return readings


def sublayer_controls(config: dict, dtype) -> dict:
    """`SUBLAYER_TOLS`' second readings (not part of a run): each part of
    `sublayers` as the program computes it against the reference with one
    of that part's multipliers left out, max abs error as a share of that
    reference's rms: `{part: {multiplier: share}}`, the sound reading under
    `sound`."""
    import numpy as np

    shape = config["kernel_shapes"]["sublayers"][0]
    sizes = {name: config[name] for name in _SIZES}
    parts = {"mixer": [("ssm_in_multiplier", None)] + [
        ("ssm_multipliers", n) for n in range(5)],
        "attention": [("key_multiplier", None)],
        "feed_forward": [("mlp_multipliers", n) for n in range(2)]}

    def share(got, want):
        return float(np.max(np.abs(got - want)) / np.sqrt(np.mean(want ** 2)))

    got, want = sublayers(config, dtype, shape, 700)
    out = {part: {"sound": share(got[part], want[part])} for part in parts}
    for part, multipliers in parts.items():
        for name, index in multipliers:
            without = 1.0 if index is None else [
                1.0 if n == index else v
                for n, v in enumerate(sizes[name])]
            _, control = sublayers(config, dtype, shape, 700,
                                   {**sizes, name: without})
            out[part][name if index is None else f"{name}[{index}]"] = share(
                got[part], control[part])
    return out


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
    from chiaswarm_tpu.models.falcon_h1 import new_cache
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
