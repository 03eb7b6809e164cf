"""The `sdar` pipeline family: everything the benchmark knows of SDAR's
language model as one stage of a pipeline with every expert held. What a
job of token ids carries, how its JSON artifact is judged and how seeded
weights are made on the device are the `kimi` family's, used from there (a
traffic file reads the same: `families/kimi.py`, "What a family of token
ids reads from a traffic file"; `job.denoising_steps` rides every job
beside `job.max_new_tokens`); here are the operations at this family's
shapes, the network's half of `correct` 5 and the compile check's operands
(README, "A family").

It reads the program through public names only:
`pipelines.text_generation.TextGenerationPipeline(..., weights=)`,
`param_shapes()` / `param_shardings()`, `prefill_program`, `block_program`,
`block_decode_program`, `cache_positions`, the attributes `params`,
`config`, `dtype`, `mesh`, `models.sdar` (`new_cache`, `empty_load`),
`models.experts` (`leaf_rule`, `held_experts`) and the operation
`ops.dot_product_attention(causal=, span=)`. A program that has no
`models/sdar.py` (the parent of PR 40) fails `register` with a
`RunFailure`, before anything is built.

**`correct` 4**: the grouped matmul over all 128 held experts at this
family's widths (128 groups of `[2048, 768]`: a block step's 1024 tokens,
64 pairs an expert in 128-row tiles, and a prefill chunk's 4096) against
`reference/moe_kernels.py`; attention under the mask that is causal
between spans of 4 and bidirectional inside one, as the program dispatches
it, on the kernel's path (a 4096-query prefill chunk at offset 4096
against 8192 keys) and on XLA's (the cell's own prefill chunk: 16 rows of
256), against `reference/block_diffusion_moe.py` `span_attention`.

**`correct` 5** is the serving path at the timed shapes, compared by logits
and never by sampled ids: the resident pipeline's own prefill program (the
configuration's `denoiser`: 256 rows, 256 prompt slots, 512 cached
positions: the program the window ran) caches every row's whole prompt
blocks, then `given_blocks` blocks of given ids (a seeded half of each
block's positions the mask id, the first block opening with the prompt's
tail) go through `block_program`, each first WITHOUT commit and then with,
and for `compared_rows` of the rows the logits of every block position of
both forwards are held against the plain reference's ONE full forward pass
over prompt + given blocks under the block mask
(`reference/block_diffusion_moe.py`: float32 on the host CPU, no cache, a
layer's weights pulled from the chip and converted at a time). The commit
forward of block `g` and the reference see the same tokens; the forward
without commit must give the same logits, and had it written anything the
next block's would differ. A position whose routing the reference finds
within `ROUTING_MARGIN` of flipping is left out on both sides (the constant
says why, and where its number was read).
"""

from __future__ import annotations

import math

from .kimi import (  # noqa: F401  (the contract's names, as they are there)
    HostWeights,
    check_artifact,
    job_fields,
    seeded_leaves,
)

FAMILY = "sdar_moe"
# the wire name the registry resolves this family by
PIPELINE_TYPE = "SdarMoeForCausalLM"

# `correct` 4, max abs error against the float32 references on bfloat16
# operands; the inputs are drawn from fixed keys, so a sound program reads
# the same number every run. A limit lies between the sound reading and
# the smallest reading of a lower precision, with room on both sides.
# Attention under the span mask (32 query heads on 4 key heads of 128,
# unit-normal operands, scores of standard deviation 1), as a share of the
# reference output's rms, as `families/exaone.py` has it and for its reason
# (a query that averages 8 k values puts out less than one that averages
# 128), a limit a path (my chip runs, PR 40). The banded kernel (a
# 4096-query chunk at offset 4096 against 8192 keys, output rms 0.0246)
# reads 0.000521, 0.021 of the rms; the reference on keys and values
# rounded to 8 bits a tensor 0.00295, 0.121 of the rms (float32 arithmetic
# on the host, as the other controls of this paragraph). XLA's path (16 rows of 256
# queries and keys, output rms 0.199) reads 0.0155, 0.078 of the rms: that
# path's scores are a bfloat16 matmul's output and its softmax weights are
# rounded to bfloat16 before the value matmul, for every family (float32
# arithmetic on the host with only those two roundings reads 0.0145); the
# reference on keys and values rounded to 8 bits a tensor 0.0301, 0.151 of
# the rms, queries too 0.0364.
SPAN_ATTENTION_TOL = {"banded": 0.06, "reference": 0.11}
# expert_matmul (gate and up, SiLU, down through the grouped kernel at
# hidden 2048 and width 768, 128 groups, on outputs of rms ~1.2): 0.0243 at
# a block step's 1024 tokens and 0.0261 at a prefill chunk's 4096 (my chip
# run, PR 40); the 1024 tokens' pairs through matrices rounded to 8 bits
# an expert read 0.0821 (float32 arithmetic on the host): the limit is 1.5
# times the larger of the first and half the second.
EXPERT_MATMUL_TOL = 0.04
# How far a position's routing has to be from changing before its logits
# are compared (`block_diffusion_moe.margin`: the distance between the 8th
# and the 9th largest SOFTMAX score, the least over the six layers; the
# scores of 128 experts sum to one, the 8th is 0.023 at the median, so this
# is no number of a sigmoid router's and is not taken from
# `families/kimi.py` or `families/exaone.py`). Read on this network, my
# chip runs, PR 40, four weight seeds. The served router's scores beside
# the reference's at 4096 positions a layer: the error of the difference
# of the two scores at the choice's boundary has an rms of 0.00008 at the
# first layer, 0.0003, 0.0006, 0.0008, 0.0010 and 0.0011 to 0.0012 at the
# last (the residual stream is bfloat16, every layer adds its rounding,
# and a token whose choice flipped upstream carries a whole expert's
# difference on), where the distance itself is 0.00095 at the median and
# 0.0033 at its ninth decile: 2 % of the positions have another chosen set
# at the first layer, 17 % at the last. Then position by position through
# `correct` 5 itself (5184 block positions, 12 blocks of 48 and 6 rows):
# 30 % read 0.03 to 0.15 against the reference (a top-8 flip somewhere on
# the way) where the others read 0.009 to 0.021, and the widest margin
# among those that flipped was 0.00061; none of the 197 positions over
# 0.00075 did. 0.00075 is 1.23 times that widest margin (a wider one keeps
# too few: 1 % of the positions are over 0.001) and leaves out 96 % of the
# positions, so the configuration compares 24 rows: 1152 positions, 33 to
# 54 of them kept, and a flip among those would raise the reading by some
# 0.003 where ten would be needed to pass the limit.
ROUTING_MARGIN = 0.00075
# Logits against the plain reference's full forward pass, relative L2 over
# the compared positions whose routing is not within `ROUTING_MARGIN` of
# changing, both forwards of every given block. My chip runs, PR 40: ten
# seeds, each its own weights and inputs, read 0.0110 to 0.0137 (bf16
# weights, activations and cache, float32 accumulation, float32 router; a
# kept position reads 0.009 to 0.021); the same network from weights
# rounded to 8 bits a tensor (`int8_control`, one weight seed, the kept
# positions of either half of its 48 rows) read 0.0464 and 0.0582. The
# limit is 2.2 times the largest of the first and 0.65 of the smallest of
# the second.
DENOISER_REL_L2_TOL = 0.03


def register(seed: int, record: dict) -> None:
    """Re-register the `sdar_moe` family in this process with a factory
    whose pipelines take their weights from `seeded_leaves`."""
    import time

    from ..harness import RunFailure

    try:
        import chiaswarm_tpu.models.sdar  # noqa: F401
        from chiaswarm_tpu.pipelines.text_generation import (
            TextGenerationPipeline,
        )
    except ImportError:
        raise RunFailure(
            "this program has no models/sdar.py: it cannot serve SDAR (the "
            "parent of PR 40)") from None
    from chiaswarm_tpu import registry

    def factory(model_name, chipset, **variant):
        started = time.perf_counter()
        phases: dict = {}
        pipe = TextGenerationPipeline(
            model_name, chipset, **variant,
            weights=lambda shapes, shardings: seeded_leaves(
                shapes, shardings, int(seed), phases))
        record.setdefault("weights_ready_s", {})[model_name] = (
            time.perf_counter() - started)
        record.setdefault("weights_phases", {})[model_name] = phases
        return pipe

    registry.register_family(FAMILY)(factory)


# --- `correct` 4: the operations at this family's shapes ---------------------


def kernel_checks(config: dict, dtype, interpret: bool = False):
    """Attention under the span mask as `ops.attention` dispatches it (the
    kernel's path and XLA's) and the grouped matmul over every expert at
    this family's widths, at the configuration's `kernel_shapes`, against
    the plain references. A reading is `{<kernel>: shape, "max_abs":
    number, "limit": its tolerance}`."""
    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu.models.experts import held_experts
    from chiaswarm_tpu.ops import dot_product_attention

    from ..reference import block_diffusion_moe, moe_kernels

    failures, readings = [], []
    shapes = config["kernel_shapes"]
    held = int(config["num_experts"])
    choices = int(config["num_experts_per_tok"])

    def note(kernel, shape, got, want, limit):
        err = float(jnp.max(jnp.abs(jnp.asarray(got, jnp.float32) - want)))
        limit = float(limit)
        readings.append({kernel: list(shape), "max_abs": err, "limit": limit})
        if not err <= limit:
            failures.append(f"{kernel} {'x'.join(map(str, shape))}: max "
                            f"abs error {err:.4f} over {limit}")

    for n, shape in enumerate(shapes["span_attention"]):
        batch, queries, keys, heads, kv_heads, dim, span = shape
        ks = jax.random.split(jax.random.key(800 + n), 3)
        q = jax.random.normal(ks[0], (batch, queries, heads, dim), dtype)
        k = jax.random.normal(ks[1], (batch, keys, kv_heads, dim), dtype)
        v = jax.random.normal(ks[2], (batch, keys, kv_heads, dim), dtype)
        got = jax.jit(lambda q, k, v, span=span: dot_product_attention(
            q, k, v, scale=dim ** -0.5, causal=True, span=span))(q, k, v)
        want = block_diffusion_moe.span_attention(q, k, v, dim ** -0.5, span)
        # the path `ops.attention` takes for these shapes on a chip
        path = "banded" if queries >= 1024 and not interpret else "reference"
        note("span_attention", shape, got, want,
             SPAN_ATTENTION_TOL[path] * jnp.sqrt(jnp.mean(want * want)))
    for n, (tokens, hidden, width) in enumerate(shapes["expert_matmul"]):
        ks = jax.random.split(jax.random.key(400 + n), 5)
        h = jax.random.normal(ks[0], (tokens, hidden), dtype)
        gate, up = (jax.random.normal(key, (held, hidden, width), dtype)
                    / math.sqrt(hidden) for key in ks[1:3])
        # outputs of unit scale, as the layer's are after its weights
        down = jax.random.normal(ks[3], (held, width, hidden), dtype) \
            * (2.0 / math.sqrt(width))
        # every token's distinct choices over all the experts, uneven (the
        # low experts drawn more often): every pair is held here
        scores = jax.random.gumbel(ks[4], (tokens, held)) \
            - 0.02 * jnp.arange(held)
        local = jax.lax.top_k(scores, choices)[1].astype(jnp.int32)
        experts = {"gate": gate, "up": up, "down": down}
        got, _ = jax.jit(lambda e, h, l: held_experts(
            e, h, l, interpret=False))(experts, h, local)
        note("expert_matmul", (tokens, hidden, width), got,
             moe_kernels.expert_ffn(h, local, gate, up, down),
             EXPERT_MATMUL_TOL)
    return failures, readings


# --- the network's half of `correct` 5 ---------------------------------------


def denoiser_inputs(pipe, config: dict, seed: int) -> dict:
    """One seeded pass at the timed shapes (the configuration's
    `denoiser`): `rows` prompts with lengths log-uniform over the traffic's
    range and ids uniform over the vocabulary, `given_blocks` blocks of
    given ids a row (a seeded half of each block's positions the mask id;
    the first block opens with the prompt's tail), and the `compared_rows`
    rows whose logits are compared."""
    import numpy as np

    want = config["denoiser"]
    rng = np.random.default_rng(seed)
    rows, slots = int(want["rows"]), int(want["prompt_slots"])
    low, high = int(want["length_min"]), int(want["length_max"])
    vocabulary = int(config["vocab_size"])
    length = int(config["assumed_sizes"]["block_length"])
    mask_id = int(config["assumed_sizes"]["mask_token_id"])
    blocks = int(want["given_blocks"])
    lengths = np.clip(np.exp(rng.uniform(
        math.log(low), math.log(high + 1), rows)).astype(np.int32), low, high)
    ids = np.zeros((rows, slots), np.int32)
    for row, n in enumerate(lengths):
        ids[row, :n] = rng.integers(0, vocabulary, n)
    given = rng.integers(0, vocabulary, (rows, blocks, length)).astype(
        np.int32)
    given[rng.random(given.shape) < 0.5] = mask_id
    for row, n in enumerate(lengths):
        whole, tail = n // length * length, n % length
        given[row, 0, :tail] = ids[row, whole:n]
    return {"ids": ids, "lengths": lengths, "given": given,
            "compared": np.sort(rng.choice(
                rows, int(want["compared_rows"]), replace=False)),
            "positions": int(want["positions"]),
            "sizes": {key: config[key] for key in (
                "hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "num_experts", "num_experts_per_tok",
                "rms_norm_eps", "rope_theta")} | {"block_length": length}}


def denoiser_reference(pipe, inputs: dict):
    """The plain reference's logits on the host CPU, one full forward pass
    a compared row over its whole prompt blocks and the given blocks: `[2,
    kept positions, vocabulary]`, the positions of `[compared rows, given
    blocks x block length]` whose routing margin is `ROUTING_MARGIN` at
    least (`inputs["kept"]`, for `denoiser_serve`), twice: once for the
    forward without commit, once for the commit. Every sequence is
    lengthened to the longest with whole blocks of id 0 behind it: no
    position sees a later block, so no compared logit changes, and the
    reference takes the rows side by side on an axis of their own (an
    operation a layer and not one a row, compiled once and not once a
    length; an expert's matrices meet all the rows' tokens at once)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..reference.block_diffusion_moe import forward_rows

    device = jax.local_devices(backend="cpu")[0]
    length = inputs["sizes"]["block_length"]
    sequences, wanted = [], []
    for row in inputs["compared"]:
        whole = int(inputs["lengths"][row]) // length * length
        sequences.append(np.concatenate(
            [inputs["ids"][row, :whole], inputs["given"][row].reshape(-1)]))
        wanted.append(np.arange(whole, len(sequences[-1])))
    longest = max(len(sequence) for sequence in sequences)
    sequences = [np.pad(sequence, (0, longest - len(sequence)))
                 for sequence in sequences]
    margins: list = []
    out = forward_rows(HostWeights(pipe.params), inputs["sizes"], sequences,
                       device=device, positions=wanted, margins=margins)
    # what `denoiser_serve` keeps too: [compared rows, blocks x length];
    # the position farthest from flipping where none is far enough (a
    # rehearsal's few positions; never the chip's)
    least = np.stack([np.asarray(margin)[at]
                      for margin, at in zip(margins, wanted)])
    far = least >= ROUTING_MARGIN
    inputs["margins"] = least
    inputs["kept"] = far if far.any() else least == least.max()
    with jax.default_device(device):
        kept = jnp.stack(out)[inputs["kept"]]
        return jnp.stack([kept, kept])


def _serve(pipe, params, inputs: dict):
    """`[2, kept positions, vocabulary]` (every position of the compared
    rows where the reference has not said which it keeps), in the order
    of `[compared rows, blocks x length]`: a block's logits are read at
    the kept positions only, so the chip never holds more of them than a
    forward's own."""
    import jax.numpy as jnp
    import numpy as np

    rows, slots = inputs["ids"].shape
    positions = inputs["positions"]
    blocks, length = inputs["given"].shape[1:]
    lengths = jnp.asarray(inputs["lengths"])
    kept = inputs.get("kept")
    if kept is None:
        kept = np.ones((len(inputs["compared"]), blocks * length), bool)
    row, at = np.nonzero(kept)  # row-major: the reference's order
    cache, _ = pipe.prefill_program(rows, slots, positions)(
        params, inputs["ids"], lengths)
    peek, commit = (pipe.block_program(rows, slots, positions, flag)
                    for flag in (False, True))
    out = np.zeros((2, len(row), int(pipe.config.vocab_size)), np.float32)
    for block in range(blocks):
        mine = at // length == block
        take = (jnp.asarray(inputs["compared"][row[mine]]),
                jnp.asarray(at[mine] % length))
        for n, program in enumerate((peek, commit)):
            logits, cache = program(params, cache, inputs["given"][:, block],
                                    lengths, block)
            out[n, mine] = np.asarray(logits[take])
            del logits
    return jnp.asarray(out)


def denoiser_serve(pipe, inputs: dict):
    """The resident pipeline's own prefill program, then every given block
    through its `block_program` without commit and with, in the serving
    dtype, the operations as dispatched: the logits of `[2, compared rows,
    blocks x block length]`, float32, at the positions the reference
    kept."""
    return _serve(pipe, pipe.params, inputs)


def int8_control(pipe, inputs: dict):
    """The low-precision control of the tolerance's second reading (not
    part of a run): the same evaluation from weights rounded to 8 bits a
    tensor (symmetric, one scale a matrix; a stack of experts one scale an
    expert), leaf by leaf and in place, as `families/kimi.py` has it: the
    pipeline serves rounded weights from here on."""
    import jax
    import jax.numpy as jnp

    def rounded(x):
        if x.ndim < 2:
            return x
        x32 = x.astype(jnp.float32)
        scale = jnp.max(jnp.abs(x32), axis=(-2, -1), keepdims=True) / 127.0
        return (jnp.round(x32 / scale) * scale).astype(x.dtype)

    program = jax.jit(rounded, donate_argnums=0)
    pipe.params = jax.tree_util.tree_map(program, pipe.params)
    return _serve(pipe, pipe.params, inputs)


# --- the compile check's operands --------------------------------------------


def compile_operands(spec: dict, devices):
    """The cell's block decode program (the pass's longer half) as the
    worker keys it, its arguments as shapes on the described `devices`, and
    its rows."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from chiaswarm_tpu.chips.device import ChipSet
    from chiaswarm_tpu.coalesce import prompt_slots
    from chiaswarm_tpu.models.sdar import empty_load, new_cache
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
        shaped(jax.eval_shape(lambda: new_cache(
            cfg, rows, pipe.cache_positions(slots, new_tokens), pipe.dtype))),
        shaped(jax.ShapeDtypeStruct((rows, slots), jnp.int32)),
        shaped(jax.ShapeDtypeStruct((rows,), jnp.int32)),
        shaped(jax.ShapeDtypeStruct((jobs, 2), jnp.uint32)),
        shaped(jax.ShapeDtypeStruct((rows,), jnp.int32)),
        shaped(jax.ShapeDtypeStruct((rows,), jnp.int32)),
        shaped(jax.ShapeDtypeStruct((), jnp.float32)),
        shaped(jax.ShapeDtypeStruct((), jnp.float32)),
        shaped(jax.eval_shape(lambda: empty_load(cfg))))
    program = pipe.block_decode_program(
        rows, slots, new_tokens, int(job["denoising_steps"]),
        job.get("confidence_threshold") is not None)
    return program, args, rows
