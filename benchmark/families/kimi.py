"""The `kimi` pipeline family: everything the benchmark knows of Kimi-K2's
language model as one chip's share of an expert-parallel deployment. Seeded
weights made on the device, what a job of token ids carries and how its
JSON artifact is judged, the new operations' comparison, the network's half
of `correct` 5 and the compile check's operands (README, "A family").

It reads the program through public names only:
`pipelines.text_generation.TextGenerationPipeline(..., weights=)`,
`param_shapes()` / `param_shardings()`, `prefill_program`, `step_program`,
`decode_program`, the attributes `params`, `config`, `dtype`, `mesh`, and
`models.kimi` (`leaf_rule`, `held_experts`, `empty_load`, `new_cache`) and
the two operations `ops.latent_attention.latent_decode_attention` and
`ops.dot_product_attention(causal=)`. A program that has no such pipeline
(the parent of PR 32) fails `register` with a `RunFailure`, before anything
is built.

**What a family of token ids reads from a traffic file** (`job_fields`):
`tokens.sequences` rows a job, each row's length log-uniform over
`[tokens.length_min, tokens.length_max]`, its ids ranks `1..tokens.vocabulary`
drawn with probability proportional to `rank ** -tokens.zipf_exponent` and
mapped to ids by a permutation. A job takes one draw of the harness's
generator (32 bits, the seed of the job's rows); the first job made from a
generator takes one more before it, the seed of the run's permutation. The
probe draws nothing: its rows and its permutation come from `probe.seed`.
`job.max_new_tokens` and `job.temperature` ride every job.

**`correct` 5** is the serving path at the timed shapes, compared by
logits and never by sampled ids: the resident pipeline's own prefill
program (the configuration's `denoiser`: 256 rows, 256 prompt slots, 512
cached positions: the program the window ran) writes the cache, then
`given_tokens` decode steps with given tokens go through it, and for
`compared_rows` of the rows the logits of the last prompt position and of
every step are held against the plain reference's ONE full forward pass over
prompt + given tokens (`reference/mla_moe.py`: float32 on the host CPU, no
cache, a layer's weights pulled from the chip and converted at a time). A
position whose routing the reference finds within `ROUTING_MARGIN` of
flipping is left out on both sides (the constant says why).
"""

from __future__ import annotations

import hashlib
import json
import math
import time
import weakref

FAMILY = "kimi_k2"
# the wire name the registry resolves this family by
PIPELINE_TYPE = "KimiK2ForCausalLM"

# `correct` 4, max abs error against the float32 references
# (`reference/moe_kernels.py`) on bfloat16 operands; the inputs are drawn
# from fixed keys, so a sound program reads the same number every run (my
# chip runs, PR 32). A limit is about twice the sound reading and under the
# smallest reading of a lower precision.
# expert_matmul (gate and up, SiLU, down through the grouped kernel, on
# outputs of rms 1.19): 0.0229 at a decode step's 256 tokens (16-row tiles)
# and 0.0225 at a prefill chunk's 4096 (128-row tiles, groups of several
# tiles): one bfloat16 rounding of the inner activation and of the output.
# The same pairs through matrices rounded to 8 bits a tensor: 0.076, 0.089.
EXPERT_MATMUL_TOL = 0.045
# latent decode attention (256 rows x 64 heads over 512 cached positions,
# scores of standard deviation 2 as the model's scale gives, context of rms
# 0.32): 0.0132. Scores rounded to bfloat16 before the softmax read 0.038,
# a cache rounded to 8 bits 0.067.
LATENT_ATTENTION_TOL = 0.025
# causal attention with 192-wide keys and 128-wide values on the XLA path
# (16 rows x 256 positions x 64 heads, the softmax weights rounded to
# bfloat16 before the value matmul, as `ops.attention` does for every
# family): 0.0149, where `checks.ATTENTION_TOL`'s own readings on that path
# reach 0.0085 at narrower heads; scores rounded to bfloat16 read 0.046.
CAUSAL_ATTENTION_TOL = 0.03
# Logits against the plain reference's full forward pass, relative L2 over
# the compared positions whose routing is not within `ROUTING_MARGIN` of
# changing (below). My chip runs, PR 32: nine runs of the cell, each its own
# weight and input seed, read 0.0188 to 0.0295 (bf16 weights, activations
# and cache, float32 accumulation, float32 router; a kept position reads
# 0.015 to 0.048); the same network from weights rounded to 8 bits a tensor
# (`int8_control`, two input seeds) read 0.101 and 0.109 over all
# positions. The limit is 1.5 times the largest of the first and 0.45 of
# the smallest of the second; one kept position that flipped after all
# (0.15 of 42) would read 0.032.
DENOISER_REL_L2_TOL = 0.045
# Top-k routing is the one discontinuity of the network: a token whose
# biased score for a held expert lies within a rounding's reach of the
# choice's boundary may have that expert flip in or out on the bfloat16
# path, which moves its output by a whole expert's part (one position in
# twelve read 0.09 to 0.30 where its neighbours read 0.02) and says nothing
# of the arithmetic. The reference reports each position's least margin
# over the expert layers (`mla_moe.held_margin`, in score units); every
# position that read over 0.05 had a margin under 0.0017, and none of the
# 140 positions over 0.002 did. Positions under this margin are left out
# of the comparison, on both sides (15 to 27 % of them).
ROUTING_MARGIN = 0.003


# --- seeded weights, made on the device --------------------------------------


def seeded_leaves(shapes, shardings, seed: int, phases: dict | None = None):
    """Every leaf of `shapes` from `seed`, each a window of one seeded
    normal pool (twice the largest leaf, float32) at an offset hashed from
    the leaf's index, scaled and shifted by the program's own rule for the
    leaf's name (`models.kimi.leaf_rule`: fan-in, norms 1, the correction
    bias N(0, 0.01^2)). `families/flux.py` has the scheme and its reasons;
    here a leaf's rule reads the rows of one matrix, so a stack of experts
    is scaled expert by expert."""
    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu.models.kimi import leaf_rule

    phases = {} if phases is None else phases
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    places = jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: isinstance(x, jax.sharding.Sharding))
    sizes = [math.prod(leaf.shape) for _, leaf in leaves]
    pool_size = 2 * max(sizes)
    takes: dict = {}

    def take(shape, dtype, sharding):
        key = (shape, str(dtype), sharding)
        if key not in takes:
            size = math.prod(shape)

            def window(pool, offset, std, shift):
                flat = jax.lax.dynamic_slice(pool, (offset,), (size,))
                return (flat.reshape(shape) * std + shift).astype(dtype)

            takes[key] = jax.jit(window, out_shardings=sharding)
        return takes[key]

    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        started = time.perf_counter()
        pool = jax.jit(
            lambda key: jax.random.normal(key, (pool_size,), jnp.float32),
            out_shardings=places[0])(jax.random.key(seed))
        pool.block_until_ready()
        phases["pool_s"] = time.perf_counter() - started
        started = time.perf_counter()
        out = []
        for index, ((path, leaf), size, place) in enumerate(
                zip(leaves, sizes, places)):
            std, shift = leaf_rule(path, leaf.shape)
            offset = (index * 2654435761) % (pool_size - size + 1)
            out.append(take(tuple(leaf.shape), leaf.dtype, place)(
                pool, offset, std, shift))
        jax.block_until_ready(out)
        phases["leaves_s"] = time.perf_counter() - started
        phases["programs"] = len(takes)
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)
    return jax.tree_util.tree_unflatten(treedef, out)


def register(seed: int, record: dict) -> None:
    """Re-register the `kimi_k2` family in this process with a factory
    whose pipelines take their weights from `seeded_leaves`."""
    from ..harness import RunFailure

    try:
        from chiaswarm_tpu.pipelines.text_generation import (
            TextGenerationPipeline,
        )
    except ImportError:
        raise RunFailure(
            "this program has no pipelines/text_generation.py: it cannot "
            "serve a txt2txt job (the parent of PR 32)") from None
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


# --- a job's own fields -------------------------------------------------------

# the run's permutation of ranks to ids, by the harness's generator
_PERMUTATIONS: weakref.WeakKeyDictionary = weakref.WeakKeyDictionary()


def draw_rows(tokens: dict, seed: int, permutation) -> list[list[int]]:
    """`tokens.sequences` rows from `seed`: lengths log-uniform over
    `[length_min, length_max]`, ids Zipf over the ranks, through
    `permutation`."""
    import numpy as np

    rng = np.random.default_rng(seed)
    count = int(tokens["sequences"])
    low, high = int(tokens["length_min"]), int(tokens["length_max"])
    lengths = np.clip(np.exp(rng.uniform(
        math.log(low), math.log(high + 1), count)).astype(int), low, high)
    ranks = np.arange(1, int(tokens["vocabulary"]) + 1, dtype=np.float64)
    weights = ranks ** -float(tokens["zipf_exponent"])
    drawn = rng.choice(len(ranks), size=int(lengths.sum()),
                       p=weights / weights.sum())
    ids = permutation[drawn]
    edges = np.cumsum(lengths)[:-1]
    return [row.tolist() for row in np.split(ids, edges)]


def permutation_of(tokens: dict, seed: int):
    import numpy as np

    return np.random.default_rng(seed).permutation(int(tokens["vocabulary"]))


def job_fields(rng, traffic: dict, count: int, probe: bool) -> dict:
    """`prompt_ids`: the job's rows (the module docstring says what is
    drawn from `rng`, and in which order)."""
    tokens = traffic["tokens"]
    if probe:
        seed = int(traffic["probe"]["seed"])
        return {"prompt_ids": draw_rows(
            tokens, seed, permutation_of(tokens, seed + 1))}
    if rng not in _PERMUTATIONS:
        _PERMUTATIONS[rng] = permutation_of(tokens, rng.getrandbits(32))
    return {"prompt_ids": draw_rows(tokens, rng.getrandbits(32),
                                    _PERMUTATIONS[rng])}


def check_artifact(blob: bytes, ref: dict, config: dict) -> str | None:
    """`correct` 1 for one job's artifact: hashes to its name, is JSON
    `{"token_ids": rows}` with the configuration's `artifact.sequences`
    rows of `artifact.max_new_tokens` ids of the held vocabulary, and the
    rows are not all alike."""
    if hashlib.sha256(blob).hexdigest() != ref.get("sha256"):
        return "artifact does not hash to its name"
    try:
        rows = json.loads(blob)["token_ids"]
    except (ValueError, KeyError, TypeError):
        return "artifact is no JSON object with token_ids"
    want = config["artifact"]
    vocabulary = int(config["vocab_size"])
    if not isinstance(rows, list) or len(rows) != int(want["sequences"]):
        return (f"token_ids holds {len(rows) if isinstance(rows, list) else 0}"
                f" rows, not {want['sequences']}")
    for row in rows:
        if not isinstance(row, list) \
                or len(row) != int(want["max_new_tokens"]):
            return (f"a row holds {len(row) if isinstance(row, list) else 0}"
                    f" ids, not {want['max_new_tokens']}")
        if not all(isinstance(i, int) and 0 <= i < vocabulary for i in row):
            return f"a row holds ids outside [0, {vocabulary})"
    if len(rows) > 1 and all(row == rows[0] for row in rows):
        return "every row holds the same ids"
    return None


# --- `correct` 4: the operations this family brings --------------------------


def kernel_checks(config: dict, dtype, interpret: bool = False):
    """The grouped matmul over held experts, the decode attention over a
    latent cache and causal attention with values narrower than keys, each
    as the program dispatches it, at the configuration's `kernel_shapes`,
    against `reference/moe_kernels.py`. A reading is `{<kernel>: shape,
    "max_abs": number, "limit": its tolerance}`."""
    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu.models.kimi import held_experts
    from chiaswarm_tpu.ops import dot_product_attention
    from chiaswarm_tpu.ops.latent_attention import latent_decode_attention

    from ..reference import moe_kernels as ref

    failures, readings = [], []
    shapes = config["kernel_shapes"]
    held = int(config["n_routed_experts"])
    router = int(config["deployment_share"]["router_width"])
    choices = int(config["num_experts_per_tok"])
    rope = int(config["qk_rope_head_dim"])
    latent, v_dim = int(config["kv_lora_rank"]), int(config["v_head_dim"])
    qk_dim = int(config["qk_nope_head_dim"]) + rope

    def note(kernel, shape, got, want, limit, mask=None):
        err = jnp.abs(jnp.asarray(got, jnp.float32) - want)
        err = float(jnp.max(err if mask is None
                            else jnp.where(mask, err, 0.0)))
        readings.append({kernel: list(shape), "max_abs": err, "limit": limit})
        if not err <= limit:
            failures.append(f"{kernel} {'x'.join(map(str, shape))}: max "
                            f"abs error {err:.4f} over {limit}")

    for n, (tokens, hidden, width) in enumerate(shapes["expert_matmul"]):
        keys = jax.random.split(jax.random.key(400 + n), 5)
        h = jax.random.normal(keys[0], (tokens, hidden), dtype)
        gate, up = (jax.random.normal(k, (held, hidden, width), dtype)
                    / math.sqrt(hidden) for k in keys[1:3])
        # outputs of unit scale, as the layer's are after its weights
        down = jax.random.normal(keys[3], (held, width, hidden), dtype) \
            * (2.0 / math.sqrt(width))
        # every token's distinct choices over the router's whole width,
        # uneven (the low experts drawn more often): a token holds 0 to
        # `choices` of the experts here, and some hold none
        scores = jax.random.gumbel(keys[4], (tokens, router)) \
            - 0.02 * jnp.arange(router)
        local = jax.lax.top_k(scores, choices)[1].astype(jnp.int32)
        experts = {"gate": gate, "up": up, "down": down}
        got, _ = jax.jit(lambda e, h, l: held_experts(
            e, h, l, interpret=False))(experts, h, local)
        note("expert_matmul", (tokens, hidden, width), got,
             ref.expert_ffn(h, local, gate, up, down), EXPERT_MATMUL_TOL)
    # the model's own softmax scale: (nope + rope)^-1/2 x mscale^2
    yarn = config["rope_scaling"]
    scale = qk_dim ** -0.5 * (
        0.1 * yarn["mscale_all_dim"] * math.log(yarn["factor"]) + 1.0) ** 2
    for n, (rows, positions, heads) in enumerate(shapes["latent_attention"]):
        keys = jax.random.split(jax.random.key(500 + n), 4)
        q_lat = jax.random.normal(keys[0], (rows, heads, latent), dtype)
        q_rope = jax.random.normal(keys[1], (rows, heads, rope), dtype)
        cache = jax.random.normal(keys[2], (rows, positions, latent + rope),
                                  dtype)
        seen = jax.random.randint(keys[3], (rows,), 1, positions + 1)
        mask = jnp.arange(positions)[None, :] < seen[:, None]
        # unit queries and keys over the cache's 576 values: scores of
        # the standard deviation the model's scale gives over its 192
        norm = scale * math.sqrt(qk_dim / (latent + rope))
        got = jax.jit(latent_decode_attention, static_argnums=4)(
            q_lat, q_rope, cache, mask, norm)
        note("latent_attention", (rows, positions, heads), got,
             ref.latent_attention(q_lat, q_rope, cache, mask, norm),
             LATENT_ATTENTION_TOL)
    for n, (rows, length, heads) in enumerate(shapes["causal_attention"]):
        keys = jax.random.split(jax.random.key(600 + n), 3)
        q = jax.random.normal(keys[0], (rows, length, heads, qk_dim), dtype)
        k = jax.random.normal(keys[1], (rows, length, heads, qk_dim), dtype)
        v = jax.random.normal(keys[2], (rows, length, heads, v_dim), dtype)
        got = jax.jit(lambda q, k, v: dot_product_attention(
            q, k, v, scale=scale, causal=True))(q, k, v)
        note("causal_attention", (rows, length, heads), got,
             ref.causal_attention(q, k, v, scale), CAUSAL_ATTENTION_TOL)
    return failures, readings


# --- the network's half of `correct` 5 ---------------------------------------


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
            "sizes": {key: config[key] for key in (
                "hidden_size", "q_lora_rank", "kv_lora_rank",
                "num_attention_heads", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
                "routed_scaling_factor", "rms_norm_eps", "rope_theta",
                "rope_scaling")} | {
                    "n_routed_experts": int(
                        config["deployment_share"]["router_width"])},
            "held": tuple(config["deployment_share"]["experts_held"])}


class HostWeights:
    """The resident tree as the reference indexes it: `layers` stays on
    the chip until a layer is asked for, anything else comes to the host
    when it is."""

    def __init__(self, tree):
        self.tree = tree

    def __len__(self):
        return len(self.tree)

    def __getitem__(self, key):
        import jax

        item = self.tree[key]
        return HostWeights(item) if key == "layers" else jax.device_get(item)


def denoiser_reference(pipe, inputs: dict):
    """The plain reference's logits on the host CPU, one full forward pass
    a row (the rows share each layer's converted weights, nothing else):
    `[kept positions, vocabulary]`, the positions of `[compared rows, 1 +
    given tokens]` whose routing margin is `ROUTING_MARGIN` at least
    (`inputs["kept"]`, for `denoiser_serve`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..reference.mla_moe import forward_rows

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
    # what `denoiser_serve` keeps too: [compared rows, 1 + given tokens]
    inputs["kept"] = np.stack([
        np.asarray(margin)[at] >= ROUTING_MARGIN
        for margin, at in zip(margins, wanted)])
    with jax.default_device(device):
        return jnp.stack(out)[inputs["kept"]]


def _serve(pipe, params, inputs: dict):
    import jax.numpy as jnp

    rows, slots = inputs["ids"].shape
    positions = inputs["positions"]
    lengths = jnp.asarray(inputs["lengths"])
    logits, cache, _ = pipe.prefill_program(rows, slots, positions)(
        params, inputs["ids"], lengths)
    compared = jnp.asarray(inputs["compared"])
    out = [logits[compared]]
    step = pipe.step_program(rows, slots, positions)
    for number in range(inputs["given"].shape[1]):
        logits, cache = step(params, cache, inputs["given"][:, number],
                             lengths, number)
        out.append(logits[compared])
    out = jnp.stack(out, axis=1)
    return out if inputs.get("kept") is None else out[inputs["kept"]]


def denoiser_serve(pipe, inputs: dict):
    """The resident pipeline's own prefill program, then its decode step
    with the given tokens through the cache, in the serving dtype, the
    operations as dispatched: the logits of `[compared rows, 1 + given
    tokens]`, float32, at the positions the reference kept."""
    return _serve(pipe, pipe.params, inputs)


def int8_control(pipe, inputs: dict):
    """The low-precision control of the tolerance's second reading (not
    part of a run): the same evaluation from weights rounded to 8 bits a
    tensor (symmetric, one scale a matrix; a stack of experts one scale an
    expert). Leaf by leaf and in place (the leaf is donated): the chip
    cannot hold the tree twice, so the pipeline serves rounded weights
    from here on."""
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
    """The cell's decode program (the pass's longer half) as the worker
    keys it, its arguments as shapes on the described `devices`, and its
    rows."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from chiaswarm_tpu.chips.device import ChipSet
    from chiaswarm_tpu.coalesce import prompt_slots
    from chiaswarm_tpu.models.kimi import empty_load, new_cache
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
