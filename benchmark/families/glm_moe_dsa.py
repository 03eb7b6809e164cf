"""The `glm_moe_dsa` pipeline family: everything the benchmark knows of
GLM-5's language model as one of 16 chips that share each layer. What a job
of token ids carries, how its JSON artifact is judged and how seeded
weights are made on the device are the `kimi` family's, used from there (a
traffic file reads the same: `families/kimi.py`, "What a family of token
ids reads from a traffic file"); here are the operations this family
brings, the network's half of `correct` 5 and the compile check's operands
(README, "A family").

It reads the program through public names only:
`pipelines.text_generation.TextGenerationPipeline(..., weights=)`,
`param_shapes()` / `param_shardings()`, `prefill_program`, `step_program`,
the attributes `params`, `config`, `dtype`, `mesh`, `models.glm_moe_dsa`,
and the three operations
`ops.lightning_indexer.lightning_indexer`, `ops.lightning_indexer.
index_select` and `ops.sparse_latent_attention.sparse_prefill_attention` /
`sparse_decode_attention`. A program that has no `models/glm_moe_dsa.py`
(the parent of PR 49) fails `register` with a `RunFailure`, before anything
is built.

**`correct` 4**, at the configuration's `kernel_shapes`, against
`reference/dsa_kernels.py` (float32, highest precision, no kernel): the
index scores of a 4096-query span at offset 28672 against 32768 keys; the
selection of 2048 of them a query, which has to be the reference's
`jax.lax.top_k` mask bit for bit on the program's own scores; the span's
attention under that selection; a decode step of 2 rows against 32896
positions (index scores, the selected columns, the gathered attention).
**Two controls have to FAIL** attention's
limit, and a run in which either passes is not `correct`: the reference's
attention over every visible key (the selection left out), and under a
selection made from index scores rounded to 8 bits.

**`correct` 5** is the serving path at the timed shapes, compared by logits
and never by sampled ids: the resident pipeline's own prefill program (the
configuration's `denoiser`: 2 rows, 32768 prompt slots, 32896 cached
positions: the program the window ran, eight spans a row) writes both
caches, then `given_tokens` decode steps with given tokens go through
them, and for the one compared row the logits of the last prompt position
and of every step are held against the plain reference's ONE full forward
pass over prompt + given tokens (`reference/dsa_mla_moe.py`: float32 at the
highest precision, no cache, no span, in blocks of queries). **Where it
runs**: on the chip (the cell) or the host CPU (the rehearsal): a
33 k-position row is 0.5 PFLOP in float32, an hour of the host's 13 cores
and under a minute of the chip at six bfloat16 passes a product; the
residual stream lives on the host between blocks, a layer's selection on
the chip as bits, and ~3 GB of the chip is held beside the resident 10.3,
because the last pass of the window may still be draining. A position whose
routing the reference finds within `ROUTING_MARGIN` of flipping is left
out on both sides (`families/kimi.py` says why); a position whose SELECTION
is near a flip is not (beside `ROUTING_MARGIN`, why).
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

FAMILY = "glm_moe_dsa"
# the wire name the registry resolves this family by
PIPELINE_TYPE = "GlmMoeDsaForCausalLM"

# `correct` 4. The inputs are drawn from fixed keys, so a sound program
# reads the same number every run (my chip runs, PR 49). A limit lies
# between the sound reading and the smallest reading of a lower precision,
# with room on both sides.
# Index scores (a 4096-query span at offset 28672 against 32768 keys, 32
# heads of 128, unit-normal queries and keys, head weights N(0, 1) x
# 32^-1/2 x 128^-1/2), max abs error as a share of the reference's rms over
# the visible pairs (0.704): the kernel reads 0.0000 of it (bfloat16
# operands are exact in a float32 product, and the kernel adds a head's 128
# products and the 32 heads in float32 as the reference does); the reference
# with the head weights rounded to bfloat16 reads 0.0181, with queries and
# keys rounded to 8 bits a tensor 0.0712.
LIGHTNING_INDEXER_TOL = 0.008
# Attention under the selection (the same span, 64 heads of 256, unit-normal
# operands, scale 256^-1/2: a query's output averages 2048 values), max abs
# error as a share of the reference output's rms (0.0382): the kernel reads
# 0.0269 (the softmax's weights rounded to bfloat16 for the value matmul);
# queries rounded to 8 bits a tensor 0.0939, keys and values 0.157. The two
# controls, which have to read OVER it: the selection left out 8.06, a
# selection from 8-bit index scores 7.76.
SPARSE_ATTENTION_TOL = 0.055
# The decode's gathered attention (2 rows x 64 heads over 2048 of 32896
# cached latents of 576), as a share of the reference context's rms
# (0.0569): 0.0111; a cache rounded to 8 bits 0.0399.
SPARSE_DECODE_TOL = 0.025
# How far a position's routing has to be from changing before its logits
# are compared (`mla_moe.held_margin`). K-EXAONE's margin: the same hidden
# width, router rule, expert width and four expert layers behind a dense
# one (families/exaone.py: the error of a score difference has an rms of
# 0.0019 at the last layer, the widest margin that flipped 0.0047). It
# keeps 14 of a seed's 25 positions here (my chip run, PR 49).
ROUTING_MARGIN = 0.010
# No such margin for the selection: a position whose selection is near a
# flip IS compared. A flipped key is one of 2048 a head averages over, at
# the tail of the index scores and so, with seeded weights, of no
# particular weight in attention.
# My chip run, PR 49, one seed, 14 kept positions: each reads 0.0136 to
# 0.0161 (nothing stands out as a flipped expert's 0.1 to 0.2 does), and
# the reference under a selection from index scores rounded to 8 bits, in
# which some tens of each query's 2048 keys change at every layer, reads
# 0.0213 against the served logits where the exact one reads 0.0150.

# Logits against the plain reference's full forward pass, relative L2 over
# the compared positions whose routing is not within `ROUTING_MARGIN` of
# changing. My chip runs, PR 49: fourteen seeds, each its own weights and
# inputs, read 0.0141 to 0.0158 (bf16 weights, activations and both caches,
# float32 accumulation, float32 index scores and router; under Kimi's 0.019
# to 0.030 with five layers for its seven, over K-EXAONE's 0.008 to 0.010
# with the absorbed latents and a 30 k-key softmax); the same network from
# weights rounded to 8 bits a tensor (`int8_control`) read 0.0438; the
# reference with the selection left out 0.109. The limit is 1.6 times the
# largest of the first and 0.57 of the second.
DENOISER_REL_L2_TOL = 0.025


def register(seed: int, record: dict) -> None:
    """Re-register the `glm_moe_dsa` family in this process with a factory
    whose pipelines take their weights from `seeded_leaves`."""
    import time

    from ..harness import RunFailure

    try:
        import chiaswarm_tpu.models.glm_moe_dsa  # noqa: F401
        from chiaswarm_tpu.pipelines.text_generation import (
            TextGenerationPipeline,
        )
    except ImportError:
        raise RunFailure(
            "this program has no models/glm_moe_dsa.py: it cannot serve "
            "GLM-5 (the parent of PR 49)") from None
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


# --- `correct` 4: the operations this family brings --------------------------


def kernel_checks(config: dict, dtype, interpret: bool = False):
    """The three operations of the selection as the program dispatches
    them, the two controls that have to fail, and a decode step's three,
    at the configuration's `kernel_shapes`, against the plain references.
    A reading is `{<kernel>: shape, "max_abs": number, "limit": its
    tolerance}`, a control's with `"has_to_exceed": true`. (The grouped
    matmul over held experts runs at K-EXAONE's exact kernel shape, which
    that family's `correct` 4 holds to its reference; here `correct` 5
    holds it, with the network around it.) Every comparison is one
    compiled function of its operands: on the chip an operation run on its
    own is a program compiled on its own, a second or more each."""
    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu.ops.lightning_indexer import (
        index_select,
        lightning_indexer,
    )
    from chiaswarm_tpu.ops.sparse_latent_attention import (
        sparse_decode_attention,
        sparse_prefill_attention,
    )

    from ..reference import dsa_kernels as ref

    failures, readings = [], []
    shapes = config["kernel_shapes"]
    topk = int(config["index_topk"])
    host = jax.local_devices(backend="cpu")[0]

    def note(kernel, shape, err, limit, control=False):
        err, limit = float(err), float(limit)
        readings.append({kernel: list(shape), "max_abs": err, "limit": limit,
                         **({"has_to_exceed": True} if control else {})})
        if control and not err > limit:
            failures.append(
                f"{kernel} {'x'.join(map(str, shape))}: the control reads "
                f"{err:.4f}, not over the limit {limit:.4f} it has to fail")
        if not control and not err <= limit:
            failures.append(f"{kernel} {'x'.join(map(str, shape))}: max "
                            f"abs error {err:.4f} over {limit:.4f}")

    @jax.jit
    def worst(got, want):
        """(the largest error, the reference's rms)."""
        return (jnp.max(jnp.abs(got.astype(jnp.float32) - want)),
                jnp.sqrt(jnp.mean(jnp.square(want))))

    @jax.jit
    def scores_apart(got, want):
        """(the largest error over the visible pairs, their rms in the
        reference, the pairs visible to one side alone)."""
        seen = jnp.isfinite(want)
        return (jnp.max(jnp.where(seen, jnp.abs(got - want), 0.0)),
                jnp.sqrt(jnp.sum(jnp.where(seen, want, 0.0) ** 2)
                         / jnp.sum(seen)),
                jnp.sum(jnp.isfinite(got) != seen))

    @jax.jit
    def masks_apart(mask, count, chosen):
        return jnp.sum((mask != 0) != chosen) + jnp.sum(
            count != jnp.sum(chosen, -1))

    for n, (index_shape, attention_shape) in enumerate(zip(
            shapes["lightning_indexer"], shapes["sparse_latent_attention"])):
        queries, keys, index_heads, index_dim = index_shape
        heads, dim = attention_shape[2:]
        assert attention_shape[:2] == [queries, keys], shapes
        scale = dim ** -0.5

        @jax.jit
        def span(key):
            """Seeded operands (the chip's own generator: half a billion
            values of the default one take its compiler 10 s a shape), and
            what the program's three operations make of them."""
            ks = jax.random.split(key, 6)
            q_i = jax.random.normal(ks[0], (queries, index_heads, index_dim),
                                    dtype)
            w_i = jax.random.normal(ks[1], (queries, index_heads)) * (
                index_heads ** -0.5 * index_dim ** -0.5)
            k_i = jax.random.normal(ks[2], (keys, index_dim), dtype)
            q, k, v = (jax.random.normal(key, (rows, heads * dim), dtype)
                       for key, rows in zip(ks[3:], (queries, keys, keys)))
            scores = lightning_indexer(q_i[None], w_i[None], k_i[None])
            mask, count = index_select(scores, topk)
            got = sparse_prefill_attention(q[None], k[None], v[None], mask,
                                           scale, heads)
            return (q_i, w_i, k_i, q, k, v), scores[0], mask[0], count[0], \
                got[0]

        (q_i, w_i, k_i, q, k, v), scores, mask, count, got = span(
            jax.random.key(800 + n, impl="rbg"))
        want = ref.index_scores(q_i, w_i, k_i)
        err, size, strays = scores_apart(scores, want)
        if int(strays):
            failures.append("lightning_indexer: the visible pairs are not "
                            "the causal ones")
        note("lightning_indexer", index_shape, err,
             LIGHTNING_INDEXER_TOL * float(size))
        # the selection, exact: top_k's mask on the program's own scores
        chosen = ref.selection(scores, topk)
        note("index_select", [queries, keys, topk],
             int(masks_apart(mask, count, chosen)), 0)
        # attention under it, and the two controls that have to fail
        err, size = worst(got, ref.masked_attention(q, k, v, chosen, scale,
                                                    heads))
        limit = SPARSE_ATTENTION_TOL * float(size)
        note("sparse_latent_attention", attention_shape, err, limit)
        note("control_no_selection", attention_shape, worst(
            got, ref.masked_attention(q, k, v, jnp.isfinite(want), scale,
                                      heads))[0], limit, control=True)
        coarse = ref.selection(ref.rounded_to_8_bits(scores), topk)
        note("control_selection_from_8_bit_scores", attention_shape, worst(
            got, ref.masked_attention(q, k, v, coarse, scale, heads))[0],
            limit, control=True)
        del q_i, w_i, k_i, q, k, v, scores, mask, got, want, chosen, coarse
    for n, (rows, positions, heads, latent, rope) in enumerate(
            shapes["decode"]):
        index_heads, index_dim = shapes["lightning_indexer"][0][2:]
        # unit queries and keys over the cache's values: scores of unit
        # standard deviation
        norm = (latent + rope) ** -0.5

        @jax.jit
        def step(key):
            ks = jax.random.split(key, 7)
            q_i = jax.random.normal(
                ks[0], (rows, 1, index_heads, index_dim), dtype)
            w_i = jax.random.normal(ks[1], (rows, 1, index_heads)) * (
                index_heads ** -0.5 * index_dim ** -0.5)
            k_i = jax.random.normal(ks[2], (rows, positions, index_dim),
                                    dtype)
            cache = jax.random.normal(ks[3], (rows, positions, latent + rope),
                                      dtype)
            q_lat = jax.random.normal(ks[4], (rows, heads, latent), dtype)
            q_rope = jax.random.normal(ks[5], (rows, heads, rope), dtype)
            seen = jax.random.randint(ks[6], (rows,), positions // 2,
                                      positions + 1)
            visible = jnp.arange(positions)[None, :] < seen[:, None]
            scores = lightning_indexer(q_i, w_i, k_i, visible)
            columns, chosen = index_select(scores, topk, "indices")
            context, read = sparse_decode_attention(
                q_lat, q_rope, cache, columns[:, 0], chosen[:, 0], norm)
            # (a row that sees fewer than `topk` fills up with column 0,
            # not chosen: those are dropped, not written)
            picked = jnp.zeros((rows, positions), bool).at[
                jnp.arange(rows)[:, None],
                jnp.where(chosen[:, 0], columns[:, 0], positions)].set(
                    True, mode="drop")
            return (q_lat, q_rope, cache), scores[:, 0], picked, context, \
                jnp.max(read)

        (q_lat, q_rope, cache), scores, picked, context, read = step(
            jax.random.key(900 + n, impl="rbg"))
        # two rows: `jax.lax.top_k` on the host, whose compiler takes a
        # sort of 32896 in under a second where the chip's takes 12
        want_mask = jax.device_put(ref.selection(
            jax.device_put(scores, host), topk), picked.sharding)
        note("index_select_decode", [rows, positions, topk],
             int(jnp.sum(picked != want_mask)), 0)
        if int(read) > topk:
            failures.append(f"a decode step read {int(read)} latent rows a "
                            f"row, over {topk}")
        err, size = worst(context, ref.latent_attention(
            q_lat, q_rope, cache, want_mask, norm))
        note("sparse_decode_attention", [rows, positions, heads], err,
             SPARSE_DECODE_TOL * float(size))
    return failures, readings


# --- the network's half of `correct` 5 ---------------------------------------

SIZES = ("hidden_size", "q_lora_rank", "kv_lora_rank", "num_attention_heads",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "index_n_heads", "index_head_dim", "index_topk",
         "num_experts_per_tok", "routed_scaling_factor", "rms_norm_eps",
         "rope_parameters")


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
            "sizes": {key: config[key] for key in SIZES} | {
                "n_routed_experts": int(
                    config["deployment_share"]["router_width"])},
            "held": tuple(config["deployment_share"]["experts_held"])}


class ChipWeights:
    """The resident tree as the reference indexes it, left where it is:
    the reference runs on the chip that holds it."""

    def __init__(self, tree):
        self.tree = tree

    def __len__(self):
        return len(self.tree)

    def __getitem__(self, key):
        item = self.tree[key]
        return ChipWeights(item) if key == "layers" else item


def denoiser_reference(pipe, inputs: dict, selection: str = "exact"):
    """The plain reference's logits, one full forward pass a compared row:
    `[kept positions, vocabulary]`, the positions of `[compared rows, 1 +
    given tokens]` whose routing margin is `ROUTING_MARGIN` at least
    (`inputs["kept"]`, for `denoiser_serve`). On the chip that holds the
    weights where there is one (the module docstring says why), else on
    the host CPU. `selection` is the controls' (`reference/dsa_mla_moe.py`),
    not a run's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..reference.dsa_mla_moe import forward_rows

    on_chip = jax.default_backend() == "tpu"
    device = (pipe.mesh.devices.flat[0] if on_chip
              else jax.local_devices(backend="cpu")[0])
    weights = (ChipWeights if on_chip else HostWeights)(pipe.params)
    sequences, wanted = [], []
    for row in inputs["compared"]:
        length = int(inputs["lengths"][row])
        sequences.append(np.concatenate(
            [inputs["ids"][row, :length], inputs["given"][row]]))
        wanted.append(np.arange(length - 1, len(sequences[-1])))
    margins: list = []
    out = forward_rows(weights, inputs["sizes"], sequences,
                       held=inputs["held"], device=device, positions=wanted,
                       margins=margins, selection=selection,
                       query_block=512, head_group=4)
    # what `denoiser_serve` keeps too: [compared rows, 1 + given tokens];
    # the position farthest from flipping where none is far enough (a
    # rehearsal's seven positions)
    least = np.stack([np.asarray(margin)[at]
                      for margin, at in zip(margins, wanted)])
    if selection == "exact":  # a control is compared where the run was
        far = least >= ROUTING_MARGIN
        inputs["kept"] = far if far.any() else least == least.max()
    with jax.default_device(device):
        return jnp.stack(out)[inputs["kept"]]


# --- the compile check's operands --------------------------------------------


def compile_operands(spec: dict, devices):
    """The cell's prefill program (the pass's longer half, and the one
    that holds the three kernels this family brings) as the worker keys it,
    its arguments as shapes on the described `devices`, and its rows."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from chiaswarm_tpu.chips.device import ChipSet
    from chiaswarm_tpu.chips.requirements import coalesce_rows_limit
    from chiaswarm_tpu.coalesce import prompt_slots
    from chiaswarm_tpu.pipelines.text_generation import (
        TextGenerationPipeline,
    )

    config, traffic = spec["config"], spec["traffic"]
    job = {**config["job"], **traffic["job"]}
    chipset = ChipSet(list(devices))
    pipe = TextGenerationPipeline(
        job["model_name"], chipset,
        dtype=jnp.dtype(config["kernel_dtype"]),
        weights=lambda shapes, shardings: jax.tree_util.tree_map(
            lambda s, place: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=place), shapes, shardings))
    slots = prompt_slots(int(traffic["tokens"]["length_max"]))
    new_tokens = int(job["max_new_tokens"])
    # the pass the program's own budget of cached positions gives
    rows = min(coalesce_rows_limit(chipset, job["model_name"],
                                   slots + new_tokens),
               int(traffic["clients"]) * int(traffic["tokens"]["sequences"]))
    whole = NamedSharding(pipe.mesh, PartitionSpec())
    args = (pipe.params,
            jax.ShapeDtypeStruct((rows, slots), jnp.int32, sharding=whole),
            jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=whole))
    return pipe.prefill_program(rows, slots, slots + new_tokens), args, rows
