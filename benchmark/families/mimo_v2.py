"""The `mimo_v2` pipeline family: everything the benchmark knows of
MiMo-V2.5's language model as one of 16 chips that share each layer. What a
job of token ids carries, how its JSON artifact is judged and how seeded
weights are made on the device are the `kimi` family's, used from there (a
traffic file reads the same: `families/kimi.py`, "What a family of token
ids reads from a traffic file"); here are the operation this family
brings, the network's half of `correct` 5 and the compile check's operands
(README, "A family").

It reads the program through public names only:
`pipelines.text_generation.TextGenerationPipeline(..., weights=)`,
`param_shapes()` / `param_shardings()`, `prefill_program`, `step_program`,
the attributes `params`, `config`, `dtype`, `mesh`, `models.mimo_v2`,
`models.experts` (`held_experts`) and the operation
`ops.wide_key_attention.wide_key_attention`. A program that has no
`models/mimo_v2.py` (the parent of PR 57) fails `register` with a
`RunFailure`, before anything is built.

**`correct` 4**, at the configuration's `kernel_shapes`, against
`reference/wide_key_kernels.py` (float32, highest precision, no kernel): a
4096-query span of 64 query heads of 192 on values of 128, a full layer's
(4 key heads, the row's last span at offset 28672 against 32768 keys) and
a window layer's (8 key heads, the span behind the 128 keys before it,
under the window of 128 with the sink); **one control has to FAIL** the
window call's limit, and a run in which it passes is not `correct`: the
reference with the sink left out. The grouped matmul over held experts at
this family's hidden width (`[tokens, 4096, 2048]` is no sibling's shape)
against `reference/moe_kernels.py`.

**`correct` 5** is the serving path at the timed shapes, compared by logits
and never by sampled ids: the resident pipeline's own prefill program (the
configuration's `denoiser`: 2 rows, 32768 prompt slots, 32896 cached
positions: the program the window ran, eight spans a row) writes both kinds
of cache, then `given_tokens` decode steps with given tokens go through the
whole cache and the rings, and for the one compared row the logits of the
last prompt position and of every step are held against the plain
reference's ONE full forward pass over prompt + given tokens
(`reference/gqa_sink_moe.py`: float32 at the highest precision, no cache,
no span, in blocks of queries and a key head at a time). **Where it runs**:
on the chip (the cell) or the host CPU (the rehearsal): a 33 k-position row
is a quarter of a PFLOP in float32, the better part of an hour of the
host's 13 cores and under a minute of the chip at six bfloat16 passes a
product (`families/glm_moe_dsa.py` did the same first). A position whose
routing the reference finds within `ROUTING_MARGIN` of flipping is left out
on both sides (`families/kimi.py` says why).
"""

from __future__ import annotations

import math

from .glm_moe_dsa import ChipWeights, compile_operands  # noqa: F401
from .kimi import (  # noqa: F401  (the contract's names, as they are there)
    HostWeights,
    check_artifact,
    denoiser_serve,
    int8_control,
    job_fields,
    seeded_leaves,
)

FAMILY = "mimo_v2"
# the wire name the registry resolves this family by
PIPELINE_TYPE = "MiMoV2ForCausalLM"

# `correct` 4, max abs error against the float32 references on bfloat16
# operands, as a share of the reference output's rms (a query that averages
# 30 k values reads an output nine times smaller than one that averages
# 128); the inputs are drawn from fixed keys, so a sound program reads the
# same number every run. My chip runs, PR 57: the full layer's call (a
# 4096-query span at offset 28672, 16 query heads a key head, rms 0.01565)
# reads 0.0133 of the rms, the reference on keys and values rounded to 8
# bits a tensor 0.0808, queries too 0.0966; the window layer's call (8 a key
# head, the sink, rms 0.1403) 0.0285, 0.1600 and 0.1620, and the control
# that has to read OVER the limit, the reference with the sink left out,
# 0.2794. The limit is 1.75 times the largest of the first and 0.62 of the
# smallest of the second.
WIDE_KEY_ATTENTION_TOL = 0.05
# expert_matmul (gate and up, SiLU, down through the grouped kernel at
# hidden 4096, on outputs of rms 1.2): 0.0164 at a decode step's 2 tokens
# and 0.0222 at a prefill span's 4096 (my chip runs, PR 57: the same every
# run); K-EXAONE's limit, whose own readings at hidden 6144 were 0.0173 and
# 0.0248 against 0.0588 and 0.0829 through matrices rounded to 8 bits an
# expert (families/exaone.py: the same kernel, the same draw of operands at
# a narrower hidden width here).
EXPERT_MATMUL_TOL = 0.04
# How far a position's routing has to be from changing before its logits
# are compared (`mla_moe.held_margin`). K-EXAONE's and GLM-5's margin: the
# same router rule over 256 sigmoid scores, the same expert width, six
# expert layers behind a dense one for their four (families/exaone.py: the
# error of a score difference has an rms of 0.0019 at the last layer, the
# widest margin that flipped 0.0047). It keeps 14 of a seed's 49 positions
# here (my chip run, PR 57).
ROUTING_MARGIN = 0.010
# Logits against the plain reference's full forward pass, relative L2 over
# the compared positions whose routing is not within `ROUTING_MARGIN` of
# changing (bf16 weights, activations and both caches, float32
# accumulation, float32 router and softmax). PERF.md section 6, PR 57, has
# the readings (my chip runs): the served network over its seeds, each its
# own weights and inputs, and the same network from weights rounded to 8
# bits a tensor (`int8_control`); the limit lies between the largest of the
# first and the second with room on both sides, and the reference with the
# sink left out, with the values' scale left out or with the two rotary
# bases swapped reads over it.
DENOISER_REL_L2_TOL = 0.012


def register(seed: int, record: dict) -> None:
    """Re-register the `mimo_v2` family in this process with a factory
    whose pipelines take their weights from `seeded_leaves`."""
    import time

    from ..harness import RunFailure

    try:
        import chiaswarm_tpu.models.mimo_v2  # noqa: F401
        from chiaswarm_tpu.pipelines.text_generation import (
            TextGenerationPipeline,
        )
    except ImportError:
        raise RunFailure(
            "this program has no models/mimo_v2.py: it cannot serve "
            "MiMo-V2.5 (the parent of PR 57)") from None
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
    """Causal grouped-query attention with keys wider than values as the
    program dispatches it, a full layer's call and a window layer's with
    its sink, the control that has to fail, and the grouped matmul over
    held experts at this family's widths, at the configuration's
    `kernel_shapes`, against the plain references. A reading is
    `{<kernel>: shape, "max_abs": number, "limit": its tolerance}`, a
    control's with `"has_to_exceed": true`."""
    import jax
    import jax.numpy as jnp

    from chiaswarm_tpu.models.experts import held_experts
    from chiaswarm_tpu.ops.wide_key_attention import wide_key_attention

    from ..reference import moe_kernels, wide_key_kernels

    failures, readings = [], []
    shapes = config["kernel_shapes"]
    held = int(config["n_routed_experts"])
    router = int(config["deployment_share"]["router_width"])
    choices = int(config["num_experts_per_tok"])

    def note(kernel, shape, got, want, limit, control=False):
        err = float(jnp.max(jnp.abs(jnp.asarray(got, jnp.float32) - want)))
        limit = float(limit)
        readings.append({kernel: list(shape), "max_abs": err, "limit": limit,
                         **({"has_to_exceed": True} if control else {})})
        if control and not err > limit:
            failures.append(
                f"{kernel} {'x'.join(map(str, shape))}: the control reads "
                f"{err:.4f}, not over the limit {limit:.4f} it has to fail")
        if not control and not err <= limit:
            failures.append(f"{kernel} {'x'.join(map(str, shape))}: max "
                            f"abs error {err:.4f} over {limit:.4f}")

    for n, shape in enumerate(shapes["wide_key_attention"]):
        queries, keys, heads, kv_heads, key_dim, value_dim, window, sink = \
            shape
        scale = key_dim ** -0.5
        # a full layer's call stands at its offset in the row's cache; a
        # window layer's is the span behind its tail
        offset = None if window else jnp.int32(keys - queries)

        @jax.jit
        def call(key):
            """Seeded operands (the chip's own generator) and what the
            program's operation makes of them."""
            ks = jax.random.split(key, 4)
            q = jax.random.normal(ks[0], (queries, heads, key_dim), dtype)
            k = jax.random.normal(ks[1], (keys, kv_heads, key_dim), dtype)
            v = jax.random.normal(ks[2], (keys, kv_heads, value_dim), dtype)
            sinks = (jax.random.normal(ks[3], (heads,)) if sink else None)
            got = wide_key_attention(q[None], k[None], v[None], scale, window,
                                     sinks, offset=offset,
                                     interpret=interpret)
            return (q, k, v, sinks), got[0]

        (q, k, v, sinks), got = call(jax.random.key(1000 + n, impl="rbg"))
        want = wide_key_kernels.wide_key_attention(
            q, k, v, scale, window, sinks, offset)
        limit = WIDE_KEY_ATTENTION_TOL * jnp.sqrt(jnp.mean(want * want))
        note("wide_key_attention", shape, got, want, limit)
        if sink:
            note("control_no_sink", shape, got,
                 wide_key_kernels.wide_key_attention(
                     q, k, v, scale, window, None, offset), limit,
                 control=True)
        del q, k, v, got, want
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
            - 0.02 * jnp.arange(router)
        local = jax.lax.top_k(scores, choices)[1].astype(jnp.int32)
        experts = {"gate": gate, "up": up, "down": down}
        got, _ = jax.jit(lambda e, h, l: held_experts(
            e, h, l, interpret=interpret))(experts, h, local)
        note("expert_matmul", (tokens, hidden, width), got,
             moe_kernels.expert_ffn(h, local, gate, up, down),
             EXPERT_MATMUL_TOL)
    return failures, readings


# --- the network's half of `correct` 5 ---------------------------------------

SIZES = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "swa_num_key_value_heads", "head_dim", "v_head_dim",
         "partial_rotary_factor", "rope_theta", "swa_rope_theta",
         "attention_value_scale", "sliding_window", "num_experts_per_tok",
         "routed_scaling_factor", "layernorm_epsilon")


def denoiser_inputs(pipe, config: dict, seed: int) -> dict:
    """One seeded pass at the timed shapes (the configuration's
    `denoiser`): `rows` prompts with lengths log-uniform over the traffic's
    range and ids uniform over the held vocabulary, `given_tokens` given
    tokens a row, and the `compared_rows` rows whose logits are compared."""
    import numpy as np

    want = config["denoiser"]
    share = config["deployment_share"]
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
                "n_routed_experts": int(share["router_width"]),
                # the layers as run, not the published 48
                "hybrid_layer_pattern": list(
                    share["layers_run"]["hybrid_layer_pattern"])},
            "held": tuple(share["experts_held"])}


def denoiser_reference(pipe, inputs: dict, control=None):
    """The plain reference's logits, one full forward pass a compared row:
    `[kept positions, vocabulary]`, the positions of `[compared rows, 1 +
    given tokens]` whose routing margin is `ROUTING_MARGIN` at least
    (`inputs["kept"]`, for `denoiser_serve`). On the chip that holds the
    weights where there is one (the module docstring says why), else on
    the host CPU. `control` is the controls' (`reference/gqa_sink_moe.py`),
    not a run's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..reference.gqa_sink_moe import forward_rows

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
                       margins=margins, control=control, query_block=256)
    # what `denoiser_serve` keeps too: [compared rows, 1 + given tokens];
    # the position farthest from flipping where none is far enough (a
    # rehearsal's seven positions)
    least = np.stack([np.asarray(margin)[at]
                      for margin, at in zip(margins, wanted)])
    if control is None:  # a control is compared where the run was
        far = least >= ROUTING_MARGIN
        inputs["kept"] = far if far.any() else least == least.max()
    with jax.default_device(device):
        return jnp.stack(out)[inputs["kept"]]
