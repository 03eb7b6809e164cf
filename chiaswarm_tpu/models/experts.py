"""What the language models with routed sparse experts share
(models/kimi.py, models/exaone.py, models/sdar.py, models/qwen3_next.py,
models/glm_moe_dsa.py, models/mimo_v2.py):
the norm, the SwiGLU, the router under either of two rules, the held
experts' grouped matmul, the tally of how the routing fell, the seeded init
of a parameter tree and the head. A dense model (models/falcon_h1.py) takes
the norm, `dot`, the init and the head from here, and the tally as its
config makes it: with `expert_layers` 0 it has no row and nothing adds to
it.

`cfg` is the model's own config dataclass; what is read of it here is
`scoring_func`, `num_experts_per_tok`, `routed_scaling_factor`,
`experts_held` ((first, count): the routed experts this chip holds of
every layer), `expert_layers`, `first_k_dense_replace`, `rms_norm_eps` and,
where it has one, `zero_centred_norms` (the norms' weights are offsets from
one: Qwen3-Next). A layer's `moe` holds `router` [hidden, all the
experts], `experts` (`gate`, `up`, `down`, the held ones stacked) and,
where the model has them, `router_bias`, `shared` and `shared_gate`
([hidden, 1]: the shared expert's output is multiplied by
`sigmoid(h . w)`, Qwen3-Next).

The experts: scores `s` in float32 over ALL the model's experts, by the
config's `scoring_func`: `sigmoid(h W_g)`, the `k` largest of `s + b`
chosen (`b` the layer's correction bias: Kimi-K2, K-EXAONE), or
`softmax(h W_g)`, the `k` largest chosen (SDAR: no bias). Either way the
weights are `s_i / sum_chosen s * scale` (normalised over all the chosen,
held here or not), and the layer adds `sum_{chosen and held} w_i E_i(h)`
and, where it has a shared expert, `E_shared(h)` (times its gate where
it has one). What the absent experts would add is left out: on a chip that
is one of many sharing the layer, the exchange that brings it is not run
and nothing stands in for it. The held
experts' part is one grouped matmul over the pairs sorted by expert
(`ops.expert_matmul`): no dropped token, no capacity factor.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..ops.expert_matmul import expert_matmul, plan, row_tile


def leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def leaf_rule(path, shape) -> tuple[float, float]:
    """(std, shift) of one leaf's seeded values from its name: ones for a
    norm's weight and N(0, 0.1^2) for a norm's offset from one (a
    zero-centred norm multiplies by `1 + offset`: seeded at zero, a
    forgotten `1 +` would pass every test), N(0, 0.01^2) for the router's
    correction bias, N(2, 1) for an attention `sink` (a logit a head beside
    scores of unit deviation: at a window of 128 keys a sink of 0 holds a
    hundredth of a row's weight and one that is left out moves the logits
    less than bfloat16 does; around 2 it holds a twentieth and a softmax
    that left it out does not pass), the embedding by its width, a unit normal for `A_log`
    (which `finish_leaf` maps to the published `log U(0, 16)`), ones for
    `dt_bias`, N(0, 0.1^2) for a LayerNorm's bias (published at zero: seeded
    apart, a norm that dropped it would not pass), N(1, 0.1^2) for a state-space mixer's skip `D` (published
    at one: seeded apart, a head that read another's would not pass) and
    N(0, 0.1^2) for its convolution's bias, else a normal scaled by fan-in
    (the rows of the one matrix: a stack of experts is scaled expert by
    expert)."""
    name = leaf_name(path)
    if name.endswith("norm") or name == "dt_bias":
        return 0.0, 1.0
    if name == "D":
        return 0.1, 1.0
    if name.endswith(("norm_offset", "norm_bias")) or name == "conv_bias":
        return 0.1, 0.0
    if name == "router_bias":
        return 0.01, 0.0
    if name == "sink":
        return 1.0, 2.0
    if name == "A_log":
        return 1.0, 0.0
    if name == "embed":
        return 1.0, 0.0
    return 1.0 / math.sqrt(shape[-2]), 0.0


def finish_leaf(path, value):
    """What `leaf_rule`'s scaled normal cannot say: `A_log` is `log A`
    with `A` uniform over (0, 16), the published init (a unit normal
    through its own distribution function is uniform over (0, 1))."""
    if leaf_name(path) != "A_log":
        return value
    uniform = jax.scipy.stats.norm.cdf(value.astype(jnp.float32))
    return jnp.log(16.0 * jnp.clip(uniform, 1e-6, 1.0)).astype(value.dtype)


def init_leaves(shapes, key) -> dict:
    """Seeded values for every leaf of `shapes` (a tree of
    `jax.ShapeDtypeStruct`s), on whatever device is the default: the tiny
    presets' init (a full-size tree is made leaf by leaf on the chip,
    benchmark/families/)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for (path, leaf), k in zip(leaves, jax.random.split(key, len(leaves))):
        std, shift = leaf_rule(path, leaf.shape)
        out.append(finish_leaf(path, (
            jax.random.normal(k, leaf.shape, jnp.float32) * std
            + shift).astype(leaf.dtype)))
    return jax.tree_util.tree_unflatten(treedef, out)


def rms_norm(x, weight, eps: float, zero_centred: bool = False):
    """`zero_centred`: `weight` is the offset from one, the norm multiplies
    by `1 + weight` (the model's config says which its norms are)."""
    x32 = x.astype(jnp.float32)
    scaled = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    weight = weight.astype(jnp.float32)
    return (scaled * (1.0 + weight if zero_centred else weight)).astype(
        x.dtype)


def dot(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def swiglu(p, x):
    return dot(jax.nn.silu(dot(x, p["gate"])) * dot(x, p["up"]), p["down"])


def route(p, cfg, h):
    """The `k` experts of every token of `h` [T, hidden] and their weights
    [T, k], float32: scores over all the experts by the config's rule
    (sigmoid, or softmax over them), the choice by score plus the
    correction bias where the layer has one, the weights from the scores
    alone."""
    logits = jnp.dot(
        h.astype(jnp.float32), p["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)
    scores = (jax.nn.softmax(logits, axis=-1)
              if cfg.scoring_func == "softmax" else jax.nn.sigmoid(logits))
    biased = (scores + p["router_bias"].astype(jnp.float32)
              if "router_bias" in p else scores)
    _, chosen = jax.lax.top_k(biased, cfg.num_experts_per_tok)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / jnp.sum(picked, -1, keepdims=True)
    return chosen, weights * cfg.routed_scaling_factor


def held_experts(experts, h, local, interpret: bool = False):
    """What the held experts give for every (token, choice) pair: `h`
    [T, hidden], `local` [T, k] each pair's expert as an index into the
    held ones (`held` or more: not here, or a token that is padding).
    Returns [T, k, hidden], zero for a pair that is not here, and the
    pairs of each held expert [held]. The pairs are sorted by expert into
    one row buffer (`ops.expert_matmul.plan`), two grouped matmuls run
    over it, and each pair reads its own row back: a token's part does
    not depend on its batchmates."""
    tokens, hidden = h.shape
    held = experts["gate"].shape[0]
    here = (local >= 0) & (local < held)
    tm = row_tile(tokens)
    where = plan(jnp.where(here, local, held), held, tm)
    rows = jnp.concatenate([h, jnp.zeros((1, hidden), h.dtype)])[
        where.row_token]
    inner = expert_matmul(rows, (experts["gate"], experts["up"]),
                          where.tile_expert, where.n_tiles, tm=tm,
                          interpret=interpret)
    outs = expert_matmul(inner, (experts["down"],), where.tile_expert,
                         where.n_tiles, tm=tm, interpret=interpret)
    mine = outs[jnp.minimum(where.pair_row, outs.shape[0] - 1)]
    return jnp.where(here[..., None], mine, 0), where.sizes


def expert_layer(p, cfg, h, valid=None, interpret: bool = False):
    """The held experts' part and, where the layer has one, the shared
    expert's for tokens `h` [T, hidden] (`valid` [T]: padding is routed
    nowhere). Returns the sum
    and how the routing fell: pairs of each held expert [held], and
    (routed pairs, the fullest expert's pairs, experts with pairs, the
    row tiles the grouped matmul visited: `ops.expert_matmul.plan`'s
    `n_tiles`, reckoned here from the pairs)."""
    first, held = cfg.experts_held
    chosen, weights = route(p, cfg, h)
    local = chosen - first
    if valid is not None:
        local = jnp.where(valid[:, None], local, held)
    parts, sizes = held_experts(p["experts"], h, local, interpret)
    # in the order of the token's own choices, in float32
    routed = jnp.sum(parts.astype(jnp.float32) * weights[..., None], axis=1)
    count = (jnp.int32(h.shape[0]) if valid is None
             else jnp.sum(valid.astype(jnp.int32)))
    tm = row_tile(h.shape[0])
    stats = jnp.stack([count * cfg.num_experts_per_tok, jnp.max(sizes),
                       jnp.sum((sizes > 0).astype(jnp.int32)),
                       jnp.sum((sizes + tm - 1) // tm)])
    out = routed.astype(h.dtype)
    if "shared" in p:
        shared = swiglu(p["shared"], h)
        if "shared_gate" in p:
            gate = jnp.dot(h, p["shared_gate"],
                           preferred_element_type=jnp.float32)
            shared = (shared * jax.nn.sigmoid(gate)).astype(h.dtype)
        out = out + shared
    return out, (sizes, stats)


def feed_forward(layer, cfg, h, valid, interpret):
    """A layer's second half for tokens `h` [T, hidden]."""
    if "mlp" in layer:
        return swiglu(layer["mlp"], h), None
    return expert_layer(layer["moe"], cfg, h, valid, interpret)


def empty_load(cfg):
    """The routing's tally of a pass, all zero: pairs of each held expert
    of each expert layer, and (routed, fullest, active, row tiles) summed
    over the expert layers' calls. A model without experts
    (`expert_layers` 0, `experts_held` (0, 0)) has a tally of no rows: it
    goes through a pass as it came, and every count read from it is 0."""
    return (jnp.zeros((cfg.expert_layers, cfg.experts_held[1]), jnp.int32),
            jnp.zeros((4,), jnp.int32))


def tally(load, index: int, cfg, told):
    if told is None:
        return load
    sizes, stats = told
    pairs, sums = load
    return (pairs.at[index - cfg.first_k_dense_replace].add(sizes),
            sums + stats)


def logits_of(params, cfg, x):
    """Final norm and the head over the held rows of the vocabulary,
    float32."""
    if getattr(cfg, "zero_centred_norms", False):
        h = rms_norm(x, params["final_norm_offset"], cfg.rms_norm_eps, True)
    else:
        h = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return jnp.dot(h, params["head"], preferred_element_type=jnp.float32)
