"""Plain reference for GLM-5's language model (`model_type` `glm_moe_dsa`:
latent attention over the keys a lightning indexer selects, sigmoid-routed
sparse experts): one sequence, one full forward pass, `jax.numpy` in
float32 at the highest matmul precision. No cache, no kernel, no span:
every position's keys and values are expanded from the latents, the full
index scores are laid out, `jax.lax.top_k` picks, a masked softmax attends,
and each held expert runs on every token, weighed by zero where the token
did not choose it (`mla_moe.py`'s `routing` and `held_margin`, the same
router; its `experts` runs an expert on the tokens that chose it, a shape a
call, which the chip would compile a call).

`sizes` is the configuration's own keys (the published `config.json` names:
`hidden_size`, `q_lora_rank`, `kv_lora_rank`, `num_attention_heads`,
`qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim`, `index_n_heads`,
`index_head_dim`, `index_topk`, `n_routed_experts`, `num_experts_per_tok`,
`routed_scaling_factor`, `rms_norm_eps`, `rope_parameters`). `weights` is
indexed as the program's tree is (`embed`, `layers[i]` with `attn`, the two
norms and `mlp` or `moe`, `final_norm`, `head`; matrices `[in, out]`,
experts stacked), a layer at a time.

With `h = RMSNorm(x)`, a layer is `x += attention(h)`, `x += ffn(RMSNorm(x))`:

- `c_q = RMSNorm(h W_qa)`; `q = c_q W_qb`, a head `[q_n | q_r]`; `[c_kv |
  k_r] = h W_kva`, `c_kv = RMSNorm(c_kv)`; a head's `[k_n | v] = c_kv
  W_kvb`; plain rotary (theta from `rope_parameters`, no scaling) on `q_r`
  and on the one `k_r` every head shares; scores `(q_n.k_n + q_r.k_r) (nope
  + rope)^-1/2`.
- the indexer: `q^I = c_q W^I_qb` (heads of `index_head_dim`), `k^I =
  LayerNorm(h W^I_k)` with weight and bias, one key a position; rotary on
  the first `qk_rope_head_dim` dims of both; `w = h W^I_w heads^-1/2
  dim^-1/2`; `I[t, s] = sum_j w[t, j] ReLU(q^I[t, j] . k^I[s])`, `s <= t`.
- `S_t` = the `min(index_topk, t + 1)` largest of `I[t, .]`
  (`jax.lax.top_k`: ties to the lower position); `o_t = sum_{s in S_t}
  softmax_s(scores) v_s`; `concat(heads) W_o`.
- experts and the dense layers as `mla_moe.py`.

It is computed in blocks of `query_block` queries and `head_group` heads so
that a row of 33 k positions fits where it runs (the full `[heads, S, S]`
scores would be 280 GB): the same numbers, block by block. The residual
stream lives on the host between blocks; `device`
says where the arithmetic runs (the host CPU for the tests and the
rehearsal; the cell's 33 k-position row takes the host an hour, so its
family hands in the chip, where float32 at the highest precision is six
bfloat16 passes a product: families/glm_moe_dsa.py).

`selection` is for the two controls that have to FAIL a comparison with
the served network: `"none"` leaves the selection out (attention over every
visible key), `"int8"` selects from index scores rounded to 8 bits (255
levels a block of queries: the scores near the choice's edge tie, and the
ties go to the lower positions).

Departures from the published code, each the same function or stated in
the configuration's `assumed`: (1)-(5) as `mla_moe.py` (`held` experts, the
stack of held experts, rotary pairs as the two halves of the rotary width
for the main heads and the indexer alike, `n_group` 1, the vocabulary rows
held); (6) the indexer's Hadamard rotation and FP8 quantisation of queries
and keys are left out (an orthogonal map of both sides of a dot product);
(7) the LayerNorm on the index key has a bias and `eps` 1e-6; (8) the
multi-token-prediction layer is left out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .dsa_kernels import rounded_to_8_bits, selected_block
from .mla_moe import held_margin, rms_norm, rotate, routing, swiglu

INDEX_NORM_EPS = 1e-6


def layer_norm(x, weight, bias, eps):
    centred = x - jnp.mean(x, -1, keepdims=True)
    return centred * jax.lax.rsqrt(
        jnp.mean(centred * centred, -1, keepdims=True) + eps) * weight + bias


def rope_angles(sizes, positions):
    """[S, rope / 2] angles of plain rotary."""
    dim = sizes["qk_rope_head_dim"]
    theta = float(sizes["rope_parameters"]["rope_theta"])
    exponent = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    return positions.astype(jnp.float32)[:, None] / theta ** exponent[None, :]


def frozen(sizes: dict) -> tuple:
    """`sizes` as something a compiled function can be keyed by."""
    return tuple(sorted(
        (key, tuple(sorted(value.items())) if isinstance(value, dict)
         else value) for key, value in sizes.items()))


def thawed(sizes: tuple) -> dict:
    return {key: dict(value) if isinstance(value, tuple) else value
            for key, value in sizes}


def experts(p, sizes, h, held):
    """The held experts' part and the shared expert's, [S, hidden]: every
    held expert on every token, its weight zero where the token did not
    choose it. The stacked expert `j` is the model's expert `held[0] +
    j`."""
    first, count = held
    chosen, weights = routing(p, sizes, h)

    def one(out, expert):
        number, matrices = expert
        weight = jnp.sum(jnp.where(chosen == first + number, weights, 0.0),
                         axis=-1)
        return out + weight[:, None] * swiglu(matrices, h), None

    return jax.lax.scan(one, swiglu(p["shared"], h),
                        (jnp.arange(count), p["experts"]))[0]


@functools.partial(jax.jit, static_argnames=("sizes", "held"))
def second_half(p, norm, x, sizes, held):
    """`x + ffn(RMSNorm(x))` for positions `x` [S, hidden], `p` a layer's
    `mlp` or `moe`, and each position's `held_margin` (infinite on a dense
    layer); `sizes` frozen."""
    sizes = thawed(sizes)
    h = rms_norm(x, norm, sizes["rms_norm_eps"])
    if "router" not in p:
        return x + swiglu(p, h), jnp.full((x.shape[0],), jnp.inf)
    return (x + experts(p, sizes, h, held),
            held_margin(p, sizes, h, held))


@functools.partial(jax.jit, static_argnames=("sizes",))
def projected(p, norm, x, start, sizes):
    """`projections` of `RMSNorm(x)`, compiled a shape; `sizes` frozen."""
    sizes = thawed(sizes)
    return projections(p, sizes, rms_norm(x, norm, sizes["rms_norm_eps"]),
                       start)


def projections(p, sizes, h, start=0):
    """What attention makes of the positions `start ..` of `h` [S, hidden]:
    `c_q` [S, q rank], the latents `c_kv` [S, latent], the rotated shared
    key [S, rope], the index keys [S, index dim] (normed, rotated) and the
    index weights [S, index heads]."""
    latent, rope = sizes["kv_lora_rank"], sizes["qk_rope_head_dim"]
    heads, dim = sizes["index_n_heads"], sizes["index_head_dim"]
    eps = sizes["rms_norm_eps"]
    angles = rope_angles(sizes, start + jnp.arange(h.shape[0]))
    c_q = rms_norm(h @ p["q_a"], p["q_norm"], eps)
    kv = h @ p["kv_a"]
    c_kv = rms_norm(kv[:, :latent], p["kv_norm"], eps)
    k_r = rotate(kv[:, latent:], angles, 1.0)
    k_i = layer_norm(h @ p["index_k"], p["index_k_norm"],
                     p["index_k_norm_bias"], INDEX_NORM_EPS)
    k_i = jnp.concatenate([rotate(k_i[:, :rope], angles, 1.0),
                           k_i[:, rope:]], axis=-1)
    w_i = (h @ p["index_w"]) * heads ** -0.5 * dim ** -0.5
    return c_q, c_kv, k_r, k_i, w_i


def index_scores(q_i, k_i, w_i):
    """`I[t, s] = sum_j w[t, j] ReLU(q[t, j] . k[s])`: `q_i` [Q, heads,
    dim], `k_i` [S, dim], `w_i` [Q, heads] -> [Q, S], a head at a time."""

    def head(total, one):
        q, w = one
        return total + jnp.maximum(q @ k_i.T, 0.0) * w[:, None], None

    return jax.lax.scan(
        head, jnp.zeros((q_i.shape[0], k_i.shape[0]), jnp.float32),
        (q_i.transpose(1, 0, 2), w_i.T))[0]


@functools.partial(jax.jit, static_argnames=("sizes", "block", "selection"))
def index_block(index_q, c_q, k_i, w_i, at, sizes, block, selection="exact"):
    """[block, S] float32: the index scores of the `block` queries from
    position `at` on against every position's index key, `-inf` where a
    query does not see a key (`c_q`, `w_i`, `k_i` are every position's;
    `index_q` is `W^I_qb` as [rank, heads, dim]; `sizes` frozen). Under
    `selection` `"int8"` rounded to 8 bits, under `"none"` zero wherever
    seen."""
    sizes = thawed(sizes)
    rope = sizes["qk_rope_head_dim"]
    positions = at + jnp.arange(block)
    visible = jnp.arange(k_i.shape[0])[None, :] <= positions[:, None]
    if selection == "none":
        return jnp.where(visible, 0.0, -jnp.inf)
    q_i = jnp.einsum("qc,chd->qhd", jax.lax.dynamic_slice_in_dim(
        c_q, at, block), index_q)
    q_i = jnp.concatenate([
        rotate(q_i[..., :rope].transpose(1, 0, 2),
               rope_angles(sizes, positions), 1.0).transpose(1, 0, 2),
        q_i[..., rope:]], axis=-1)
    scores = jnp.where(visible, index_scores(
        q_i, k_i, jax.lax.dynamic_slice_in_dim(w_i, at, block)), -jnp.inf)
    # one scale over the visible scores
    return rounded_to_8_bits(scores) if selection == "int8" else scores


def selected(index_q, c_q, k_i, w_i, sizes, block, selection="exact"):
    """[S / block, block, S / 8] uint8: the keys every query attends to as
    bits (`jnp.packbits`: a layer's selection is kept whole, 0.13 GB so
    where it would be 1.1), a block of `block` queries at a time (S a
    multiple of `block` and of 8; `sizes` frozen): the `index_topk` largest
    visible index scores a query by `jax.lax.top_k`
    (`dsa_kernels.selected_block`, the program `correct` 4 compiled for
    the same shape), every visible key where there are no more (or under
    `selection` `"none"`)."""
    topk = int(thawed(sizes)["index_topk"])

    def one(at):
        scores = index_block(index_q, c_q, k_i, w_i, at, sizes, block,
                             selection)
        return jnp.packbits(
            scores > -jnp.inf if selection == "none"
            else selected_block(scores, topk), axis=-1)

    return jnp.stack([one(at) for at in range(0, c_q.shape[0], block)])


@functools.partial(jax.jit, static_argnames=("sizes", "group"))
def expanded(c_kv, kv_b, first, sizes, group):
    """The keys' `k_n` [S, group, nope] and values [S, group, v] of the
    `group` heads from head `first` on: `c_kv` [S, latent] through `kv_b`
    [latent, heads, nope + v]; `sizes` frozen."""
    nope = thawed(sizes)["qk_nope_head_dim"]
    up = jnp.einsum("sc,chd->shd", c_kv, jax.lax.dynamic_slice_in_dim(
        kv_b, first, group, axis=1))
    return up[..., :nope], up[..., nope:]


@functools.partial(jax.jit, static_argnames=("sizes", "keys"))
def attended(c_q, q_b, k_n, k_r, v, masks, w_o, blocks, first, sizes, keys):
    """The blocks of queries `blocks` [B] (numbers) and the group of heads
    from head `first` on: `c_q` [S, rank] every position's, `q_b` [rank,
    heads, nope + rope], the group's keys `k_n` [S, G, nope] and values
    `v` [S, G, v] and the shared `k_r` [S, rope], of which the first `keys`
    are read, `masks` `selected`'s (every block's), `w_o` [heads, v,
    hidden] -> the group's part of the blocks' output [B, block, hidden];
    `sizes` frozen."""
    sizes = thawed(sizes)
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    group, block = k_n.shape[1], masks.shape[1]
    q_b = jax.lax.dynamic_slice_in_dim(q_b, first, group, axis=1)
    w_o = jax.lax.dynamic_slice_in_dim(w_o, first, group, axis=0)
    k_n, k_r, v = k_n[:keys], k_r[:keys], v[:keys]

    def one(number):
        at = number * block
        mask = jnp.unpackbits(jax.lax.dynamic_index_in_dim(
            masks, number, keepdims=False), axis=-1)[:, :keys] != 0
        q = jnp.einsum("qc,chd->qhd", jax.lax.dynamic_slice_in_dim(
            c_q, at, block), q_b)
        q_r = rotate(q[..., nope:].transpose(1, 0, 2),
                     rope_angles(sizes, at + jnp.arange(block)), 1.0)
        scores = (jnp.einsum("qhd,khd->hqk", q[..., :nope], k_n)
                  + jnp.einsum("hqd,kd->hqk", q_r, k_r)
                  ) * (nope + rope) ** -0.5
        weights = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf),
                                 -1)
        return jnp.einsum("qhd,hdo->qo",
                          jnp.einsum("hqk,khd->qhd", weights, v), w_o)

    return jax.lax.map(one, blocks)


def attention(p, sizes, x, norm, selection="exact", query_block=512,
              head_group=8, token_block=4096, key_steps=2):
    """`x` [S, hidden] (a host array; `norm` the input norm's weight) ->
    attention's output [S, hidden] (a host array): causal, the softmax
    over the selected keys. Blocks of `query_block` queries (the positions
    padded up to whole blocks of `token_block` tokens, so that rows of
    28.7 k to 32.8 k positions are one shape and one compile: a padded
    position is later than every real one, seen by none of them, and what
    it computes is dropped), groups of `head_group` heads, a block's keys up
    to its last query rounded up to one of `key_steps` lengths (so that a
    block's program is compiled that many times and no more: a compile
    costs the chip more than the keys past a block's queries, which no
    query of it sees); a layer's selection stays on the device."""
    s = x.shape[0]
    heads = sizes["num_attention_heads"]
    nope, v_dim = sizes["qk_nope_head_dim"], sizes["v_head_dim"]
    rope, latent = sizes["qk_rope_head_dim"], sizes["kv_lora_rank"]
    key = frozen(sizes)
    block = min(query_block, -(-s // 8) * 8)
    span = min(token_block, -(-s // block) * block)
    padded = -(-s // span) * span
    x = np.pad(x, ((0, padded - s), (0, 0)))
    c_q, c_kv, k_r, k_i, w_i = (jnp.concatenate(part) for part in zip(*(
        projected(p, norm, jnp.asarray(x[at:at + span]), at, key)
        for at in range(0, padded, span))))
    index_q = p["index_q"].reshape(-1, sizes["index_n_heads"],
                                   sizes["index_head_dim"])
    masks = selected(index_q, c_q, k_i, w_i, key, block, selection)
    # the blocks whose keys end with the same step, together
    blocks = padded // block
    step = -(-blocks // key_steps)
    parts = [(jnp.arange(lo, min(lo + step, blocks)),
              min(lo + step, blocks) * block)
             for lo in range(0, blocks, step)]
    q_b = p["q_b"].reshape(-1, heads, nope + rope)
    kv_b = p["kv_b"].reshape(latent, heads, nope + v_dim)
    w_o = p["o"].reshape(heads, v_dim, -1)
    out = [0.0 for _ in parts]
    head_group = min(head_group, heads)
    for first in range(0, heads, head_group):
        k_n, v = expanded(c_kv, kv_b, first, key, head_group)
        for n, (numbers, keys) in enumerate(parts):
            out[n] = out[n] + attended(c_q, q_b, k_n, k_r, v, masks, w_o,
                                       numbers, first, key, keys)
    return np.concatenate([np.asarray(part).reshape(-1, x.shape[1])
                           for part in out])[:s]


def forward_rows(weights, sizes, rows, held=None, device=None,
                 positions=None, margins=None, selection="exact",
                 query_block=512, head_group=8, token_block=4096):
    """`forward` for several sequences: the layers in turn, each sequence
    through a layer on its own (a sequence never meets another).
    `positions[i]` picks the positions of sequence `i` whose logits are
    returned. The residual stream lives on the host; a layer's matrices are
    converted to float32 on `device` a part at a time (attention's, the
    dense or shared expert's; a held expert's as it is used), and the
    position-wise parts of a layer run `token_block` positions at a time.
    A list given as `margins` receives, a sequence, the least `held_margin`
    of each position over the expert layers."""
    device = device or jax.local_devices(backend="cpu")[0]
    held = tuple(held or (0, sizes["n_routed_experts"]))
    eps = sizes["rms_norm_eps"]

    def there(tree):
        return jax.tree_util.tree_map(
            lambda w: jax.device_put(w, device), tree)

    def f32(tree):
        return jax.tree_util.tree_map(
            lambda w: jnp.asarray(w, jnp.float32), there(tree))

    with jax.default_device(device), \
            jax.default_matmul_precision("highest"):
        embed = np.asarray(f32(weights["embed"]))
        xs = [embed[np.asarray(ids)] for ids in rows]
        del embed
        least = None if margins is None else [
            np.full((len(ids),), np.inf, np.float32) for ids in rows]
        layers = weights["layers"]
        for index in range(len(layers)):
            layer = layers[index]
            attn, norms = f32(layer["attn"]), f32(
                {name: layer[name] for name in ("input_norm", "post_norm")})
            for n, x in enumerate(xs):
                xs[n] = x + attention(
                    attn, sizes, x, norms["input_norm"], selection,
                    query_block, head_group, token_block)
            del attn
            dense = "mlp" in layer
            # the stack of held experts stays in the weights' own dtype: a
            # float32 product with it converts an expert as it is used
            second = f32(layer["mlp"]) if dense else {
                **f32({name: leaf for name, leaf in layer["moe"].items()
                       if name != "experts"}),
                "experts": there(layer["moe"]["experts"])}
            for n, x in enumerate(xs):
                # whole blocks of tokens: one compiled shape
                span = min(token_block, len(x))
                x = np.pad(x, ((0, -len(x) % span), (0, 0)))
                for at in range(0, len(x), span):
                    out, margin = second_half(
                        second, norms["post_norm"],
                        jnp.asarray(x[at:at + span]), frozen(sizes), held)
                    x[at:at + span] = np.asarray(out)
                    if least is not None:
                        keep = min(span, len(least[n]) - at)
                        least[n][at:at + keep] = np.minimum(
                            least[n][at:at + keep],
                            np.asarray(margin)[:keep])
                xs[n] = x[:len(xs[n])]
            del second
        if margins is not None:
            margins.extend(least)
        if positions is not None:
            xs = [x[np.asarray(at)] for x, at in zip(xs, positions)]
        norm, head = f32(weights["final_norm"]), f32(weights["head"])
        return jax.block_until_ready(
            [rms_norm(jnp.asarray(x), norm, eps) @ head for x in xs])


def forward(weights, sizes, ids, held=None, device=None, positions=None,
            selection="exact"):
    """Logits [S, vocab] (or at `positions` only) of one sequence `ids`
    [S], float32, on `device` (the host CPU where none is given)."""
    return forward_rows(weights, sizes, [ids], held, device,
                        None if positions is None else [positions],
                        selection=selection)[0]
