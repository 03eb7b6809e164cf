"""Plain reference for SDAR's language model (`model_type` `sdar_moe`:
grouped-query attention under a mask that is causal between blocks and
bidirectional inside one, softmax-routed sparse experts on every layer):
one sequence, one full forward pass over prompt and given blocks,
`jax.numpy` in float32 at the highest matmul precision. No cache, no
kernel, no chunk of positions: who sees whom is one explicit matrix, and
each expert runs on the tokens that chose it. Beside it the plain rule by
which a block's masked positions take their ids (`unmask`), a row at a
time, and the attention alone (`span_attention`), for the kernel's
comparison.

`sizes` is the configuration's own keys (the published `config.json`
names: `hidden_size`, `num_attention_heads`, `num_key_value_heads`,
`head_dim`, `num_experts`, `num_experts_per_tok`, `rms_norm_eps`,
`rope_theta`) and `block_length`, which the configuration lists under
`assumed`. `weights` is indexed as the program's tree is (`embed`,
`layers[i]` with `attn` (`q`, `k`, `v`, `o`, `q_norm`, `k_norm`), the two
norms and `moe` (`router`, `experts`), `final_norm`, `head`; matrices `[in,
out]`, experts stacked), a layer at a time, so a caller can convert one
layer's weights to float32 as it is asked for.

With `h = RMSNorm(x)`, a layer is `x += attention(h)`, `x += moe(RMSNorm(x))`:

- attention: `q = h W_q` (heads x head_dim), `k = h W_k`, `v = h W_v` (key
  heads x head_dim); `q`, `k` RMS-normed over the head's dims with a
  learned weight, then rotary (`rope_theta`, all the dims, the token's
  position); the token at `t` sees the one at `u` iff `u // B <= t // B`.
  Query head `j` reads key head `j // G`. `softmax(q . k / sqrt(head_dim))
  v`, heads concatenated, `W_o`.
- experts: `p = softmax(h W_g)` over all the experts, the `k` largest
  chosen, weights `p_i / sum_chosen p`; the layer adds `sum_chosen w_i
  E_i(h)`, `E_i(h) = (silu(h G_i) * h U_i) D_i`.

Departures from the published description, each the same function or
stated in the configuration: (1) the layers are the first
`len(weights["layers"])`: one stage of a pipeline. (2) `block_length`, the
mask id and the un-masking rule are the family's published `generate`'s,
not keys of `config.json`: the configuration lists them under `assumed`.
(3) Pre-norm residuals, the query / key norm and rotary over the whole head
are the family's (`Qwen3MoE`'s, which `sdar_moe` is built on), not keys of
`config.json`. (4) `unmask` takes "masked" as a state it is handed, not as
`id == mask id`, and moves no position that is not masked: the published
loop's `topk` over confidences, `-inf` where not masked, can pick a fixed
position when fewer than `count` are masked and overwrite it with the
drawn id; here those positions keep their ids, the prompt's given tail
among them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .gqa_window_moe import rotate
from .mla_moe import rms_norm, swiglu

# queries a block of the score matrix holds
QUERY_BLOCK = 1024
# an expert's tokens are made up to a multiple of this
TOKEN_PAD = 64


def visibility(queries, keys, block: int):
    """[Q, K] bool from the tokens' positions: the token at `t` sees the
    one at `u` iff `u // block <= t // block`."""
    t = jnp.asarray(queries)[:, None]
    u = jnp.asarray(keys)[None, :]
    return u // block <= t // block


def span_attention(q, k, v, scale, span):
    """The operation alone, for the kernel's comparison: q [B, Sq, Hq, D],
    k / v [B, Skv, Hkv, D] -> [B, Sq, Hq, D], the queries the last `Sq`
    positions of the keys, under `visibility`; query head `j` against key
    head `j // (Hq / Hkv)`, a head at a time."""
    q, k, v = (jnp.asarray(x, jnp.float32) for x in (q, k, v))
    sq, skv = q.shape[1], k.shape[1]
    group = q.shape[2] // k.shape[2]
    seen = visibility(jnp.arange(sq) + (skv - sq), jnp.arange(skv), span)
    out = []
    with jax.default_matmul_precision("highest"):
        for head in range(q.shape[2]):
            scores = jnp.einsum("bqd,bkd->bqk", q[:, :, head],
                                k[:, :, head // group]) * scale
            weights = jax.nn.softmax(
                jnp.where(seen, scores, -jnp.inf), axis=-1)
            out.append(jnp.einsum("bqk,bkd->bqd", weights,
                                  v[:, :, head // group]))
    return jnp.stack(out, axis=2)


def attention(p, sizes, h, positions):
    """`h` [..., N, hidden], the N tokens of a sequence at `positions` [N]
    (axes before them are sequences of that length side by side, which
    never meet); returns the same shape, every token attending to all N of
    its sequence under the block mask."""
    lead, length = h.shape[:-2], h.shape[-2]
    heads, kv_heads = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dim, eps = sizes["head_dim"], sizes["rms_norm_eps"]
    theta = sizes["rope_theta"]
    q = rotate(rms_norm((h @ p["q"]).reshape(*lead, length, heads, dim),
                        p["q_norm"], eps), positions, theta)
    k = rotate(rms_norm((h @ p["k"]).reshape(*lead, length, kv_heads, dim),
                        p["k_norm"], eps), positions, theta)
    v = (h @ p["v"]).reshape(*lead, length, kv_heads, dim)
    seen = visibility(positions, positions, sizes["block_length"])
    group = heads // kv_heads
    out = []
    # blocked by queries so that the scores fit the host; a query head at
    # a time against its key head `j // group`
    for at in range(0, length, QUERY_BLOCK):
        mask = seen[at:at + QUERY_BLOCK]
        block = []
        for head in range(heads):
            scores = jnp.einsum(
                "...qd,...kd->...qk", q[..., at:at + QUERY_BLOCK, head, :],
                k[..., head // group, :]) * dim ** -0.5
            weights = jax.nn.softmax(
                jnp.where(mask, scores, -jnp.inf), axis=-1)
            block.append(weights @ v[..., head // group, :])
        out.append(jnp.stack(block, axis=-2).reshape(
            *lead, -1, heads * dim))
    return jnp.concatenate(out, axis=-2) @ p["o"]


def scores(p, h):
    """The router's softmax scores [S, all the experts]."""
    return jax.nn.softmax(h @ p["router"], axis=-1)


def routing(p, sizes, h):
    """Chosen experts [S, k] and their weights [S, k], normalised over the
    chosen."""
    s = scores(p, h)
    picked, chosen = jax.lax.top_k(s, sizes["num_experts_per_tok"])
    return chosen, picked / picked.sum(-1, keepdims=True)


def margin(p, sizes, h):
    """[S]: how far each token's routing is from changing: the distance
    between the `k`-th and the `k+1`-th largest score (every expert is
    held, so any change of the chosen set changes what is computed). Top-k
    is the one discontinuity of the network."""
    k = sizes["num_experts_per_tok"]
    top = jax.lax.top_k(scores(p, h), k + 1)[0]
    return top[:, k - 1] - top[:, k]


def experts(p, sizes, rows):
    """`sum_chosen w_i E_i(h)` for every sequence `h` [S, hidden] of `rows`:
    a loop over the experts, each on the tokens that chose it, of whatever
    sequence (an expert sees a token, not its sequence, so an expert's three
    matrices are taken out of the stack once and a layer is `experts` calls
    and not that times the sequences). The tokens an expert takes are made
    up to a multiple of `TOKEN_PAD` with rows of zeros, whose outputs are
    dropped: a few shapes to compile, not one a count."""
    import numpy as np

    h = np.concatenate([np.asarray(h) for h in rows])
    chosen, weights = (np.asarray(x) for x in routing(p, sizes, h))
    out = np.zeros_like(h)
    for j in range(p["experts"]["gate"].shape[0]):
        token, slot = np.nonzero(chosen == j)
        if token.size:
            mine = np.pad(h[token], ((0, -token.size % TOKEN_PAD), (0, 0)))
            one = {name: p["experts"][name][j]
                   for name in ("gate", "up", "down")}
            out[token] += np.asarray(swiglu(one, mine))[:token.size] \
                * weights[token, slot][:, None]
    edges = np.cumsum([len(h) for h in rows])[:-1]
    return [jnp.asarray(part) for part in np.split(out, edges)]


def forward_rows(weights, sizes, rows, device=None, positions=None,
                 margins=None):
    """`forward` for several sequences of one length (a caller lengthens
    them with whole blocks behind, which no position before sees): the
    layers in turn, the sequences side by side on an axis of their own
    (they never meet in attention, and an expert takes tokens one at a
    time), so a layer's weights are converted once. `positions[i]` picks
    the positions of sequence `i` whose logits are returned. Every layer
    is waited for before the next is converted: the host holds one layer's
    float32 weights at a time. A list given as `margins` receives, a
    sequence, the least `margin` of each position over the layers."""
    import numpy as np

    device = device or jax.local_devices(backend="cpu")[0]
    eps = sizes["rms_norm_eps"]

    def f32(tree):
        return jax.tree_util.tree_map(
            lambda w: jnp.asarray(jax.device_put(w, device), jnp.float32),
            tree)

    with jax.default_device(device), \
            jax.default_matmul_precision("highest"):
        x = f32(weights["embed"])[jnp.asarray(np.stack(rows))]
        count, length, hidden = x.shape
        least = jnp.full((count * length,), jnp.inf)
        layers = weights["layers"]
        for index in range(len(layers)):
            layer = f32(layers[index])
            x = x + attention(layer["attn"], sizes,
                              rms_norm(x, layer["input_norm"], eps),
                              jnp.arange(length))
            h = rms_norm(x, layer["post_norm"], eps).reshape(-1, hidden)
            x = x + experts(layer["moe"], sizes, [h])[0].reshape(x.shape)
            if margins is not None:
                least = jnp.minimum(least, margin(layer["moe"], sizes, h))
            jax.block_until_ready(x)
            del layer
        if margins is not None:
            margins.extend(least.reshape(count, length))
        xs = list(x) if positions is None else [
            one[jnp.asarray(at)] for one, at in zip(x, positions)]
        norm, head = f32(weights["final_norm"]), f32(weights["head"])
        # one product with the head for all the sequences' positions
        logits = rms_norm(jnp.concatenate(xs), norm, eps) @ head
        edges = np.cumsum([len(one) for one in xs])[:-1]
        return jax.block_until_ready(jnp.split(logits, edges))


def forward(weights, sizes, ids, device=None, positions=None):
    """Logits [T, vocab] (or at `positions` only) of one sequence `ids`
    [T], float32, on `device` (the host CPU where none is given). The
    logits at a position predict that position's own token."""
    return forward_rows(weights, sizes, [ids], device,
                        None if positions is None else [positions])[0]


def unmask(ids, masked, drawn, confidence, count: int, threshold=None):
    """One row's block after a denoise forward, plain Python over its
    positions: `ids`, `masked`, `drawn`, `confidence` are lists of the
    block's length. The `count` masked positions of highest confidence take
    their drawn id (ties to the earlier position; all of them where fewer
    are masked); with a `threshold`, every masked position whose confidence
    is over it instead, where those are `count` at least. Returns (ids,
    masked)."""
    candidates = sorted((at for at in range(len(ids)) if masked[at]),
                        key=lambda at: -confidence[at])
    take = candidates[:count]
    if threshold is not None:
        high = [at for at in candidates if confidence[at] > threshold]
        if len(high) >= count:
            take = high
    ids, masked = list(ids), list(masked)
    for at in take:
        ids[at], masked[at] = drawn[at], False
    return ids, masked
