"""Plain reference for MiMo-V2's language model (`model_type` `mimo_v2`:
grouped-query attention with keys of 192 on values of 128, window layers
with a learned sink in their softmax beside full layers, sigmoid-routed
sparse experts and no shared one): one sequence, one full forward pass over
prompt and given tokens, `jax.numpy` in float32 at the highest matmul
precision. No cache, no kernel, no span: a layer's window is a mask over
explicit scores, the sink is ONE MORE COLUMN of the scores that is dropped
after the softmax, and each held expert runs on every token, weighed by
zero where the token did not choose it (`mla_moe.py`'s `routing` and
`held_margin`: the same router as Kimi-K2's, by the same equations).

`sizes` is the configuration's own keys (the published `config.json` names:
`hidden_size`, `num_attention_heads`, `num_key_value_heads`,
`swa_num_key_value_heads`, `head_dim`, `v_head_dim`,
`partial_rotary_factor`, `rope_theta`, `swa_rope_theta`,
`attention_value_scale`, `sliding_window`, `hybrid_layer_pattern`,
`n_routed_experts`, `num_experts_per_tok`, `routed_scaling_factor`,
`layernorm_epsilon`). `weights` is indexed as the program's tree is
(`embed`, `layers[i]` with `attn` (`qkv`, `o` and on a window layer
`sink`), the two norms and `mlp` or `moe`, `final_norm`, `head`; matrices
`[in, out]`, experts stacked), a layer at a time.

With `h = RMSNorm(x)`, a layer is `x += attention(h)`, `x += ffn(RMSNorm(x))`:

- `[q | k | v] = h W_qkv`: `q` heads x `head_dim`, `k` key heads x
  `head_dim`, `v` key heads x `v_head_dim`, `v` times
  `attention_value_scale`; the key heads are `num_key_value_heads` on a
  full layer (`hybrid_layer_pattern` 0) and `swa_num_key_value_heads` on a
  window layer (1); query head `j` reads key head `j // G`.
- rotary on the first `int(head_dim x partial_rotary_factor)` dims of every
  query and key head (the two halves of those as the pairs), base
  `rope_theta` on a full layer and `swa_rope_theta` on a window layer.
- a full layer: `a[t, u] = q_t . k_u head_dim^-1/2` for `u <= t`, `o_t =
  sum_u softmax_u(a[t, .]) v_u`.
- a window layer: `t - sliding_window < u <= t`, and the head's learned
  logit `s_j` joins the softmax: `p = softmax([a[t, .], s_j])`, `o_t =
  sum_u p_u v_u` over the keys alone (the sink has no value).
- `x += concat(o) W_o`; the dense layer and the experts as `mla_moe.py`,
  with no shared expert.

It is computed in blocks of `token_block` positions, inside them
`query_block` queries and a key head at a time, so that a row of 33 k
positions fits where it runs (a full layer's `[64, S, S]` scores would be
280 GB): the same numbers, block by block. `device` says where the
arithmetic runs (the host CPU for the tests and the rehearsal; the cell's
33 k-position row would take the host an hour, so its family hands in the
chip, where float32 at the highest precision is six bfloat16 passes a
product: families/mimo_v2.py).

`control` is for the three controls that have to FAIL a comparison with the
served network: `"no_sink"` leaves the sink out of the softmax,
`"no_value_scale"` leaves `attention_value_scale` out, `"swapped_theta"`
gives each kind of layer the other's rotary base.

Departures from the published model, each the same function or stated in
the configuration's `assumed`: (1) `held = (first, count)` names the routed
experts whose part is computed, one chip's share; the router still scores
all of them and the weights are normalised over all the chosen. (2) The
weights stack only the held experts. (3) `n_group` 1 and `topk_group` 1:
the group-limited choice is the plain one; `routed_scaling_factor` null is
1. (4) The vocabulary is the rows held. (5) The layers are the first
`len(weights["layers"])` of `hybrid_layer_pattern`. (6) The sink's form,
the value scale's place, rotary pairs and pre-norm residuals are not keys
of `config.json`: the configuration lists them under `assumed`. (7) The
three multi-token-prediction layers and the vision and audio towers are
left out: no next-token logit of a text prompt depends on them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .mla_moe import held_margin, rms_norm, routing, swiglu

CONTROLS = (None, "no_sink", "no_value_scale", "swapped_theta")


def frozen(sizes: dict) -> tuple:
    """`sizes` as something a compiled function can be keyed by."""
    return tuple(sorted(
        (key, tuple(value) if isinstance(value, (list, tuple)) else value)
        for key, value in sizes.items() if not isinstance(value, dict)))


def rotate(x, positions, theta: float, rotary: int):
    """`x` [T, heads, dim] at `positions` [T]: rotary on the first `rotary`
    dims, the two halves of those as the pairs (`rope_type` `default`)."""
    exponent = jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary
    angles = positions.astype(jnp.float32)[:, None, None] / theta ** exponent
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    a, b = x[..., :rotary // 2], x[..., rotary // 2:rotary]
    return jnp.concatenate(
        [a * cos - b * sin, a * sin + b * cos, x[..., rotary:]], axis=-1)


def _kind(sizes: dict, sliding: bool, control):
    """(key heads, rotary base, value scale, window) of a layer's kind."""
    bases = (sizes["rope_theta"], sizes["swa_rope_theta"])
    if control == "swapped_theta":
        bases = bases[::-1]
    return (sizes["swa_num_key_value_heads"] if sliding
            else sizes["num_key_value_heads"],
            float(bases[int(sliding)]),
            1.0 if control == "no_value_scale"
            else float(sizes["attention_value_scale"]),
            int(sizes["sliding_window"]) if sliding else 0)


@functools.partial(jax.jit, static_argnames=("sizes", "sliding", "control"))
def keys_values(qkv, norm, x, start, sizes, sliding: bool, control=None):
    """The keys [T, key heads, head_dim] (rotated) and values [T, key
    heads, v_head_dim] (scaled) of positions `start .. start + T`, `x` [T,
    hidden]; `sizes` frozen."""
    sizes = dict(sizes)
    kv_heads, theta, value_scale, _ = _kind(sizes, sliding, control)
    heads, d, dv = (sizes["num_attention_heads"], sizes["head_dim"],
                    sizes["v_head_dim"])
    h = rms_norm(x, norm, sizes["layernorm_epsilon"])
    kv = h @ qkv[:, heads * d:]
    k = kv[:, :kv_heads * d].reshape(-1, kv_heads, d)
    v = kv[:, kv_heads * d:].reshape(-1, kv_heads, dv) * value_scale
    positions = start + jnp.arange(x.shape[0])
    return rotate(k, positions, theta,
                  int(d * sizes["partial_rotary_factor"])), v


@functools.partial(jax.jit, static_argnames=(
    "sizes", "sliding", "control", "query_block"))
def attended(p, norm, x, k, v, start, sizes, sliding: bool, control=None,
             query_block: int = 512):
    """`x + attention(RMSNorm(x))` for the positions `start .. start + T`,
    `x` [T, hidden], against every position's keys `k` [S, key heads,
    head_dim] and values `v` [S, key heads, v_head_dim]; `p` the layer's
    `attn`, `sizes` frozen. `query_block` queries and a key head at a time;
    a window layer's block reads the `query_block + window` keys that end
    with its last query."""
    sizes = dict(sizes)
    kv_heads, theta, _, window = _kind(sizes, sliding, control)
    heads, d = sizes["num_attention_heads"], sizes["head_dim"]
    group = heads // kv_heads
    rotary = int(d * sizes["partial_rotary_factor"])
    total, block = k.shape[0], min(query_block, x.shape[0])
    assert x.shape[0] % block == 0, (x.shape, block)
    reach = min(block + window, total) if window else total
    sink = (p["sink"].reshape(kv_heads, group)
            if sliding and control != "no_sink" else None)
    w_q = p["qkv"][:, :heads * d]
    w_o = p["o"].reshape(kv_heads, group, sizes["v_head_dim"], -1)

    def one(number):
        at = start + number * block
        mine = jax.lax.dynamic_slice_in_dim(x, number * block, block)
        h = rms_norm(mine, norm, sizes["layernorm_epsilon"])
        rows = at + jnp.arange(block)
        q = rotate((h @ w_q).reshape(block, heads, d), rows, theta, rotary)
        q = q.reshape(block, kv_heads, group, d)
        # the keys the block can see at all: every one, or under a window
        # those that end with the block's last query
        low = jnp.clip(at + block - reach, 0, total - reach)
        cols = low + jnp.arange(reach)
        seen = cols[None, :] <= rows[:, None]
        if window:
            seen = seen & (rows[:, None] - cols[None, :] < window)

        def head(out, number):
            keys = jax.lax.dynamic_slice_in_dim(k[:, number], low, reach)
            values = jax.lax.dynamic_slice_in_dim(v[:, number], low, reach)
            scores = jnp.einsum("qgd,kd->gqk", q[:, number], keys) * d ** -0.5
            scores = jnp.where(seen[None], scores, -jnp.inf)
            if sink is not None:  # one more column, dropped after the softmax
                scores = jnp.concatenate([scores, jnp.broadcast_to(
                    sink[number][:, None, None], (group, block, 1))], -1)
            weights = jax.nn.softmax(scores, axis=-1)[..., :reach]
            o = jnp.einsum("gqk,kd->qgd", weights, values)
            return out + jnp.einsum("qgd,gdo->qo", o, w_o[number]), None

        return jax.lax.scan(head, mine, jnp.arange(kv_heads))[0]

    return jax.lax.map(one, jnp.arange(x.shape[0] // block)).reshape(x.shape)


def experts(p, sizes, h, held):
    """The held experts' part, [S, hidden]: every held expert on every
    token, its weight zero where the token did not choose it (no shared
    expert). The stacked expert `j` is the model's expert `held[0] + j`."""
    first, count = held
    chosen, weights = routing(p, sizes, h)

    def one(out, expert):
        number, matrices = expert
        weight = jnp.sum(jnp.where(chosen == first + number, weights, 0.0),
                         axis=-1)
        return out + weight[:, None] * swiglu(matrices, h), None

    return jax.lax.scan(one, jnp.zeros_like(h),
                        (jnp.arange(count), p["experts"]))[0]


@functools.partial(jax.jit, static_argnames=("sizes", "held"))
def second_half(p, norm, x, sizes, held):
    """`x + ffn(RMSNorm(x))` for positions `x` [T, hidden], `p` a layer's
    `mlp` or `moe`, and each position's `held_margin` (infinite on a dense
    layer); `sizes` frozen."""
    sizes = dict(sizes)
    h = rms_norm(x, norm, sizes["layernorm_epsilon"])
    if "router" not in p:
        return x + swiglu(p, h), jnp.full((x.shape[0],), jnp.inf)
    return x + experts(p, sizes, h, held), held_margin(p, sizes, h, held)


def forward_rows(weights, sizes, rows, held=None, device=None,
                 positions=None, margins=None, control=None,
                 query_block: int = 512, token_block: int = 4096):
    """`forward` for several sequences: the layers in turn, each sequence
    through a layer on its own (a sequence never meets another), in blocks
    of `token_block` positions (a sequence is padded up to whole blocks, so
    that rows of 28.7 k to 32.8 k positions are one shape and one compile:
    a padded position is later than every real one, seen by none of them,
    and what it computes is dropped). `positions[i]` picks the positions of
    sequence `i` whose logits are returned. A layer's matrices are
    converted to float32 on `device` a part at a time (attention's, the
    dense layer's; a held expert's as it is used). A list given as
    `margins` receives, a sequence, the least `held_margin` of each
    position over the expert layers."""
    assert control in CONTROLS, control
    device = device or jax.local_devices(backend="cpu")[0]
    sizes = {**sizes, "routed_scaling_factor":
             sizes.get("routed_scaling_factor") or 1.0}
    held = tuple(held or (0, sizes["n_routed_experts"]))
    key = frozen(sizes)

    def there(tree):
        return jax.tree_util.tree_map(
            lambda w: jax.device_put(w, device), tree)

    def f32(tree):
        return jax.tree_util.tree_map(
            lambda w: jnp.asarray(w, jnp.float32), there(tree))

    with jax.default_device(device), \
            jax.default_matmul_precision("highest"):
        embed = f32(weights["embed"])
        xs = []
        for ids in rows:
            span = min(token_block, -(-len(ids) // 8) * 8)
            padded = np.pad(np.asarray(ids), (0, -len(ids) % span))
            xs.append([embed[jnp.asarray(padded[at:at + span])]
                       for at in range(0, len(padded), span)])
        del embed
        least = None if margins is None else [
            np.full((len(ids),), np.inf, np.float32) for ids in rows]
        layers = weights["layers"]
        for index in range(len(layers)):
            layer = layers[index]
            sliding = bool(sizes["hybrid_layer_pattern"][index])
            attn, norms = f32(layer["attn"]), f32(
                {name: layer[name] for name in ("input_norm", "post_norm")})
            dense = "mlp" in layer
            # the stack of held experts stays in the weights' own dtype: a
            # float32 product with it converts an expert as it is used
            second = f32(layer["mlp"]) if dense else {
                **f32({name: leaf for name, leaf in layer["moe"].items()
                       if name != "experts"}),
                "experts": there(layer["moe"]["experts"])}
            for n, blocks in enumerate(xs):
                span = blocks[0].shape[0]
                k, v = (jnp.concatenate(part) for part in zip(*(
                    keys_values(attn["qkv"], norms["input_norm"], x,
                                at * span, key, sliding, control)
                    for at, x in enumerate(blocks))))
                for at, x in enumerate(blocks):
                    x = attended(attn, norms["input_norm"], x, k, v,
                                 at * span, key, sliding, control,
                                 min(query_block, span))
                    blocks[at], margin = second_half(
                        second, norms["post_norm"], x, key, held)
                    if least is not None:
                        keep = max(min(span, len(least[n]) - at * span), 0)
                        where = slice(at * span, at * span + keep)
                        least[n][where] = np.minimum(
                            least[n][where], np.asarray(margin)[:keep])
                del k, v
            jax.block_until_ready(xs)
            del attn, second
        if margins is not None:
            margins.extend(least)
        xs = [jnp.concatenate(blocks)[:len(ids)]
              for blocks, ids in zip(xs, rows)]
        if positions is not None:
            xs = [x[jnp.asarray(at)] for x, at in zip(xs, positions)]
        norm, head = f32(weights["final_norm"]), f32(weights["head"])
        return jax.block_until_ready(
            [rms_norm(x, norm, sizes["layernorm_epsilon"]) @ head
             for x in xs])


def forward(weights, sizes, ids, held=None, device=None, positions=None,
            control=None):
    """Logits [S, vocab] (or at `positions` only) of one sequence `ids`
    [S], float32, on `device` (the host CPU where none is given)."""
    return forward_rows(weights, sizes, [ids], held, device,
                        None if positions is None else [positions],
                        control=control)[0]
