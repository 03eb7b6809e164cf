"""What a text family's module under `models/` gives the pipeline, and the
parts of attention the families share: rotary, a decode step against a
cache of keys, a window layer's ring (models/experts.py has the second
half of a layer, models/prefill_chunks.py how a pass's rows go through a
prefill). No family's module imports another's.

**The interface.** pipelines/text_generation.py asks a family's module
(text_families.py names it, `family_module` hands it over) for these names
and no others, and every module has all of them (`family_module` refuses
one that does not, when the pipeline's module is imported, not in the
middle of a pass):

- `config_for(model_name)`: the config, a frozen dataclass (the tiny preset
  for a name with `tiny`, else the chip's share at the published widths),
  with `vocab_size`, `expert_layers`, `experts_held` and what
  models/experts.py reads (a family without experts says `expert_layers`
  0 and `experts_held` (0, 0): its tally then has no row, every count the
  pipeline reads from it is 0, and nothing is divided by either);
  `block_length` and `mask_token_id` besides where the family decodes by
  blocks;
- `param_shapes(cfg, dtype)`, `init_params(cfg, key, dtype)`: the parameter
  tree as `jax.ShapeDtypeStruct`s, and seeded values for it;
- `new_cache(cfg, rows, positions, dtype)`: a pass's cache, all zero;
- `cache_bytes(cfg, rows, positions, itemsize)`: three numbers, (bytes of
  that cache, the part of it that is rings of a window, the part that is
  recurrent state: neither grows with the positions), 0 where the family
  has none of a kind;
- `empty_load(cfg)`: the routing's tally, all zero (models/experts.py:
  `[expert_layers, held]` pairs and four sums; a dense family's goes
  through `prefill` and `step` as it came);
- `prefill(params, cfg, ids, lengths, positions, chunk_rows, chunk_slots)`:
  `ids` [rows, slots] through every layer in chunks of `chunk_rows` rows x
  `chunk_slots` positions; returns (the last prompt position's logits
  [rows, vocab], the cache, the tally), and where the family decodes by
  blocks (the cache, the tally);
- `POSITION_CHUNKS`: whether a chunk may be a span of one row's positions
  (else a long row goes through whole);
- `prefill_account(lengths, slots, chunk_rows, chunk_slots)`: the host's
  account of what `prefill` ran for rows of these `lengths`, without jax:
  ({width: the chunks run at it}, the chunks not run, the slots computed),
  by the rule the module's own `prefill` applies on the device
  (models/prefill_chunks.py `chunk_account` over the module's plan of
  chunks and its rule for a span);
- one of the two ways to decode, as the family's row says (`block_length`):
  `step(params, cfg, tokens, lengths, number, slots, cache, load, valid=)`,
  every row's generated token `number` in, (logits [rows, vocab], cache,
  tally) out; or `block_step(params, cfg, ids, lengths, block, slots,
  cache, load, valid=, commit=, finished=)`, a block a row in, (logits
  [rows, block, vocab], cache, tally) out, with `first_block(cfg, ids,
  lengths)`, `unmask(ids, masked, drawn, confidence, count, threshold)`,
  `blocks_of(cfg, new_tokens)` and `cache_positions(cfg, slots,
  new_tokens)`;
- where the family's attention reads the keys a learned index selects (its
  row says `selects`): `index_cache_bytes(cfg, rows, positions, itemsize)`,
  the index keys' part of `cache_bytes`' first number, and a tally of
  three leaves, the third the selection's, which `selection_counts(leaf)`
  turns into (the positions the real queries saw, those attention read for
  them, the key positions the prefill's spans walked, spans x the bucket's
  width) on the host.
- where the family's decode attention is bounded by what a row's mask
  shows (its row says `bounds_decode`): `decode_cache_blocks(cfg, lengths,
  slots, positions, steps)`, the host's account, without jax, of the
  cache's column blocks the decode's attention went over, by the rule the
  module's own `step` applies on the device: (walked, rows x the blocks of
  the cache's width), summed over rows, layers and steps.
- where the key side of the family's prefill spans is bounded by the span's
  end and nothing on the device counts it (its row says `bounds_prefill`):
  `prefill_key_extent(cfg, lengths, slots, chunk_rows, chunk_slots)`, the
  host's account, without jax, of the key positions the spans' full
  attention went over, by the rule the module's own `prefill` applies:
  (walked: rows x the span's end, bucket: rows x the prompt slots' width),
  summed over the spans that ran and the layers that keep every position.

The forwards themselves (`_forward`, `prefill_rows`, `step`, `block_step`)
are each module's own: the networks differ.
"""

import importlib

import jax
import jax.numpy as jnp

from ..text_families import TEXT_FAMILIES

INTERFACE = ("config_for", "param_shapes", "init_params", "new_cache",
             "cache_bytes", "empty_load", "prefill", "POSITION_CHUNKS",
             "prefill_account")
# the two ways to decode: a module has one of them whole, and
# tests/test_text_serving.py holds it to having nothing of the other
BY_TOKEN = ("step",)
BY_BLOCKS = ("block_step", "first_block", "unmask", "blocks_of",
             "cache_positions")
# what a family that selects keys gives besides
SELECTS = ("index_cache_bytes", "selection_counts")
# what a family whose decode attention is bounded by the mask gives besides
BOUNDS_DECODE = ("decode_cache_blocks",)
# what a family whose prefill spans' key side is bounded by the span's end,
# and which has no selection's tally to count it on the device, gives besides
BOUNDS_PREFILL = ("prefill_key_extent",)


def family_module(family: str):
    """The module of `family`'s row, held to the interface: a name it
    lacks is an AttributeError that says which, of which module."""
    row = TEXT_FAMILIES[family]
    module = importlib.import_module(f"{__package__}.{row['module']}")
    for name in (INTERFACE
                 + (BY_BLOCKS if row.get("block_length") else BY_TOKEN)
                 + (SELECTS if row.get("selects") else ())
                 + (BOUNDS_DECODE if row.get("bounds_decode") else ())
                 + (BOUNDS_PREFILL if row.get("bounds_prefill") else ())):
        getattr(module, name)
    return module


# --- rotary ------------------------------------------------------------------


def rope_tables(dim: int, theta: float, positions):
    """cos and sin `[..., dim / 2]` of whole-number `positions`: plain
    rotary over `dim` dims of a head (Kimi's YaRN tables are its own)."""
    exponent = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    angles = positions.astype(jnp.float32)[..., None] / theta ** exponent
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x, cos, sin):
    """Rotate the two halves of the last axis; `cos` / `sin` broadcast."""
    half = x.shape[-1] // 2
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                           axis=-1).astype(x.dtype)


# --- a decode step against a cache of keys -----------------------------------


def decode_mask(lengths, slots: int, positions: int, number, column=None):
    """[R, positions]: what a row sees of a cache that keeps every position
    at generated token `number`: its own prompt (the first `lengths`
    columns) and the generated columns up to `slots + number`, which is
    being written (`column`: that sum, where the caller has it)."""
    columns = jnp.arange(positions)[None, :]
    return (columns < lengths[:, None]) | (
        (columns >= slots)
        & (columns <= (slots + number if column is None else column)))


def cached_attention(q, keys, values, mask, scale: float, sink=None):
    """One new token a row against its cache: `q` [R, heads, D], `keys`
    [R, S, key heads, D], `values` [R, S, key heads, Dv] (`Dv` the values'
    own width: MiMo-V2 keeps keys of 192 on values of 128), `mask` [R, S]
    the columns the row may see. A group's query heads meet their one
    cached head in one batched matmul; the cache is not repeated. `sink`
    [heads] float32, where the layer has one, is a learned logit a query
    head that joins the softmax's denominator and has no value: one more
    column of the scores, dropped after the softmax, so a row's weights
    add up to less than one. Returns [R, heads * Dv]."""
    rows, heads, d = q.shape
    kv_heads = keys.shape[2]
    q = q.reshape(rows, kv_heads, heads // kv_heads, d)
    scores = jnp.einsum("rhgd,rshd->rhgs", q, keys,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    if sink is None:
        weights = jax.nn.softmax(scores, axis=-1).astype(values.dtype)
    else:
        column = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(1, kv_heads, -1, 1),
            (*scores.shape[:-1], 1))
        weights = jax.nn.softmax(
            jnp.concatenate([scores, column], axis=-1),
            axis=-1)[..., :-1].astype(values.dtype)
    out = jnp.einsum("rhgs,rshd->rhgd", weights, values,
                     preferred_element_type=jnp.float32)
    # the values' width, which is the keys' in every family but MiMo-V2
    return out.astype(values.dtype).reshape(rows, heads * values.shape[-1])


# --- a window layer's ring ---------------------------------------------------


def ring_fill(ring, entry, start, lengths):
    """A window layer's ring [R, W, ...] after a chunk: `entry` [R, C,
    ...] are the chunk's positions `start .. start + C` (`start` a number
    or a traced scalar); column `j` takes the row's last real position
    congruent to `j` if the chunk holds it (a row's real positions are the
    first `lengths`)."""
    window, chunk = ring.shape[1], entry.shape[1]
    end = jnp.minimum(lengths, start + chunk)[:, None]  # [R, 1]
    column = jnp.arange(window)[None, :]
    position = end - 1 - jnp.mod(end - 1 - column, window)  # [R, W]
    mine = position >= start
    index = jnp.clip(position - start, 0, chunk - 1)
    picked = jnp.take_along_axis(entry, index[:, :, None, None], axis=1)
    return jnp.where(mine[:, :, None, None], picked, ring)


def ring_mask(window: int, lengths, number):
    """[R, window]: what a row sees of a ring at generated token `number`:
    column `j` holds the last position congruent to `j` up to the token's
    own, seen if there is one (of a full layer's cache it sees what
    `decode_mask` says)."""
    at = (lengths + number)[:, None]
    return at - jnp.mod(at - jnp.arange(window)[None, :], window) >= 0
