"""Text completion from token ids: one jitted prefill and one jitted cached
decode over a resident language model, whichever family the name resolves
to: a family is a row of text_families.py `TEXT_FAMILIES` (Kimi-K2,
K-EXAONE, SDAR, Qwen3-Next, Falcon-H1, GLM-5, MiMo-V2) and the module under
`models/`
the row names,
which gives what models/text_model.py says a family's module gives and is
asked for nothing else. There are two ways to decode, and the family's row
says which is its own (`block_length`): by `step` (a token a row a forward,
below) or by `block_step` (a block of tokens a row over several forwards,
"By blocks" further down).

A pass is a set of rows (sequences), each a prompt of token ids, all
generating the same number of new tokens. Rows are padded to a power-of-two
row bucket and prompts to a power-of-two slot bucket (a row's prompt first,
padding after it), so a pass is keyed by (rows, prompt slots, new tokens):

- **prefill** runs the prompts through every layer in chunks of at most
  `PREFILL_CHUNK_TOKENS` tokens so that the widest layer's activations
  fit (`prefill_chunk`: whole rows where rows are short, a span of one
  row's positions where a row is longer, each span attending to what the
  spans before it cached), writes each layer's cache and returns the
  logits of every row's last prompt token. A row of a chunk of whole
  rows goes through at the narrowest of the widths its model offers that
  holds it (Kimi: the bucket and its halvings; SDAR, Qwen3-Next and
  Falcon-H1: the bucket; K-EXAONE, GLM-5 and MiMo-V2 run fixed chunks of
  their own), and a chunk takes rows of one width (models/prefill_chunks.py:
  read from `lengths` on the device, so it is one program whatever a pass
  brings, and a row's bits do not depend on its batchmates); a pass hands
  its rows over longest first, so that rows of a width stand together,
  runs prefill and decode in that order and puts the ids back in the
  jobs' order at the read-back;
- **decode** is a `lax.scan` of `new tokens - 1` steps through the cache
  (the first token comes from prefill's logits, the last one is never fed):
  a step feeds every row its last token, and samples the next on the device
  from a key folded from the job's seed, the row's number in its job and
  the step, so a row's ids do not depend on its batchmates (the sampler,
  ops/sampling.py `sample`, is both decodes': one uniform number a
  position of that key over a running sum of the position's own
  `exp(logit / temperature - max)` in float32, the whole vocabulary held,
  nothing truncated; temperature 0 takes the largest logit's id);
- **step** is the decode step alone, given tokens in, logits out: what a
  comparison with the plain reference needs.

**By blocks** (a family with a `block_length`: block diffusion). Prefill caches
each row's whole prompt blocks and returns no logits. The decode program
(`block_decode_program`) is a scan over blocks of `block_length` positions;
a block starts as the mask id (the first one behind the `L mod B` ids of
the prompt that did not fill a block), and inside the scan a bounded loop
of **denoise** forwards runs the block's `rows x block_length` tokens
against the cache, draws an id a position from the position's own logits
(key: job, row, block, forward), and fixes the `block_length /
denoising_steps` still-masked positions it is surest of (with a
`confidence_threshold`: every one over it, if those are as many), until no
row has a mask left in the block, or after `denoising_steps` forwards. A
finished block's keys and values are not in the cache yet, and no forward
of its own puts them there: the next block's first denoise forward is a
**fused** one, which takes the finished ids beside its own as `2 x
block_length` positions a row, writes the finished block's keys and values
and yields logits for its own positions only (models/sdar.py
`block_step`, `finished=`); the pass's last block is never written, nobody
reads it. So what a forward yields is a number to count: the program counts
the forwards, those of them that were fused, and the positions that took an
id or were already fixed, on the device, and they come back with the ids
(`swarm_block_forward_rows_total`, `swarm_block_fused_commit_rows_total`,
`swarm_block_slots_total`, `swarm_generated_tokens_total`; the envelope's
`decode_steps` are the forwards the decode made, `forwards` says of which
kind). `block_program` is one forward of a block alone with given ids,
logits out, the cache written under `commit`: the comparison's.

What a pass caches is the model's to say (`cache_bytes`: Kimi-K2 a latent
a position a layer, K-EXAONE keys and values a position on its full
layers and a ring of its window on the others, SDAR keys and values a
position on every layer, Qwen3-Next keys and values a position on every
fourth layer and on the others a recurrent state and a convolution's tail
a row, which do not grow with the positions, Falcon-H1 both on every
layer, GLM-5 a latent AND an index key a position a layer, two caches side
by side, MiMo-V2 keys of 192 on values of 128 a position on its full
layers' 4 key heads and a ring of its window on the others' 8): the whole is
`swarm_pass_cache_bytes{model}`, the rings' part
`swarm_pass_window_cache_bytes{model}`, the states' part
`swarm_pass_state_bytes{model}`, and where the family selects keys (its
row's `selects`: GLM-5) the index keys' part
`swarm_pass_index_cache_bytes{model}`. Such a family's attention reads, a
query, the `index_topk` positions its indexer scores highest and no other:
the positions the real queries saw and those attention read for them are
summed on the device beside the routing's tally and come back with the ids
(`swarm_sparse_visible_positions_total{model, phase}`,
`swarm_sparse_selected_positions_total{model, phase}`, `phase` `prefill` |
`decode`; the envelope's `selection`), and beside them the key positions
the prefill's spans went over, each bounded by its own end, against spans x
the bucket's width (`swarm_prefill_key_extent_total{model, extent}`,
`extent` `walked` | `bucket`; the envelope's `selection` has them as
`prefill_key_extent`; a family whose full layers' spans are bounded so
and which has no selection, its row's `bounds_prefill`: MiMo-V2, counts the
same two on the host, its module's `prefill_key_extent`, and its envelope
has `prefill_key_extent` alone). Where the family's decode attention is bounded by
what a row's mask shows (its row's `bounds_decode`: Kimi-K2), the cache's
column blocks the decode went over against rows x the blocks of the
cache's width are the module's own account on the host, from the lengths
and the step count (`swarm_decode_cache_blocks_total{model, extent}`; the
envelope's `decode_cache_blocks`, beside `decode_steps`). A pass counts its
prompt slots
(`swarm_prefill_slots_total{model, kind}`: `real` ids, the `padding`
that was computed all the same, and the slots of the bucket `skipped`:
what lies past a chunk's width, and chunks the model's prefill did not
run because none of their rows reached them: the module's own
`prefill_account`) and the chunks it ran at each width
(`swarm_prefill_chunks_total{model, width}`); how the routing fell comes
back with the ids (`swarm_expert_pairs_total`, `swarm_routed_tokens_total`,
`swarm_expert_pairs_max_total`, `swarm_expert_row_tiles_total`; the
envelope's `routing` has the same a program, with the experts that had a
pair, `active`, and the calls: `tiles / active` is the row tiles an
expert's matrices serve in a call, for which they cross from HBM once
where the grouped kernel holds them in one block, `ops/expert_matmul.py`).
A family without experts (Falcon-H1: `expert_layers` 0) hands over a tally
of no rows (models/experts.py `empty_load`), which goes through both
programs as it came: its `routing` reads 0 in every count, `calls`
included, `pairs_by_expert` is empty, and the four counters stand at 0.

No tokenizer: ids travel on the wire, and there is no stop token, every
row generates `max_new_tokens`. `test/` names are seeded weights: `tiny` in
the name is the family's tiny preset, any other the chip's share of the
deployment at the published widths (`KIMI_K2_EP32`, `EXAONE_236B_EP8`,
`SDAR_30B_PP8`, `QWEN3_NEXT_80B_EP4`, `FALCON_H1_34B_PP18`, `GLM5_EP16`:
`test/GLM-5`, `test/tiny-glm-5`; `MIMO_V25_EP16`: `test/MiMo-V2.5`,
`test/tiny-mimo`;
`weights=` hands the tree in already on the chip, as `FluxPipeline` takes
it: the host init of billions of parameters is minutes).
"""

from __future__ import annotations

import logging
import threading
import time
import zlib
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..coalesce import checked_denoising_steps, prompt_slots
from ..models.text_model import family_module
from ..ops import platform, sampling
from ..parallel.mesh import make_mesh, replicated
from ..registry import _auto_family, register_family
from ..telemetry import Span
from ..text_families import TEXT_FAMILIES
from ..weights import require_weights_present
from .common import RESIDENT_PARAM_BYTES, pad_bucket, program_cache_cap

logger = logging.getLogger(__name__)

# the tokens a prefill chunk holds at most (rows x positions: at 4096 the
# 18432-wide layer's three activations are 450 MB in bf16 and the
# worst-case expert buffer 470 MB)
PREFILL_CHUNK_TOKENS = 4096

EXPERT_PAIRS = telemetry.counter(
    "swarm_expert_pairs_total",
    "Token-expert pairs the experts held here computed (the grouped "
    "matmul's real rows), by model", ("model",))
ROUTED_TOKENS = telemetry.counter(
    "swarm_routed_tokens_total",
    "Token-expert pairs the router made, held here or not (tokens x "
    "experts per token, every expert-layer call), by model", ("model",))
EXPERT_PAIRS_MAX = telemetry.counter(
    "swarm_expert_pairs_max_total",
    "The fullest held expert's pairs, summed over expert-layer calls, by "
    "model", ("model",))
EXPERT_ROW_TILES = telemetry.counter(
    "swarm_expert_row_tiles_total",
    "Row tiles the grouped matmul visited (each held expert's pairs in "
    "whole tiles), summed over expert-layer calls, by model: over the "
    "experts that had a pair, the tiles an expert's matrices serve",
    ("model",))
PASS_CACHE_BYTES = telemetry.gauge(
    "swarm_pass_cache_bytes",
    "Bytes of the cache one pass holds (every layer's, rows x the "
    "positions the layer keeps), set when the pass's programs are placed, "
    "by model", ("model",))
PASS_WINDOW_CACHE_BYTES = telemetry.gauge(
    "swarm_pass_window_cache_bytes",
    "The part of swarm_pass_cache_bytes that is rings of a window (rows "
    "x window, the layers that attend to a window only), by model",
    ("model",))
PASS_STATE_BYTES = telemetry.gauge(
    "swarm_pass_state_bytes",
    "The part of swarm_pass_cache_bytes that is recurrent state and "
    "convolution tail (rows x the linear-attention layers: it does not "
    "grow with the positions), by model", ("model",))
PASS_INDEX_CACHE_BYTES = telemetry.gauge(
    "swarm_pass_index_cache_bytes",
    "The part of swarm_pass_cache_bytes that is index keys (rows x "
    "positions x the index key's width x layers: what a lightning indexer "
    "scores a query against), by model; only a family that selects keys "
    "sets it", ("model",))
SPARSE_VISIBLE = telemetry.counter(
    "swarm_sparse_visible_positions_total",
    "Cached positions the real queries of a family that selects keys could "
    "see (a query at position t: t + 1), summed over layers, on the "
    "device, by model and phase (prefill | decode)", ("model", "phase"))
SPARSE_SELECTED = telemetry.counter(
    "swarm_sparse_selected_positions_total",
    "Of swarm_sparse_visible_positions_total, the positions the selection "
    "picked and attention read (min(index_topk, visible) a query), by "
    "model and phase", ("model", "phase"))
PREFILL_KEY_EXTENT = telemetry.counter(
    "swarm_prefill_key_extent_total",
    "Key positions the prefill spans of a family that selects keys went "
    "over, summed over rows and layers on the device (on the host, from the "
    "pass's lengths, for a family whose row says bounds_prefill: the layers "
    "that keep every position), by model and extent "
    "(walked: up to the span's end, what the key side of a span is bounded "
    "by; bucket: the prompt slots' whole width a span): walked / bucket is "
    "36 / 64 for a row of eight spans, and 1 says no span was bounded",
    ("model", "extent"))
DECODE_CACHE_BLOCKS = telemetry.counter(
    "swarm_decode_cache_blocks_total",
    "Column blocks of the cache the decode attention of a family that "
    "bounds it by the mask went over, summed over rows, layers and steps "
    "on the host from the pass's lengths, by model and extent (walked: the "
    "blocks in which one of a grid step's rows saw a position, which the "
    "kernel fetches and computes for those rows and no others; bucket: "
    "rows x the blocks of the cache's width): walked / bucket is 1 where "
    "the plain form ran, which walks the width",
    ("model", "extent"))
BLOCK_FORWARD_ROWS = telemetry.counter(
    "swarm_block_forward_rows_total",
    "Real rows x forwards of a block decode, by model and kind (denoise: "
    "a forward that reads the cache and may fix positions, a fused one "
    "included; commit: a forward of its own that writes a finished "
    "block's keys and values and yields nothing, which the decode no "
    "longer makes: it stays 0)", ("model", "kind"))
BLOCK_FUSED_COMMIT_ROWS = telemetry.counter(
    "swarm_block_fused_commit_rows_total",
    "Real rows x the denoise forwards of a block decode that were fused: "
    "the first forward of a block, which also writes the keys and values "
    "of the finished block before it, by model", ("model",))
GENERATED_TOKENS = telemetry.counter(
    "swarm_generated_tokens_total",
    "Ids a block decode handed back (real rows x new tokens), by model",
    ("model",))
BLOCK_SLOTS = telemetry.counter(
    "swarm_block_slots_total",
    "Positions of real rows that a block decode's denoise forwards "
    "computed, by model and kind (unmasked: took an id in the forward; "
    "idle: were fixed before it, a prompt's given tail included)",
    ("model", "kind"))
PREFILL_SLOTS = telemetry.counter(
    "swarm_prefill_slots_total",
    "Prompt slots of the prefill programs' passes, by model and kind (real: "
    "a prompt's ids; padding: slots that hold no id and were computed; "
    "skipped: slots of the bucket no chunk computed, past a chunk's "
    "width or in a chunk that was not run)", ("model", "kind"))
PREFILL_CHUNKS = telemetry.counter(
    "swarm_prefill_chunks_total",
    "Chunks the prefill programs ran, by model and the width the chunk was "
    "run at (the slots of a row it computed)", ("model", "width"))


def prefill_chunk(rows: int, slots: int, spans: bool = True
                  ) -> tuple[int, int]:
    """(rows, positions) of one prefill chunk: whole rows where rows are
    short, a span of one row's positions where a row is longer and the
    model's prefill can attend to what earlier spans cached (`spans`: its
    module's `POSITION_CHUNKS`; else the long row whole)."""
    if spans and slots > PREFILL_CHUNK_TOKENS:
        return 1, PREFILL_CHUNK_TOKENS
    return max(min(rows, PREFILL_CHUNK_TOKENS // slots), 1), slots


class TextGenerationPipeline:
    """One resident language model per (model, slice)."""

    def __init__(self, model_name: str, chipset=None, dtype=None,
                 allow_random_init: bool = False, weights=None):
        """`weights(shapes, shardings)`, where given, returns the
        parameter tree already on this pipeline's mesh and takes the place
        of the seeded host init."""
        self.model_name = model_name
        self.chipset = chipset
        family = _auto_family(model_name)
        self.model = family_module(family)
        self.config = self.model.config_for(model_name)
        # the way this family decodes: a block a row, or a token a row
        self.by_blocks = bool(TEXT_FAMILIES[family].get("block_length"))
        # whether its attention reads the keys a learned index selects
        self.selects = bool(TEXT_FAMILIES[family].get("selects"))
        # whether its decode attention is bounded by what the mask shows
        self.bounds_decode = bool(
            TEXT_FAMILIES[family].get("bounds_decode"))
        # whether its prefill spans' key side is bounded by the span's end
        self.bounds_prefill = bool(
            TEXT_FAMILIES[family].get("bounds_prefill"))
        if dtype is None:
            dtype = (jnp.bfloat16 if jax.default_backend() == "tpu"
                     else jnp.float32)
        self.dtype = jnp.dtype(dtype)
        self.mesh = (chipset.mesh() if chipset is not None
                     else make_mesh(jax.devices()[:1]))
        started = time.perf_counter()
        shapes = self.param_shapes()
        if weights is not None:
            params = weights(shapes, self.param_shardings(shapes))
            if (jax.tree_util.tree_map(lambda x: tuple(x.shape), params)
                    != jax.tree_util.tree_map(lambda x: tuple(x.shape),
                                              shapes)):
                raise ValueError(
                    f"{model_name}: the weights handed in are not the tree "
                    "param_shapes() describes")
        else:
            require_weights_present(
                model_name, None, allow_random_init, component="language "
                "model", hint="This family has no checkpoint conversion "
                "path yet; `test/` names serve seeded weights.")
            seed = zlib.crc32(model_name.encode())
            params = jax.device_put(
                self.model.init_params(self.config, jax.random.key(seed),
                                       self.dtype),
                self.param_shardings(shapes))
        self.params = params
        from ..parallel.tensor import largest_device_bytes

        RESIDENT_PARAM_BYTES.set(largest_device_bytes(params),
                                 model=model_name)
        logger.info("%s resident in %.1fs (dtype=%s)", model_name,
                    time.perf_counter() - started, self.dtype)
        self._jit_lock = threading.Lock()
        self._programs: OrderedDict = OrderedDict()

    # --- the parameter tree ---

    def param_shapes(self):
        return self.model.param_shapes(self.config, self.dtype)

    def param_shardings(self, shapes=None):
        """Every leaf whole on every chip of the slice: one chip's share
        of an expert-parallel deployment is one chip's (the exchange
        between the chips that share a layer is not run here)."""
        shapes = self.param_shapes() if shapes is None else shapes
        whole = replicated(self.mesh)
        return jax.tree_util.tree_map(lambda _: whole, shapes)

    # --- programs ---

    def cache_bytes(self, rows: int, positions: int) -> tuple[int, int, int]:
        """(bytes of a pass's cache, the rings' part of it, the recurrent
        states' part of it)."""
        return self.model.cache_bytes(
            self.config, rows, positions, self.dtype.itemsize)

    def _program(self, key: tuple, build):
        with self._jit_lock:
            if key in self._programs:
                self._programs.move_to_end(key)
                return self._programs[key]
            program = self._programs[key] = build()
            cap = program_cache_cap()
            while cap and len(self._programs) > cap:
                self._programs.popitem(last=False)
            return program

    def cache_positions(self, slots: int, new_tokens: int) -> int:
        """Columns of the cache of a pass of `slots` prompt slots and
        `new_tokens` ids a row (by blocks: every block but the last whole,
        at most `block_length - 2` columns over `slots + new_tokens`)."""
        if self.by_blocks:
            return self.model.cache_positions(self.config, slots, new_tokens)
        return slots + new_tokens

    def prefill_program(self, rows: int, slots: int, positions: int):
        """`(params, ids [rows, slots], lengths [rows]) -> (logits of each
        row's last prompt token [rows, vocab], cache, tally)`; a model
        that decodes by blocks returns `(cache, tally)`."""
        cfg, model = self.config, self.model
        chunk = prefill_chunk(rows, slots, model.POSITION_CHUNKS)

        def build():
            whole, rings, state = self.cache_bytes(rows, positions)
            PASS_CACHE_BYTES.set(whole, model=self.model_name)
            PASS_WINDOW_CACHE_BYTES.set(rings, model=self.model_name)
            PASS_STATE_BYTES.set(state, model=self.model_name)
            if self.selects:
                PASS_INDEX_CACHE_BYTES.set(
                    model.index_cache_bytes(cfg, rows, positions,
                                            self.dtype.itemsize),
                    model=self.model_name)

            def text_prefill(params, ids, lengths):
                return model.prefill(
                    params, cfg, ids, lengths, positions, *chunk)

            return jax.jit(text_prefill)

        return self._program(("prefill", rows, slots, positions), build)

    def step_program(self, rows: int, slots: int, positions: int):
        """One decode step with given tokens: `(params, cache, tokens
        [rows], lengths [rows], step) -> (logits [rows, vocab], cache)`:
        the tokens are each row's generated token number `step`. On a chip
        the cache is donated, as the decode programs': a cache that is
        most of the memory left beside the weights cannot stand there
        twice."""
        cfg, model = self.config, self.model

        def text_step(params, cache, tokens, lengths, number):
            logits, cache, _ = model.step(
                params, cfg, tokens, lengths, number, slots, cache,
                model.empty_load(cfg), valid=lengths > 0)
            return logits, cache

        donate = (1,) if platform.trace_platform() == "tpu" else ()
        return self._program(
            ("step", rows, slots, positions),
            lambda: jax.jit(text_step, donate_argnums=donate))

    def decode_program(self, rows: int, slots: int, new_tokens: int):
        """`(params, cache, logits, lengths, job_keys, job_of_row,
        row_in_job, temperature, tally) -> (ids [rows, new_tokens],
        tally, cache)`. On a chip the cache is donated and comes back as
        the same buffers (nobody reads it: returning it is what lets the
        scan write it in place)."""
        cfg, model = self.config, self.model

        def sample(logits, keys, step, temperature):
            keys = jax.vmap(lambda key: jax.random.fold_in(key, step))(keys)
            return sampling.sample(keys, logits, temperature)[0]

        def text_decode(params, cache, logits, lengths, job_keys,
                        job_of_row, row_in_job, temperature, load):
            keys = jax.vmap(jax.random.fold_in)(
                jax.random.wrap_key_data(job_keys)[job_of_row], row_in_job)
            first = sample(logits, keys, 0, temperature)

            def step(carry, number):
                tokens, cache, load = carry
                logits, cache, load = model.step(
                    params, cfg, tokens, lengths, number, slots, cache,
                    load, valid=lengths > 0)
                tokens = sample(logits, keys, number + 1, temperature)
                return (tokens, cache, load), tokens

            (_, cache, load), rest = jax.lax.scan(
                step, (first, cache, load), jnp.arange(new_tokens - 1))
            return jnp.concatenate([first[None], rest]).T, load, cache

        donate = (1,) if platform.trace_platform() == "tpu" else ()
        return self._program(
            ("decode", rows, slots, new_tokens),
            lambda: jax.jit(text_decode, donate_argnums=donate))

    def block_program(self, rows: int, slots: int, positions: int,
                      commit: bool):
        """One forward of a block with given ids: `(params, cache, ids
        [rows, block_length], lengths [rows], block) -> (logits [rows,
        block_length, vocab], cache)`, the cache written under `commit`
        and else as it came (on a chip donated and handed back as the
        same buffers either way, as the decode programs')."""
        cfg, model = self.config, self.model

        def text_block_forward(params, cache, ids, lengths, block):
            logits, cache, _ = model.block_step(
                params, cfg, ids, lengths, block, slots, cache,
                model.empty_load(cfg), valid=lengths > 0, commit=commit)
            return logits, cache

        donate = (1,) if platform.trace_platform() == "tpu" else ()
        return self._program(
            ("block", rows, slots, positions, commit),
            lambda: jax.jit(text_block_forward, donate_argnums=donate))

    def block_decode_program(self, rows: int, slots: int, new_tokens: int,
                             denoising_steps: int, thresholded: bool):
        """`(params, cache, ids [rows, slots], lengths, job_keys,
        job_of_row, row_in_job, temperature, threshold, tally) -> (ids
        [rows, new_tokens], tally, counts, cache)`: the opening block's
        bounded loop of denoise forwards, then the scan over the blocks
        behind it, in each the fused forward that commits the block before
        and the bounded loop of the later ones (the last block is not
        committed: nobody reads it). `counts` are the pass's (denoise
        forwards, those of them that were fused, positions of real rows
        unmasked, positions of real rows computed though fixed), counted
        on the device: under a threshold the forwards are data. The cache
        is donated, as the other decode's."""
        cfg, model = self.config, self.model
        length = cfg.block_length
        blocks = model.blocks_of(cfg, new_tokens)
        count = length // denoising_steps

        def text_block_decode(params, cache, ids, lengths, job_keys,
                              job_of_row, row_in_job, temperature,
                              threshold, load):
            valid = lengths > 0
            keys = jax.vmap(jax.random.fold_in)(
                jax.random.wrap_key_data(job_keys)[job_of_row], row_in_job)

            def denoise(number, cache, state, finished=None):
                """One forward of block `number` and what it fixes; with
                `finished` a fused one, which writes the cache."""
                forward, tokens, masked, load, counts = state
                logits, cache, load = model.block_step(
                    params, cfg, tokens, lengths, number, slots, cache,
                    load, valid=valid, finished=finished)
                forward_keys = jax.vmap(lambda key: jax.random.fold_in(
                    jax.random.fold_in(key, number), forward))(keys)
                # an id a position from its own logits, and the probability
                # it was drawn with (at temperature 0 the largest logit's)
                drawn, log_p = sampling.sample(
                    forward_keys, logits, temperature)
                tokens, left = model.unmask(
                    tokens, masked, drawn, jnp.exp(log_p), count,
                    threshold if thresholded else None)
                took = jnp.sum(masked & ~left)
                fixed = jnp.sum(valid[:, None] & ~masked)
                return (forward + 1, tokens, left, load, counts + jnp.stack(
                    [1, int(finished is not None), took, fixed])), cache

            def unfinished(state):
                forward, _, masked, _, _ = state
                return (forward < denoising_steps) & jnp.any(masked)

            def block(number, tokens, masked, cache, load, counts,
                      finished=None):
                """Block `number` from `tokens` until no row has a mask
                left in it or it has had its forwards. With `finished`
                (the block before it, which the cache lacks) the first
                forward is the fused one and runs whatever the masks: a
                block behind another starts all masked. No later forward
                writes the cache."""
                state = (jnp.int32(0), tokens, masked & valid[:, None],
                         load, counts)
                if finished is not None:
                    state, cache = denoise(number, cache, state, finished)
                _, tokens, _, load, counts = jax.lax.while_loop(
                    unfinished,
                    lambda state: denoise(number, cache, state)[0], state)
                return tokens, cache, load, counts

            def later(carry, number):
                finished, *rest = carry
                tokens, *rest = block(
                    number, jnp.full_like(finished, cfg.mask_token_id),
                    jnp.ones_like(finished, bool), *rest, finished=finished)
                return (tokens, *rest), tokens

            first, *rest = block(
                jnp.int32(0), *model.first_block(cfg, ids, lengths), cache,
                load, jnp.zeros((4,), jnp.int32))
            (_, cache, load, counts), out = jax.lax.scan(
                later, (first, *rest), jnp.arange(1, blocks))
            out = jnp.concatenate([first[None], out]).transpose(1, 0, 2)
            # a row's first new id stands behind its prompt's given tail
            at = (lengths % length)[:, None] + jnp.arange(new_tokens)
            out = jnp.take_along_axis(out.reshape(rows, -1), at, axis=1)
            return out, load, counts, cache

        donate = (1,) if platform.trace_platform() == "tpu" else ()
        return self._program(
            ("block_decode", rows, slots, new_tokens, denoising_steps,
             thresholded),
            lambda: jax.jit(text_block_decode, donate_argnums=donate))

    # --- a pass ---

    def run_batched(self, requests: list[dict], *, max_new_tokens: int,
                    temperature: float = 1.0,
                    denoising_steps: int | None = None,
                    confidence_threshold: float | None = None):
        """One pass over every row of `requests` (each `prompt_ids`: a
        list of rows of ids, and `rng`: the job's key). Returns per
        request its ids `[rows, max_new_tokens]` (numpy) and the pass's
        `pipeline_config`. `denoising_steps` (the denoise forwards a block
        gets at most: a divisor of the block length, which is the default)
        and `confidence_threshold` are a block decode's."""
        cfg = self.config
        new_tokens = int(max_new_tokens)
        if new_tokens < 1:
            raise ValueError("max_new_tokens must be at least 1")
        if self.by_blocks:
            steps = checked_denoising_steps(denoising_steps, cfg.block_length)
        elif denoising_steps is not None or confidence_threshold is not None:
            raise ValueError(
                f"{self.model_name} decodes a token a step: it takes no "
                "denoising_steps or confidence_threshold")
        prompts = [row for request in requests
                   for row in request["prompt_ids"]]
        if not prompts or any(len(row) < 1 for row in prompts):
            raise ValueError("every row of prompt_ids needs at least one id")
        flat = np.fromiter((i for row in prompts for i in row), np.int64)
        if flat.min() < 0 or flat.max() >= cfg.vocab_size:
            raise ValueError(
                f"prompt_ids outside [0, {cfg.vocab_size}): the rows of "
                f"the vocabulary {self.model_name} holds")
        real = len(prompts)
        rows = pad_bucket(real)
        slots = prompt_slots(max(len(row) for row in prompts))
        ids = np.zeros((rows, slots), np.int32)
        # a row that only pads the pass to its bucket has no prompt: it is
        # computed and routed nowhere
        lengths = np.zeros((rows,), np.int32)
        job_of_row = np.zeros((rows,), np.int32)
        row_in_job = np.zeros((rows,), np.int32)
        at = 0
        for job, request in enumerate(requests):
            for number, row in enumerate(request["prompt_ids"]):
                ids[at, :len(row)] = row
                lengths[at] = len(row)
                job_of_row[at], row_in_job[at] = job, number
                at += 1
        # longest first, the rows that only pad the pass last: a prefill
        # chunk takes rows of one width, and the whole pass runs in this
        # order (a row's keys are its job's and its number in the job, so
        # it draws the same ids wherever it stands)
        order = np.argsort(-lengths, kind="stable")
        ids, lengths, job_of_row, row_in_job = (
            x[order] for x in (ids, lengths, job_of_row, row_in_job))
        job_keys = jnp.stack([jax.random.key_data(request["rng"])
                              for request in requests])
        positions = self.cache_positions(slots, new_tokens)
        prefill = self.prefill_program(rows, slots, positions)
        timings: dict = {}
        counts = None
        with Span("prefill", timings):
            # the last prompt position's logits first, where the model
            # decodes a token a step
            *logits, cache, filled = prefill(self.params, ids, lengths)
            jax.block_until_ready(filled)
        with Span("decode", timings):
            sampling = (job_keys, job_of_row, row_in_job,
                        jnp.float32(temperature))
            if self.by_blocks:
                out, load, counts, cache = self.block_decode_program(
                    rows, slots, new_tokens, steps,
                    confidence_threshold is not None)(
                    self.params, cache, ids, lengths, *sampling,
                    jnp.float32(confidence_threshold or 0.0), filled)
            else:
                out, load, cache = self.decode_program(
                    rows, slots, new_tokens)(
                    self.params, cache, *logits, lengths, *sampling, filled)
            del cache
            jax.block_until_ready(out)
        with Span("readback", timings):
            # back in the jobs' order
            out = np.asarray(out)[np.argsort(order)]
            # the routing's tally, and behind it the selection's where
            # the family has one
            (pairs, sums, *chose), (before, before_sums, *chose_before) = (
                tuple(np.asarray(x) for x in tally)
                for tally in (load, filled))
            if counts is not None:
                denoise, fused, unmasked, idle = (
                    int(x) for x in np.asarray(counts))

        def tally(pairs, sums, calls):
            return {"pairs": int(pairs.sum()), "routed": int(sums[0]),
                    "pairs_max": int(sums[1]), "active": int(sums[2]),
                    "tiles": int(sums[3]), "calls": calls}

        # the chunks the prefill program ran and those it did not, by the
        # model's own rule on the lengths it was given
        widths, skipped, computed = self.model.prefill_account(
            lengths, slots, *prefill_chunk(
                rows, slots, self.model.POSITION_CHUNKS))
        chunks = sum(widths.values())
        # expert-layer calls of either program: a block model's prefill
        # chunk, whose logits nobody reads, stops before its last layer's
        # experts; every forward of either decode runs them all (a fused
        # forward's last layer for its own block)
        layers = cfg.expert_layers
        forwards = denoise if self.by_blocks else new_tokens - 1
        prefill_calls = (layers - 1 if self.by_blocks else layers) * chunks
        decode_calls = layers * forwards
        # the whole pass, and its two programs apart (decode's tally began
        # where prefill's ended)
        routing = {
            **tally(pairs, sums, prefill_calls + decode_calls),
            "prefill": tally(before, before_sums, prefill_calls),
            "decode": tally(pairs - before, sums - before_sums,
                            decode_calls),
            "pairs_by_expert": pairs.sum(axis=0).tolist()}
        label = {"model": self.model_name}
        selection = {}
        if self.selects:
            # (visible, selected) of the pass and of its prefill: decode's
            # tally began where prefill's ended; behind them the key
            # positions the prefill's spans walked, and the bucket's
            (*whole, _, _), (*first, walked, bucket) = (
                self.model.selection_counts(leaf)
                for leaf in (chose[0], chose_before[0]))
            by_phase = [{"prefill": before, "decode": total - before}
                        for total, before in zip(whole, first)]
            for counter, phases in zip((SPARSE_VISIBLE, SPARSE_SELECTED),
                                       by_phase):
                for phase, count in phases.items():
                    counter.inc(count, phase=phase, **label)
            PREFILL_KEY_EXTENT.inc(walked, extent="walked", **label)
            PREFILL_KEY_EXTENT.inc(bucket, extent="bucket", **label)
            selection = {"selection": {
                **dict(zip(("visible", "selected"), by_phase)),
                "prefill_key_extent": {"walked": walked, "bucket": bucket}}}
        bounded = {}
        if self.bounds_prefill:
            walked, bucket = self.model.prefill_key_extent(
                cfg, lengths, slots, *prefill_chunk(
                    rows, slots, self.model.POSITION_CHUNKS))
            PREFILL_KEY_EXTENT.inc(walked, extent="walked", **label)
            PREFILL_KEY_EXTENT.inc(bucket, extent="bucket", **label)
            bounded = {"prefill_key_extent": {"walked": walked,
                                              "bucket": bucket}}
        if self.bounds_decode:
            walked, bucket = self.model.decode_cache_blocks(
                cfg, lengths, slots, positions, forwards)
            DECODE_CACHE_BLOCKS.inc(walked, extent="walked", **label)
            DECODE_CACHE_BLOCKS.inc(bucket, extent="bucket", **label)
            bounded["decode_cache_blocks"] = {"walked": walked,
                                              "bucket": bucket}
        blocks = {}
        if self.by_blocks:
            BLOCK_FORWARD_ROWS.inc(real * denoise, kind="denoise", **label)
            # no commit is a forward of its own: the label stays, at 0
            BLOCK_FORWARD_ROWS.inc(0, kind="commit", **label)
            BLOCK_FUSED_COMMIT_ROWS.inc(real * fused, **label)
            GENERATED_TOKENS.inc(real * new_tokens, **label)
            BLOCK_SLOTS.inc(unmasked, kind="unmasked", **label)
            BLOCK_SLOTS.inc(idle, kind="idle", **label)
            blocks = {"block_length": cfg.block_length,
                      "denoising_steps": steps,
                      "confidence_threshold": confidence_threshold,
                      "blocks": self.model.blocks_of(cfg, new_tokens),
                      "forwards": {"denoise": denoise, "commit": 0,
                                   "fused": fused}}
        EXPERT_PAIRS.inc(routing["pairs"], **label)
        ROUTED_TOKENS.inc(routing["routed"], **label)
        EXPERT_PAIRS_MAX.inc(routing["pairs_max"], **label)
        EXPERT_ROW_TILES.inc(routing["tiles"], **label)
        prompt_tokens = int(lengths.sum())
        PREFILL_SLOTS.inc(prompt_tokens, kind="real", **label)
        PREFILL_SLOTS.inc(computed - prompt_tokens, kind="padding", **label)
        PREFILL_SLOTS.inc(rows * slots - computed, kind="skipped", **label)
        for width, count in widths.items():
            PREFILL_CHUNKS.inc(count, width=width, **label)
        cache_bytes, cache_bytes_window, cache_bytes_state = self.cache_bytes(
            rows, positions)
        results, at = [], 0
        for request in requests:
            n = len(request["prompt_ids"])
            results.append((out[at:at + n], {
                "model_name": self.model_name,
                "sequences": n,
                "batch_rows": [at, n],
                "pass_rows": real,
                "padded_rows": rows,
                "prompt_slots": slots,
                "prompt_tokens": int(sum(
                    len(row) for row in request["prompt_ids"])),
                "max_new_tokens": new_tokens,
                "decode_steps": forwards,
                **bounded,
                "temperature": float(temperature),
                **blocks,
                "prefill_chunks": chunks,
                "prefill_chunks_skipped": skipped,
                "prefill_chunk_widths": dict(widths),
                "cache_bytes": cache_bytes,
                "cache_bytes_window": cache_bytes_window,
                "cache_bytes_state": cache_bytes_state,
                "routing": routing,
                **selection,
                "timings": dict(timings)}))
            at += n
        return results

    def release(self) -> None:
        self._programs.clear()
        self.params = None


for _family in TEXT_FAMILIES:
    # a module that lacks a name of the interface fails here, at import
    family_module(_family)
    register_family(_family)(TextGenerationPipeline)
