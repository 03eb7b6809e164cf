"""Text completion from token ids: one jitted prefill and one jitted cached
decode over a resident Kimi-K2 language model (models/kimi.py).

A pass is a set of rows (sequences), each a prompt of token ids, all
generating the same number of new tokens. Rows are padded to a power-of-two
row bucket and prompts to a power-of-two slot bucket (a row's prompt first,
padding after it), so a pass is keyed by (rows, prompt slots, new tokens):

- **prefill** runs the prompts through every layer, rows in chunks so that
  the widest layer's activations fit, writes each layer's latent cache and
  returns the logits of every row's last prompt token;
- **decode** is a `lax.scan` of `new tokens - 1` steps through the cache
  (the first token comes from prefill's logits, the last one is never fed):
  a step feeds every row its last token, and samples the next on the device
  from a key folded from the job's seed, the row's number in its job and
  the step, so a row's ids do not depend on its batchmates;
- **step** is the decode step alone, given tokens in, logits out: what a
  comparison with the plain reference needs.

The cache holds `kv_lora_rank + qk_rope_head_dim` values a position a layer
(`swarm_pass_cache_bytes{model}`); how the routing fell comes back with the
ids (`swarm_expert_pairs_total`, `swarm_routed_tokens_total`,
`swarm_expert_pairs_max_total`; the envelope's `routing` has the same a
program, with the experts that had a pair and the calls).

No tokenizer: ids travel on the wire, and there is no stop token, every
row generates `max_new_tokens`. `test/` names are seeded weights: `tiny` in
the name is the tiny preset, any other the chip's share `KIMI_K2_EP32` at
the published widths (`weights=` hands the tree in already on the chip, as
`FluxPipeline` takes it: the host init of 4.8 B parameters is minutes).
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
from ..coalesce import prompt_slots
from ..models import kimi
from ..ops import platform
from ..parallel.mesh import make_mesh, replicated
from ..registry import register_family
from ..telemetry import Span
from ..weights import require_weights_present
from .common import RESIDENT_PARAM_BYTES, pad_bucket, program_cache_cap

logger = logging.getLogger(__name__)

# the tokens a prefill chunk holds at most (rows x slots: at 4096 the
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
PASS_CACHE_BYTES = telemetry.gauge(
    "swarm_pass_cache_bytes",
    "Bytes of the latent cache one pass holds (rows x positions x cache "
    "width x layers), set when the pass's programs are placed, by model",
    ("model",))


def prefill_chunk_rows(rows: int, slots: int) -> int:
    return max(min(rows, PREFILL_CHUNK_TOKENS // slots), 1)


def _config_for(model_name: str) -> kimi.KimiConfig:
    return (kimi.KIMI_TINY if "tiny" in model_name.lower()
            else kimi.KIMI_K2_EP32)


class TextGenerationPipeline:
    """One resident language model per (model, slice)."""

    def __init__(self, model_name: str, chipset=None, dtype=None,
                 allow_random_init: bool = False, weights=None):
        """`weights(shapes, shardings)`, where given, returns the
        parameter tree already on this pipeline's mesh and takes the place
        of the seeded host init."""
        self.model_name = model_name
        self.chipset = chipset
        self.config = _config_for(model_name)
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
                kimi.init_params(self.config, jax.random.key(seed),
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
        return kimi.param_shapes(self.config, self.dtype)

    def param_shardings(self, shapes=None):
        """Every leaf whole on every chip of the slice: one chip's share
        of an expert-parallel deployment is one chip's (the exchange
        between the chips that share a layer is not run here)."""
        shapes = self.param_shapes() if shapes is None else shapes
        whole = replicated(self.mesh)
        return jax.tree_util.tree_map(lambda _: whole, shapes)

    # --- programs ---

    def cache_bytes(self, rows: int, positions: int) -> int:
        cfg = self.config
        return (rows * positions * cfg.cache_width * self.dtype.itemsize
                * cfg.num_hidden_layers)

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

    def prefill_program(self, rows: int, slots: int, positions: int):
        """`(params, ids [rows, slots], lengths [rows]) -> (logits of each
        row's last prompt token [rows, vocab], cache, tally)`."""
        cfg = self.config
        chunk = prefill_chunk_rows(rows, slots)

        def build():
            PASS_CACHE_BYTES.set(self.cache_bytes(rows, positions),
                                 model=self.model_name)
            return jax.jit(lambda params, ids, lengths: kimi.prefill(
                params, cfg, ids, lengths, positions, chunk))

        return self._program(("prefill", rows, slots, positions), build)

    def step_program(self, rows: int, slots: int, positions: int):
        """One decode step with given tokens: `(params, cache, tokens
        [rows], lengths [rows], step) -> (logits [rows, vocab], cache)`:
        the tokens are each row's generated token number `step`."""
        cfg = self.config

        def step(params, cache, tokens, lengths, number):
            column = slots + number
            logits, cache, _ = kimi.decode_step(
                params, cfg, tokens, lengths + number, cache, column,
                kimi.decode_mask(lengths, slots, positions, column),
                kimi.empty_load(cfg), valid=lengths > 0)
            return logits, cache

        return self._program(("step", rows, slots, positions),
                             lambda: jax.jit(step))

    def decode_program(self, rows: int, slots: int, new_tokens: int):
        """`(params, cache, logits, lengths, job_keys, job_of_row,
        row_in_job, temperature, tally) -> (ids [rows, new_tokens],
        tally, cache)`. On a chip the cache is donated and comes back as
        the same buffers (nobody reads it: returning it is what lets the
        scan write it in place)."""
        cfg = self.config
        positions = slots + new_tokens

        def sample(logits, keys, step, temperature):
            keys = jax.vmap(lambda key: jax.random.fold_in(key, step))(keys)
            drawn = jax.vmap(jax.random.categorical)(
                keys, logits / jnp.maximum(temperature, 1e-6))
            return jnp.where(temperature > 0, drawn,
                             jnp.argmax(logits, axis=-1)).astype(jnp.int32)

        def decode(params, cache, logits, lengths, job_keys, job_of_row,
                   row_in_job, temperature, load):
            keys = jax.vmap(jax.random.fold_in)(
                jax.random.wrap_key_data(job_keys)[job_of_row], row_in_job)
            first = sample(logits, keys, 0, temperature)

            def step(carry, number):
                tokens, cache, load = carry
                column = slots + number
                logits, cache, load = kimi.decode_step(
                    params, cfg, tokens, lengths + number, cache, column,
                    kimi.decode_mask(lengths, slots, positions, column),
                    load, valid=lengths > 0)
                tokens = sample(logits, keys, number + 1, temperature)
                return (tokens, cache, load), tokens

            (_, cache, load), rest = jax.lax.scan(
                step, (first, cache, load), jnp.arange(new_tokens - 1))
            return jnp.concatenate([first[None], rest]).T, load, cache

        donate = (1,) if platform.trace_platform() == "tpu" else ()
        return self._program(
            ("decode", rows, slots, new_tokens),
            lambda: jax.jit(decode, donate_argnums=donate))

    # --- a pass ---

    def run_batched(self, requests: list[dict], *, max_new_tokens: int,
                    temperature: float = 1.0):
        """One pass over every row of `requests` (each `prompt_ids`: a
        list of rows of ids, and `rng`: the job's key). Returns per
        request its ids `[rows, max_new_tokens]` (numpy) and the pass's
        `pipeline_config`."""
        cfg = self.config
        new_tokens = int(max_new_tokens)
        if new_tokens < 1:
            raise ValueError("max_new_tokens must be at least 1")
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
        job_keys = jnp.stack([jax.random.key_data(request["rng"])
                              for request in requests])
        positions = slots + new_tokens
        timings: dict = {}
        with Span("prefill", timings):
            logits, cache, filled = self.prefill_program(
                rows, slots, positions)(self.params, ids, lengths)
            jax.block_until_ready(logits)
        with Span("decode", timings):
            out, load, cache = self.decode_program(rows, slots, new_tokens)(
                self.params, cache, logits, lengths, job_keys, job_of_row,
                row_in_job, jnp.float32(temperature), filled)
            del cache
            jax.block_until_ready(out)
        with Span("readback", timings):
            out = np.asarray(out)
            (pairs, sums), (before, before_sums) = (
                tuple(np.asarray(x) for x in tally)
                for tally in (load, filled))
        def tally(pairs, sums, calls):
            return {"pairs": int(pairs.sum()), "routed": int(sums[0]),
                    "pairs_max": int(sums[1]), "active": int(sums[2]),
                    "calls": cfg.expert_layers * calls}

        # the whole pass, and its two programs apart (decode's tally began
        # where prefill's ended)
        chunks = rows // prefill_chunk_rows(rows, slots)
        routing = {
            **tally(pairs, sums, chunks + new_tokens - 1),
            "prefill": tally(before, before_sums, chunks),
            "decode": tally(pairs - before, sums - before_sums,
                            new_tokens - 1),
            "pairs_by_expert": pairs.sum(axis=0).tolist()}
        label = {"model": self.model_name}
        EXPERT_PAIRS.inc(routing["pairs"], **label)
        ROUTED_TOKENS.inc(routing["routed"], **label)
        EXPERT_PAIRS_MAX.inc(routing["pairs_max"], **label)
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
                "decode_steps": new_tokens - 1,
                "temperature": float(temperature),
                "cache_bytes": self.cache_bytes(rows, positions),
                "routing": routing,
                "timings": dict(timings)}))
            at += n
        return results

    def release(self) -> None:
        self._programs.clear()
        self.params = None


@register_family("kimi_k2")
def _build(model_name: str, chipset=None, **variant):
    return TextGenerationPipeline(model_name, chipset, **variant)
