"""The text families: one row a language model whose jobs carry token ids
and whose rows are sequences (pipelines/text_generation.py). Everything
that has to know a family by name reads its row here, and nothing else in
`chiaswarm_tpu/` spells a family's key: the hive and the worker through
coalesce.py (which hands `TEXT_FAMILIES` and `text_family_of` on: this
module is jax-free, as that one is), the registry its wire name, the
admission its footprint (chips/requirements.py), the pipeline its module
(models/text_model.py `family_module`, which also holds the module to what
a family's module has to give). A new language model is a module under
`models/` and a row here.

A row:

- `name`: the word of a model's name that tells the family;
- `wire`: the wire name of its pipeline type;
- `block_length`: only where the family decodes a block of positions at a
  time (its module then has `block_step` and not `step`): the block's
  length, which its config's `block_length` equals;
- `module`: its module under `models/`;
- `selects`: only where the family's attention reads the keys a learned
  index selects (its module then has `index_cache_bytes` and
  `selection_counts`, and its tally a third leaf: models/text_model.py);
- `bounds_decode`: only where the family's decode attention is bounded by
  what a row's mask shows and not by the cache's width (its module then
  has `decode_cache_blocks`: models/text_model.py);
- `bounds_prefill`: only where the key side of the family's prefill spans
  is bounded by the span's end and nothing on the device counts it (its
  module then has `prefill_key_extent`: models/text_model.py);
- the footprint admission reckons with, in bf16 on one chip. Admission is
  the weights the chip holds (`params_gb`, GiB), a working set that does
  not grow with the rows (`working_gb`: a prefill chunk's activations and
  worst-case expert buffer, the logits), and a row's cache: a row of
  `size` positions (prompt slots + new tokens) costs, a kind of layer,
  `min(size, the kind's window or size)` positions of `bytes a position`
  (`cache_layers`: (bytes a position summed over the layers of the kind,
  the window they keep or 0 for every position)), and, where the family
  has layers that keep a recurrent state and no keys, `row_bytes`: what
  those layers hold a row whatever its positions, so that a row never
  costs nothing however short it is. The bytes are what the module's own
  `cache_bytes` reckons from its full-size config (tests/test_text_serving.py
  holds the two equal); they stand here because this module imports no
  jax.
"""

TEXT_FAMILIES: dict[str, dict] = {
    # one chip's share of a 32-chip expert-parallel deployment
    # (models/kimi.py KIMI_K2_EP32): 4.85 B parameters in bf16 = 9.70 GB; a
    # position is 576 values x 2 bytes on each of 7 layers, all kept whole;
    # the working set is what the compile for a described v5e counted for
    # the 256-row programs beside weights and cache
    # (benchmark/compile_check.py, PERF.md)
    "kimi_k2": {
        "name": "kimi", "wire": "KimiK2ForCausalLM", "module": "kimi",
        "bounds_decode": True, "params_gb": 9.04, "working_gb": 3.0,
        "cache_layers": ((8064.0, 0),)},
    # one chip's share of an 8-chip deployment (models/exaone.py
    # EXAONE_236B_EP8): 3.71 B parameters = 7.42 GB; a position is a key
    # and a value on 8 heads of 128 x 2 bytes = 4096 B a layer, kept whole
    # on the one full layer and as a ring of 128 on the four sliding ones;
    # the working set is a 4096-token chunk's activations, its expert
    # buffer and a 16384-slot row's keys beside them
    "exaone_moe": {
        "name": "exaone", "wire": "ExaoneMoeForCausalLM", "module": "exaone",
        "params_gb": 6.91, "working_gb": 3.0,
        "cache_layers": ((4096.0, 0), (16384.0, 128))},
    # one stage of an 8-stage pipeline with every expert held
    # (models/sdar.py SDAR_30B_PP8): 4.36 B parameters = 8.72 GB; a
    # position is a key and a value on 4 heads of 128 x 2 bytes = 2048 B on
    # each of 6 layers, all kept whole; the working set is what the compile
    # for a described v5e counted for the 256-row block decode beside
    # weights and cache (benchmark/compile_check.py, PERF.md: 4.14 GB of
    # temporaries, a block step's float32 logits over the whole vocabulary,
    # the sampler's copies of them and its random bits, the expert buffer;
    # the columns a pass's last committed block may overhang `prompt slots
    # + new tokens` by are in it too)
    "sdar_moe": {
        "name": "sdar", "wire": "SdarMoeForCausalLM", "module": "sdar",
        "block_length": 4, "params_gb": 8.12, "working_gb": 3.9,
        "cache_layers": ((12288.0, 0),)},
    # one of 4 chips that share each layer, one of 6 pipeline stages
    # (models/qwen3_next.py QWEN3_NEXT_80B_EP4): 3.667 B parameters =
    # 7.33 GB; each of the 6 linear layers holds a row a float32 state of
    # 32 heads x 128 x 128 (2,097,152 B) and a convolution's tail of 3 x
    # 8192 values x 2 bytes (49,152 B): 12,877,824 B a row whatever its
    # positions; a position is a key and a value on 2 heads of 256 x 2
    # bytes = 2048 B on each of the 2 full layers, kept whole; the working
    # set is what the compile for a described v5e counted beside weights
    # and cache for the 256-row programs (1.36 GB of temporaries for the
    # prefill, a chunk's float32 operands of the chunk rule among them,
    # 0.60 GB for the decode: benchmark/compile_check.py, PERF.md; the
    # chip's own peak lies 0.12 GB over weights and cache)
    "qwen3_next": {
        "name": "qwen3-next", "wire": "Qwen3NextForCausalLM",
        "module": "qwen3_next", "params_gb": 6.83, "working_gb": 1.5,
        "cache_layers": ((4096.0, 0),), "row_bytes": 12877824.0},
    # one stage of an 18-stage pipeline, four whole layers and the whole
    # vocabulary (models/falcon_h1.py FALCON_H1_34B_PP18): 4.394 B
    # parameters = 8.79 GB; every layer holds a row BOTH a float32 state of
    # 32 heads x 256 x 128 (4,194,304 B) with a convolution's tail of 3 x
    # 5120 values x 2 bytes (30,720 B): 16,900,096 B a row over the 4
    # layers whatever its positions, AND a key and a value a position on 4
    # heads of 128 x 2 bytes = 2048 B a layer, kept whole; no experts; the
    # working set is what the compile for a described v5e counted beside
    # weights and cache for the 256-row programs
    # (benchmark/compile_check.py, PERF.md section 6, PR 46)
    "falcon_h1": {
        "name": "falcon-h1", "wire": "FalconH1ForCausalLM",
        "module": "falcon_h1", "params_gb": 8.19, "working_gb": 2.0,
        "cache_layers": ((8192.0, 0),), "row_bytes": 16900096.0},
    # one of 16 chips that share each layer (models/glm_moe_dsa.py
    # GLM5_EP16): one dense and four expert layers, experts 0-15 of 256, an
    # eighth of the vocabulary: 3.910 B parameters = 7.82 GB; a position is
    # a latent of 576 values AND an index key of 128 values x 2 bytes =
    # 1408 B on each of 5 layers, both kept whole (attention reads 2048
    # selected positions a query, the indexer scores every one); the
    # working set is what the compile for a described v5e counted beside
    # weights and cache for the 2-row, 32768-slot prefill program (a span's
    # index scores [4096, 32768] float32, its mask, and the 64 heads' keys
    # and values expanded from 32768 cached latents:
    # benchmark/compile_check.py, PERF.md section 6, PR 49)
    "glm_moe_dsa": {
        "name": "glm-5", "wire": "GlmMoeDsaForCausalLM",
        "module": "glm_moe_dsa", "selects": True, "params_gb": 7.28,
        "working_gb": 4.0, "cache_layers": ((7040.0, 0),)},
    # one of 16 chips that share each layer (models/mimo_v2.py
    # MIMO_V25_EP16): layer 0 (full, dense) and one whole period behind it
    # (five window layers, one full), experts 0-15 of 256, an eighth of the
    # vocabulary: 3.430 B parameters = 6.86 GB; a position is a key of 192
    # and a value of 128 x 2 bytes a key head: 4 heads = 2560 B on each of
    # the 2 full layers, kept whole, 8 heads = 5120 B on each of the 5
    # window layers, kept as a ring of 128; the working set is over what the
    # compile for a described v5e counted beside weights and cache for the
    # 2-row, 32768-slot prefill program (1.19 GB: a span's queries and a
    # row's keys padded to the kernel's 256 lanes, the span's expert buffer:
    # benchmark/compile_check.py; the chip's own peak lies 0.75 GB over
    # weights and cache: PERF.md section 6, PR 57), with the siblings' room
    # for a pass of many short rows, which no cell runs
    "mimo_v2": {
        "name": "mimo", "wire": "MiMoV2ForCausalLM", "module": "mimo_v2",
        "bounds_prefill": True, "params_gb": 6.39, "working_gb": 3.0,
        "cache_layers": ((5120.0, 0), (25600.0, 128))},
}


def text_family_of(model_name: str) -> str | None:
    """The text family a model's name tells, None for any other model."""
    name = model_name.lower()
    for family, what in TEXT_FAMILIES.items():
        # the family's word, or its key (a worker reckons a family's
        # appetite with the key as the model's name)
        if what["name"] in name or family in name:
            return family
    return None
