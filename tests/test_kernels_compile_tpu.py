"""The Pallas kernels (the UNet's two, the grouped expert matmul, the
gated delta rule's step, a learned key selection's three, Kimi's decode
attention over its latent cache and MiMo-V2's prefill attention with keys
wider than values and a sink), one
MMDiT block across the four chips of the slice, and K-EXAONE's prefill
program at its cell's shape,
compiled for a described v5e chip at the published widths (no chip attached: the TPU compiler is installed here and
refuses what the chip's would — block shapes the lowering cannot tile,
casts Mosaic has no layout for, more fast memory than a kernel may use).
Interpret mode checks none of that; every shape below was refused before
the kernels were repaired for the chip.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from chiaswarm_tpu.ops.banded_attention import banded_attention
from chiaswarm_tpu.ops.flash_attention import flash_attention
from chiaswarm_tpu.ops.group_norm import (
    _fused_group_norm,
    _vmem_budget,
    fused_tile_bytes,
)


@pytest.fixture(scope="module")
def v5e_slice():
    """The four described chips of a v5e 2x2."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu in this installation
        pytest.skip(f"TPU topology cannot be described here: {e}")
    # a described-topology compile is written to the persistent cache but
    # cannot be read back without a chip (it warns and recompiles)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def v5e(v5e_slice):
    return SingleDeviceSharding(v5e_slice[0])


def _shape(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("b,sq,skv,h,d", [
    pytest.param(2, 4096, 4096, 10, 64, id="sdxl-self-4096x10x64"),
    pytest.param(2, 1024, 1024, 20, 64, id="sdxl-self-1024x20x64"),
    pytest.param(2, 4096, 77, 10, 64, id="sdxl-cross-4096q-77kv"),
    pytest.param(2, 1024, 77, 20, 64, id="sdxl-cross-1024q-77kv"),
    pytest.param(2, 9216, 9216, 5, 64, id="sd21-768-9216x5x64"),
    pytest.param(2, 2304, 2304, 10, 64, id="sd21-768-2304x10x64"),
    pytest.param(2, 9216, 77, 5, 64, id="sd21-cross-9216q-77kv"),
    pytest.param(8, 4096, 4096, 10, 64, id="gang-sdxl-self-4096x10x64"),
    pytest.param(8, 9216, 9216, 5, 64, id="gang-sd21-9216x5x64"),
    # what _flash_route hands one chip of four
    pytest.param(2, 2304, 9216, 5, 64, id="per-chip-sd21-rows-2304q-9216kv"),
    pytest.param(2, 1024, 1024, 5, 64, id="per-chip-sdxl-5-of-20-heads"),
    pytest.param(1, 4608, 4608, 24, 128, id="flux-4608x24x128"),
    # FLUX.1-dev on [data=1, tensor=4]: six of its 24 heads a chip, a gang
    # of two at 1024^2, and the benchmark's one-row evaluation at 512^2
    pytest.param(2, 4608, 4608, 6, 128, id="per-chip-flux-6-of-24-heads"),
    pytest.param(1, 1536, 1536, 6, 128, id="per-chip-flux-512sq-canvas"),
])
def test_flash_attention_compiles_for_v5e(v5e, b, sq, skv, h, d):
    """With the blocks the rule gives the shape, as the program calls it."""
    q = _shape(v5e, (b, sq, h, d))
    kv = _shape(v5e, (b, skv, h, d))
    compiled = flash_attention.lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _largest_admitted_tile() -> tuple[int, int]:
    """The [N, C] bf16 tile nearest the admission budget, over the UNet
    levels' token counts and 128-lane channel counts."""
    admitted = [
        (fused_tile_bytes(n, c, 2), n, c)
        for n in (64, 256, 1024, 4096)
        for c in range(128, 4097, 128)
        if fused_tile_bytes(n, c, 2) <= _vmem_budget()
    ]
    _, n, c = max(admitted)
    return n, c


@pytest.mark.parametrize("sq,skv,window", [
    pytest.param(4096, 16384, 0, id="exaone-last-chunk-full-layer"),
    pytest.param(4096, 4096, 0, id="exaone-first-chunk-full-layer"),
    pytest.param(4096, 4096 + 128, 128, id="exaone-chunk-and-tail-window"),
    pytest.param(4096, 4096, 128, id="exaone-first-chunk-window"),
    pytest.param(1024, 1024, 128, id="short-rows-window"),
])
def test_banded_attention_compiles_for_v5e(v5e, sq, skv, window):
    """K-EXAONE's prefill chunk: 64 query heads on 8 key heads of 128,
    with the blocks the rule gives, as `ops.attention` calls it."""
    q = _shape(v5e, (1, sq, 64, 128))
    kv = _shape(v5e, (1, skv, 8, 128))
    compiled = banded_attention.lower(q, kv, kv, window=window).compile()
    assert "banded_attention" in compiled.as_text()


@pytest.mark.parametrize("sq,skv", [
    pytest.param(4096, 8192, id="sdar-second-chunk"),
    pytest.param(1024, 1024, id="sdar-short-rows"),
])
def test_banded_attention_under_a_span_compiles_for_v5e(v5e, sq, skv):
    """SDAR's prefill chunk: 32 query heads on 4 key heads of 128 under a
    mask that is bidirectional inside a span of 4 (the edge's whole-number
    division, in the mask and in the block rule, is the chip's to take)."""
    q = _shape(v5e, (1, sq, 32, 128))
    kv = _shape(v5e, (1, skv, 4, 128))
    compiled = banded_attention.lower(q, kv, kv, span=4).compile()
    assert "banded_attention" in compiled.as_text()


@pytest.mark.parametrize("n,c", [
    pytest.param(32 * 32, 1280, id="32x32x1280"),
    pytest.param(32 * 32, 640, id="32x32x640"),
    pytest.param(16 * 16, 1280, id="16x16x1280"),
    pytest.param(*_largest_admitted_tile(), id="largest-admitted"),
])
def test_fused_group_norm_compiles_for_v5e(v5e, n, c):
    vec = _shape(v5e, (c,), jnp.float32)
    compiled = _fused_group_norm.lower(
        _shape(v5e, (2, n, c)), vec, vec, groups=32, eps=1e-5, silu=True,
        interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_admission_rule_stays_inside_the_scoped_vmem_limit():
    """What the dispatch rule admits must fit the compiler's own 16 MiB
    scoped-VMEM default on a v5e with room to spare; 32x32x640 (SDXL's
    first level-3 resnet norm) is in, 32x32x1280 is out."""
    assert _vmem_budget() <= 8 * 1024 * 1024
    n, c = _largest_admitted_tile()
    assert fused_tile_bytes(n, c, 2) <= _vmem_budget()
    assert fused_tile_bytes(32 * 32, 640, 2) <= _vmem_budget()
    assert fused_tile_bytes(32 * 32, 1280, 2) > _vmem_budget()


@pytest.mark.parametrize("tokens,width,n,gated,held", [
    # Kimi-K2's 12 held experts (hidden 7168, expert width 2048): a decode
    # step's 256 tokens in 16-row tiles, a prefill chunk's 4096 in 128-row
    pytest.param(256, 7168, 2048, True, 12, id="kimi-decode-gate-up"),
    pytest.param(256, 2048, 7168, False, 12, id="kimi-decode-down"),
    pytest.param(4096, 7168, 2048, True, 12, id="kimi-prefill-gate-up"),
    pytest.param(4096, 2048, 7168, False, 12, id="kimi-prefill-down"),
    # SDAR's 128 held experts (hidden 2048, expert width 768): a block
    # step's 256 rows x 4 positions in 128-row tiles, a prefill chunk's
    pytest.param(1024, 2048, 768, True, 128, id="sdar-block-gate-up"),
    pytest.param(1024, 768, 2048, False, 128, id="sdar-block-down"),
    pytest.param(4096, 2048, 768, True, 128, id="sdar-prefill-gate-up"),
    # a fused forward's 256 rows x 8 positions (ISSUE 41: the finished
    # block beside the new one), 128 pairs an expert on average
    pytest.param(2048, 2048, 768, True, 128, id="sdar-fused-gate-up"),
    pytest.param(2048, 768, 2048, False, 128, id="sdar-fused-down"),
    # Qwen3-Next's 128 held experts (hidden 2048, expert width 512): a
    # decode step's 256 tokens in 16-row tiles, a prefill chunk's 4096
    pytest.param(256, 2048, 512, True, 128, id="qwen3next-decode-gate-up"),
    pytest.param(256, 512, 2048, False, 128, id="qwen3next-decode-down"),
    pytest.param(4096, 2048, 512, True, 128, id="qwen3next-prefill-gate-up"),
    pytest.param(4096, 512, 2048, False, 128, id="qwen3next-prefill-down"),
    # K-EXAONE's 16 held experts (hidden 6144, expert width 2048): a
    # prefill span's 4096 tokens
    pytest.param(4096, 6144, 2048, True, 16, id="exaone-prefill-gate-up"),
    pytest.param(4096, 2048, 6144, False, 16, id="exaone-prefill-down"),
])
def test_expert_matmul_compiles_for_v5e(v5e, tokens, width, n, gated, held):
    """The grouped kernel at the row buffer's worst-case size, its grid's
    first extent a traced number (only the tiles that hold rows), under
    the VMEM it declares. ISSUE 44: SDAR's and Qwen3-Next's matrices are
    one block (K whole: an expert's matrices stay in VMEM over its row
    tiles, and no accumulator is kept), Kimi's and K-EXAONE's are streamed
    in the blocks they had."""
    from chiaswarm_tpu.ops.expert_matmul import (
        _VMEM_LIMIT,
        _grouped,
        blocks,
        buffer_rows,
        row_tile,
    )

    tm = row_tile(tokens)
    rows = buffer_rows(tokens, 8, held, tm)
    weights = tuple(_shape(v5e, (held, width, n)) for _ in range(1 + gated))
    compiled = jax.jit(
        lambda x, w, tiles, count: _grouped(x, w, tiles, count, tm=tm)
    ).lower(_shape(v5e, (rows, width)), weights,
            _shape(v5e, (rows // tm,), jnp.int32),
            _shape(v5e, (), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    one_block = blocks(width, n, len(weights), tm, 2) == (width, n)
    assert one_block == (min(width, n) <= 768)
    # the scoped memory the call was compiled under is what the kernel
    # declares: a step over it is refused by the compile above
    call, = (line for line in text.splitlines()
             if "custom-call(" in line and "expert_matmul" in line)
    assert f'"size":"{_VMEM_LIMIT}"' in call


@pytest.mark.parametrize("rows,heads,keys,values", [
    # Qwen3-Next's linear layers at the cell's pass: a row's 32 matrices of
    # [128, 128] float32 are 2 MB, 8 rows a grid step in two sets of 16 MB
    # (ISSUE 48); the 8 rows of the benchmark's comparison are one step, a
    # lone row one step of one row
    pytest.param(256, 32, 128, 128, id="qwen3next-decode-256x32x128x128"),
    pytest.param(8, 32, 128, 128, id="qwen3next-decode-8-rows"),
    pytest.param(1, 32, 128, 128, id="qwen3next-decode-1-row"),
])
def test_gated_delta_step_compiles_for_v5e(v5e, rows, heads, keys, values):
    """The step kernel as the decode calls it: float32 state in and out
    under one buffer, left in HBM and moved by the kernel, a head's query
    and key as columns."""
    from chiaswarm_tpu.ops.gated_delta_rule import _step_pallas

    f32 = jnp.float32
    compiled = _step_pallas.lower(
        _shape(v5e, (rows, heads, keys), f32),
        _shape(v5e, (rows, heads, keys), f32),
        _shape(v5e, (rows, heads, values), f32),
        _shape(v5e, (rows, heads), f32), _shape(v5e, (rows, heads), f32),
        _shape(v5e, (rows, heads, keys, values), f32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "gated_delta_step" in text
    _the_state_streams_under_its_limit(compiled, "gated_delta_step")
    # nothing of the state's size beside the state itself
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * rows * (
        heads * keys * values) // 8


def _the_state_streams_under_its_limit(compiled, name):
    """The call was compiled under the scoped memory `ops/state_rows.py`
    declares (two sets of a step's rows over it are refused by the compile
    itself), and the state is one buffer in and out."""
    from chiaswarm_tpu.ops.state_rows import VMEM_LIMIT

    call, = (line for line in compiled.as_text().splitlines()
             if "custom-call(" in line and name in line)
    assert f'"size":"{VMEM_LIMIT}"' in call
    assert "output_to_operand_aliasing" in call


@pytest.mark.parametrize("positions, vocab", [
    pytest.param(1024, 151936, id="sdar-block-decode-1024x151936"),
    pytest.param(256, 261120, id="falconh1-decode-256x261120"),
    pytest.param(256, 37984, id="qwen3next-decode-256x37984"),
    pytest.param(4, 19200, id="exaone-decode-4x19200"),
])
def test_the_samplers_kernels_compile_for_v5e(v5e, positions, vocab):
    """The sampler's two kernels as a decode step calls them (ISSUE 47):
    the pass over the float32 logits, whole blocks of 1024 ids and a
    shorter last one, and the fetch of a position's block; nothing as large
    as the logits beside the logits."""
    from chiaswarm_tpu.ops.sampling import (
        _block_pallas,
        _blocks,
        _statistics_pallas,
    )

    f32 = jnp.float32
    logits = _shape(v5e, (positions, vocab), f32)
    statistics = _statistics_pallas.lower(
        logits, _shape(v5e, (positions,), f32)).compile()
    block = _block_pallas.lower(
        logits, _shape(v5e, (positions,), jnp.int32)).compile()
    for compiled, name in ((statistics, "sampler_statistics"),
                           (block, "sampler_block")):
        text = compiled.as_text()
        assert "tpu_custom_call" in text and name in text
        assert compiled.memory_analysis().temp_size_in_bytes < 4 * (
            positions * max(vocab // 16, 4 * _blocks(vocab)[0]))


@pytest.mark.parametrize("sq,skv", [
    pytest.param(4096, 32768, id="glm5-last-span"),
    pytest.param(4096, 4096, id="glm5-first-span"),
])
def test_the_key_selections_kernels_compile_for_v5e(v5e, sq, skv):
    """`glm5-long-context`'s prefill span (ISSUE 49): 32 index heads of
    128 on one shared key, the radix select of 2048 over whole rows of
    float32 scores in VMEM with an int8 mask out, and the flash kernel at
    heads of 256 that reads the mask's block beside the keys, each with
    the blocks its own rule gives, the span's first position a scalar
    handed to the kernel (one compiled kernel a row's every span); no
    operand is copied on its way in (keys and values come as `[rows, keys,
    heads x 256]`, the layout the expansion from the latents leaves them
    in). Since ISSUE 50 the span's END is traced too and bounds a loop of
    each: the key-block axis of the indexer's and the attention's grids
    and the row-block axis of the expansion's are grid bounds that are
    data, the selection's counts a loop inside the kernel up to it."""
    from chiaswarm_tpu.ops.lightning_indexer import (
        _indexer_pallas,
        _select_pallas,
    )
    from chiaswarm_tpu.ops.sparse_latent_attention import (
        _expand_pallas,
        _prefill_pallas,
    )

    traced = _shape(v5e, (), jnp.int32)
    compiled = _indexer_pallas.lower(
        _shape(v5e, (1, sq, 32, 128)), _shape(v5e, (1, sq, 32), jnp.float32),
        _shape(v5e, (1, skv, 128)), traced, traced).compile()
    assert "lightning_indexer" in compiled.as_text()
    compiled = _select_pallas.lower(
        _shape(v5e, (1, sq, skv), jnp.float32), topk=2048,
        end=traced).compile()
    assert "index_select" in compiled.as_text()
    compiled = _expand_pallas.lower(
        _shape(v5e, (1, skv, 576)), _shape(v5e, (576, 64 * 256)),
        _shape(v5e, (512, 64 * 256)), traced).compile()
    assert "latent_expansion" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes == 0
    compiled = _prefill_pallas.lower(
        _shape(v5e, (1, sq, 64 * 256)), _shape(v5e, (1, skv, 64 * 256)),
        _shape(v5e, (1, skv, 64 * 256)), _shape(v5e, (1, sq, skv), jnp.int8),
        scale=256 ** -0.5, heads=64, offset=traced).compile()
    assert "sparse_latent_attention" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("kv_heads, skv, window", [
    pytest.param(4, 32768, 0, id="mimo-full-layer-span"),
    pytest.param(8, 4096 + 128, 128, id="mimo-window-layer-span-and-tail"),
])
def test_wide_key_attention_compiles_for_v5e(v5e, kv_heads, skv, window):
    """`mimo-long-context`'s two calls (ISSUE 57): a 4096-query span of 64
    query heads of 192 on values of 128. A full layer's, 16 query heads a
    key head against the row's whole 32768-slot cache, the span's first
    position a scalar handed to the kernel and the key-block axis of the
    grid a bound that is data (one compiled kernel a row's every span); a
    window layer's, 8 a key head against the span and the 128 keys before
    it, with the sink and the floor that masks an empty tail."""
    from chiaswarm_tpu.ops.wide_key_attention import _wide_key_pallas

    traced = _shape(v5e, (), jnp.int32)
    compiled = _wide_key_pallas.lower(
        _shape(v5e, (1, 4096, 64, 192)), _shape(v5e, (1, skv, kv_heads, 192)),
        _shape(v5e, (1, skv, kv_heads, 128)),
        _shape(v5e, (64,), jnp.float32) if window else None,
        None if window else traced, traced if window else None,
        window=window).compile()
    text = compiled.as_text()
    assert "wide_key_attention" in text
    assert f"queries[4096] keys[{skv}] window[{window}]" in text


@pytest.mark.parametrize("rows, positions, heads", [
    # `kimi-batch-decode`'s pass: 256 rows, 256 prompt slots + 256 new
    # tokens, 64 heads against a latent of 512 and a rotary key of 64
    pytest.param(256, 512, 64, id="kimi-decode-256x512x64"),
    # a pass whose new tokens leave the cache's last block ragged
    pytest.param(8, 456, 64, id="kimi-decode-ragged-last-block"),
])
def test_latent_decode_attention_compiles_for_v5e(v5e, rows, positions,
                                                   heads):
    """Kimi's decode attention as one kernel (ISSUE 53): the cache read
    once under a table of live blocks that is data, the scores in VMEM: no
    `[rows, heads, positions]` float32 array is in the program, and the
    call is compiled under the scoped memory the kernel declares, which is
    inside the compiler's own 16 MiB default (what the admission test
    above holds the fused GroupNorm to)."""
    from chiaswarm_tpu.ops.latent_attention import (
        _VMEM_LIMIT,
        _decode_pallas,
    )

    compiled = _decode_pallas.lower(
        _shape(v5e, (rows, heads, 512)), _shape(v5e, (rows, heads, 64)),
        _shape(v5e, (rows, positions, 576)),
        _shape(v5e, (rows, positions), jnp.bool_), scale=0.1352).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "latent_attention" in text
    assert f"f32[{rows},{heads},{positions}]" not in text
    assert _VMEM_LIMIT <= 16 * 1024 * 1024
    call, = (line for line in text.splitlines()
             if "custom-call(" in line and "latent_attention" in line)
    assert f'"size":"{_VMEM_LIMIT}"' in call


@pytest.mark.parametrize("rows, heads, size, dim, groups", [
    # a row's 32 matrices of [256, 128] float32 are 4 MB: 4 rows a grid
    # step in two sets of 16 MB (ISSUE 48), so the comparison's 8 rows are
    # two steps and a lone row one step of one row
    pytest.param(256, 32, 256, 128, 2, id="falconh1-decode-256x32x256x128"),
    pytest.param(8, 32, 256, 128, 2, id="falconh1-decode-8-rows"),
    pytest.param(1, 32, 256, 128, 2, id="falconh1-decode-1-row"),
])
def test_ssd_step_compiles_for_v5e(v5e, rows, heads, size, dim, groups):
    """The state-space step kernel as the decode calls it (ISSUE 46):
    float32 state in and out under one buffer, left in HBM and moved by
    the kernel, a group's `B` and `C` as columns fetched once a group."""
    from chiaswarm_tpu.ops.ssd import _step_pallas

    f32 = jnp.float32
    compiled = _step_pallas.lower(
        _shape(v5e, (rows, heads, dim), f32), _shape(v5e, (rows, heads), f32),
        _shape(v5e, (heads,), f32), _shape(v5e, (rows, groups, size), f32),
        _shape(v5e, (rows, groups, size), f32), _shape(v5e, (heads,), f32),
        _shape(v5e, (rows, heads, size, dim), f32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ssd_step" in text
    _the_state_streams_under_its_limit(compiled, "ssd_step")
    # nothing of the state's size beside the state itself
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * rows * (
        heads * size * dim) // 8


def test_flux_double_block_overlaps_its_collectives_on_four_v5e(
        v5e_slice, monkeypatch):
    """FLUX.1-dev's double block at `flux-backlog`'s shapes on [data=1,
    tensor=4]: the chip's compiler makes each ring hop of the pair's
    products a `collective-permute-start` / `-done`, and its scheduler
    puts matmuls between the two (ISSUE 33)."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from chiaswarm_tpu.models.flux import DoubleStreamBlock, FluxConfig
    from chiaswarm_tpu.ops import attention, platform
    from chiaswarm_tpu.parallel import tensor
    from chiaswarm_tpu.parallel.mesh import make_mesh

    for module in (platform, attention):  # the trace is for the chip
        monkeypatch.setattr(module, "trace_platform", lambda: "tpu")
    cfg, rows, n_img, n_txt = FluxConfig(), 2, 4096, 512
    mesh = make_mesh(v5e_slice, tensor=4)
    assert tensor._ring_order(mesh) == [0, 1, 3, 2]  # never a diagonal
    block = DoubleStreamBlock(cfg, dtype=jnp.bfloat16, head_groups=4)
    whole = NamedSharding(mesh, P())
    args = [_shape(whole, dims) for dims in (
        (rows, n_img, cfg.hidden_size), (rows, n_txt, cfg.hidden_size),
        (rows, cfg.hidden_size), (rows, n_img + n_txt, cfg.head_dim // 2),
        (rows, n_img + n_txt, cfg.head_dim // 2))]
    params = jax.eval_shape(
        lambda *a: block.init(jax.random.key(0), *a), *args)["params"]
    shardings = tensor.sharding_tree(
        mesh, {"double_blocks_0": params}, tensor.flux_partition_rules())
    params = jax.tree_util.tree_map(
        lambda s, place: _shape(place, s.shape), params,
        shardings["double_blocks_0"])
    jax.clear_caches()  # the products are jitted by mesh and shape
    with platform.mesh_scope(mesh):
        hlo = jax.jit(lambda p, *a: block.apply({"params": p}, *a)).lower(
            params, *args).compile().as_text()
    jax.clear_caches()
    assert "tpu_custom_call" in hlo  # the flash kernel, six heads a chip
    assert not re.search(r" all-reduce(-start)?\(", hlo)
    # the scheduled entry computation, in order: what runs between a hop's
    # start and its done (kOutput: a fusion rooted at a matmul)
    entry = hlo[hlo.index("\nENTRY "):].splitlines()
    started, straddled, hops = {}, 0, 0
    for at, line in enumerate(entry):
        start = re.match(r"\s*%(collective-permute-start[\w.]*) = ", line)
        done = re.search(r"collective-permute-done\(%([\w.\-]+)\)", line)
        if start:
            started[start.group(1)] = at
        elif done:
            hops += 1
            between = entry[started[done.group(1)]:at]
            straddled += any("kind=kOutput" in other for other in between)
    # 8 products of 3 hops, each way for `img`'s chunks and one way for
    # `txt`'s; the first hop of a gather is waited for ahead of its first
    # matmul, the others travel under one
    assert hops == 4 * 6 + 4 * 3
    assert straddled >= hops // 2, (straddled, hops)


def test_exaone_prefill_keeps_a_conditional_a_span_on_v5e(v5e, monkeypatch):
    """`exaone-long-documents`' prefill program (4 rows of 16384 slots, a
    chunk one row's 4096 positions, the published widths): the chip's
    compiler keeps each span's `conditional` (ISSUE 37), the branch that
    runs holds the span's five attention calls and its four expert
    layers' two grouped matmuls each, the other no kernel at all."""
    import re

    from chiaswarm_tpu.models import exaone
    from chiaswarm_tpu.ops import attention, platform
    from chiaswarm_tpu.pipelines.text_generation import prefill_chunk

    for module in (platform, attention):  # the trace is for the chip
        monkeypatch.setattr(module, "trace_platform", lambda: "tpu")
    cfg, rows, slots, new = exaone.EXAONE_236B_EP8, 4, 16384, 128
    chunk = prefill_chunk(rows, slots, exaone.POSITION_CHUNKS)
    assert chunk == (1, 4096)
    params = jax.tree_util.tree_map(
        lambda leaf: _shape(v5e, leaf.shape),
        exaone.param_shapes(cfg, jnp.bfloat16))
    compiled = jax.jit(lambda p, ids, lengths: exaone.prefill(
        p, cfg, ids, lengths, slots + new, *chunk)).lower(
            params, _shape(v5e, (rows, slots), jnp.int32),
            _shape(v5e, (rows,), jnp.int32)).compile()
    # the compiled module, a computation's lines under its name
    bodies, name = {}, None
    for line in compiled.as_text().splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif name:
            bodies[name].append(line)

    def kernels(name, seen):
        """(banded_attention, expert_matmul) calls of a computation and
        of every computation it calls."""
        if name in seen or name not in bodies:
            return 0, 0
        seen.add(name)
        counts = [sum(bool(re.match(rf"\s*%{kernel}[.\d]* = .*custom-call\(",
                                    line)) for line in bodies[name])
                  for kernel in ("banded_attention", "expert_matmul")]
        for called in re.findall(
                r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)",
                "\n".join(bodies[name])):
            more = kernels(called, seen)
            counts = [a + b for a, b in zip(counts, more)]
        return tuple(counts)

    spans = [re.findall(r"%?([\w.\-]+)", branches)
             for lines in bodies.values() for line in lines
             for branches in re.findall(
                 r" conditional\(.*branch_computations=\{([^}]*)\}", line)]
    assert len(spans) == slots // chunk[1]
    for skipped, run in spans:
        assert kernels(skipped, set()) == (0, 0)
        assert kernels(run, set()) == (len(cfg.windows),
                                       2 * cfg.expert_layers)
    # the scoped branches' temporaries: 2.16 GB where every span ran
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


@pytest.mark.parametrize("module, preset, widths, temp", [
    # the program's temporaries compiled here before ISSUE 43 (one chunk
    # width): 1.182, 0.469 and 1.357 GB; a cell's `hbm_peak_gb` is held to
    # 1 % of 12 GB
    ("kimi", "KIMI_K2_EP32", (256, 128, 64), 1.20e9),
    ("sdar", "SDAR_30B_PP8", (256,), 0.50e9),
    ("qwen3_next", "QWEN3_NEXT_80B_EP4", (256,), 1.40e9),
    # ISSUE 46: no expert, so no grouped matmul at all; the chunk form's
    # float32 operands and the 21,504-wide feed-forward's activations
    ("falcon_h1", "FALCON_H1_34B_PP18", (256,), 0.80e9),
])
def test_a_batch_decode_cells_prefill_has_a_branch_a_width_on_v5e(
        v5e, monkeypatch, module, preset, widths, temp):
    """The three batch-decode cells' prefill program (256 rows of 256
    slots, 512 positions, the published widths; ISSUE 43): a copy of the
    layers a width the model's module offers and no more; where it offers
    several (Kimi: 256, 128, 64 slots) the chip's compiler keeps one
    `conditional` in the loop over the chunks, a branch a width that holds
    every layer's two grouped matmuls and one that runs no kernel, and the
    branches' temporaries do not add up: no more of them than the one
    width took."""
    import importlib
    import re

    from chiaswarm_tpu.ops import attention, platform
    from chiaswarm_tpu.pipelines.text_generation import prefill_chunk

    for patched in (platform, attention):  # the trace is for the chip
        monkeypatch.setattr(patched, "trace_platform", lambda: "tpu")
    model = importlib.import_module(f"chiaswarm_tpu.models.{module}")
    cfg, rows, slots, positions = getattr(model, preset), 256, 256, 512
    chunk = prefill_chunk(rows, slots, model.POSITION_CHUNKS)
    assert chunk == (16, 256)
    assert model.prefill_widths(slots, chunk[1]) == widths
    params = jax.tree_util.tree_map(
        lambda leaf: _shape(v5e, leaf.shape, leaf.dtype),
        model.param_shapes(cfg, jnp.bfloat16))
    compiled = jax.jit(lambda p, ids, lengths: model.prefill(
        p, cfg, ids, lengths, positions, *chunk)).lower(
            params, _shape(v5e, (rows, slots), jnp.int32),
            _shape(v5e, (rows,), jnp.int32)).compile()
    text = compiled.as_text()
    branches = [re.findall(r"%?([\w.\-]+)", found) for found in re.findall(
        r" conditional\(.*branch_computations=\{([^}]*)\}", text)]
    kernel = r"\s*%expert_matmul[.\d]* = .*custom-call\("
    # a block model's prefill stops before its last layer's experts
    layers = cfg.expert_layers - hasattr(model, "block_step")
    assert sum(bool(re.match(kernel, line)) for line in text.splitlines()
               ) == 2 * layers * len(widths)
    assert compiled.memory_analysis().temp_size_in_bytes < temp
    if len(widths) == 1:
        return
    wide = [found for found in branches if len(found) == len(widths) + 1]
    assert len(wide) == 1, branches
    # a computation's lines under its name, and the grouped matmuls of a
    # computation and of every computation it calls
    bodies, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif name:
            bodies[name].append(line)

    def matmuls(name, seen):
        if name in seen or name not in bodies:
            return 0
        seen.add(name)
        lines = "\n".join(bodies[name])
        called = re.findall(
            r"(?:calls|to_apply|body|condition|true_computation|"
            r"false_computation)=%?([\w.\-]+)", lines) + [
            branch for found in re.findall(
                r"branch_computations=\{([^}]*)\}", lines)
            for branch in re.findall(r"%?([\w.\-]+)", found)]
        return sum(bool(re.match(kernel, line))
                   for line in bodies[name]) + sum(
            matmuls(other, seen) for other in called)

    assert [matmuls(branch, set()) for branch in wide[0]] == [0] + [
        2 * layers] * len(widths)
