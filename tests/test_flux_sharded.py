"""FLUX.1's MMDiT and T5 under head-aligned tensor parallelism (ISSUE 27).

Tiny preset only, on the conftest's virtual CPU devices:
(a) the flax module against the plain reference (benchmark/reference/mmdit.py);
(b) the 4-way sharded forward against the same reference, with the sharding
    held to be real: the largest device's share of the parameter bytes, and
    the collectives of the compiled step;
(c) `FluxPipeline.run_batched` at [data=1, tensor=4] against `run` on one
    device, row for row;
and the rules around them: which names are the published geometry, what the
capacity gate makes of it on one and on four chips, the residency gauge.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference.mmdit import mmdit_forward
from chiaswarm_tpu.models.flux import (
    TINY_FLUX,
    FluxTransformer,
    grouped_layout,
    head_groups_for,
)
from chiaswarm_tpu.ops.platform import mesh_scope
from chiaswarm_tpu.parallel.mesh import make_mesh
from chiaswarm_tpu.parallel.tensor import (
    flux_partition_rules,
    largest_device_bytes,
    shard_params,
    t5_partition_rules,
)

# the tiny preset with heads a 4-way tensor axis divides (TINY_FLUX has two),
# and deep enough that the blocks, which shard, outweigh the embedders, which
# do not, as they do at the published depth of 19 + 38
TP_FLUX = dataclasses.replace(TINY_FLUX, num_heads=4, hidden_size=64,
                              depth_double=3, depth_single=8)


def _inputs(cfg, rows=2, side=4, n_txt=8):
    rng = np.random.default_rng(7)
    n_img = side * side
    img_ids = np.zeros((rows, n_img, 3), np.int32)
    img_ids[:, :, 1] = np.arange(n_img)[None] // side
    img_ids[:, :, 2] = np.arange(n_img)[None] % side
    normal = lambda *shape: jnp.asarray(
        rng.standard_normal(shape), jnp.float32)
    return dict(
        img=normal(rows, n_img, cfg.in_channels), img_ids=jnp.asarray(img_ids),
        txt=normal(rows, n_txt, cfg.context_dim),
        txt_ids=jnp.zeros((rows, n_txt, 3), jnp.int32),
        timesteps=jnp.asarray([0.3, 0.9][:rows], jnp.float32),
        pooled=normal(rows, cfg.pooled_dim),
        guidance=jnp.asarray([3.5, 1.5][:rows], jnp.float32))


def _params(model, inputs):
    """A seeded tree made from the init's shapes alone (no init program is
    compiled): kernels at fan-in scale, every bias and scale moved off
    flax's 0 and 1, so that each counts."""
    args = [inputs[k] for k in ("img", "img_ids", "txt", "txt_ids",
                                "timesteps", "pooled")]
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(1), *args, guidance=inputs["guidance"])["params"])
    rng = np.random.default_rng(1)

    def leaf(path, shape):
        noise = rng.standard_normal(shape.shape).astype(np.float32)
        name = path[-1].key
        if name == "kernel":
            return jnp.asarray(noise / np.sqrt(shape.shape[0]))
        return jnp.asarray(0.1 * noise + (name == "scale"))

    return jax.tree_util.tree_map_with_path(leaf, shapes)


def _apply(model, params, inputs):
    return model.apply(
        {"params": params}, inputs["img"], inputs["img_ids"], inputs["txt"],
        inputs["txt_ids"], inputs["timesteps"], inputs["pooled"],
        guidance=inputs["guidance"])


_jit_apply = jax.jit(_apply, static_argnums=0)


@pytest.fixture(scope="module")
def tp_case():
    inputs = _inputs(TP_FLUX)
    params = _params(FluxTransformer(TP_FLUX), inputs)
    return inputs, params, mmdit_forward(params, TP_FLUX, **inputs)


def test_module_matches_the_plain_reference(tp_case):
    """(a) on the case (b) shards; `benchmark/tests/test_flux_family.py` has
    the tiny presets, dev and schnell."""
    inputs, params, want = tp_case
    with jax.default_matmul_precision("highest"):
        got = _jit_apply(FluxTransformer(TP_FLUX), params, inputs)
    # float32 on both sides in another operation order: a few ulp of
    # values of order 1; any departure in the mathematics (a swapped shift
    # and scale, another GELU, a missed qk-norm) is 1e-2 or more
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-4
    assert float(jnp.max(jnp.abs(want))) > 0.5


def test_grouped_layout_is_a_permutation_with_an_inverse(tp_case):
    inputs, params, want = tp_case
    grouped = grouped_layout(params, TP_FLUX, 4)
    qkv = params["double_blocks_0"]["img_attn_qkv"]["kernel"]
    assert not bool((grouped["double_blocks_0"]["img_attn_qkv"]["kernel"]
                     == qkv).all())
    back = grouped_layout(grouped, TP_FLUX, 4, inverse=True)
    assert all(bool((a == b).all()) for a, b in zip(
        jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(params)))
    # (the module undoing it: the sharded forward below, and the pipeline's)
    assert head_groups_for(TP_FLUX, 4) == 4
    assert head_groups_for(TINY_FLUX, 4) == 1  # two heads: nothing to align


def _collectives(hlo: str, op: str) -> list[list[int]]:
    """Element counts of every operand of every `op` in a compiled module's
    text (a combined collective lists several)."""
    found = []
    for line in hlo.splitlines():
        m = re.search(rf" = (.*?) {op}(?:-start)?\(", line)
        if m:
            found.append([
                int(np.prod([int(n) for n in dims.split(",") if n] or [1]))
                for dims in re.findall(r"[a-z]+\d*\[([\d,]*)\]", m.group(1))])
    return found


@pytest.mark.parametrize("ways,ring", [
    (2, None), (2, [0, 1, 3, 2]), (1, None),
], ids=["two-ways", "two-ways-ring-0-1-3-2", "one-way"])
def test_sharded_forward_matches_reference_and_really_shards(
        tp_case, ways, ring, monkeypatch):
    from chiaswarm_tpu.parallel import tensor

    if ways == 2:  # as chunks of 256 tokens and more travel: in two halves
        monkeypatch.setattr(tensor, "_TWO_WAY_TOKENS", 2)
    if ring:  # chips where a v5e 2x2 has them: the ring is not the axis
        monkeypatch.setattr(tensor, "_ring_order", lambda mesh: ring)
    jax.clear_caches()  # the pair's products are jitted by mesh and shape
    inputs, params, want = tp_case
    cfg = TP_FLUX
    mesh = make_mesh(jax.devices()[:4], tensor=4)
    placed = shard_params(mesh, grouped_layout(params, cfg, 4),
                          flux_partition_rules())
    # (b1) sharded, not copied: a rule that fell through to replicated
    # would read 1.0 here. The blocks are a quarter each; the embedders,
    # `img_in`, `txt_in` and the row-parallel biases stay whole
    total = sum(x.nbytes for x in jax.tree_util.tree_leaves(placed))
    assert largest_device_bytes(placed) <= 0.30 * total
    qkv = placed["double_blocks_0"]["img_attn_qkv"]["kernel"]
    assert qkv.addressable_shards[0].data.shape == (
        cfg.hidden_size, 3 * cfg.hidden_size // 4)

    model = FluxTransformer(cfg, head_groups=4)
    step = jax.jit(lambda p: _apply(model, p, inputs))
    with mesh_scope(mesh):
        compiled = step.lower(placed).compile()
        got = step(placed)
    jax.clear_caches()
    # (b2) float32, the row-parallel sums taken in another order
    assert float(np.max(np.abs(np.asarray(got) - np.asarray(want)))) <= 1e-4

    # (b3) the step's collectives. No all-reduce is left: the sum behind a
    # row-parallel kernel (attention projection and MLP of each stream in a
    # double block, `linear2` in a single one) and the gather ahead of a
    # column-parallel one (qkv and MLP of each stream; `linear1`) each go
    # round the ring in three hops of a chip's token chunk: each way half
    # of it, or (a short chunk, as `txt`'s 128 tokens are) one way whole ...
    hlo = compiled.as_text()
    rows, n_img, n_txt = 2, inputs["img"].shape[1], inputs["txt"].shape[1]
    assert _collectives(hlo, "all-reduce") == []
    hops = sorted(n for op in _collectives(hlo, "collective-permute")
                  for n in op)
    piece = lambda tokens: rows * tokens // 4 // ways * cfg.hidden_size
    assert hops == sorted(
        [piece(n_img), piece(n_txt)] * 3 * ways * 4 * cfg.depth_double
        + [piece(n_img + n_txt)] * 3 * ways * 2 * cfg.depth_single)
    # ... with one gather of each modulation vector, and of the result from
    # each chip's quarter of the image tokens; nothing the size of a weight
    # or of a [rows, tokens, hidden] activation travels in one piece
    gathered = sorted(n for op in _collectives(hlo, "all-gather") for n in op)
    vec = rows * cfg.hidden_size
    assert gathered == sorted(
        [6 * vec] * 2 * cfg.depth_double + [3 * vec] * cfg.depth_single
        + [2 * vec] + [rows * n_img * cfg.in_channels])
    smallest_sharded_kernel = cfg.hidden_size * cfg.hidden_size
    assert max(gathered + hops) < min(
        smallest_sharded_kernel, rows * n_txt * cfg.hidden_size)
    for op in ("all-to-all", "reduce-scatter"):
        assert _collectives(hlo, op) == [], op


def test_t5_rules_shard_heads_and_ffn():
    from chiaswarm_tpu.models.t5 import TINY_T5, T5Encoder

    model = T5Encoder(TINY_T5)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 1000, (2, 16)),
                      jnp.int32)
    params = jax.jit(lambda: model.init(jax.random.key(0), ids)["params"])()
    want = jax.jit(lambda p: model.apply({"params": p}, ids))(params)
    mesh = make_mesh(jax.devices()[:4], tensor=4)
    placed = shard_params(mesh, params, t5_partition_rules())
    block = placed["block_0"]
    inner = TINY_T5.num_heads * TINY_T5.d_kv
    assert block["attention"]["q"]["kernel"].addressable_shards[0].data.shape \
        == (TINY_T5.d_model, inner // 4)
    assert block["wo"]["kernel"].addressable_shards[0].data.shape \
        == (TINY_T5.d_ff // 4, TINY_T5.d_model)
    assert placed["token_embedding"]["embedding"].addressable_shards[0] \
        .data.shape == (TINY_T5.vocab_size, TINY_T5.d_model)
    encode = jax.jit(lambda p: model.apply({"params": p}, ids))
    with mesh_scope(mesh):
        hlo = encode.lower(placed).compile().as_text()
        got = encode(placed)
    assert float(np.max(np.abs(np.asarray(got) - np.asarray(want)))) <= 1e-4
    # two row-parallel kernels a layer (`o`, `wo`), and nothing else
    assert len(_collectives(hlo, "all-reduce")) == 2 * TINY_T5.num_layers
    for op in ("all-gather", "all-to-all", "collective-permute"):
        assert _collectives(hlo, op) == [], op


def test_batched_on_a_tensor_mesh_matches_solo_on_one_device(
        monkeypatch, sdaas_root):
    from chiaswarm_tpu.chips.device import ChipSet
    from chiaswarm_tpu.pipelines import flux as flux_pipeline
    from chiaswarm_tpu.pipelines.common import RESIDENT_PARAM_BYTES as gauge

    monkeypatch.setattr(flux_pipeline, "TINY_FLUX", dataclasses.replace(
        TINY_FLUX, num_heads=4, hidden_size=64))
    # the normal path: seeded init on the host, regrouped and placed
    four = flux_pipeline.FluxPipeline(
        "test/tiny-flux", ChipSet(jax.devices()[:4], tensor=4),
        dtype=jnp.float32)
    sharded = gauge.value(model="test/tiny-flux")
    shardings = four.param_shardings()
    assert tuple(shardings["t5"]["block_0"]["wi_0"]["kernel"].spec) \
        == (None, "tensor")
    assert tuple(shardings["flux"]["double_blocks_0"]["img_mod"]["lin"]
                 ["kernel"].spec) == (None, "tensor")
    assert shardings["vae"]["decoder"]["conv_in"]["kernel"].spec == ()
    assert shardings["clip"] == jax.tree_util.tree_map(
        lambda _: shardings["vae"]["decoder"]["conv_in"]["kernel"],
        shardings["clip"])
    kernel = four.params["flux"]["single_blocks_0"]["linear1"]["kernel"]
    assert kernel.addressable_shards[0].data.shape[1] * 4 == kernel.shape[1]

    # the seam: the same weights, in the checkpoint's order again, handed
    # to a one-device pipeline already placed (`weights=`)
    seen = {}

    def weights(shapes, shardings):
        seen["shapes"] = shapes
        tree = dict(four.params, flux=grouped_layout(
            four.params["flux"], four.config, 4, inverse=True))
        return jax.device_put(tree, shardings)

    one = flux_pipeline.FluxPipeline(
        "test/tiny-flux", ChipSet(jax.devices()[:1]), dtype=jnp.float32,
        weights=weights)
    assert (one.head_groups, four.head_groups) == (1, 4)
    assert jax.tree_util.tree_map(lambda x: x.shape, seen["shapes"]) \
        == jax.tree_util.tree_map(lambda x: x.shape, one.params)
    # the gauge reads the largest chip's bytes of the tree just placed:
    # the MMDiT and T5 a quarter each, CLIP, the VAE and T5's table whole
    whole = gauge.value(model="test/tiny-flux")
    assert whole == sum(
        x.nbytes for x in jax.tree_util.tree_leaves(one.params))
    assert 0 < sharded < 0.75 * whole
    with pytest.raises(ValueError):
        flux_pipeline.FluxPipeline(
            "test/tiny-flux", dtype=jnp.float32,
            weights=lambda shapes, shardings: {"flux": {}})

    # every program is traced under `mesh_scope(self.mesh)`: the kernel
    # routing sees the mesh (on a TPU the flash kernel then runs in
    # shard_map at per-chip shapes; outside the scope Mosaic refuses it)
    from chiaswarm_tpu.ops import attention, platform

    seen_meshes = []

    def spy():
        seen_meshes.append(platform.active_mesh())
        return seen_meshes[-1]

    monkeypatch.setattr(attention, "active_mesh", spy)

    shared = dict(height=64, width=64, num_inference_steps=2,
                  guidance_scale=3.5)
    requests = [{"prompt": "a fox", "rng": jax.random.key(3)},
                {"prompt": "a crab", "rng": jax.random.key(9)}]
    outs = four.run_batched([dict(r) for r in requests], **shared)
    # (the solo programs' scope: tests/test_flux_serving.py's fallback)
    assert seen_meshes and all(m is four.mesh for m in seen_meshes)
    for request, (images, config) in zip(requests, outs):
        solo, _ = one.run(prompt=request["prompt"], rng=request["rng"],
                          **shared)
        # one uint8 level: float32 sums in another order (tests/test_flux.py
        # holds the batched pass to its solo twin by the same step)
        np.testing.assert_allclose(
            np.asarray(images[0], np.int16), np.asarray(solo[0], np.int16),
            atol=1, rtol=0)
        assert config["batched_with"] == 2 and images[0].size == (64, 64)


def test_names_select_the_published_geometry():
    from chiaswarm_tpu.models.flux import FluxConfig
    from chiaswarm_tpu.models.t5 import TINY_T5, T5Config
    from chiaswarm_tpu.pipelines.flux import _flux_configs

    for name in ("test/FLUX.1-dev", "black-forest-labs/FLUX.1-dev"):
        flux, t5, _, _, size, steps, dynamic = _flux_configs(name)
        assert (flux, t5, size, steps, dynamic) == (
            FluxConfig(), T5Config(), 1024, 28, True)
    flux, t5, _, _, size, steps, dynamic = _flux_configs(
        "test/FLUX.1-schnell")
    assert (flux.hidden_size, flux.guidance_embed, t5, steps, dynamic) == (
        3072, False, T5Config(), 4, False)
    for name in ("test/tiny-flux", "test/tiny-flux-schnell",
                 "black-forest-labs/tiny-FLUX"):
        flux, t5, _, _, size, _, _ = _flux_configs(name)
        assert (flux.hidden_size, t5, size) == (32, TINY_T5, 64)


class _Slice:
    """A described v5e slice: 16 GB a chip."""

    platform = "tpu"
    seq = 1

    def __init__(self, chips, tensor):
        self.chips, self.tensor = chips, tensor

    def hbm_bytes(self):
        return self.chips * (16 << 30)

    def chip_count(self):
        return self.chips


@pytest.mark.parametrize("chips,tensor,want", [
    (4, 4, (2, "resident")),    # 31.4 / 4 + 2.5 an image: three fit, two asked
    (1, 1, (1, "streaming")),   # one chip holds the tail and pages the blocks
    (4, 1, (0, "refuse")),      # four copies of a model no chip holds
])
def test_seeded_full_size_flux_is_accounted_like_the_real_one(
        chips, tensor, want, sdaas_root):
    from chiaswarm_tpu.chips.requirements import (
        coalesce_rows_limit,
        fit_batch,
        flux_admissible,
    )

    chipset = _Slice(chips, tensor)
    got = flux_admissible(chipset, 2, 1024, model_name="test/FLUX.1-dev")
    assert got == want
    assert got == flux_admissible(
        chipset, 2, 1024, model_name="black-forest-labs/FLUX.1-dev")
    # a tiny stand-in is a few MB whatever it mimics. The rule is the
    # pipelines' own: SD and Flux give a `test/` name without `tiny` the
    # published geometry (SDXL's four rows fit a chip by arithmetic: 8 +
    # 4 x 2 GB), every other family gives any `test/` name its tiny preset
    assert fit_batch(chipset, "test/tiny-flux", 8, 1024) == 8
    assert fit_batch(chipset, "test/kandinsky-3", 64, 1024) == 64
    assert fit_batch(
        _Slice(1, 1), "test/stable-diffusion-xl-base-1.0", 8, 1024) == 4
    if want[1] == "resident":
        assert coalesce_rows_limit(chipset, "test/FLUX.1-dev", 1024) == 2


def test_benchmark_family_compares_a_regrouped_sharded_model(
        monkeypatch, sdaas_root):
    """The benchmark's `correct` 5 for this family, on a 4-way tensor mesh:
    weights made already sharded through the seam, the reference fed block
    by block from the chips in the checkpoint's order again."""
    from benchmark import checks
    from benchmark.families import flux as family
    from chiaswarm_tpu import registry
    from chiaswarm_tpu.chips.device import ChipSet
    from chiaswarm_tpu.pipelines import flux as flux_pipeline

    monkeypatch.setattr(flux_pipeline, "TINY_FLUX", dataclasses.replace(
        TINY_FLUX, num_heads=4, hidden_size=64))
    monkeypatch.setitem(registry._FACTORIES, "flux",
                        registry._FACTORIES.get("flux"))  # restored after
    record = {}
    family.register(11, record)
    pipe = registry._FACTORIES["flux"](
        "test/tiny-flux", ChipSet(jax.devices()[:4], tensor=4),
        dtype=jnp.float32)
    assert pipe.head_groups == 4 and "test/tiny-flux" in record[
        "weights_ready_s"]
    kernel = pipe.params["flux"]["double_blocks_0"]["img_attn_qkv"]["kernel"]
    assert len({s.device for s in kernel.addressable_shards}) == 4
    assert kernel.addressable_shards[0].data.shape[1] * 4 == kernel.shape[1]

    config = {"job": {"height": 64, "width": 64}, "denoiser_txt_len": 8}
    inputs = family.denoiser_inputs(pipe, config, 5)
    want = family.denoiser_reference(pipe, inputs)
    failures, reading = checks.denoiser(family, pipe, inputs, want)
    # float32 serving: a reference fed the grouped order would read ~1
    assert failures == [] and reading["rel_l2"] < 1e-5, reading
