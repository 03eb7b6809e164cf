"""Flux multi-chip serving readiness (VERDICT r03 item 4).

Three claims, each previously asserted only in prose:
1. The TP-sharded Flux forward on an 8-device mesh computes EXACTLY what
   the single-device forward computes, with CONVERTED weights (diffusers
   layout -> convert_flux) — not just with random trees — and the kernels
   really are sharded (ISSUE 27: under the UNet's rules none was).
2. The requirements math is fact-based: FAMILY_PARAMS_GB["flux"] matches
   the parameter bytes of the real flux-dev geometry (measured via
   eval_shape, no materialization), and min_chips derives a >=2-chip TP
   requirement for a 16 GB v5e chip.
3. A 1-chip slice REFUSES flux jobs with the tensor-degree fix named, and
   the worker's capability advertisement carries flux_min_chips so a
   capability-aware hive never sends un-runnable flux jobs.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chiaswarm_tpu.models.flux import TINY_FLUX, FluxTransformer

import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from test_flux import _flux_flax_to_diffusers  # noqa: E402


def _tiny_inputs():
    rng = np.random.default_rng(7)
    b, s_img, s_txt = 2, 16, 8
    img = rng.standard_normal((b, s_img, TINY_FLUX.in_channels)).astype(
        np.float32
    )
    img_ids = np.zeros((b, s_img, 3), np.int32)
    img_ids[:, :, 1] = np.arange(s_img)[None] // 4
    img_ids[:, :, 2] = np.arange(s_img)[None] % 4
    txt = rng.standard_normal((b, s_txt, TINY_FLUX.context_dim)).astype(
        np.float32
    )
    txt_ids = np.zeros((b, s_txt, 3), np.int32)
    t = np.array([0.3, 0.9], np.float32)
    pooled = rng.standard_normal((b, TINY_FLUX.pooled_dim)).astype(np.float32)
    guidance = np.array([3.5, 3.5], np.float32)
    return img, img_ids, txt, txt_ids, t, pooled, guidance


def test_tp_forward_matches_single_with_converted_weights():
    from chiaswarm_tpu.models.conversion import convert_flux
    from chiaswarm_tpu.models.flux import grouped_layout, head_groups_for
    from chiaswarm_tpu.ops.platform import mesh_scope
    from chiaswarm_tpu.parallel.mesh import make_mesh
    from chiaswarm_tpu.parallel.tensor import (
        flux_partition_rules,
        largest_device_bytes,
        shard_params,
    )

    model = FluxTransformer(TINY_FLUX)
    img, img_ids, txt, txt_ids, t, pooled, guidance = _tiny_inputs()
    ref = jax.jit(lambda: model.init(
        jax.random.key(1), jnp.asarray(img), jnp.asarray(img_ids),
        jnp.asarray(txt), jnp.asarray(txt_ids), jnp.asarray(t),
        jnp.asarray(pooled), guidance=jnp.asarray(guidance),
    )["params"])()
    ref = jax.tree_util.tree_map(lambda x: np.asarray(x, np.float32), dict(ref))
    converted = convert_flux(_flux_flax_to_diffusers(ref))

    args = (
        jnp.asarray(img), jnp.asarray(img_ids), jnp.asarray(txt),
        jnp.asarray(txt_ids), jnp.asarray(t), jnp.asarray(pooled),
    )
    out_single = np.asarray(jax.jit(
        lambda p, *a: model.apply({"params": p}, *a,
                                  guidance=jnp.asarray(guidance))
    )(converted, *args))

    # TINY_FLUX has two heads: a 2-way tensor axis gives each chip one
    assert len(jax.devices()) >= 8, "conftest provides 8 virtual devices"
    mesh = make_mesh(jax.devices()[:8], tensor=2)
    assert mesh.shape["tensor"] == 2 and mesh.shape["data"] == 4
    groups = head_groups_for(TINY_FLUX, 2)
    assert groups == 2
    # the converted (checkpoint-order) tree, regrouped once and placed by
    # the MMDiT's own rules: with rules that match nothing (as the UNet's
    # did) every leaf is whole on every chip and these fail
    sharded = shard_params(
        mesh, grouped_layout(converted, TINY_FLUX, groups),
        flux_partition_rules())
    hidden = TINY_FLUX.hidden_size
    block = sharded["double_blocks_0"]
    for name, want in (("img_attn_qkv", (hidden, 3 * hidden // 2)),
                       ("txt_attn_proj", (hidden // 2, hidden)),
                       ("img_mlp_0", (hidden, 4 * hidden // 2)),
                       ("txt_mlp_2", (4 * hidden // 2, hidden))):
        assert block[name]["kernel"].addressable_shards[0].data.shape == want
    single = sharded["single_blocks_0"]
    assert single["linear1"]["kernel"].addressable_shards[0].data.shape == (
        hidden, 7 * hidden // 2)
    assert single["linear2"]["kernel"].addressable_shards[0].data.shape == (
        5 * hidden // 2, hidden)
    total = sum(x.nbytes for x in jax.tree_util.tree_leaves(sharded))
    assert largest_device_bytes(sharded) < 0.75 * total

    grouped_model = FluxTransformer(TINY_FLUX, head_groups=groups)

    @jax.jit
    def run(p, *a):
        return grouped_model.apply({"params": p}, *a,
                                   guidance=jnp.asarray(guidance))

    with mesh_scope(mesh):
        out_tp = np.asarray(run(sharded, *args))
    np.testing.assert_allclose(out_tp, out_single, atol=2e-4, rtol=1e-3)


def test_flux_params_gb_is_fact_based():
    """The capacity table's flux footprint must match the real flux-dev
    geometry (bf16 bytes), measured without materializing anything."""
    from chiaswarm_tpu.chips.requirements import FAMILY_PARAMS_GB
    from chiaswarm_tpu.pipelines.flux import _flux_configs

    flux_cfg, t5_cfg, clip_cfg, vae_cfg, _, _, _ = _flux_configs(
        "black-forest-labs/FLUX.1-dev"
    )
    from chiaswarm_tpu.models.clip import CLIPTextEncoder
    from chiaswarm_tpu.models.flux import FluxTransformer
    from chiaswarm_tpu.models.t5 import T5Encoder
    from chiaswarm_tpu.models.vae import AutoencoderKL

    def count(module, *args, **kwargs):
        import functools

        fn = (functools.partial(module.init, **kwargs) if kwargs
              else module.init)
        shapes = jax.eval_shape(fn, jax.random.key(0), *args)["params"]
        return sum(
            int(np.prod(x.shape))
            for x in jax.tree_util.tree_leaves(shapes)
        )

    n = count(
        FluxTransformer(flux_cfg),
        jnp.zeros((1, 4, flux_cfg.in_channels)),
        jnp.zeros((1, 4, 3), jnp.int32),
        jnp.zeros((1, 8, flux_cfg.context_dim)),
        jnp.zeros((1, 8, 3), jnp.int32),
        jnp.zeros((1,)),
        jnp.zeros((1, flux_cfg.pooled_dim)),
        guidance=jnp.ones((1,)),
    )
    n += count(T5Encoder(t5_cfg), jnp.zeros((1, 8), jnp.int32))
    n += count(CLIPTextEncoder(clip_cfg), jnp.zeros((1, 77), jnp.int32))
    n += count(AutoencoderKL(vae_cfg), jnp.zeros((1, 32, 32, 3)))
    measured_gb = n * 2 / (1 << 30)  # bf16
    table_gb = FAMILY_PARAMS_GB["flux"]
    assert abs(measured_gb - table_gb) / table_gb < 0.2, (
        f"requirements table says {table_gb} GB, geometry measures "
        f"{measured_gb:.1f} GB"
    )


def test_one_chip_refuses_flux_naming_the_fix(monkeypatch, sdaas_root):
    """With weight streaming DISABLED (the round-4 contract), a 1-chip
    slice still refuses flux naming the tensor-degree fix; with streaming
    on (the default) the same slice is admitted — test_flux_stream.py."""
    from chiaswarm_tpu.chips.requirements import check_capacity, min_chips

    assert min_chips("black-forest-labs/FLUX.1-dev", 16.0) >= 2

    class FakeChip:
        platform = "tpu"
        tensor = 1
        seq = 1

        def hbm_bytes(self):
            return 16 << 30

        def chip_count(self):
            return 1

    assert check_capacity(
        FakeChip(), "black-forest-labs/FLUX.1-dev", 1, 1024) == 1

    monkeypatch.setenv("SDAAS_FLUX_STREAMING", "0")
    with pytest.raises(ValueError) as e:
        check_capacity(FakeChip(), "black-forest-labs/FLUX.1-dev", 1, 1024)
    assert "tensor" in str(e.value)


def test_capability_advertises_flux_min_chips(sdaas_root):
    """The worker tells the hive how many chips flux needs on THIS
    hardware, so a capability-aware hive can place (or skip) accordingly."""
    import asyncio

    from chiaswarm_tpu.chips.allocator import SliceAllocator
    from chiaswarm_tpu.settings import Settings
    from chiaswarm_tpu.worker import Worker

    w = Worker(
        settings=Settings(sdaas_token="t", worker_name="w"),
        allocator=SliceAllocator(chips_per_job=4),
        hive_uri="http://127.0.0.1:1/api",
    )
    caps = w._capabilities()
    # CPU slices are exempt from the HBM gate (fit_batch), so the
    # advertisement says runnable — matching what check_capacity admits;
    # flux_min_chips only appears on TPU slices where HBM math is real
    assert caps["flux_runnable"] == 1
    assert "flux_min_chips" not in caps
    assert "unconverted_families" in caps
    asyncio.run(w.hive.close())
