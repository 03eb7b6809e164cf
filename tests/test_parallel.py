"""Mesh/sharding/ring-attention tests on the 8-virtual-device CPU backend."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chiaswarm_tpu.parallel import (
    make_mesh,
    pad_batch,
    ring_self_attention_sharded,
    shard_batch,
)
from chiaswarm_tpu.parallel.tensor import partition_spec_tree, shard_params
from chiaswarm_tpu.ops.attention import reference_attention
from jax.sharding import PartitionSpec as P


def test_make_mesh_shapes():
    mesh = make_mesh()
    assert mesh.shape == {"data": 8, "tensor": 1, "seq": 1}
    mesh = make_mesh(data=2, tensor=2, seq=2)
    assert mesh.shape == {"data": 2, "tensor": 2, "seq": 2}
    with pytest.raises(ValueError):
        make_mesh(data=3, tensor=2)


def test_pad_and_shard_batch():
    mesh = make_mesh(data=4, tensor=2)
    assert pad_batch(3, 4) == 4
    x = np.ones((4, 8, 8, 3), np.float32)
    placed = shard_batch(mesh, {"x": x, "s": np.float32(2.0)})
    assert placed["x"].sharding.spec == P("data", None, None, None)
    np.testing.assert_array_equal(np.asarray(placed["x"]), x)


@pytest.mark.parametrize("seq_devices", [2, 4, 8])
def test_ring_attention_matches_full(seq_devices):
    mesh = make_mesh(data=8 // seq_devices, seq=seq_devices)
    # move seq axis adjacency into the mesh: use only the seq submesh
    b, s, h, d = 2, 64, 4, 16
    rng = np.random.default_rng(0)
    q, k, v = (rng.standard_normal((b, s, h, d)).astype(np.float32) for _ in range(3))

    expected = reference_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    got = ring_self_attention_sharded(mesh, q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)


def test_ring_attention_bf16():
    mesh = make_mesh(data=2, seq=4)
    b, s, h, d = 1, 32, 2, 8
    rng = np.random.default_rng(1)
    q, k, v = (
        jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16) for _ in range(3)
    )
    expected = reference_attention(q, k, v)
    got = ring_self_attention_sharded(mesh, q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(expected, np.float32), atol=3e-2
    )


def test_tensor_partition_rules_shard_attention_kernels():
    params = {
        "attn": {"to_q": {"kernel": np.zeros((32, 32), np.float32)},
                 "to_out_0": {"kernel": np.zeros((32, 32), np.float32),
                              "bias": np.zeros((32,), np.float32)}},
        "conv_in": {"kernel": np.zeros((3, 3, 4, 32), np.float32)},
    }
    specs = partition_spec_tree(params)
    assert specs["attn"]["to_q"]["kernel"] == P(None, "tensor")
    assert specs["attn"]["to_out_0"]["kernel"] == P("tensor", None)
    assert specs["attn"]["to_out_0"]["bias"] == P()
    assert specs["conv_in"]["kernel"] == P()

    mesh = make_mesh(data=4, tensor=2)
    placed = shard_params(mesh, params)
    assert placed["attn"]["to_q"]["kernel"].sharding.spec == P(None, "tensor")


def test_tensor_parallel_matmul_matches_dense():
    """Column->row parallel pair under pjit == dense matmul (psum inserted by XLA)."""
    mesh = make_mesh(data=1, tensor=8)
    rng = np.random.default_rng(2)
    x = rng.standard_normal((4, 64)).astype(np.float32)
    w1 = rng.standard_normal((64, 128)).astype(np.float32)
    w2 = rng.standard_normal((128, 64)).astype(np.float32)

    from jax.sharding import NamedSharding

    xw = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P()))
    w1s = jax.device_put(jnp.asarray(w1), NamedSharding(mesh, P(None, "tensor")))
    w2s = jax.device_put(jnp.asarray(w2), NamedSharding(mesh, P("tensor", None)))

    out = jax.jit(lambda x, a, b: jax.nn.relu(x @ a) @ b)(xw, w1s, w2s)
    expected = np.maximum(x @ w1, 0) @ w2
    np.testing.assert_allclose(np.asarray(out), expected, rtol=2e-4, atol=2e-4)


def test_sd_pipeline_tensor_parallel_matches_replicated():
    """THE serving-path TP check (VERDICT weak #4): the same job on a
    data+tensor ChipSet mesh must match the single-chip replicated run —
    same random weights (seeded by model name), same seed, sharded kernels.
    """
    from chiaswarm_tpu.chips.device import ChipSet
    from chiaswarm_tpu.pipelines.stable_diffusion import SDPipeline

    chipset = ChipSet(jax.devices(), tensor=2)  # data=4, tensor=2
    tp = SDPipeline("test/tiny-sd", chipset=chipset)
    assert tp.tensor_parts == 2 and tp.data_parts == 4
    # UNet attention kernels actually sharded, not replicated
    spec = jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(
            lambda x: x.sharding.spec,
            tp.params["unet"],
            is_leaf=lambda x: hasattr(x, "sharding"),
        )
    )
    assert any(s == P(None, "tensor") for s in spec)

    ref = SDPipeline("test/tiny-sd")
    kw = dict(prompt="tp parity", height=64, width=64, num_inference_steps=2,
              num_images_per_prompt=4)
    a = np.asarray(tp.run(rng=jax.random.key(11), **kw)[0][0], np.int32)
    b = np.asarray(ref.run(rng=jax.random.key(11), **kw)[0][0], np.int32)
    # fp32 CPU: sharded matmul + psum reassociates float sums; after uint8
    # quantization the outputs agree to the last-bit rounding boundary
    assert np.abs(a - b).max() <= 2, np.abs(a - b).max()


# --- the pair's products: collectives under the matmuls (ISSUE 33) ----------


def _operands(rows, tokens, k=24, n=32, seed=3):
    rng = np.random.default_rng(seed)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape),
                                        jnp.float32)
    return normal(rows, tokens, k), normal(k, n), normal(n)


def _in_ring_order(mesh, x, lengths=None):
    """A feature-sharded [B, S, C] with each chip's columns in that chip's
    ring order of the token chunks (what lies between the pair)."""
    from chiaswarm_tpu.parallel import tensor

    lengths = lengths or (x.shape[1],)
    rows = np.asarray(tensor.ring_rows(mesh, x, tuple(lengths)))  # [B,S,T,C]
    parts = rows.shape[2]
    width = x.shape[-1] // parts
    return jnp.concatenate(
        [rows[:, :, chip, chip * width:(chip + 1) * width]
         for chip in range(parts)], axis=-1)


@pytest.fixture(params=["two-ways", "two-ways-ring-0-1-3-2", "one-way"])
def ring_mesh(request, monkeypatch):
    """Four chips on the tensor axis. A chunk travels in two halves, one
    each way, at these tiny lengths too, but for the last case (what the
    512 T5 tokens do at the real ones); the second case has the chips
    where a v5e 2x2 has them (rows of two), so the ring is not the axis'
    order."""
    from chiaswarm_tpu.parallel import tensor

    if request.param != "one-way":
        monkeypatch.setattr(tensor, "_TWO_WAY_TOKENS", 2)
    if request.param.endswith("ring-0-1-3-2"):
        monkeypatch.setattr(tensor, "_ring_order", lambda mesh: [0, 1, 3, 2])
    jax.clear_caches()  # the products are jitted by mesh and shape
    mesh = make_mesh(jax.devices()[:4], tensor=4)
    want = ([[0, 1, 3, 2], [1, 3, 2, 0], [2, 0, 1, 3], [3, 2, 0, 1]]
            if request.param.endswith("ring-0-1-3-2") else
            [[0, 1, 2, 3], [1, 2, 3, 0], [2, 3, 0, 1], [3, 0, 1, 2]])
    assert tensor.ring_chunks(mesh).tolist() == want
    yield mesh
    jax.clear_caches()


@pytest.mark.parametrize("rows", [1, 2])
@pytest.mark.parametrize("product", ["gather_matmul", "matmul_scatter"])
def test_pair_product_matches_a_plain_matmul(product, rows, ring_mesh):
    from chiaswarm_tpu.parallel import tensor

    x, w, b = _operands(rows, 40)
    with jax.default_matmul_precision("highest"):
        want = x @ w + b
        if product == "gather_matmul":
            got = tensor.gather_matmul(ring_mesh, x, w, b)
            want = _in_ring_order(ring_mesh, want)
        else:
            got = tensor.matmul_scatter(
                ring_mesh, _in_ring_order(ring_mesh, x), w, b)
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5
    # what comes out is sharded as the next product takes it: by columns,
    # every token on every chip, or the finished sum of a chip's own tokens
    shard = got.addressable_shards[0].data.shape
    assert shard == ((rows, 40, 8) if product == "gather_matmul"
                     else (rows, 10, 32))


@pytest.mark.parametrize("rows", [1, 2])
def test_pair_products_on_two_streams_of_unequal_length(rows, ring_mesh):
    """The MMDiT's 4096 + 512 tokens scaled down: each chip keeps its chunk
    of `txt` beside its chunk of `img`, and the pair never sees another
    order."""
    from chiaswarm_tpu.parallel import tensor

    mesh = ring_mesh
    txt_len, img_len = 8, 64
    both, w, b = _operands(rows, txt_len + img_len)
    _, back, back_b = _operands(1, 1, k=32, n=24, seed=4)
    order = tensor.token_shard_order(4, txt_len, img_len)
    assert sorted(order) == list(range(txt_len + img_len))
    assert list(order[:2]) == [0, 1] and list(order[2:5]) == [8, 9, 10]

    def joined(both, w, b, back, back_b):
        txt, img = both[:, :txt_len], both[:, txt_len:]
        x = tensor.join_token_shards(mesh, txt, img)
        hidden = tensor.gather_matmul(mesh, x, w, b)
        out = tensor.matmul_scatter(mesh, hidden, back, back_b)
        return x, hidden, tensor.last_token_shards(mesh, out, img_len)

    with jax.default_matmul_precision("highest"):
        x, hidden, img_out = jax.jit(joined)(both, w, b, back, back_b)
        want = both @ w + b
        want_out = (want @ back + back_b)[:, txt_len:]
    assert bool((x == both[:, order]).all())
    assert float(jnp.max(jnp.abs(
        hidden - _in_ring_order(mesh, want[:, order])))) <= 1e-5
    assert float(jnp.max(jnp.abs(img_out - want_out))) <= 1e-4
    assert img_out.addressable_shards[0].data.shape == (rows, img_len // 4, 24)
    # two streams gathered one by one and laid end to end (a double block's
    # q, k, v): each in ring order by itself
    cos = both[..., :6]
    got = np.asarray(tensor.ring_rows(mesh, cos, (txt_len, img_len)))
    chunks = tensor.ring_chunks(mesh)
    for chip in range(4):
        want_rows = np.concatenate(
            [start + np.arange(n).reshape(4, -1)[chunks[chip]].reshape(-1)
             for start, n in ((0, txt_len), (txt_len, img_len))])
        assert (got[:, :, chip] == np.asarray(cos)[:, want_rows]).all()


def _traced_block(block, *token_counts, tensor_parts=4):
    """Trace one MMDiT block (shapes only) under a tensor mesh; the jaxpr's
    text and what `swarm_kernel_traces_total{op="tensor_matmul"}` gained."""
    from chiaswarm_tpu.ops.platform import KERNEL_TRACES, mesh_scope

    cfg = block.config
    hidden, rope = cfg.hidden_size, cfg.head_dim // 2
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.float32)
    total = sum(token_counts)
    args = (*(shape(2, n, hidden) for n in token_counts), shape(2, hidden),
            shape(2, total, rope), shape(2, total, rope))
    params = jax.eval_shape(
        lambda *a: block.init(jax.random.key(0), *a), *args)
    read = lambda: np.asarray([KERNEL_TRACES.value(
        op="tensor_matmul", path=p) for p in ("overlapped", "reduced")])
    before = read()
    with mesh_scope(make_mesh(jax.devices()[:tensor_parts],
                              tensor=tensor_parts)):
        jaxpr = str(jax.make_jaxpr(block.apply)(params, *args))
    return jaxpr, tuple(read() - before)


@pytest.mark.parametrize("case,overlapped,reduced", [
    ("double", 8, 0),            # qkv, proj, mlp_0, mlp_2 of both streams
    ("single", 2, 0),            # linear1, linear2
    ("double-odd-tokens", 0, 8),  # 10 image tokens: no four chunks
    ("single-odd-tokens", 0, 2),
    ("double-two-heads", 0, 8),  # TINY_FLUX: kernels whole on every chip
    ("double-one-chip", 0, 0),   # no tensor axis: nothing to reduce
])
def test_blocks_choose_the_product_from_mesh_heads_and_tokens(
        case, overlapped, reduced):
    import dataclasses

    from chiaswarm_tpu.models.flux import (
        TINY_FLUX,
        DoubleStreamBlock,
        SingleStreamBlock,
        head_groups_for,
    )

    cfg = TINY_FLUX if "two-heads" in case else dataclasses.replace(
        TINY_FLUX, num_heads=4, hidden_size=64)
    tokens = (10, 8) if "odd" in case else (16, 8)
    parts = 1 if "one-chip" in case else 4
    groups = head_groups_for(cfg, parts)
    assert groups == (1 if "two-heads" in case or parts == 1 else 4)
    if case.startswith("double"):
        block = DoubleStreamBlock(cfg, head_groups=groups)
    else:
        block, tokens = SingleStreamBlock(cfg, head_groups=groups), (
            sum(tokens),)
    jaxpr, counts = _traced_block(block, *tokens, tensor_parts=parts)
    assert counts == (overlapped, reduced)
    # the plain graph has no ring in it; the other has nothing but
    assert ("ppermute" in jaxpr) == bool(overlapped)
    assert ("shard_map" in jaxpr) == bool(overlapped)
