"""`models/layers.py` `DeclaredParams` (ISSUE 56): a parameter that is
already in the tree bound for `apply` is checked against the shape the
layer declares, and no initialiser is traced for it.

It leans on three private names of flax 0.12.3 (`_initialization_allowed`,
`_name_taken`, `_state.children`): the tree, error and jaxpr cases below
are what fails if another flax changes `Module.param`.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import errors

from chiaswarm_tpu.models import configs, flux, layers
from chiaswarm_tpu.models.clip import CLIPTextEncoder
from chiaswarm_tpu.models.unet2d import UNet2DConditionModel
from chiaswarm_tpu.models.vae import AutoencoderKL

IMAGE = np.ones((2, 8, 8, 32), np.float32)
TOKENS = np.array([[1, 2, 3], [4, 5, 6]])

# name -> (the layer under the mixin, flax's own layer, an input, its leaves)
LAYERS = {
    "Dense": (layers.Dense(16), nn.Dense(16), IMAGE, ("kernel", "bias")),
    "Dense-no-bias": (layers.Dense(16, use_bias=False),
                      nn.Dense(16, use_bias=False), IMAGE, ("kernel",)),
    "Conv": (layers.Conv(16, (3, 3), strides=(2, 2)),
             nn.Conv(16, (3, 3), strides=(2, 2)), IMAGE, ("kernel", "bias")),
    "LayerNorm": (layers.LayerNorm(epsilon=1e-5),
                  nn.LayerNorm(epsilon=1e-5), IMAGE, ("scale", "bias")),
    "Embed": (layers.Embed(10, 4), nn.Embed(10, 4), TOKENS, ("embedding",)),
    "FusedGroupNorm": (layers.FusedGroupNorm(8), nn.GroupNorm(8), IMAGE,
                       ("scale", "bias")),
}


def _leaves(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf
            in jax.tree_util.tree_leaves_with_path(tree)}


def _reads():
    return {path: layers.PARAM_READS.value(path=path)
            for path in ("declared", "traced")}


def _delta(before):
    return {path: count - before[path] for path, count in _reads().items()}


@pytest.mark.parametrize("name", LAYERS)
def test_init_gives_the_tree_flax_gives(name):
    mine, flaxs, x, leaves = LAYERS[name]
    assert type(mine).__name__ == (
        name.split("-")[0])  # an unnamed child keeps flax's `Dense_0`
    got = _leaves(mine.init(jax.random.key(7), x))
    want = _leaves(flaxs.init(jax.random.key(7), x))
    assert list(got) == list(want)
    assert sorted(want) == sorted(f"['params']['{leaf}']" for leaf in leaves)
    for path, leaf in want.items():
        assert got[path].shape == leaf.shape, path
        assert got[path].dtype == leaf.dtype, path
        np.testing.assert_array_equal(got[path], leaf, err_msg=path)


@pytest.mark.parametrize("name", LAYERS)
def test_apply_reads_each_leaf_by_its_declared_shape(name):
    mine, flaxs, x, _ = LAYERS[name]
    params = mine.init(jax.random.key(7), x)
    before = _reads()
    out = mine.apply(params, x)
    assert _delta(before) == {
        "declared": len(jax.tree.leaves(params)), "traced": 0}
    if name != "FusedGroupNorm":  # its arithmetic is ops.group_norm's
        np.testing.assert_array_equal(out, flaxs.apply(params, x))


@pytest.mark.parametrize("name,leaf", [
    (name, leaf) for name, layer in LAYERS.items() for leaf in layer[3]])
def test_a_leaf_of_a_wrong_shape_is_refused_as_flax_refuses_it(name, leaf):
    mine, flaxs, x, _ = LAYERS[name]
    params = mine.init(jax.random.key(7), x)
    right = params["params"][leaf]
    params["params"][leaf] = jnp.zeros(right.shape + (2,), right.dtype)
    with pytest.raises(errors.ScopeParamShapeError) as got:
        mine.apply(params, x)
    with pytest.raises(errors.ScopeParamShapeError) as want:
        flaxs.apply(params, x)
    assert f'"{leaf}"' in str(got.value)
    assert str(got.value) == str(want.value)


class _Twice(layers.DeclaredParams, nn.Module):
    @nn.compact
    def __call__(self, x):
        return x * self.param("w", nn.initializers.ones, (3,)) \
            * self.param("w", nn.initializers.ones, (3,))


class _Odd(layers.DeclaredParams, nn.Module):
    """Calls the mixin does not answer: a keyword, a shape that is no
    plain tuple of ints, an initialiser with no argument."""

    @nn.compact
    def __call__(self, x):
        a = self.param("a", nn.initializers.ones, shape=(3,))
        b = self.param("b", nn.initializers.ones, (np.int64(3),))
        c = self.param("c", lambda key: jnp.ones((3,)))
        return x * a * b * c


def test_the_guards_around_the_check_are_flaxs():
    with pytest.raises(errors.NameInUseError):
        _Twice().apply({"params": {"w": jnp.ones((3,))}}, jnp.ones((3,)))

    class Late(layers.DeclaredParams, nn.Module):
        def __call__(self, x):
            return x * self.param("w", nn.initializers.ones, (3,))

    with pytest.raises(ValueError, match="setup"):
        Late().apply({"params": {"w": jnp.ones((3,))}}, jnp.ones((3,)))


def test_any_other_call_takes_flaxs_own_path_and_is_counted():
    x = jnp.ones((3,))
    before = _reads()
    params = _Odd().init(jax.random.key(0), x)
    assert _delta(before) == {"declared": 0, "traced": 0}  # absent: init
    np.testing.assert_array_equal(_Odd().apply(params, x), x)
    assert _delta(before) == {"declared": 0, "traced": 3}
    params["params"]["b"] = jnp.ones((4,))
    with pytest.raises(errors.ScopeParamShapeError):
        _Odd().apply(params, x)


def test_a_boxed_parameter_takes_flaxs_own_path():
    boxed = nn.with_partitioning(nn.initializers.ones, ("data",))

    class Boxed(layers.DeclaredParams, nn.Module):
        @nn.compact
        def __call__(self, x):
            return x * self.param("w", boxed, (3,))

    x = jnp.ones((3,))
    params = Boxed().init(jax.random.key(0), x)
    assert isinstance(params["params"]["w"], nn.Partitioned)
    before = _reads()
    np.testing.assert_array_equal(Boxed().apply(params, x), x)
    assert _delta(before) == {"declared": 0, "traced": 1}


# --- the served models: every leaf by its declared shape, nothing traced ---


def _unet():
    cfg = configs.TINY_XL_UNET
    added = {"text_embeds": jnp.ones((2, 32)), "time_ids": jnp.ones((2, 6))}
    return UNet2DConditionModel(cfg), (
        jnp.ones((2, 8, 8, cfg.in_channels)), jnp.ones((2,)),
        jnp.ones((2, 5, cfg.cross_attention_dim)), added)


def _vae():
    return AutoencoderKL(configs.TINY_VAE), (jnp.ones((1, 16, 16, 3)),)


def _clip():
    return CLIPTextEncoder(configs.TINY_CLIP_2), (
        jnp.ones((2, 7), jnp.int32),)


def _mmdit_block():
    cfg = flux.TINY_FLUX
    cos, sin = flux.rope_frequencies(
        jnp.zeros((2, 8 + 12, 3), jnp.int32), cfg.axes_dims_rope, cfg.theta)
    return flux.DoubleStreamBlock(cfg), (
        jnp.ones((2, 12, cfg.hidden_size)), jnp.ones((2, 8, cfg.hidden_size)),
        jnp.ones((2, cfg.hidden_size)), cos, sin)


MODELS = {"unet": _unet, "vae": _vae, "clip": _clip,
          "mmdit_block": _mmdit_block}


@pytest.fixture()
def staging(tmp_path, monkeypatch):
    """jax's staging events listened to (`compile_cache`), the persistent
    cache in `tmp_path`; jax's settings put back afterwards."""
    from chiaswarm_tpu import compile_cache

    kept = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.setattr(compile_cache, "DEFAULT_DIR", tmp_path / "xla")
    compile_cache.enable_compile_cache()
    yield compile_cache
    jax.config.update("jax_compilation_cache_dir", kept)


def _jaxpr(model, params, args) -> str:
    def applied(params, *args):  # a new function a call: jax keeps traces
        return model.apply(params, *args)

    return str(jax.make_jaxpr(applied)(params, *args))


def _lambda_traces(staging):
    row = {row["function"]: row for row in staging.staging()}.get("<lambda>")
    return row["events"] if row else 0


@pytest.mark.parametrize("name", MODELS)
def test_tracing_a_served_model_traces_no_initialiser(name, staging):
    model, args = MODELS[name]()
    params = jax.eval_shape(model.init, jax.random.key(0), *args)
    leaves = len(jax.tree.leaves(params))
    assert leaves > 10
    reads, lambdas = _reads(), _lambda_traces(staging)
    _jaxpr(model, params, args)
    assert _delta(reads) == {"declared": leaves, "traced": 0}
    assert _lambda_traces(staging) == lambdas


def test_the_jaxpr_is_the_one_flaxs_own_path_traces(staging, monkeypatch):
    model, args = _unet()
    params = jax.eval_shape(model.init, jax.random.key(0), *args)
    mine = _jaxpr(model, params, args)
    # the twin: the same classes with `param` flax's own
    monkeypatch.setattr(layers.DeclaredParams, "param", nn.Module.param)
    reads, lambdas = _reads(), _lambda_traces(staging)
    twin = _jaxpr(model, params, args)
    assert _delta(reads) == {"declared": 0, "traced": 0}
    assert _lambda_traces(staging) - lambdas == len(jax.tree.leaves(params))
    assert mine == twin
