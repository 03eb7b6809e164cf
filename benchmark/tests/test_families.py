"""The on-device weight factory: its tree is the one `eval_shape` of the
modules' own `init` gives, in the serving dtype, from the seed."""

import jax
import jax.numpy as jnp
import pytest

from benchmark.families import sd


@pytest.mark.parametrize("model", ["test/tiny-sd", "test/tiny-xl"])
def test_tree_equals_eval_shape_of_init(model):
    pipe = sd.make_pipeline_class()(model, dtype=jnp.float32)
    shapes = sd.init_shapes(pipe)
    sd.assert_same_tree(pipe.params, shapes)
    assert (jax.tree_util.tree_structure(pipe.params)
            == jax.tree_util.tree_structure(shapes))
    assert {leaf.dtype for leaf in jax.tree_util.tree_leaves(pipe.params)} \
        == {jnp.dtype(jnp.float32)}


def test_rules_and_seed():
    pipe = sd.make_pipeline_class()("test/tiny-sd", dtype=jnp.float32)
    shapes = sd.init_shapes(pipe)
    one = sd.seeded_params(shapes, 5, jnp.float32)
    same = sd.seeded_params(shapes, 5, jnp.float32)
    other = sd.seeded_params(shapes, 6, jnp.float32)
    conv = one["unet"]["conv_in"]
    assert float(jnp.abs(conv["bias"]).max()) == 0.0
    assert float(one["unet"]["conv_norm_out"]["scale"].min()) == 1.0
    fan_in = conv["kernel"].shape[0] * conv["kernel"].shape[1] \
        * conv["kernel"].shape[2]
    assert float(conv["kernel"].std()) == pytest.approx(
        fan_in ** -0.5, rel=0.2)
    assert bool((conv["kernel"] == same["unet"]["conv_in"]["kernel"]).all())
    assert not bool(
        (conv["kernel"] == other["unet"]["conv_in"]["kernel"]).all())


def test_a_changed_tree_fails_loudly():
    pipe = sd.make_pipeline_class()("test/tiny-sd", dtype=jnp.float32)
    shapes = sd.init_shapes(pipe)
    broken = dict(pipe.params, vae={})
    with pytest.raises(RuntimeError):
        sd.assert_same_tree(broken, shapes)
