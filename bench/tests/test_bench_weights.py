"""The weights are a function of the seed, and the reference draws any one
layer again, bit for bit, from the seed alone."""
import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import model, weights
from bench.tests import tiny

W = model.widths(tiny.CONFIG)


def test_one_layer_drawn_again_matches_the_stack():
    seed = 2 ** 32 + 5           # bits above 32 count, not dropped
    p = weights.build(seed, W)
    key = weights.seed_key(seed)
    for i in range(W.layers):
        one = weights.layer(key, i, W)
        assert (np.asarray(one["wq"]) ==
                np.asarray(p["layers"]["attn"]["wq"]["w"][i])).all()
        assert (np.asarray(one["wd"]) ==
                np.asarray(p["layers"]["mlp"]["wd"]["w"][i])).all()
        assert (np.asarray(one["ln2"]) ==
                np.asarray(p["layers"]["ln2"]["scale"][i])).all()
    assert (np.asarray(weights.unembedding(key, W)) ==
            np.asarray(p["embed"]["unembed"]["w"])).all()
    assert p["embed"]["embedding"].dtype == jnp.bfloat16
    assert not (np.asarray(weights.build(seed + 2 ** 32, W)["final_norm"]
                           ["scale"]) == np.asarray(p["final_norm"]["scale"])
                ).all()


def test_layout_is_the_programs():
    from repro.models import api
    pcfg = model.program_config("tiny", tiny.CONFIG)
    want = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype),
                                  api.abstract_params(pcfg))
    got = jax.tree_util.tree_map(
        lambda x: (x.shape, x.dtype),
        jax.eval_shape(lambda: weights.program_params(weights.seed_key(0), W)))
    assert got == want


def test_draws_are_uniform_with_the_stated_spread():
    p = weights.build(2 ** 31 + 3, W)
    wi = np.asarray(p["layers"]["mlp"]["wi"]["w"], np.float32)
    std = W.d ** -0.5
    assert abs(wi.std() / std - 1) < 0.05
    assert np.abs(wi).max() <= std * 3 ** 0.5 * 1.01
    assert abs(wi.mean()) < 0.05 * std
    ln = np.asarray(p["layers"]["ln1"]["scale"], np.float32)
    assert ln.min() >= 0.75 and ln.max() <= 1.25
    # layers and leaves draw apart: no two share their values
    assert not np.allclose(wi[0], wi[1])
    wu = np.asarray(p["layers"]["mlp"]["wu"]["w"], np.float32)
    assert abs(np.corrcoef(wi.ravel(), wu.ravel())[0, 1]) < 0.05
