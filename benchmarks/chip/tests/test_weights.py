"""The benchmark's weights: the program's parameter layout, one jitted draw,
and any layer drawn alone equal to its slice, bit for bit."""
import jax
import numpy as np
import pytest

import harness
import weights
from conftest import HERE

FIXTURES = HERE / "fixtures"


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(FIXTURES, "tiny.tiny-mix")


def test_layout_is_the_programs(cell):
    from repro.models import build_model

    import modelspec

    want = build_model(modelspec.model_config(cell.spec)).param_struct()
    got = jax.eval_shape(lambda: weights.make_params(cell.spec, 7))
    assert jax.tree.map(lambda x: (x.shape, x.dtype), got) == \
        jax.tree.map(lambda x: (x.shape, x.dtype), want)


def test_layer_alone_equals_its_slice(cell):
    s = cell.spec
    seed = 2**31 + 5
    p = weights.make_params(s, seed)
    for layer in range(s.layers):
        for path, v in weights.layer_params(s, seed, layer).items():
            node = p["layers"]
            for part in path.split("/"):
                node = node[part]
            assert v.dtype == node.dtype
            np.testing.assert_array_equal(np.asarray(node[layer]), np.asarray(v))
    for k, v in weights.top_params(s, seed).items():
        np.testing.assert_array_equal(np.asarray(p[k]), np.asarray(v))


def test_seeds_differ_and_repeat(cell):
    a = weights.make_params(cell.spec, 1)["embed"]
    b = weights.make_params(cell.spec, 1)["embed"]
    c = weights.make_params(cell.spec, 2**33 + 1)["embed"]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    with pytest.raises(ValueError):
        weights.seed_key(-1)
