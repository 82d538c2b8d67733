"""The benchmark's weights: the program's parameter layout, one jitted draw,
and any layer drawn alone equal to its slice, bit for bit, for the dense
architecture and for one that exists only as fixture files."""
import jax
import numpy as np
import pytest

import harness
import weights
from conftest import BENCH, HERE

FIXTURES = HERE / "fixtures"
CONFIGS = [(FIXTURES, "tiny"), (FIXTURES, "moe-tiny"), (BENCH, "minicpm-2b"),
           (BENCH, "mistral-7b-v0.3-16l")]


@pytest.mark.parametrize("where, name", CONFIGS, ids=[n for _, n in CONFIGS])
def test_layout_is_the_programs(where, name):
    from repro.models import build_model

    _, arch, s = harness.load_config(where, name)
    want = build_model(arch.model_config(s)).param_struct()
    got = jax.eval_shape(lambda: weights.make_params(arch, s, 7))
    assert jax.tree.map(lambda x: (x.shape, x.dtype), got) == \
        jax.tree.map(lambda x: (x.shape, x.dtype), want)


@pytest.mark.parametrize("name", ["tiny", "moe-tiny"])
def test_layer_alone_equals_its_slice(name):
    _, arch, s = harness.load_config(FIXTURES, name)
    seed = 2**31 + 5
    p = weights.make_params(arch, s, seed)
    for layer in range(s.layers):
        for path, v in weights.layer_params(arch, s, seed, layer).items():
            node = p["layers"]
            for part in path.split("/"):
                node = node[part]
            assert v.dtype == node.dtype
            np.testing.assert_array_equal(np.asarray(node[layer]), np.asarray(v))
    for k, v in weights.top_params(arch, s, seed).items():
        np.testing.assert_array_equal(np.asarray(p[k]), np.asarray(v))


def test_seeds_differ_and_repeat():
    _, arch, s = harness.load_config(FIXTURES, "tiny")
    a = weights.make_params(arch, s, 1)["embed"]
    b = weights.make_params(arch, s, 1)["embed"]
    c = weights.make_params(arch, s, 2**33 + 1)["embed"]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    with pytest.raises(ValueError):
        weights.seed_key(-1)
