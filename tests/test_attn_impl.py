"""attn_impl config plumbing: the Pallas flash kernels are a first-class
model option and agree with the jnp paths end-to-end."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model


@pytest.fixture(scope="module")
def pair():
    base = get_config("llama3-8b-smoke").replace(dtype="float32")
    pal = base.replace(attn_impl="pallas")
    model = build_model(base)
    return base, pal, model.init(jax.random.key(0))


def _batch(cfg, b=2, s=32, seed=0):
    rng = np.random.default_rng(seed)
    t = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)
    return dict(inputs=t, labels=t)


def test_pallas_forward_matches_auto(pair):
    base, pal, params = pair
    batch = _batch(base)
    l0 = build_model(base).forward_train(params, batch, remat=False)
    l1 = build_model(pal).forward_train(params, batch, remat=False)
    np.testing.assert_allclose(np.asarray(l0), np.asarray(l1), rtol=2e-4, atol=2e-4)


def test_pallas_prefill_matches_auto(pair):
    base, pal, params = pair
    batch = _batch(base, seed=1)
    lg0, _ = build_model(base).prefill(params, batch)
    lg1, _ = build_model(pal).prefill(params, batch)
    np.testing.assert_allclose(np.asarray(lg0), np.asarray(lg1), rtol=2e-4, atol=2e-4)


def test_pallas_falls_back_for_windowed(pair):
    """Sliding-window layers aren't kernel-supported; the dispatcher must
    fall through to jnp paths rather than mis-masking."""
    base, _, _ = pair
    win = base.replace(sliding_window=8, alternate_local_global=True, attn_impl="pallas")
    model = build_model(win)
    params = model.init(jax.random.key(0))
    logits = model.forward_train(params, _batch(win), remat=False)
    assert bool(jnp.isfinite(logits).all())


def test_pallas_decode_matches_auto(pair):
    """One query token goes to the flash-decode kernel: a decode step over
    a prefilled cache agrees with the jnp path."""
    base, pal, params = pair
    from repro.models.transformer import chunk_prefill_step, decode_step

    model = build_model(base)
    toks = _batch(base, b=2, s=16, seed=2)["inputs"]
    start = jnp.zeros((2,), jnp.int32)
    valid = jnp.asarray([16, 11], jnp.int32)
    _, cache = chunk_prefill_step(params, toks, start, valid, base, model.init_cache(2, 64))
    nxt = jnp.asarray([[3], [7]], jnp.int32)
    lg0, c0 = decode_step(params, nxt, valid, base, cache)
    lg1, c1 = decode_step(params, nxt, valid, pal, cache)
    np.testing.assert_allclose(np.asarray(lg0), np.asarray(lg1), rtol=2e-4, atol=2e-4)
    # layer 0 writes identical KV; deeper layers see the kernel's rounding
    np.testing.assert_array_equal(np.asarray(c0["k"][0]), np.asarray(c1["k"][0]))
    np.testing.assert_allclose(np.asarray(c0["k"]), np.asarray(c1["k"]), rtol=2e-4, atol=2e-4)
