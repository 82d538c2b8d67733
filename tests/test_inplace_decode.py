"""The decode engine updates its cache in place (DESIGN.md §kvcache).

The engine's step consumes the cache it is given and writes only each live
lane's new K/V row. Each test holds it to the formula it replaced, kept
here as the reference: gather the lanes' whole slots (or pages), run
`transformer.decode_step` on them, scatter them back. In float32 on the
tiny fixtures, MHA and GQA: the logits and every non-scratch row agree,
and no row outside the live lanes' (slot, position) changes. A windowed
ring cache keeps the whole-slot path; a warmed engine, whose steps and
attach have consumed its cache, serves the tokens of a fresh one.
"""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.request import Request, SLOSpec
from repro.models import build_model, transformer
from repro.serving import engine
from repro.serving.kvcache import gather_slots, scatter_slots

M = 32  # max_len
SLOTS = 4  # + the scratch slot
# float32 through two layers; the row path sums the softmax in another order
TOL = dict(rtol=1e-5, atol=1e-5)


@pytest.fixture(scope="module", params=["minicpm-2b-smoke", "llama3-8b-smoke"], ids=["mha", "gqa"])
def tiny(request):
    cfg = get_config(request.param).replace(dtype="float32")
    assert (cfg.num_kv_heads == cfg.num_heads) == (request.param == "minicpm-2b-smoke")
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.key(0))


def _random_cache(model, batch, rows, seed=0):
    rng = np.random.default_rng(seed)
    return {
        name: jnp.asarray(rng.standard_normal(leaf.shape), jnp.float32)
        for name, leaf in model.init_cache(batch, rows).items()
    }


@jax.jit
def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


@jax.jit
def _old_pages_view(pool, page_idx):
    b, p = page_idx.shape
    return {
        n: jnp.take(x, page_idx.reshape(-1), axis=1).reshape(x.shape[0], b, p * x.shape[2], *x.shape[3:])
        for n, x in pool.items()
    }


def _old_slot_step(params, tokens, positions, cache, slot_idx, cfg):
    sub = gather_slots(cfg, cache, slot_idx)
    logits, sub = transformer.decode_step(params, tokens, positions, cfg, sub)
    return logits, scatter_slots(cfg, cache, sub, slot_idx)


def _old_page_step(params, tokens, positions, pool, page_idx, cfg):
    sub = _old_pages_view(pool, page_idx)
    logits, sub = transformer.decode_step(params, tokens, positions, cfg, sub)
    b, p = page_idx.shape
    return logits, {
        n: x.at[:, page_idx.reshape(-1)].set(sub[n].reshape(x.shape[0], b * p, *x.shape[2:]))
        for n, x in pool.items()
    }


_old_slot_step = jax.jit(_old_slot_step, static_argnums=(5,))
_old_page_step = jax.jit(_old_page_step, static_argnums=(5,))


def _check(old, new, before, written, scratch):
    """``old``/``new``: (logits, cache) of the two formulas; ``written``: the
    live lanes' (slot or page, row); rows of ``scratch`` are not compared."""
    np.testing.assert_allclose(np.asarray(new[0]), np.asarray(old[0]), **TOL)
    for name in before:
        a, b, x0 = (np.asarray(t[name]) for t in (old[1], new[1], before))
        keep = np.arange(a.shape[1]) != scratch
        np.testing.assert_allclose(b[:, keep], a[:, keep], **TOL)
        changed = np.any(b != x0, axis=(0, 3, 4))
        changed[scratch] = False
        assert sorted(map(tuple, np.argwhere(changed))) == sorted(written), name


def test_row_step_matches_whole_slot_step(tiny):
    cfg, model, params = tiny
    cache = _random_cache(model, SLOTS + 1, M)
    # live lanes at position 0, at max_len - 1 and between; a pad lane on
    # the scratch slot
    slots = jnp.asarray([2, 0, 3, SLOTS], jnp.int32)
    positions = jnp.asarray([0, M - 1, 17, 0], jnp.int32)
    tokens = jnp.asarray([[5], [77], [130], [0]], jnp.int32)
    old = _old_slot_step(params, tokens, positions, cache, slots, cfg)
    new = engine._slot_step(params, tokens, positions, _copy(cache), slots, cfg)
    _check(old, new, cache, [(2, 0), (0, M - 1), (3, 17)], SLOTS)


def test_row_page_step_matches_whole_page_step_with_shared_prefix(tiny):
    cfg, model, params = tiny
    ps, n_pages = 4, 12
    sp = n_pages  # scratch page
    pool = _random_cache(model, n_pages + 1, ps, seed=1)
    # lanes 1 and 2 share prefix pages 0 and 1 (positions 0-7); every lane
    # writes past the shared head; lane 3 is padding on the scratch page
    table = jnp.asarray([
        [2, 3, 10, sp, sp, sp, sp, sp],
        [0, 1, 4, 5, 6, 7, 8, 9],
        [0, 1, 11, sp, sp, sp, sp, sp],
        [sp] * 8,
    ], jnp.int32)
    positions = jnp.asarray([0, M - 1, 9, 0], jnp.int32)
    tokens = jnp.asarray([[5], [77], [130], [0]], jnp.int32)
    old = _old_page_step(params, tokens, positions, pool, table, cfg)
    new = engine._page_step(params, tokens, positions, _copy(pool), table, cfg)
    _check(old, new, pool, [(2, 0), (9, 3), (11, 1)], sp)


def test_windowed_cache_keeps_the_whole_slot_path():
    cfg = get_config("gemma2-9b-smoke").replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    eng = engine.DecodeEngine(model, params, engine.EngineConfig(max_slots=SLOTS, max_len=64),
                              None, peek=lambda: 0.0)
    assert "k_local" in eng.cache and eng.kv_write == "slot"
    cache = _random_cache(model, SLOTS + 1, 64)
    slots = jnp.asarray([1, SLOTS], jnp.int32)
    positions = jnp.asarray([40, 0], jnp.int32)
    tokens = jnp.asarray([[9], [0]], jnp.int32)
    old = _old_slot_step(params, tokens, positions, cache, slots, cfg)
    new = engine._slot_step(params, tokens, positions, _copy(cache), slots, cfg)
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(old[0]))
    for name in cache:
        np.testing.assert_array_equal(np.asarray(new[1][name]), np.asarray(old[1][name]))


@pytest.mark.parametrize("page_size", [None, 4], ids=["slots", "pages"])
def test_donated_cache_serves_the_tokens_of_a_fresh_engine(tiny, page_size):
    """A warmed engine (its steps and attach write have already consumed its
    cache) serves staggered requests, so each attach lands while another
    request decodes, and gives the tokens of an engine built fresh and of
    the scheduling-free reference. The arrays a step consumed are gone."""
    from repro.serving.clock import ManualClock

    cfg, model, params = tiny
    rng = np.random.default_rng(7)
    prompts = [list(map(int, rng.integers(2, cfg.vocab_size, n))) for n in (11, 6, 9)]
    reqs = [
        (Request(rid=i, arrival=0.004 * i, input_len=len(p), output_len=6,
                 slo=SLOSpec(ttft=120.0, tpot=10.0)), p)
        for i, p in enumerate(prompts)
    ]
    outs = []
    for warm in (True, False):
        srv = engine.DisaggServer(
            model, params,
            engine.EngineConfig(max_slots=SLOTS, max_len=M, chunk_size=8, page_size=page_size),
            clock=ManualClock(auto_step=1e-3),
        )
        assert srv.decode.kv_write == "row"
        held = srv.decode.cache or srv.decode.pool
        if warm:
            srv.warmup()
            assert all(x.is_deleted() for x in held.values())
            held = srv.decode.cache or srv.decode.pool
        outs.append(srv.serve(copy.deepcopy(reqs)))
        assert all(x.is_deleted() for x in held.values())
    assert outs[0] == outs[1]
    for i, p in enumerate(prompts):
        assert outs[0][i] == engine.reference_generate(model, params, p, 6, M)
