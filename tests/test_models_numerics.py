"""Numerical invariants: MoE dispatch vs dense oracle, SSD chunk-size
invariance, decode-vs-prefill consistency, blockwise attention exactness."""
import itertools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.models.layers import attention, blockwise_attention, naive_attention, attention_mask
from repro.models.moe import init_moe_params, moe_ffn, moe_ffn_ref
from repro.models.ssm import init_ssm_params, ssd_chunked, ssm_decode_step, ssm_forward


def test_moe_matches_dense_oracle_when_capacity_ample():
    cfg = get_config("phi3.5-moe-42b-a6.6b-smoke").replace(
        dtype="float32", capacity_factor=8.0, moe_group_size=16
    )
    params = init_moe_params(jax.random.key(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (3, 10, cfg.d_model), jnp.float32)
    out = moe_ffn(x, params, cfg)
    ref = moe_ffn_ref(x, params, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-4, atol=3e-4)


def test_moe_capacity_drops_are_partial_not_corrupt():
    cfg = get_config("grok-1-314b-smoke").replace(
        dtype="float32", capacity_factor=0.5, moe_group_size=16
    )
    params = init_moe_params(jax.random.key(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model), jnp.float32)
    out = moe_ffn(x, params, cfg)
    assert bool(jnp.isfinite(out).all())


def test_ssd_chunk_size_invariance():
    b, l, h, p, n = 2, 96, 4, 16, 8
    key = jax.random.key(0)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (b, l, h, p)) * 0.3
    dt = jax.random.uniform(ks[1], (b, l, h), minval=0.001, maxval=0.1)
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, l, 1, n)) * 0.3
    C = jax.random.normal(ks[0], (b, l, 1, n)) * 0.3
    y16, s16 = ssd_chunked(x, dt, A, B, C, chunk=16)
    y32, s32 = ssd_chunked(x, dt, A, B, C, chunk=32)
    y96, s96 = ssd_chunked(x, dt, A, B, C, chunk=96)
    np.testing.assert_allclose(np.asarray(y16), np.asarray(y32), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y16), np.asarray(y96), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s16), np.asarray(s32), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s16), np.asarray(s96), rtol=1e-4, atol=1e-5)


def test_ssm_decode_matches_forward_stepwise():
    cfg = get_config("mamba2-130m-smoke").replace(dtype="float32")
    params = init_ssm_params(jax.random.key(0), cfg, jnp.float32)
    b, l = 1, 12
    x = jax.random.normal(jax.random.key(1), (b, l, cfg.d_model)) * 0.3
    y_full, cache_full = ssm_forward(params, x, cfg)

    # replay the same tokens through the recurrent decode path
    W = cfg.ssm_conv_width
    from repro.models.ssm import conv_channels
    cache = dict(
        conv=jnp.zeros((b, W - 1, conv_channels(cfg)), jnp.float32),
        state=jnp.zeros((b, cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state_dim), jnp.float32),
    )
    ys = []
    for t in range(l):
        y_t, cache = ssm_decode_step(params, x[:, t : t + 1], cfg, cache)
        ys.append(y_t)
    y_dec = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_dec), np.asarray(y_full), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(cache["state"]), np.asarray(cache_full["state"]), rtol=2e-4, atol=2e-4
    )


def test_blockwise_attention_matches_naive():
    b, sq, skv, hq, hkv, dh = 2, 64, 192, 4, 2, 16
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, sq, hq, dh))
    k = jax.random.normal(ks[1], (b, skv, hkv, dh))
    v = jax.random.normal(ks[2], (b, skv, hkv, dh))
    q_pos = jnp.broadcast_to(jnp.arange(sq) + 100, (b, sq)).astype(jnp.int32)
    kv_valid = jnp.asarray([150, 192], jnp.int32)
    out_blk = blockwise_attention(q, k, v, q_pos, kv_valid, window=0, causal=True,
                                  logit_cap=0.0, kv_block=32)
    mask = attention_mask(q_pos, skv, kv_valid, 0, True)
    out_ref = naive_attention(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out_blk), np.asarray(out_ref), rtol=2e-5, atol=2e-5)


def test_sliding_window_masks_old_positions():
    b, s, h, dh = 1, 32, 2, 8
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (b, s, h, dh))
    k = jax.random.normal(ks[1], (b, s, h, dh))
    v = jax.random.normal(ks[2], (b, s, h, dh))
    pos = jnp.broadcast_to(jnp.arange(s), (b, s)).astype(jnp.int32)
    out_win = attention(q, k, v, pos, window=4)
    # last query must equal attention computed over only its last 4 keys
    out_ref = attention(q[:, -1:], k[:, -4:], v[:, -4:], pos[:, -1:] - 28, causal=True)
    np.testing.assert_allclose(
        np.asarray(out_win[:, -1]), np.asarray(out_ref[:, 0]), rtol=2e-5, atol=2e-5
    )


def test_decode_step_consistent_with_prefill():
    """Greedy: prefill(prompt) last logits == decode path replaying tokens."""
    cfg = get_config("llama3-8b-smoke").replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    prompt = list(np.random.default_rng(0).integers(2, cfg.vocab_size, 9))
    logits_pf, _ = model.prefill(params, dict(inputs=jnp.asarray([prompt], jnp.int32)))

    cache = model.init_cache(1, 32)
    lg = None
    for t, tok in enumerate(prompt):
        lg, cache = model.decode(
            params, jnp.asarray([[tok]], jnp.int32), jnp.asarray([t], jnp.int32), cache
        )
    np.testing.assert_allclose(np.asarray(lg), np.asarray(logits_pf), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("starts", [(0, 32, 64, 96), (0, 10, 42, 74, 106)])
def test_chunked_prefill_writes_each_chunk_at_its_start(starts):
    """Chunks of 32 over a 120-token prompt in a 128-row cache give the same
    last-token logits as one pass over the whole prompt, wherever the chunks
    start: a last chunk at 106 runs past the cache's end (106 + 32 > 128),
    and its K/V must still land at rows 106-119."""
    from repro.models.transformer import chunk_prefill_step

    cfg = get_config("llama3-8b-smoke").replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    max_len, chunk, n = 128, 32, 120
    prompt = [int(t) for t in np.random.default_rng(1).integers(2, cfg.vocab_size, n)]
    step = jax.jit(chunk_prefill_step, static_argnums=(4,))

    def run(bounds, width):
        cache = model.init_cache(1, max_len)
        for a, b in itertools.pairwise(bounds):
            toks = prompt[a:b] + [0] * (width - (b - a))
            logits, cache = step(
                params, jnp.asarray([toks], jnp.int32), jnp.asarray([a], jnp.int32),
                jnp.asarray([b - a], jnp.int32), cfg, cache,
            )
        return np.asarray(logits)

    ref = run((0, n), max_len)
    got = run((*starts, n), chunk)
    np.testing.assert_allclose(got, ref, rtol=0, atol=1e-5)


@pytest.mark.parametrize("start", [0, 5, 96, 100, 120, 128])
def test_chunk_write_lands_at_start_and_keeps_other_rows(start):
    """Rows [start, start + rows) of the cache take the chunk's rows, up to
    the cache's end; every other row is the cache's own. Where the chunk
    fits, this is the plain ``dynamic_update_slice``, bit for bit."""
    from repro.models.transformer import _write_chunk

    m, rows = 128, 32
    rng = np.random.default_rng(start)
    cache = jnp.asarray(rng.standard_normal((m, 2, 4)), jnp.float32)
    new = jnp.asarray(rng.standard_normal((rows, 2, 4)), jnp.float32)
    got = np.asarray(jax.jit(partial(_write_chunk, rows=rows))(cache, new, jnp.int32(start)))
    want = np.asarray(cache).copy()
    n = min(rows, m - start)
    want[start:start + n] = np.asarray(new)[:n]
    np.testing.assert_array_equal(got, want)
    if start + rows <= m:
        plain = jax.lax.dynamic_update_slice(cache, new, (start, 0, 0))
        np.testing.assert_array_equal(got, np.asarray(plain))
