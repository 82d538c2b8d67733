"""Shared neural-net layers (pure JAX, functional params-as-pytrees).

Conventions
-----------
- Weights live in bf16 (cfg.dtype); norms/softmax run in fp32.
- Attention tensors are (batch, seq, heads, head_dim).
- Every layer is shape-polymorphic over batch/seq so the same code serves
  train (full seq), chunked prefill (chunk + cache) and decode (seq=1).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# Above this many query*key positions per head we switch to the blockwise
# (flash-style, lax.scan) attention path to avoid materializing S_q x S_kv.
_NAIVE_ATTN_LIMIT = 8192 * 8192
_KV_BLOCK = 1024
_Q_BLOCK = 512

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32, "float16": jnp.float16}[name]


# ----------------------------------------------------------------------------
# Norms
# ----------------------------------------------------------------------------

def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    orig = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(orig)


def gated_rms_norm(x: jax.Array, gate: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Mamba2-style RMSNorm(x * silu(gate))."""
    orig = x.dtype
    x = x.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(orig)


# ----------------------------------------------------------------------------
# Rotary position embeddings
# ----------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Apply RoPE. x: (..., seq, heads, head_dim); positions: (..., seq)."""
    head_dim = x.shape[-1]
    half = head_dim // 2
    freqs = jnp.exp(-math.log(theta) * (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, half)
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ----------------------------------------------------------------------------
# Attention
# ----------------------------------------------------------------------------

def softcap(x: jax.Array, cap: float) -> jax.Array:
    if cap and cap > 0:
        return cap * jnp.tanh(x / cap)
    return x


def _expand_kv(k: jax.Array, q_heads: int) -> jax.Array:
    """(B,S,Hkv,D) -> (B,S,Hq,D) by repeating each kv head q_per_kv times."""
    b, s, hkv, d = k.shape
    rep = q_heads // hkv
    if rep == 1:
        return k
    k = jnp.repeat(k, rep, axis=2)
    return k


def _window_active(window) -> bool:
    """True if a sliding window should be applied. `window` may be a python
    int (0/None => global) or a traced int32 scalar (always applied; callers
    pass a huge value for global layers, e.g. gemma2's alternating pattern
    inside a scan)."""
    if window is None:
        return False
    if isinstance(window, int):
        return window > 0
    return True  # traced value


def attention_mask(
    q_pos: jax.Array,  # (B, Sq) int32
    kv_len: int,
    kv_valid: Optional[jax.Array] = None,  # (B,) valid kv length
    window=0,
    causal: bool = True,
    q_seg: Optional[jax.Array] = None,  # (B, Sq) packed-segment ids
    kv_seg: Optional[jax.Array] = None,  # (B, Skv)
) -> jax.Array:
    """Boolean mask (B, Sq, Skv); True = attend."""
    kv_pos = jnp.arange(kv_len, dtype=jnp.int32)[None, None, :]
    qp = q_pos[:, :, None]
    mask = jnp.ones((q_pos.shape[0], q_pos.shape[1], kv_len), dtype=bool)
    if causal:
        mask &= kv_pos <= qp
    if _window_active(window):
        mask &= kv_pos > qp - window
    if kv_valid is not None:
        mask &= kv_pos < kv_valid[:, None, None]
    if q_seg is not None and kv_seg is not None:
        mask &= q_seg[:, :, None] == kv_seg[:, None, :]
    return mask


def naive_attention(
    q: jax.Array,  # (B, Sq, Hq, D)
    k: jax.Array,  # (B, Skv, Hkv, D)
    v: jax.Array,
    mask: jax.Array,  # (B, Sq, Skv) bool
    logit_cap: float = 0.0,
    scale: Optional[float] = None,
) -> jax.Array:
    """Grouped-head GQA attention: never materializes repeated K/V (a
    (B, Skv, Hq, D) repeat is GBs at decode shapes)."""
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = q.reshape(b, sq, hkv, g, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32)
    scores = softcap(scores * scale, logit_cap)
    scores = jnp.where(mask[:, None, None, :, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return out.reshape(b, sq, hq, d)


def attention_with_new_row(
    q: jax.Array,  # (B, 1, Hq, D)
    ck: jax.Array,  # (B, M, Hkv, D) the lane's cached rows
    cv: jax.Array,
    k: jax.Array,  # (B, 1, Hkv, D) the new token's own K/V, not in the cache
    v: jax.Array,
    mask: jax.Array,  # (B, M) bool: cached rows the query sees
    logit_cap: float = 0.0,
    scale: Optional[float] = None,
) -> jax.Array:
    """One query per lane over its cached rows and its own new K/V row, one
    softmax across both. The cache is only read, so the caller may write
    the new row after the step instead of into a view before it."""
    b, _, hq, d = q.shape
    m, hkv = ck.shape[1], ck.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = q.reshape(b, hkv, hq // hkv, d)
    cached = jnp.einsum("bhgd,bkhd->bhgk", qg, ck, preferred_element_type=jnp.float32)
    own = jnp.einsum("bhgd,bhd->bhg", qg, k[:, 0], preferred_element_type=jnp.float32)
    scores = softcap(jnp.concatenate([cached, own[..., None]], -1) * scale, logit_cap)
    seen = jnp.concatenate([mask, jnp.ones((b, 1), bool)], -1)
    probs = jax.nn.softmax(jnp.where(seen[:, None, None, :], scores, _NEG_INF), axis=-1)
    out = jnp.einsum(
        "bhgk,bkhd->bhgd", probs[..., :m].astype(cv.dtype), cv,
        preferred_element_type=jnp.float32,
    ) + probs[..., m:] * v[:, 0, :, None, :].astype(jnp.float32)
    return out.astype(v.dtype).reshape(b, 1, hq, d)


def blockwise_attention(
    q: jax.Array,  # (B, Sq, Hq, D)
    k: jax.Array,  # (B, Skv, Hkv, D)
    v: jax.Array,
    q_pos: jax.Array,  # (B, Sq)
    kv_valid: Optional[jax.Array],
    window,
    causal: bool,
    logit_cap: float,
    scale: Optional[float] = None,
    kv_block: int = _KV_BLOCK,
) -> jax.Array:
    """Flash-style exact attention: lax.scan over KV blocks, online softmax.

    Never materializes (Sq, Skv); memory per step is (B, H, Sq, kv_block).
    Wrapped in jax.checkpoint by callers for training so the backward pass
    recomputes block scores instead of saving them.
    """
    b, sq, hq, d = q.shape
    skv = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    nblk = -(-skv // kv_block)
    pad = nblk * kv_block - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = _expand_kv(k, hq).reshape(b, nblk, kv_block, hq, d).transpose(1, 0, 2, 3, 4)
    vb = _expand_kv(v, hq).reshape(b, nblk, kv_block, hq, d).transpose(1, 0, 2, 3, 4)

    kv_valid_eff = kv_valid if kv_valid is not None else jnp.full((b,), skv, jnp.int32)

    def step(carry, inputs):
        acc, m, l = carry  # (B,H,Sq,D) f32, (B,H,Sq), (B,H,Sq)
        blk_idx, kblk, vblk = inputs
        kv_pos = blk_idx * kv_block + jnp.arange(kv_block, dtype=jnp.int32)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, kblk, preferred_element_type=jnp.float32)
        scores = softcap(scores * scale, logit_cap)
        msk = kv_pos[None, None, :] < kv_valid_eff[:, None, None]  # (B,1,kblk)
        if causal:
            msk &= kv_pos[None, None, :] <= q_pos[:, :, None]
        if _window_active(window):
            msk &= kv_pos[None, None, :] > q_pos[:, :, None] - window
        scores = jnp.where(msk[:, None, :, :].transpose(0, 1, 2, 3), scores, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(vblk.dtype), vblk, preferred_element_type=jnp.float32
        )
        return (acc, m_new, l_new), None

    acc0 = jnp.zeros((b, hq, sq, d), jnp.float32)
    m0 = jnp.full((b, hq, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hq, sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        step, (acc0, m0, l0), (jnp.arange(nblk, dtype=jnp.int32), kb, vb)
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # (B,Sq,H,D)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_pos: jax.Array,
    kv_valid: Optional[jax.Array] = None,
    *,
    window=0,
    causal: bool = True,
    logit_cap: float = 0.0,
    scale: Optional[float] = None,
    q_seg: Optional[jax.Array] = None,
    kv_seg: Optional[jax.Array] = None,
    impl: str = "auto",
) -> jax.Array:
    """Dispatch between naive / blockwise / Pallas-kernel attention.

    impl="pallas" routes to the flash kernels, compiled on an accelerator
    and interpreted only on the CPU (`repro.kernels.interpret_default`): a
    single query token (decode) goes to the flash-decode kernel, anything
    longer to the chunked-prefill kernel. Only the kernel-supported case
    qualifies — causal, no window, no packed segments — otherwise falls
    through to the jnp paths. Packed-segment masks force the naive path
    (segments only occur in the CPU engine where sequences are short).
    """
    sq, skv = q.shape[1], k.shape[1]
    if (
        impl == "pallas"
        and q_seg is None
        and causal
        and not _window_active(window)
    ):
        kv_valid_eff = (
            kv_valid if kv_valid is not None
            else jnp.full((q.shape[0],), skv, jnp.int32)
        )
        if sq == 1:
            # one token at position p attends to [0, p]: with the cache's
            # valid length p + 1 (what decode passes) causality is the
            # length mask the decode kernel applies
            from repro.kernels.decode_attention.ops import decode_attention

            kv_len = jnp.minimum(kv_valid_eff, q_pos[:, 0] + 1)
            out = decode_attention(
                q[:, 0], k, v, kv_len, scale=scale, logit_cap=logit_cap
            )
            return out[:, None]
        from repro.kernels.prefill_attention.ops import prefill_attention

        return prefill_attention(
            q, k, v, q_pos, kv_valid_eff, scale=scale, logit_cap=logit_cap
        )
    use_blockwise = impl == "blockwise" or (
        impl in ("auto", "pallas") and q_seg is None and sq * skv > _NAIVE_ATTN_LIMIT
    )
    if use_blockwise:
        return blockwise_attention(
            q, k, v, q_pos, kv_valid, window, causal, logit_cap, scale
        )
    mask = attention_mask(q_pos, skv, kv_valid, window, causal, q_seg, kv_seg)
    return naive_attention(q, k, v, mask, logit_cap, scale)


# ----------------------------------------------------------------------------
# MLP
# ----------------------------------------------------------------------------

def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": partial(jax.nn.gelu, approximate=True)}[name]


def gated_mlp(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array, act: str) -> jax.Array:
    g = act_fn(act)(jnp.einsum("...d,df->...f", x, w_gate))
    u = jnp.einsum("...d,df->...f", x, w_up)
    return jnp.einsum("...f,fd->...d", g * u, w_down)


# ----------------------------------------------------------------------------
# Initializers
# ----------------------------------------------------------------------------

def dense_init(key, shape, dtype, in_axis: int = 0) -> jax.Array:
    fan_in = shape[in_axis]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)
