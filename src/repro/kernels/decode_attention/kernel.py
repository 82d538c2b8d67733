"""Pallas TPU kernel: GQA flash decode (single-token attention over a KV cache).

The decode instance's hot loop and the quantity the paper's LUT models: one
query token per sequence reads its whole KV prefix. Memory-bound — the
kernel streams KV blocks HBM->VMEM once, computing the online softmax for
the q_per_kv query-head group of each KV head (a (qpk, dh) x (dh, bk)
matmul per block).

Layout is head-major: K/V are (B, Hkv, S, D), so a KV block's last two
dimensions are (bk, D) and the query group's are (qpk, D) — both legal TPU
tiles (a multiple of 8 or the whole dimension, then the whole head_dim).
The per-row valid length is scalar-prefetched into SMEM; blocks past it
are neither computed nor fetched.

Grid: (batch, kv_heads, kv_blocks), kv innermost with VMEM carry.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _decode_kernel(
    kvlen_ref,  # (B,) i32 in SMEM (scalar prefetch)
    q_ref,  # (1, 1, qpk, dh)
    k_ref,  # (1, 1, bk, dh)
    v_ref,  # (1, 1, bk, dh)
    o_ref,  # (1, 1, qpk, dh)
    acc_ref,  # (qpk, dh) f32
    m_ref,  # (qpk, 1) f32
    l_ref,  # (qpk, 1) f32
    *,
    scale: float,
    bk: int,
    logit_cap: float,
):
    ib = pl.program_id(0)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)
    kv_len = kvlen_ref[ib]

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(ik * bk < kv_len)
    def _block():
        q = q_ref[0, 0]  # (qpk, dh)
        k = k_ref[0, 0]  # (bk, dh)
        v = v_ref[0, 0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (qpk, bk)
        if logit_cap > 0.0:
            s = logit_cap * jnp.tanh(s / logit_cap)

        kvp = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kvp < kv_len, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_decode_attention(
    q: jax.Array,  # (B, Hkv, qpk, Dh) — grouped by KV head
    k: jax.Array,  # (B, Hkv, S, Dh)
    v: jax.Array,
    kv_len: jax.Array,  # (B,)
    *,
    scale: float | None = None,
    logit_cap: float = 0.0,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    b, hkv, qpk, dh = q.shape
    s = k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    bk = min(block_k, s)
    assert s % bk == 0, (s, bk)
    grid = (b, hkv, s // bk)

    def kv_map(ib, ih, ik, kvlen_ref):
        last = jnp.maximum(kvlen_ref[ib] - 1, 0) // bk
        return ib, ih, jnp.minimum(ik, last), 0

    kernel = functools.partial(_decode_kernel, scale=scale, bk=bk, logit_cap=logit_cap)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, qpk, dh), lambda ib, ih, ik, kl: (ib, ih, 0, 0)),
                pl.BlockSpec((1, 1, bk, dh), kv_map),
                pl.BlockSpec((1, 1, bk, dh), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, qpk, dh), lambda ib, ih, ik, kl: (ib, ih, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((qpk, dh), jnp.float32),
                pltpu.VMEM((qpk, 1), jnp.float32),
                pltpu.VMEM((qpk, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, qpk, dh), q.dtype),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), q, k, v)
