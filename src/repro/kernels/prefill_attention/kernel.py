"""Pallas TPU kernel: chunked-prefill causal flash attention with GQA.

The prefill instance's hot loop: a chunk of queries (at context offset
`q_pos`) attends to the KV cache prefix `[0, kv_len)`. Online softmax over
KV blocks keeps VMEM at O(block) — never materializing (Sq, Skv).

Layout is head-major, (B, H, S, D), so every block's last two dimensions
are (sequence block, head_dim): the TPU compiler requires those to be
multiples of (8, 128) or the whole array dimension, and head_dim is always
taken whole (64 and 128 both compile). Query positions ride along as a
(B, Sq, 1) column block; the per-row valid KV length is scalar-prefetched
into SMEM, which also lets the kernel skip (and not DMA) KV blocks past it.

Grid: (batch, q_heads, q_blocks, kv_blocks); kv innermost so the f32
accumulator scratch carries across KV steps. GQA maps query head h to KV
head h // (Hq // Hkv) in the K/V BlockSpec index maps.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _flash_kernel(
    kvlen_ref,  # (B,) i32 in SMEM (scalar prefetch) — valid KV prefix per row
    qpos_ref,  # (1, bq, 1) i32 — absolute positions of this q block
    q_ref,  # (1, 1, bq, dh)
    k_ref,  # (1, 1, bk, dh)
    v_ref,  # (1, 1, bk, dh)
    o_ref,  # (1, 1, bq, dh)
    acc_ref,  # (bq, dh) f32 scratch
    m_ref,  # (bq, 1) f32 scratch
    l_ref,  # (bq, 1) f32 scratch
    *,
    scale: float,
    bk: int,
    logit_cap: float,
):
    ib = pl.program_id(0)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)
    kv_len = kvlen_ref[ib]

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # blocks wholly past the valid prefix contribute exactly nothing
    @pl.when(ik * bk < kv_len)
    def _block():
        q = q_ref[0, 0]  # (bq, dh)
        k = k_ref[0, 0]  # (bk, dh)
        v = v_ref[0, 0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        s = s * scale
        if logit_cap > 0.0:
            s = logit_cap * jnp.tanh(s / logit_cap)

        qp = qpos_ref[0]  # (bq, 1)
        kvp = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = (kvp <= qp) & (kvp < kv_len)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)  # (bq, bk)
        corr = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def flash_prefill_attention(
    q: jax.Array,  # (B, Hq, Sq, Dh)
    k: jax.Array,  # (B, Hkv, Skv, Dh)
    v: jax.Array,
    q_pos: jax.Array,  # (B, Sq) i32 absolute positions
    kv_len: jax.Array,  # (B,) i32 valid prefix
    *,
    scale: float | None = None,
    logit_cap: float = 0.0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Head-major flash attention; returns (B, Hq, Sq, Dh). `ops.py` holds
    the (B, S, H, D) drop-in wrapper the model calls."""
    b, hq, sq, dh = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    qpk = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)
    grid = (b, hq, sq // bq, skv // bk)

    def kv_map(ib, ih, iq, ik, kvlen_ref):
        # clamp to the last needed block: a repeated block index is not
        # re-fetched, so skipped blocks cost no HBM traffic either
        last = jnp.maximum(kvlen_ref[ib] - 1, 0) // bk
        return ib, ih // qpk, jnp.minimum(ik, last), 0

    kernel = functools.partial(_flash_kernel, scale=scale, bk=bk, logit_cap=logit_cap)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, 1), lambda ib, ih, iq, ik, kl: (ib, iq, 0)),
                pl.BlockSpec((1, 1, bq, dh), lambda ib, ih, iq, ik, kl: (ib, ih, iq, 0)),
                pl.BlockSpec((1, 1, bk, dh), kv_map),
                pl.BlockSpec((1, 1, bk, dh), kv_map),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, bq, dh), lambda ib, ih, iq, ik, kl: (ib, ih, iq, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((bq, dh), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, dh), q.dtype),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), q_pos.astype(jnp.int32)[:, :, None], q, k, v)
