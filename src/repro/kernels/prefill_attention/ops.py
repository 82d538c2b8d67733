"""Drop-in wrapper: (B, S, H, D) attention layout -> the head-major kernel,
padding the sequence dims to whole blocks (interpret on CPU, compiled on
TPU)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import interpret_default
from repro.kernels.prefill_attention.kernel import flash_prefill_attention


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def prefill_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_pos: jax.Array,
    kv_len: jax.Array,
    *,
    scale=None,
    logit_cap: float = 0.0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Drop-in attention: (B,Sq,Hq,Dh) x (B,Skv,Hkv,Dh) -> (B,Sq,Hq,Dh).

    A sequence no longer than its block is taken whole (a block equal to
    the array dimension always tiles); longer ones are padded to whole
    blocks — padded kv is masked via kv_len, padded q rows are sliced off.
    """
    sq, skv = q.shape[1], k.shape[1]
    bq = sq if sq <= block_q else block_q
    bk = skv if skv <= block_k else block_k
    qh = _pad_to(q.transpose(0, 2, 1, 3), 2, bq)
    kh = _pad_to(k.transpose(0, 2, 1, 3), 2, bk)
    vh = _pad_to(v.transpose(0, 2, 1, 3), 2, bk)
    pos = _pad_to(q_pos.astype(jnp.int32), 1, bq)
    out = flash_prefill_attention(
        qh, kh, vh, pos, kv_len,
        scale=scale, logit_cap=logit_cap, block_q=bq, block_k=bk,
        interpret=interpret_default() if interpret is None else interpret,
    )
    return out[:, :, :sq].transpose(0, 2, 1, 3)
