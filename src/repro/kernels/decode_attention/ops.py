"""Drop-in wrapper: (B, Hq, Dh) query and (B, S, Hkv, Dh) cache -> the
grouped, head-major kernel layout, with the sequence padded to whole
blocks (interpret on CPU, compiled on TPU)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import interpret_default
from repro.kernels.decode_attention.kernel import flash_decode_attention


def decode_attention(
    q: jax.Array,  # (B, Hq, Dh)
    k: jax.Array,  # (B, S, Hkv, Dh)
    v: jax.Array,
    kv_len: jax.Array,  # (B,)
    *,
    scale=None,
    logit_cap: float = 0.0,
    block_k: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    b, hq, dh = q.shape
    s, hkv = k.shape[1], k.shape[2]
    qpk = hq // hkv
    qg = q.reshape(b, hkv, qpk, dh)
    bk = s if s <= block_k else block_k
    pad = (-s) % bk
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    if pad:
        widths = ((0, 0), (0, 0), (0, pad), (0, 0))
        kh = jnp.pad(kh, widths)
        vh = jnp.pad(vh, widths)
    out = flash_decode_attention(
        qg, kh, vh, kv_len, scale=scale, logit_cap=logit_cap, block_k=bk,
        interpret=interpret_default() if interpret is None else interpret,
    )
    return out.reshape(b, hq, dh)
