"""The work a serving step needs, counted from shapes, and the chip's peaks.

Counts are of what the algorithm needs, whatever implements the step:
weights read once per step; the KV of the live context of real lanes read
once and each new token's KV written once; for prefill, valid tokens only;
no pad lanes, pad tokens or full-``max_len`` copies. FLOPs count the
multiply-adds of the projections, the attention scores and values over the
live context, and the head for the rows whose logits the step returns.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Sequence, Tuple

from modelspec import Spec

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """``{"flops": FLOP/s, "bytes": B/s}`` of one chip; unknown kinds fail."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    row = table[device_kind]
    return {"flops": float(row["flops_bf16"]), "bytes": float(row["hbm_bytes_per_s"])}


def itemsize(s: Spec) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[s.dtype]


def layer_params(s: Spec) -> int:
    """Projection weights of one layer (norm gains apart)."""
    return 2 * s.d * s.q_dim + 2 * s.d * s.kv_dim + 3 * s.d * s.ffn


def weight_bytes(s: Spec) -> int:
    """Every weight one step reads: layers, norms and the head."""
    per_layer = layer_params(s) + 2 * s.d
    head = s.vocab * s.d
    return itemsize(s) * (s.layers * per_layer + head + s.d)


def kv_bytes_per_token(s: Spec) -> int:
    return 2 * s.layers * s.kv_dim * itemsize(s)


def _attn_flops(s: Spec, positions: Sequence[int]) -> int:
    """Scores and values of queries at `positions` over [0, position]."""
    return 4 * s.layers * s.heads * s.head_dim * sum(p + 1 for p in positions)


def chunk(s: Spec, start: int, valid: int) -> Tuple[int, int]:
    """(FLOPs, bytes) of one chunk-prefill call: `valid` tokens after
    `start` already in the cache; logits for the last token only."""
    flops = 2 * valid * s.layers * layer_params(s) + 2 * s.d * s.vocab
    flops += _attn_flops(s, range(start, start + valid))
    kv = kv_bytes_per_token(s)
    byts = weight_bytes(s) + start * kv + valid * kv
    byts += valid * 4 + valid * s.d * itemsize(s) + s.vocab * 4
    return flops, byts


def decode(s: Spec, positions: Sequence[int]) -> Tuple[int, int]:
    """(FLOPs, bytes) of one decode step over the real lanes, each writing
    the token at `positions[i]` and attending to [0, positions[i]]."""
    n = len(positions)
    flops = 2 * n * (s.layers * layer_params(s) + s.d * s.vocab)
    flops += _attn_flops(s, positions)
    kv = kv_bytes_per_token(s)
    byts = weight_bytes(s) + sum(positions) * kv + n * kv
    byts += n * 8 + n * s.d * itemsize(s) + n * s.vocab * 4
    return flops, byts


def least_time(flops: float, byts: float, peak: Dict[str, float]) -> Tuple[float, str]:
    """The roofline's least time for the work, and which bound sets it."""
    tc, tb = flops / peak["flops"], byts / peak["bytes"]
    return (tc, "compute") if tc >= tb else (tb, "memory")
