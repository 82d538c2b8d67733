"""The chip's peaks and the roofline's least time for a step's work.

What a step needs is counted from shapes by the configuration's
architecture module (``arch.chunk_work(spec, start, valid)`` and
``arch.decode_work(spec, positions)``: FLOPs and bytes), of what the
algorithm needs, whatever implements the step: weights read once per step;
the cached state of the live context of real lanes read once and each new
token's state written once; for prefill, valid tokens only; no pad lanes,
pad tokens or full-``max_len`` copies. FLOPs count the multiply-adds of the
projections, the attention scores and values over the live context, and
the head for the rows whose logits the step returns.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Tuple

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """``{"flops": FLOP/s, "bytes": B/s}`` of one chip; unknown kinds fail."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    row = table[device_kind]
    return {"flops": float(row["flops_bf16"]), "bytes": float(row["hbm_bytes_per_s"])}


def itemsize(s) -> int:
    """Bytes of one value in the configuration's dtype."""
    return {"bfloat16": 2, "float16": 2, "float32": 4}[s.dtype]


def least_time(flops: float, byts: float, peak: Dict[str, float]) -> Tuple[float, str]:
    """The roofline's least time for the work, and which bound sets it."""
    tc, tb = flops / peak["flops"], byts / peak["bytes"]
    return (tc, "compute") if tc >= tb else (tb, "memory")
