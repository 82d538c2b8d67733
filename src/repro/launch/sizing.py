"""Pick the engine size a model is served at on one device.

`choose_size` tries ``(max_slots, max_len)`` candidates widest first and
returns the first whose compiled steps fit the device beside what the
engine keeps resident. Used by ``launch/serve.py`` and ``chip_smoke.py``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax

from repro.models.model import Model, cache_struct
from repro.serving.engine import EngineConfig, lower_steps

# (max_slots, max_len), widest first
SIZES: Tuple[Tuple[int, int], ...] = ((8, 1024), (4, 1024), (2, 1024))
CHUNK = 256


def tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _footprint(stats) -> int:
    return (
        stats.argument_size_in_bytes + stats.output_size_in_bytes
        + stats.temp_size_in_bytes - stats.alias_size_in_bytes
    )


def device_limit(device) -> Optional[int]:
    """The device's memory limit in bytes; None where it reports none (the
    CPU)."""
    return (device.memory_stats() or {}).get("bytes_limit")


def choose_size(
    model: Model,
    n_requests: int,
    seq_len: int,
    device,
    sizes: Sequence[Tuple[int, int]] = SIZES,
    chunk: int = CHUNK,
) -> Tuple[int, int, Dict[str, Any]]:
    """The widest ``(max_slots, max_len)`` in `sizes` that holds `seq_len`
    tokens per request and fits `device`'s memory. Returns ``(max_slots,
    max_len, report)``; raises ValueError when none fits.

    Each step is compiled from shapes and its `memory_analysis()` read
    (arguments + outputs + temporaries, less the bytes an output aliases:
    the decode step updates its donated cache in place, so the cache counts
    once; the chunk step donates nothing). Beside the step sits what the engine
    keeps resident: the decode step runs while up to n-1 other requests
    hold a (1, max_len) prefill cache; a chunk step runs while the decode
    cache and n-1 other prefill caches are held. A device that reports no
    limit (the CPU) takes the widest size that holds `seq_len`.
    """
    cfg = model.cfg
    bytes_limit = device_limit(device)
    for slots, max_len in sizes:
        if max_len < seq_len + 1:
            continue
        report: Dict[str, Any] = dict(
            max_slots=slots, max_len=max_len, chunk=chunk, bytes_limit=bytes_limit
        )
        if bytes_limit is None:
            return slots, max_len, report
        ecfg = EngineConfig(max_slots=slots, max_len=max_len, chunk_size=chunk)
        lows = lower_steps(model, ecfg, device)
        step = {k: _footprint(low.compile().memory_analysis()) for k, low in lows.items()}
        prefill_cache = tree_bytes(cache_struct(cfg, 1, max_len))
        decode_cache = tree_bytes(cache_struct(cfg, slots + 1, max_len))
        others = (n_requests - 1) * prefill_cache
        report.update(
            need_bytes=max(step["decode"] + others, step["chunk"] + decode_cache + others),
            decode_step_bytes=step["decode"], chunk_step_bytes=step["chunk"],
            prefill_cache_bytes=prefill_cache, decode_cache_bytes=decode_cache,
        )
        if report["need_bytes"] <= bytes_limit:
            return slots, max_len, report
    raise ValueError(
        f"no size in {list(sizes)} holds {seq_len} tokens and fits "
        f"{bytes_limit} B for {cfg.name}"
    )


def describe(report: Dict[str, Any], n_requests: int) -> str:
    """One line saying which size was chosen and why."""
    head = (
        f"size {report['max_slots']} slots x max_len {report['max_len']}, "
        f"chunk {report['chunk']}"
    )
    if report.get("need_bytes") is None:
        return f"{head}: the widest candidate; the device reports no memory limit"
    return (
        f"{head}: the widest candidate whose compiled peak fits: decode step "
        f"{report['decode_step_bytes']} B, or chunk step {report['chunk_step_bytes']} B "
        f"+ decode cache {report['decode_cache_bytes']} B, beside {n_requests - 1} live "
        f"prefill caches of {report['prefill_cache_bytes']} B -> {report['need_bytes']} B "
        f"<= limit {report['bytes_limit']} B"
    )
