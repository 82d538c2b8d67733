"""Production mesh construction.

Importing this module never touches jax device state; meshes are built by
functions only. Single pod: 16x16 = 256 chips ('data' x 'model'); multi-pod:
2 x 16 x 16 = 512 chips ('pod' x 'data' x 'model') — 'pod' is the DCN axis.
"""
from __future__ import annotations

import os
from typing import Optional, Tuple

import jax


def ensure_host_platform_devices(n: int = 512) -> None:
    """Expose `n` host platform devices to XLA (production-mesh dry-runs on
    CPU). Must run before jax's backend initializes — i.e. before the first
    device query, NOT before `import jax` (backends are created lazily), so
    CLI mains call this as their first statement and module tops stay
    import-only (ruff E402)."""
    if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + f" --xla_force_host_platform_device_count={n}"
        ).strip()


def _mk(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Elastic variant: arbitrary shapes (degraded device counts, smoke)."""
    return _mk(shape, axes)


def make_host_mesh():
    """Whatever devices exist, one axis each of data/model (CPU tests)."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))
