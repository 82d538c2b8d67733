"""Pallas TPU kernels for the serving hot path (attention) and the SSD scan.

Each kernel package holds ``kernel.py`` (the ``pallas_call``), ``ops.py``
(the drop-in wrapper callers use) and ``ref.py`` (a pure-jnp oracle).
"""
from __future__ import annotations

import jax


def interpret_default() -> bool:
    """Pallas interpret mode runs only where the backend is the CPU: on an
    accelerator the kernel is always compiled, never silently interpreted."""
    return jax.default_backend() == "cpu"
