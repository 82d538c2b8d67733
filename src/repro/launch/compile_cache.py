"""Persistent XLA compilation cache for the launchers.

Every launcher's ``main()`` calls `enable_compile_cache()` first (never at
import). A compiled program is then written to disk and read back by the
next process that compiles it on the same kind of device, so a second run
of a full-width model skips its compiles.

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads that directory itself, and
  nothing here sets another.
* otherwise: ``.jax_cache/`` at the root of the checkout (listed in
  ``.gitignore``). The path is fixed on purpose: a temporary or per-run
  directory would never be found again by the next run.

Every program is kept, however fast it compiled. JAX's default keeps only
programs that took a second or more, and minicpm-2b's step programs compile
in about that long on a TPU v5e, so whether a run found them depended on
how long they had happened to take the run before.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
