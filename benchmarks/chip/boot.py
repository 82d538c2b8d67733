"""Start-up shared by the entry points: where the checkout is, the program's
sources on the path, and JAX's compilation cache inside the checkout.
Importing this module does not import JAX."""
from __future__ import annotations

import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def paths() -> bool:
    """Put the benchmark and the program's sources on the path; False where
    the program's sources are not in the checkout."""
    if not (ROOT / "src" / "repro").is_dir():
        return False
    for p in (str(ROOT / "src"), str(HERE)):
        if p not in sys.path:
            sys.path.insert(0, p)
    return True


def compile_cache(cache: Path = ROOT / ".jax_cache") -> str:
    """Keep every compiled program in `cache`, by default ``.jax_cache/`` at
    the checkout's root (a fixed path: a directory that moves never hits)."""
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return str(cache)
