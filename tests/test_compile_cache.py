"""The launchers' persistent compilation cache: where it lives, and that
entries land there."""
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.launch.compile_cache import CACHE_DIR, enable_compile_cache

_ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_secs)


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert CACHE_DIR == _ROOT / ".jax_cache"
    assert enable_compile_cache() == str(CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(CACHE_DIR)
    ignored = (_ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_env_dir_wins_and_nothing_else_is_set(monkeypatch, restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert enable_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before
    # every program is kept, not only those that took a second to compile
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_launchers_enable_it_in_main_not_at_import():
    src = (_ROOT / "src" / "repro" / "launch")
    for path in (src / "serve.py", src / "loadgen.py", src / "evaluate.py",
                 _ROOT / "benchmarks" / "run.py", _ROOT / "chip_smoke.py"):
        text = path.read_text()
        main = text[text.index("def main("):]
        assert "enable_compile_cache()" in main.split("\ndef ")[0], path
        assert text.count("enable_compile_cache()") == 1, path


def test_entries_land_in_the_env_dir(tmp_path):
    """A program that compiles in milliseconds is kept too: JAX's default
    one-second threshold would keep nothing here."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "enable_compile_cache()\n"
        "jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()\n"
    )
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path),
        PYTHONPATH=str(_ROOT / "src") + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert any(tmp_path.iterdir())
