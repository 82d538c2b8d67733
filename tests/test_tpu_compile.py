"""Compile for a described TPU v5e, with no chip attached.

The TPU compiler ships with the installed JAX, so it can refuse here what a
chip would refuse: a block shape that does not tile, a program that does
not fit HBM. Each test compiles from shapes only — nothing is allocated,
nothing runs. The topology is described inside a fixture (never while a
module is imported), so every test worker collects the same tests and only
the worker given this file loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.kernels.decode_attention import ops as decode_ops
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.prefill_attention import ops as prefill_ops
from repro.kernels.prefill_attention.ops import prefill_attention
from repro.launch.sizing import tree_bytes
from repro.models import build_model
from repro.models.model import cache_struct
from repro.serving.engine import EngineConfig, lower_steps

# (q heads, kv heads, head_dim) at published widths
HEADS = {"minicpm-2b": (36, 36, 64), "llama3-8b": (32, 8, 128)}
# the size chip_smoke.py serves minicpm-2b at on one v5e
SMOKE_SIZE = dict(max_slots=4, max_len=1024, chunk_size=256)
V5E_HBM = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described-device compile cannot be read back without the chip: keep
    # it out of any persistent cache an earlier test turned on
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("arch", sorted(HEADS))
@pytest.mark.parametrize("b,sq,skv", [(1, 256, 1024), (4, 1, 1024), (2, 100, 300)])
def test_prefill_kernel_compiles(one_chip, arch, b, sq, skv):
    hq, hkv, dh = HEADS[arch]
    s = lambda *shape: _spec(one_chip, shape)  # noqa: E731
    i = lambda *shape: _spec(one_chip, shape, jnp.int32)  # noqa: E731
    f = jax.jit(lambda q, k, v, p, n: prefill_attention(q, k, v, p, n, interpret=False))
    text = f.lower(
        s(b, sq, hq, dh), s(b, skv, hkv, dh), s(b, skv, hkv, dh), i(b, sq), i(b)
    ).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("arch", sorted(HEADS))
@pytest.mark.parametrize("b,s", [(4, 1024), (1, 300)])
def test_decode_kernel_compiles(one_chip, arch, b, s):
    hq, hkv, dh = HEADS[arch]
    kv = _spec(one_chip, (b, s, hkv, dh))
    f = jax.jit(lambda q, k, v, n: decode_attention(q, k, v, n, interpret=False))
    text = f.lower(
        _spec(one_chip, (b, hq, dh)), kv, kv, _spec(one_chip, (b,), jnp.int32)
    ).compile().as_text()
    assert "tpu_custom_call" in text


def _footprint(m) -> int:
    """Device bytes of one compiled step: the cache it aliases counts once."""
    return (
        m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
        - m.alias_size_in_bytes
    )


def test_engine_steps_compile_at_full_width(topo):
    """minicpm-2b's decode and chunk-prefill steps at the smoke's size fit
    one v5e, and so does the decode step at eight slots (the next size up)
    now that it updates its cache in place instead of holding two."""
    cfg = get_config("minicpm-2b")
    model = build_model(cfg)
    mem = {
        k: low.compile().memory_analysis()
        for k, low in lower_steps(model, EngineConfig(**SMOKE_SIZE), topo.devices[0]).items()
    }
    params = tree_bytes(model.param_struct())
    cache = tree_bytes(cache_struct(cfg, SMOKE_SIZE["max_slots"] + 1, SMOKE_SIZE["max_len"]))
    assert mem["decode"].argument_size_in_bytes >= params + cache
    for m in mem.values():
        assert _footprint(m) < V5E_HBM
    wide = lower_steps(model, EngineConfig(**dict(SMOKE_SIZE, max_slots=8)), topo.devices[0])
    assert _footprint(wide["decode"].compile().memory_analysis()) < V5E_HBM


# The benchmark's two decode cells, built here (tier-1 imports nothing of
# benchmarks/): minicpm-2b at 4 x 1024, and Mistral-7B-v0.3's widths cut to
# 16 layers at 8 x 2048. Their caches' minor dims (36 x 64 and 8 x 128)
# tile differently on the chip, and a batched scatter of the new rows, or
# rows written into a cache carried through the layer scan, relayouts the
# whole 64-wide cache.
DECODE_CELLS = {
    "minicpm-2b": (get_config("minicpm-2b"), dict(max_slots=4, max_len=1024)),
    "mistral-7b-v0.3-16l": (
        ModelConfig(
            name="mistral-7b-v0.3-16l", family="dense", num_layers=16, d_model=4096,
            num_heads=32, num_kv_heads=8, head_dim=128, d_ff=14336, vocab_size=32768,
            rope_theta=1e6, norm_eps=1e-5, tie_embeddings=False,
        ),
        dict(max_slots=8, max_len=2048),
    ),
}


@pytest.mark.parametrize("cell", sorted(DECODE_CELLS))
def test_decode_step_updates_the_cache_in_place(topo, cell):
    """The compiled decode step aliases its whole cache to its output and
    copies no cache-sized array: the new rows are written in place."""
    cfg, size = DECODE_CELLS[cell]
    low = lower_steps(build_model(cfg), EngineConfig(chunk_size=256, **size), topo.devices[0])
    compiled = low["decode"].compile()
    cache = cache_struct(cfg, size["max_slots"] + 1, size["max_len"])
    assert compiled.memory_analysis().alias_size_in_bytes >= tree_bytes(cache)
    shapes = {",".join(map(str, x.shape)) for x in cache.values()}
    copies = [
        line for line in compiled.as_text().splitlines()
        if re.search(r"= \w+\[(" + "|".join(shapes) + r")\]\{[^}]*\} copy\(", line)
    ]
    assert not copies, copies[:2]


def test_engine_steps_compile_with_pallas_kernels(topo, monkeypatch):
    """The Pallas path of both steps at full width: the kernels compile
    inside the 40-layer programs. The backend here is the CPU, so the test
    turns interpretation off itself, as the chip's backend would."""
    for mod in (prefill_ops, decode_ops):
        monkeypatch.setattr(mod, "interpret_default", lambda: False)
    model = build_model(get_config("minicpm-2b").replace(attn_impl="pallas"))
    for low in lower_steps(model, EngineConfig(**SMOKE_SIZE), topo.devices[0]).values():
        assert "tpu_custom_call" in low.compile().as_text()
