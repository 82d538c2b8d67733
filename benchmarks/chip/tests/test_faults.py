"""A run whose timed path is broken underneath must come out not correct.

Each fault is planted in the program's serving path (never in the
benchmark), and the rest of a run is driven as on the chip, at the fixture
cell's size on the CPU: a served token altered where the decode step
produces it; a decode step that returns its cache unchanged (the new
token's KV dropped: the cache as it was before the call, since the step
consumes the one it is given); a chunk-prefill step that returns its cache
unchanged (the chunk's KV never reaches the cache). Each fault is run in
the three fixture cells: ``tiny.tiny-mix`` and ``moe-tiny.tiny-mix`` (an
architecture that exists only as fixture files) compare the widest gap,
``small.tiny-mix`` the mean gap, as the chip's cells do, and the number
each cell compares must read over its limit."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from conftest import HERE

FIXTURES = HERE / "fixtures"
CELLS = ["tiny.tiny-mix", "small.tiny-mix", "moe-tiny.tiny-mix"]


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    jax.config.update("jax_enable_compilation_cache", False)


def _altered_token(monkeypatch):
    from repro.serving.engine import DecodeEngine

    step = DecodeEngine.step

    def bad(self, batch, key):
        toks = np.array(step(self, batch, key))
        toks[0] = (toks[0] + 1) % self.model.cfg.vocab_size
        return toks

    monkeypatch.setattr(DecodeEngine, "step", bad)


def _state_unchanged(name):
    def plant(monkeypatch):
        from repro.serving import engine

        good = getattr(engine, name)
        cache_arg = 3 if name == "_slot_step" else 5

        @functools.wraps(good)
        def bad(*args):
            before = jax.tree.map(jnp.copy, args[cache_arg])
            logits, _ = good(*args)
            return logits, before

        monkeypatch.setattr(engine, name, bad)

    return plant


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("plant", [
    _altered_token, _state_unchanged("_slot_step"), _state_unchanged("_chunk_step"),
], ids=["token-altered", "decode-state-unchanged", "prefill-state-unchanged"])
def test_fault_is_not_correct(monkeypatch, plant, cell):
    plant(monkeypatch)
    lines = []
    res = harness.run_cell(FIXTURES, cell, 2**31 + 21, 2.0, False, jax.devices()[0],
                           log=lines.append)
    assert res["correct"] is False, res["checks"]
    for name in harness.load_cell(FIXTURES, cell).cell["check"]["limits"]:
        assert res["checks"][name]["value"] > res["checks"][name]["limit"], (name, res["checks"])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = harness.run_cell(FIXTURES, cell, 2**31 + 21, 2.0, False, jax.devices()[0],
                           log=lambda _m: None)
    assert res["correct"] is True, res["checks"]
