"""The check's control, at a size a CPU test run holds: the reference put in
the program's place in int8 (weights per output column, activations per
token), its tokens in the served tokens' place, must come out not correct
through the same comparison on every seed, while the program passes.

On the chip the same readings come from ``calibrate.py control`` at each
cell's own size; PERF.md gives them and the limits set from them. Here the
fixture ``small.tiny-mix`` (8 layers, width 256, vocabulary 4096) read, over
seeds 1-6 on the CPU, a mean served-token gap of at most 0.00014 for the
program and at least 0.00074 for the control; its limit is 0.0004.
"""
import jax
import pytest

import harness
from conftest import HERE

FIXTURES = HERE / "fixtures"


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    jax.config.update("jax_enable_compilation_cache", False)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_where_the_program_passes(seed):
    c = harness.load_cell(FIXTURES, "small.tiny-mix")
    limit = c.cell["check"]["limits"]["mean_served_gap"]
    got = harness.control_readings(c, seed, 3.0, jax.devices()[0])
    assert got["tokens"] >= 100, got
    assert got["program_correct"] is True and got["control_correct"] is False, got
    gap = "mean_served_gap"
    assert got["program"][gap]["value"] < limit < got["control"][gap]["value"], got
