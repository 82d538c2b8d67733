"""The trace reduction, on interval arithmetic and on a small trace recorded
on a TPU v5e (the fixture cell, traced for its window's middle seconds)."""
import pytest

import xplane
from conftest import BENCH

TRACE = BENCH / "testdata" / "tiny.xplane.pb"
SPANS = ("session.step", "prefill_sched.select", "prefill.run_chunk", "decode.admit",
         "decode_sched.select", "decode.step", "driver.submit", "driver.idle")


def test_union_clip_complement():
    u = xplane.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5), (6, 7)])
    assert u == [(0, 2.5), (3, 4), (6, 7)]
    assert xplane.clip(u, 1, 6.5) == [(1, 2.5), (3, 4), (6, 6.5)]
    assert xplane.complement(xplane.clip(u, 1, 6.5), 1, 8) == [(2.5, 3), (4, 6), (6.5, 8)]


def test_gap_takes_the_innermost_host_span():
    spans = {"session.step": [(0.0, 10.0)], "decode.step": [(2.0, 3.0)]}
    assert xplane._label(spans, 2.5) == "decode.step"
    assert xplane._label(spans, 5.0) == "session.step"
    assert xplane._label(spans, 11.0) == "host:outside-spans"


def test_names():
    assert xplane.module_name("jit__slot_step(1234)") == "jit__slot_step"
    assert xplane.op_name("%fusion.86 = s32[64]{0} fusion(...)") == "fusion.86"


@pytest.fixture(scope="module")
def red():
    r = xplane.reduce(str(TRACE), SPANS)
    assert r is not None
    return r


def test_recorded_trace_busy_within_window(red):
    assert red.chips == 1
    assert 0 < red.busy_s < red.window_s
    idle = sum(v for _, v in red.gaps)
    assert idle == pytest.approx(red.window_s - red.busy_s, rel=1e-6)


def test_recorded_trace_finds_both_steps(red):
    assert len(red.modules["jit_chunk_prefill_step"]) > 0
    assert len(red.modules["jit__slot_step"]) > 0
    assert all(t > 0 for ts in red.modules.values() for t in ts)
    # the per-op times attribute to programs, most first
    assert red.ops[0][1] >= red.ops[-1][1]
    assert any(k.startswith("jit__slot_step/") for k, _ in red.ops)


def test_recorded_trace_labels_gaps_by_host_span(red):
    labels = {k for k, _ in red.gaps}
    assert labels <= set(SPANS) | {"host:outside-spans"}
    assert labels & {"decode.step", "session.step", "driver.idle", "prefill.run_chunk"}

