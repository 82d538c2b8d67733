"""CPU rehearsal of the readers of the program's own counters: a traced run
of the fixture cell reports every one of them, and the counters say the
same work as the benchmark's wrappers, call for call, so the roofline
readers can be moved onto them."""
import jax
import pytest

import harness
from conftest import HERE

FIXTURES = HERE / "fixtures"
CELL = "tiny.tiny-mix"
READERS = [("decode_launch_ms", "ms"), ("decode_sync_ms", "ms"), ("decode_pad_share", "%"),
           ("select_ms_per_round", "ms"), ("round_host_ms", "ms")]


@pytest.fixture(scope="module")
def traced():
    """The result of one traced run, and the run data its readers saw."""
    jax.config.update("jax_enable_compilation_cache", False)
    seen = {}
    real = harness.load_reader

    def spy(name, bench_dir=harness.HERE):
        read = real(name, bench_dir)

        def wrapped(run):
            seen["run"] = run
            return read(run)
        return wrapped

    harness.load_reader = spy
    try:
        res = harness.run_cell(FIXTURES, CELL, 2**31 + 17, 2.0, True, jax.devices()[0], READERS,
                               log=lambda _m: None)
    finally:
        harness.load_reader = real
    return res, seen["run"]


def test_every_counter_reader_reports(traced):
    res, _ = traced
    assert res["correct"] is True
    for name, _unit in READERS:
        assert name in res["metrics"], name
        assert res["metrics"][name]["value"] >= 0
    assert 0 <= res["metrics"]["decode_pad_share"]["value"] < 100


def test_counters_match_the_wrappers_records(traced):
    from _counters import stretch

    _, run = traced
    steps = stretch(run, "decode_step", "positions")
    chunks = stretch(run, "prefill_chunk", "take")
    assert steps and chunks
    assert [e.data["positions"] for e in steps] == run.spans.records["decode.step"]
    assert [(e.data["start"], e.data["take"]) for e in chunks] == [
        tuple(r) for r in run.spans.records["prefill.run_chunk"]]


def test_readers_are_silent_without_counters():
    """No events, no pair of stalls, or a program whose events lack the
    counters (a DECODE_STEP with its older fields only): no number."""
    from repro.obs import Event, EventType

    cell = harness.load_cell(FIXTURES, CELL)
    spans = harness.Spans()
    stalls = [(0.0, 0.1), (5.0, 6.0)]
    older = [Event(EventType.DECODE_STEP, 1.0, data=dict(batch=1, step_time=0.01))]
    for events, st in ((None, stalls), ([], stalls[:1]), (older, stalls)):
        run = harness.RunData(cell.spec, None, [], events, spans, (0.0, 1.0), 3, None,
                              stalls=st)
        for name, _unit in READERS:
            assert harness.load_reader(name)(run) is None
