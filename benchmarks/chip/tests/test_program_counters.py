"""CPU rehearsal of the readers of the program's own counters: a traced run
of the fixture cell reports every one of them, and the counters that feed
the roofline readers' work (DECODE_STEP ``positions``, PREFILL_CHUNK
``start`` / ``take``) say, call for call, what the engine was asked to do,
as seen by spies on its two calls."""
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
    """The result of one traced run, the run data its readers saw, and the
    engine calls the spies saw: (session time, what was asked)."""
    from repro.serving.engine import DecodeEngine, PrefillEngine

    jax.config.update("jax_enable_compilation_cache", False)
    seen, calls = {}, {"decode": [], "chunk": []}
    real = harness.load_reader
    step, run_chunk, build = DecodeEngine.step, PrefillEngine.run_chunk, harness.build_server

    def spy(name, bench_dir=harness.HERE):
        read = real(name, bench_dir)

        def wrapped(run):
            seen["run"] = run
            return read(run)
        return wrapped

    def built(*a, **k):
        seen["srv"] = srv = build(*a, **k)
        return srv

    def spy_step(self, batch, key):
        calls["decode"].append((seen["srv"]._now(), [lr.req.seq_len - 1 for lr in batch]))
        return step(self, batch, key)

    def spy_chunk(self, lr, take):
        r = lr.req
        calls["chunk"].append((seen["srv"]._now(), (r.prefix_cached_tokens + r.prefilled_tokens, take)))
        return run_chunk(self, lr, take)

    harness.load_reader, harness.build_server = spy, built
    DecodeEngine.step, PrefillEngine.run_chunk = spy_step, spy_chunk
    try:
        res = harness.run_cell(FIXTURES, CELL, 2**31 + 17, 2.0, True, jax.devices()[0], READERS,
                               log=lambda _m: None)
    finally:
        harness.load_reader, harness.build_server = real, build
        DecodeEngine.step, PrefillEngine.run_chunk = step, run_chunk
    return res, seen["run"], calls


def test_every_counter_reader_reports(traced):
    res, _, _ = traced
    assert res["correct"] is True
    for name, _unit in READERS:
        assert name in res["metrics"], name
        assert res["metrics"][name]["value"] >= 0
    assert 0 <= res["metrics"]["decode_pad_share"]["value"] < 100


def test_counters_say_what_the_engine_was_asked(traced):
    from _counters import stretch
    from _programs import chunk_work, decode_work

    _, run, calls = traced
    lo, hi = run.stalls[0][1], run.stalls[1][0]

    def inside(kind):
        return [what for t, what in calls[kind] if lo <= t < hi]

    steps = stretch(run, "decode_step", "positions")
    chunks = stretch(run, "prefill_chunk", "take")
    assert steps and chunks
    assert [e.data["positions"] for e in steps] == inside("decode")
    assert [(e.data["start"], e.data["take"]) for e in chunks] == inside("chunk")
    assert decode_work(run) == [run.arch.decode_work(run.spec, p) for p in inside("decode")]
    assert chunk_work(run) == [run.arch.chunk_work(run.spec, *c) for c in inside("chunk")]


def test_readers_are_silent_without_counters():
    """No events, no pair of stalls, or a program whose events lack the
    counters (a DECODE_STEP with its older fields only): no number."""
    from repro.obs import Event, EventType

    cell = harness.load_cell(FIXTURES, CELL)
    stalls = [(0.0, 0.1), (5.0, 6.0)]
    older = [Event(EventType.DECODE_STEP, 1.0, data=dict(batch=1, step_time=0.01))]
    for events, st in ((None, stalls), ([], stalls[:1]), (older, stalls)):
        run = harness.RunData(cell.arch, cell.spec, None, [], events, 3, None, stalls=st)
        for name, _unit in [*READERS, ("decode_step_roofline", "%"), ("mfu.prefill", "%")]:
            assert harness.load_reader(name)(run) is None
