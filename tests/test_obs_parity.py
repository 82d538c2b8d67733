"""Event-level parity across backends (the tracing mirror of the serving
bit-parity contracts): the same request served by different substrates must
tell the same lifecycle story in the shared repro.obs schema.

  * sim vs engine: identical per-request event-TYPE sequences (timestamps
    live in different time bases — cost-model virtual seconds vs ManualClock
    reads — so only the shape is comparable);
  * async-engine vs 1-replica router, and 1-replica router vs 1P:1D
    never-deflection disagg: identical per-request (type, timestamp)
    sequences, exact floats — these pairs share one clock discipline, so
    the event streams inherit the serving layer's bit-parity.

Backend-tag events (ROUTE, DEFLECT) are excluded: they narrate where a
backend-specific layer placed work, not the request's lifecycle.
"""
import asyncio
import copy

import numpy as np
import pytest

from repro.core.request import Phase, Request, SLOSpec
from repro.obs import EventType, TraceRecorder

_BACKEND_TAGS = {EventType.ROUTE, EventType.DEFLECT}


def _signature(events, with_times=True):
    """(per-rid lifecycle sequences, scheduler DECODE_STEP count), tags
    excluded. ``with_times=False`` compares shape only (cross-time-base).
    With times, a live session's ROUND counters ride along under rid -1;
    without, they are left out: the simulator has no host rounds."""
    per, steps = {}, 0
    for e in events:
        if e.type in _BACKEND_TAGS:
            continue
        if e.type is EventType.ROUND:
            if with_times:
                per.setdefault(-1, []).append((e.type.value, e.t))
            continue
        if e.rid < 0:
            steps += 1
            continue
        item = (e.type.value, e.t) if with_times else e.type.value
        per.setdefault(e.rid, []).append(item)
    return per, steps


@pytest.fixture(scope="module")
def tiny_model():
    import jax

    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config("llama3-8b-smoke").replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _server(tiny_model, clock=None, trace=None):
    from repro.serving.clock import ManualClock
    from repro.serving.engine import DisaggServer, EngineConfig

    cfg, model, params = tiny_model
    return DisaggServer(
        model, params, EngineConfig(max_slots=4, max_len=64, chunk_size=16),
        clock=clock or ManualClock(auto_step=1e-4), trace=trace,
    )


def _requests(cfg, n=5, max_out=4, seed=2, arrival_gap=0.01):
    rng = np.random.default_rng(seed)
    pairs = []
    for i in range(n):
        length = int(rng.integers(4, 14))
        prompt = list(map(int, rng.integers(2, cfg.vocab_size, length)))
        pairs.append((
            Request(rid=i, arrival=i * arrival_gap, input_len=length,
                    output_len=max_out, slo=SLOSpec(ttft=120.0, tpot=10.0)),
            prompt,
        ))
    return pairs


def test_sim_and_engine_tell_the_same_lifecycle(tiny_model):
    """One request, prompt within a single prefill chunk: the simulator and
    the live engine emit the identical event-type sequence — submit, admit,
    one prefill slice, the END->QUEUED->START handoff burst with the first
    token, attach, then per-step tokens and done."""
    from repro.serving.session import ServeSession
    from repro.sim.simulator import DisaggSimulator

    cfg = tiny_model[0]
    tr_engine = TraceRecorder()
    sess = ServeSession(_server(tiny_model, trace=tr_engine))
    prompt = list(map(int, np.random.default_rng(0).integers(2, cfg.vocab_size, 8)))
    req = Request(rid=0, arrival=0.0, input_len=8, output_len=3,
                  slo=SLOSpec(ttft=120.0, tpot=10.0))
    sess.run([(req, prompt)])
    assert req.phase == Phase.DONE

    tr_sim = TraceRecorder()
    sim = DisaggSimulator(trace=tr_sim)
    twin = Request(rid=0, arrival=0.0, input_len=8, output_len=3,
                   slo=SLOSpec(ttft=120.0, tpot=10.0))
    sim.run([twin])
    assert twin.phase == Phase.DONE

    sig_e, steps_e = _signature(tr_engine.events, with_times=False)
    sig_s, steps_s = _signature(tr_sim.events, with_times=False)
    assert sig_e == sig_s
    # the first token rides the prefill-finish burst, so output_len=3 takes
    # exactly two decode steps — on both substrates
    assert steps_e == steps_s == 2


def test_one_replica_router_events_match_async_engine(tiny_model):
    from repro.serving.frontend import AsyncServeSession
    from repro.serving.router import RouterSession

    cfg = tiny_model[0]
    pairs_a = _requests(cfg)
    pairs_r = copy.deepcopy(pairs_a)

    async def run_async():
        tr = TraceRecorder()
        frontend = AsyncServeSession(_server(tiny_model), trace=tr)
        async with frontend:
            await frontend.replay(pairs_a, clients=3)
        return tr

    async def run_router():
        tr = TraceRecorder()
        router = RouterSession([_server(tiny_model)], policy="round-robin",
                               trace=tr)
        async with router:
            await router.replay(pairs_r, clients=3)
        return tr

    tr_a = asyncio.run(run_async())
    tr_r = asyncio.run(run_router())
    # the router timeline carries one extra ROUTE tag per request, nothing else
    assert sum(e.type is EventType.ROUTE for e in tr_r.events) == len(pairs_r)
    sig_a, steps_a = _signature(tr_a.events)
    sig_r, steps_r = _signature(tr_r.events)
    assert sig_a == sig_r  # exact (type, timestamp) floats, per request
    assert steps_a == steps_r


def test_disagg_1p1d_never_deflection_events_match_router(tiny_model):
    from repro.serving.clock import ManualClock
    from repro.serving.disagg import DisaggFleetSession
    from repro.serving.engine import DisaggServer, EngineConfig

    cfg, model, params = tiny_model
    pairs_r = _requests(cfg)
    pairs_d = copy.deepcopy(pairs_r)

    async def run_router():
        from repro.serving.router import RouterSession

        tr = TraceRecorder()
        router = RouterSession([_server(tiny_model)], policy="round-robin",
                               trace=tr)
        async with router:
            await router.replay(pairs_r, clients=3)
        return tr

    async def run_disagg():
        tr = TraceRecorder()
        clock = ManualClock(auto_step=1e-4)
        ecfg = EngineConfig(max_slots=4, max_len=64, chunk_size=16)
        mk = lambda: DisaggServer(model, params, ecfg, clock=clock)
        fleet = DisaggFleetSession([mk()], [mk()], deflection="never", trace=tr)
        async with fleet:
            await fleet.replay(pairs_d, clients=3)
        return tr

    tr_r = asyncio.run(run_router())
    tr_d = asyncio.run(run_disagg())
    sig_r, steps_r = _signature(tr_r.events)
    sig_d, steps_d = _signature(tr_d.events)
    assert sig_r == sig_d  # exact (type, timestamp) floats, per request
    assert steps_r == steps_d
    # the two timelines differ only in backend tags and pool labels
    pools_d = {e.pool for e in tr_d.events}
    assert {"prefill:0", "decode:0"} <= pools_d


def test_decode_steps_say_how_they_wrote_the_cache(tiny_model):
    """Every DECODE_STEP names the engine's cache write: "row", each live
    lane's new row in place, for the dense fixture's plain k/v cache.
    Tracing changes no token and no timing."""
    from repro.serving.session import ServeSession

    cfg = tiny_model[0]
    runs = []
    for tr in (TraceRecorder(), None):
        pairs = _requests(cfg)
        sess = ServeSession(_server(tiny_model), trace=tr)
        sess.run(pairs)
        runs.append((sess.outputs, [(r.rid, r.ttft(), r.mean_tpot()) for r, _ in pairs]))
        if tr is not None:
            steps = [e for e in tr.events if e.type is EventType.DECODE_STEP]
            assert steps and all(e.data["kv_write"] == "row" for e in steps)
    assert runs[0] == runs[1]


def test_windowed_engine_steps_say_slot():
    """The windowed ring cache (max_len 64 > window 32) is decoded by whole
    slots, and each step says so in the stats DECODE_STEP carries. A session
    cannot serve it (chunked prefill takes a plain k/v cache), so the
    engine is driven directly: attach a prefill cache, then step."""
    import jax

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving.engine import DecodeEngine, EngineConfig, LiveRequest

    cfg = get_config("gemma2-9b-smoke").replace(dtype="float32")
    model = build_model(cfg)
    eng = DecodeEngine(model, model.init(jax.random.key(0)),
                       EngineConfig(max_slots=4, max_len=64), None, peek=lambda: 0.0)
    req = Request(rid=0, arrival=0.0, input_len=3, output_len=2,
                  slo=SLOSpec(ttft=120.0, tpot=10.0))
    req.prefilled_tokens = 3
    lr = LiveRequest(req=req, tokens=[5, 6, 7], prefill_cache=model.init_cache(1, 64))
    assert eng.admit(lr)
    for _ in range(2):
        eng.step([lr], jax.random.key(1))
        assert eng.last_step.kv_write == "slot"
