"""The serving round's own tracing: profiler spans at every layer boundary
and the host-time counters in the obs stream (PREFILL_CHUNK, ROUND and the
engine fields of DECODE_STEP), on both round loops, on the wall clock."""
import jax
import numpy as np
import pytest

from repro.core.request import Phase, Request, SLOSpec
from repro.obs import EventType, TraceRecorder, chrome_trace, read_jsonl, write_jsonl

CHUNK = 16
# span -> the span it must sit inside
PARENT = {
    "prefill_sched.select": "session.step",
    "prefill.run_chunk": "session.step",
    "decode.admit": "session.step",
    "decode_sched.select": "session.step",
    "decode.step": "session.step",
    "decode.launch": "decode.step",
    "decode.sync": "decode.step",
    "session.tokens": "session.step",
}


@pytest.fixture(scope="module")
def tiny_model():
    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config("llama3-8b-smoke").replace(dtype="float32")
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.key(0))


def _requests(cfg, n=5, seed=3):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        length = int(rng.integers(4, 40))  # up to three chunks of 16
        prompt = [int(t) for t in rng.integers(2, cfg.vocab_size, length)]
        out.append((Request(rid=i, arrival=0.002 * i, input_len=length, output_len=5,
                            slo=SLOSpec(ttft=60.0, tpot=10.0)), prompt))
    return out


def _serve(tiny_model, kind):
    """Serve a few requests traced, on the wall clock, through `kind`."""
    from repro.serving.disagg import DisaggSession
    from repro.serving.engine import DisaggServer, EngineConfig
    from repro.serving.session import ServeSession

    cfg, model, params = tiny_model
    ecfg = EngineConfig(max_slots=4, max_len=64, chunk_size=CHUNK)
    tr = TraceRecorder()
    if kind == "serve":
        sess = ServeSession(DisaggServer(model, params, ecfg), trace=tr)
    else:
        a = DisaggServer(model, params, ecfg)
        b = DisaggServer(model, params, ecfg, clock=a.clock)
        sess = DisaggSession([a], [b], trace=tr)
    pairs = _requests(cfg)
    for r, p in pairs:
        sess.submit(r, p)
    while sess.has_work:
        sess.step()
    assert all(r.phase == Phase.DONE for r, _ in pairs)
    return pairs, tr.events


@pytest.mark.parametrize("kind", ["serve", "disagg-1p1d"])
def test_round_counters(tiny_model, kind):
    pairs, events = _serve(tiny_model, kind)
    by = lambda t: [e for e in events if e.type is t]
    # one PREFILL_CHUNK per chunk, contiguous, covering each prompt
    for r, prompt in pairs:
        chunks = [e.data for e in by(EventType.PREFILL_CHUNK) if e.rid == r.rid]
        assert sum(c["take"] for c in chunks) == len(prompt)
        assert [c["start"] for c in chunks] == list(
            np.cumsum([0] + [c["take"] for c in chunks[:-1]]))
        assert all(0 < c["take"] <= c["chunk_size"] == CHUNK for c in chunks)
    steps = by(EventType.DECODE_STEP)
    assert steps
    for e in steps:
        d = e.data
        assert d["batch"] <= d["bucket"]
        assert len(d["positions"]) == d["batch"]
        assert all(4 <= p < 64 for p in d["positions"])
        assert d["launch_s"] >= 0 and d["sync_s"] >= 0
        assert d["kv_write"] == "row"  # a plain k/v cache: rows in place
    rounds = by(EventType.ROUND)
    assert rounds and all(e.rid == -1 for e in rounds)
    for e in rounds:
        d = e.data
        assert 0 <= d["select_s"] and 0 <= d["engine_s"]
        assert d["select_s"] + d["engine_s"] <= d["wall_s"]
    # on the wall clock the engines take time: some round counts it
    assert sum(e.data["engine_s"] for e in rounds) > 0
    assert sum(e.data["launch_s"] + e.data["sync_s"] for e in steps) > 0


def test_profiler_holds_every_span_inside_its_parent(tiny_model, tmp_path):
    from jax.profiler import ProfileData

    _serve(tiny_model, "serve")  # compiled outside the trace
    with jax.profiler.trace(str(tmp_path)):
        _serve(tiny_model, "serve")
    path = next(tmp_path.rglob("*.xplane.pb"))
    spans = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in PARENT or ev.name == "session.step":
                    spans.setdefault(ev.name, []).append((ev.start_ns, ev.end_ns))
    assert set(spans) == set(PARENT) | {"session.step"}
    for name, parent in PARENT.items():
        outer = spans[parent]
        for a, b in spans[name]:
            assert any(pa <= a and b <= pb for pa, pb in outer), (name, parent)


def test_exporters_take_the_counters(tiny_model, tmp_path):
    _, events = _serve(tiny_model, "serve")
    path = str(tmp_path / "t.jsonl")
    write_jsonl(events, path)
    assert [e.as_dict() for e in read_jsonl(path)] == [e.as_dict() for e in events]
    doc = chrome_trace(events)
    rounds = [e for e in doc["traceEvents"] if e["name"] == "round"]
    assert len(rounds) == sum(e.type is EventType.ROUND for e in events)
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in rounds)
    assert any(e["name"].startswith("prefill_chunk r") for e in doc["traceEvents"])
    tracks = {}
    for e in doc["traceEvents"]:
        if e["ph"] != "M":
            tracks.setdefault((e["pid"], e["tid"]), []).append(e["ts"])
    assert all(a <= b for ts in tracks.values() for a, b in zip(ts, ts[1:], strict=False))
