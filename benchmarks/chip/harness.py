"""Run one benchmark cell once: build, warm up, drive, measure, check.

A cell is found by name: ``cells/<name>.json`` gives its configuration,
traffic mix, rate, drain limit and the check's limit; the configuration is
``configs/<config>.json`` and the mix ``traffic/<traffic>.json``. Per-layer
metrics are readers in ``layer_metrics/<metric>.py``, each with a
``read(run)`` that returns a number or None. The model is reached only
through the architecture module the configuration names (``modelspec``).
Nothing here names a cell, a configuration or an architecture.

`run_cell` is the whole run apart from finding the chip:

1. make the weights on the device from the seed (one jitted call);
2. build one ``DisaggServer`` at the configuration's engine size and warm
   up every program the cell's path runs (``srv.warmup()``, the sampler
   at each decode bucket, and two requests through a throwaway session);
3. drive ``ServeSession.submit``/``step`` open loop on the wall clock: each
   request is submitted once its due time has passed and is timed from
   that due time, so a stall charges every request queued behind it;
4. after the window, keep the arrivals coming until every counted request
   has finished or the drain limit has passed;
5. read the metrics, free the program's state, and compare a seeded sample
   of the finished requests' served tokens with the plain reference.
"""
from __future__ import annotations

import collections
import gc
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import modelspec
import reference
import stats
import traffic
import weights
import work
import xplane

HERE = Path(__file__).resolve().parent

# every served token is the argmax; no token is an end-of-sequence, so each
# request runs to its full length
NO_EOS = -1
# the program's spans and the driver's that label the trace's idle gaps
SPAN_NAMES = (
    "session.step", "prefill_sched.select", "prefill.run_chunk", "decode.admit",
    "decode_sched.select", "decode.step", "decode.launch", "decode.sync", "session.tokens",
    "driver.submit", "driver.idle",
)
# traced seconds of the window (at most half of it), from 2 s (or a quarter) in:
# long enough that a steady cell runs each of its step programs in them
TRACE_S = 8.0


@dataclass
class Cell:
    name: str
    conf: Dict[str, Any]  # configs/<config>.json
    mix: Dict[str, Any]  # traffic/<traffic>.json
    cell: Dict[str, Any]  # cells/<name>.json
    arch: ModuleType  # archs/<arch>.py of the configuration
    spec: Any  # the architecture's Spec of the configuration


def load_config(bench_dir: Path, name: str) -> Tuple[Dict[str, Any], ModuleType, Any]:
    """``configs/<name>.json``, its architecture module and its Spec."""
    conf = modelspec.load(bench_dir / "configs" / f"{name}.json")
    arch = modelspec.arch_of(conf, bench_dir)
    return conf, arch, arch.spec_of(conf)


def load_cell(bench_dir: Path, name: str) -> Cell:
    cell = modelspec.load(bench_dir / "cells" / f"{name}.json")
    conf, arch, spec = load_config(bench_dir, cell["config"])
    mix = modelspec.load(bench_dir / "traffic" / f"{cell['traffic']}.json")
    return Cell(name, conf, mix, cell, arch, spec)


def slo_limits(conf: Dict[str, Any], n_in: int) -> Tuple[float, float]:
    """(TTFT limit, TPOT limit) in seconds for a prompt of n_in tokens."""
    slo = conf["slo"]
    k = slo["k"]
    return k * (slo["ttft_base_s"] + slo["ttft_per_token_s"] * n_in), k * slo["tpot_s"]


class CompileCounter:
    """Counts JAX tracing, compilation and compile-cache loads while on."""

    EVENTS = ("jaxpr_trace_duration", "backend_compile_duration", "cache_retrieval_time")

    def __init__(self) -> None:
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name: str, _secs: float, **_kw) -> None:
        if self.on and any(e in name for e in self.EVENTS):
            self.count += 1


@dataclass
class RunData:
    """What a per-layer metric reader may read."""

    arch: ModuleType
    spec: Any
    peak: Optional[Dict[str, float]]
    counted: List[Any]  # the counted requests (repro Request objects)
    events: Optional[List[Any]]  # obs events of the session (traced runs)
    rounds: int  # session rounds inside the window
    trace: Optional[xplane.Reduction]
    stalls: List[Tuple[float, float]] = field(default_factory=list)  # profiler start/stop, session time
    timed: List[stats.Timed] = field(default_factory=list)  # counted requests, driver's clock


# ------------------------------------------------------------------ building
def build_server(c: Cell, seed: int, device):
    """The server at the configuration's size, with weights from `seed`."""
    from repro.models import build_model
    from repro.serving.engine import DisaggServer, EngineConfig

    cfg = c.arch.model_config(c.spec)
    model = build_model(cfg)
    want = jax.tree.map(lambda x: (x.shape, str(x.dtype)), model.param_struct())
    params = weights.make_params(c.arch, c.spec, seed, device)
    got = jax.tree.map(lambda x: (x.shape, str(x.dtype)), params)
    if got != want:
        raise ValueError("the benchmark's weight layout differs from the program's parameters")
    eng = c.conf["engine"]
    ecfg = EngineConfig(
        max_slots=eng["max_slots"], max_len=eng["max_len"], chunk_size=eng["chunk_size"],
        prefill_policy=eng["prefill_policy"], decode_policy=eng["decode_policy"],
        eos_token=NO_EOS,
    )
    return DisaggServer(model, params, ecfg, device=device)


def warm_up(srv, c: Cell, device) -> jax.Array:
    """Compile every program the window runs; returns a committed scalar
    whose increment serves as a device barrier."""
    from repro.core.request import Request, SLOSpec
    from repro.serving.engine import _bucket
    from repro.serving.sampler import sample
    from repro.serving.session import ServeSession

    srv.warmup()
    ecfg = srv.ecfg
    for bs in sorted({_bucket(n, ecfg.decode_buckets) for n in range(1, ecfg.max_slots + 1)}):
        lg = jax.device_put(jnp.zeros((bs, c.spec.vocab), jnp.float32), device)
        np.asarray(sample(lg, temperature=ecfg.temperature))
    n = ecfg.chunk_size + 1
    session = ServeSession(srv)
    session.run([
        (Request(rid=-1 - i, arrival=0.0, input_len=n, output_len=3, slo=SLOSpec(60.0, 60.0)),
         [traffic.MIN_TOKEN_ID] * n)
        for i in range(2)
    ])
    bar = jax.device_put(jnp.zeros((), jnp.int32), device)
    (bar + 1).block_until_ready()
    return bar


# ------------------------------------------------------------------- driving
@dataclass
class Drive:
    counted: List[Any]  # the program's Request objects, read for the phase only
    timed: Dict[int, stats.Timed]  # per counted rid, on the driver's clock
    prompts: Dict[int, List[int]]
    outputs: Dict[int, List[int]]  # tokens as they reached on_token
    window_tokens: int
    rounds_in_window: int
    late_p90_s: float
    late_max_s: float
    end: float  # driver time the loop stopped
    events: Optional[List[Any]]
    trace_dir: Optional[str]
    stalls: List[Tuple[float, float]] = field(default_factory=list)


def drive(srv, c: Cell, seed: int, seconds: float, trace: bool, bar: jax.Array,
          compiles: CompileCounter) -> Drive:
    """Drive the session open loop. Every time here is the driver's own
    clock (seconds since the session's clock was reset); each token's time
    is read when it reaches the driver's ``on_token`` callback."""
    from repro.core.request import Phase, Request, SLOSpec
    from repro.obs.events import TraceRecorder
    from repro.serving.session import ServeSession

    lead = float(c.mix["lead_in_s"])
    drain = float(c.cell["drain_s"])
    items = traffic.generate(c.mix, c.cell["rate"], seconds, drain, seed, c.spec.vocab)
    w0, w1 = lead, lead + seconds
    counter = {"tokens": 0}
    timed: Dict[int, stats.Timed] = {}
    outputs: Dict[int, List[int]] = {}
    origin = 0.0  # set where the session's clock is reset, below

    def clock() -> float:
        return time.perf_counter() - origin

    def on_token(req, tok, _t):
        t = clock()
        if w0 <= t < w1:
            counter["tokens"] += 1
        r = timed.get(req.rid)
        if r is not None:
            r.times.append(t)
            outputs[req.rid].append(int(tok))

    rec = TraceRecorder() if trace else None
    session = ServeSession(srv, on_token=on_token, trace=rec)
    t_lo = w0 + min(seconds / 4, 2.0)
    t_hi = t_lo + min(TRACE_S, seconds / 2)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    tracing = False
    window_ann = None
    counted: List[Any] = []
    prompts: Dict[int, List[int]] = {}
    late: List[float] = []
    remaining = set()
    rounds = 0
    stalls: List[Tuple[float, float]] = []  # session time spent starting/stopping the profiler
    pending = collections.deque(items)
    srv.reset_clock()
    origin = time.perf_counter()
    now = 0.0
    while True:
        now = clock()
        compiles.on = w0 <= now < w1
        while pending and pending[0].due <= now:
            it = pending.popleft()
            ttft, tpot = slo_limits(c.conf, len(it.prompt))
            req = Request(rid=it.rid, arrival=it.due, input_len=len(it.prompt),
                          output_len=it.n_out, slo=SLOSpec(ttft, tpot))
            if it.counted:
                counted.append(req)
                prompts[it.rid] = it.prompt
                timed[it.rid] = stats.Timed(it.due, it.n_out, ttft, tpot)
                outputs[it.rid] = []
                remaining.add(it.rid)
                late.append(now - it.due)
            with jax.profiler.TraceAnnotation("driver.submit"):
                session.submit(req, it.prompt)
        if trace and not tracing and window_ann is None and now >= t_lo:
            s0 = srv._now()
            (bar + 1).block_until_ready()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1  # the spans, without the runtime's own events
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            stalls.append((s0, srv._now()))
            window_ann = jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN)
            window_ann.__enter__()
            tracing = True
        if tracing and now >= t_hi:
            s0 = srv._now()
            (bar + 1).block_until_ready()
            window_ann.__exit__(None, None, None)
            jax.profiler.stop_trace()  # writes the trace: the loop stalls
            stalls.append((s0, srv._now()))
            tracing = False
        if now >= w1 and (not remaining or now >= w1 + drain):
            break
        if session.has_work:
            done = session.step()
            remaining.difference_update(done)
            if w0 <= now < w1:
                rounds += 1
        else:
            nxt = pending[0].due if pending else now + 0.001
            with jax.profiler.TraceAnnotation("driver.idle"):
                time.sleep(min(0.001, max(0.0, nxt - clock())))
    if tracing:
        window_ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
    for r in counted:
        if r.phase != Phase.DONE:
            r.phase = Phase.FAILED
    return Drive(
        counted=counted, timed=timed, prompts=prompts, outputs=outputs,
        window_tokens=counter["tokens"], rounds_in_window=rounds,
        late_p90_s=stats.percentile(late, 90) if late else 0.0,
        late_max_s=max(late) if late else 0.0, end=now,
        events=list(rec.events) if rec is not None else None,
        trace_dir=trace_dir, stalls=stalls,
    )


# ------------------------------------------------------------------- metrics
def request_times(d: Drive) -> Tuple[List[float], List[float], List[bool], int]:
    """Per counted request, on the driver's clock: TTFT and TPOT in seconds,
    whether it met both limits, and the number that failed (`stats.latencies`)."""
    return stats.latencies([d.timed[r.rid] for r in d.counted], d.end)


def end_to_end(d: Drive, seconds: float, setup_s: float) -> Dict[str, float]:
    ttft, tpot, met, _ = request_times(d)
    gaps = stats.token_gaps([d.timed[r.rid] for r in d.counted], d.end)
    return {
        "ttft_p90_s": stats.percentile(ttft, 90),
        "tpot_p90_ms": 1e3 * stats.percentile(tpot, 90),
        "itl_p90_ms": 1e3 * stats.percentile(gaps, 90) if gaps else float("inf"),
        "slo_attainment": stats.attainment(met),
        "decode_tok_s": d.window_tokens / seconds,
        "setup_s": setup_s,
    }


def describe(d: Drive) -> str:
    """The latencies' shape, logged in every run beside the metrics."""
    ttft, tpot, _, _ = request_times(d)
    gaps = stats.token_gaps([d.timed[r.rid] for r in d.counted], d.end)
    q = stats.percentile
    return (f"bench: ttft p50/p90/p99 {q(ttft, 50)!r} {q(ttft, 90)!r} {q(ttft, 99)!r} s; "
            f"tpot p50/p90 {1e3 * q(tpot, 50)!r} {1e3 * q(tpot, 90)!r} ms; "
            f"{len(gaps)} token gaps p50/p90/p95/p99 "
            f"{1e3 * q(gaps, 50)!r} {1e3 * q(gaps, 90)!r} {1e3 * q(gaps, 95)!r} "
            f"{1e3 * q(gaps, 99)!r} ms, mean {1e3 * float(np.mean(gaps))!r} ms"
            if gaps and ttft else "bench: no counted request")


def load_reader(name: str, bench_dir: Path = HERE):
    """The ``read`` function of ``layer_metrics/<name>.py``, looked for
    beside the cell files first and then with the benchmark."""
    path = modelspec.find("layer_metrics", name, bench_dir)
    return modelspec.module(path, f"layer_metric_{name}").read


UNITS = {"ttft_p90_s": "s", "tpot_p90_ms": "ms", "itl_p90_ms": "ms", "slo_attainment": "%",
         "decode_tok_s": "tokens/s", "setup_s": "s"}


# ---------------------------------------------------------------- the check
def choose_sample(d: Drive, seed: int, target_tokens: Optional[int]) -> List[int]:
    """Finished counted requests to compare: the one with the most served
    tokens, the one with the longest prompt, then others in an order drawn
    from the seed until `target_tokens` served tokens are in (all of them
    where `target_tokens` is None)."""
    from repro.core.request import Phase

    done = [r.rid for r in d.counted if r.phase == Phase.DONE and d.timed[r.rid].finished]
    if not done:
        return []
    longest_out = max(done, key=lambda i: (len(d.outputs[i]), len(d.prompts[i]), -i))
    longest_in = max(done, key=lambda i: (len(d.prompts[i]), len(d.outputs[i]), -i))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    pick = list(dict.fromkeys([longest_out, longest_in]))
    rest = [i for i in done if i not in pick]
    rest = [rest[j] for j in rng.permutation(len(rest))]
    n = sum(len(d.outputs[i]) for i in pick)
    for i in rest:
        if target_tokens is not None and n >= target_tokens:
            break
        pick.append(i)
        n += len(d.outputs[i])
    return pick


def free(*trees) -> None:
    """Delete every device array reachable from `trees` now."""
    for t in trees:
        for x in jax.tree.leaves(t):
            if isinstance(x, jax.Array) and not x.is_deleted():
                x.delete()


# ------------------------------------------------------------------ the run
def run_cell(bench_dir: Path, name: str, seed: int, seconds: float, trace: bool,
             device, per_layer: Sequence[Tuple[str, str]] = (), t_start: Optional[float] = None,
             log=lambda msg: print(msg, file=sys.stderr, flush=True)) -> Dict[str, Any]:
    """One run of cell `name` on `device`; returns the result object."""
    t_start = time.perf_counter() if t_start is None else t_start
    c = load_cell(bench_dir, name)
    compiles = CompileCounter()
    srv = build_server(c, seed, device)
    bar = warm_up(srv, c, device)
    setup_s = time.perf_counter() - t_start
    log(f"bench: {name} seed {seed}: set-up {setup_s:.3f} s")
    d = drive(srv, c, seed, seconds, trace, bar, compiles)
    _, _, _, failed = request_times(d)
    log(f"bench: {len(d.counted)} counted requests, {failed} failed; "
        f"{d.rounds_in_window} rounds in the window; submission late p90 "
        f"{d.late_p90_s * 1e3:.2f} ms, max {d.late_max_s * 1e3:.2f} ms; "
        f"compiles in the window {compiles.count}")
    log(describe(d))
    stats_mem = device.memory_stats() or {}
    dev_block = {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": int(stats_mem.get("peak_bytes_in_use", 0)),
    }
    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown = None
    if not trace:
        for k, v in end_to_end(d, seconds, setup_s).items():
            metrics[k] = {"value": v, "unit": UNITS[k]}
    else:
        red = None
        path = next(iter(sorted(Path(d.trace_dir).rglob("*.xplane.pb"))), None)
        if path is not None:
            red = xplane.reduce(str(path), SPAN_NAMES, chips=1)
        shutil.rmtree(d.trace_dir, ignore_errors=True)
        try:
            peak = work.peaks(device.device_kind)
        except KeyError:
            if device.platform == "tpu":
                raise
            peak = None  # a CPU rehearsal: no roofline exists
        run = RunData(
            arch=c.arch, spec=c.spec, peak=peak, counted=d.counted, events=d.events,
            rounds=d.rounds_in_window, trace=red, stalls=d.stalls,
            timed=[d.timed[r.rid] for r in d.counted],
        )
        for m, unit in per_layer:
            v = load_reader(m, bench_dir)(run)
            if v is not None:
                metrics[m] = {"value": v, "unit": unit}
        if red is not None:
            dev_block["busy_s"] = red.busy_s
            dev_block["window_s"] = red.window_s
            breakdown = {
                "device_ops": [[k, v] for k, v in red.ops[:10]],
                "idle_gaps": [[k, v] for k, v in red.gaps[:10]],
            }
            log(f"bench: trace: busy {red.busy_s:.6f} s of {red.window_s:.6f} s; programs "
                + ", ".join(f"{k} x{len(v)}" for k, v in sorted(red.modules.items())))
    checks = check(c, seed, d, srv, log)
    correct = is_correct(checks)
    out: Dict[str, Any] = {
        "correct": correct, "attempted": len(d.counted), "failed": failed,
        "metrics": metrics, "device": dev_block,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def gap_numbers(gaps: np.ndarray) -> Dict[str, float]:
    """The compared numbers of a check, from the per-token gaps: the widest
    gap and the mean gap (in logits) of the sampled served tokens."""
    if gaps.size == 0 or not np.all(np.isfinite(gaps)):
        return {"served_token_gap": float("inf"), "mean_served_gap": float("inf")}
    return {"served_token_gap": float(np.max(gaps)), "mean_served_gap": float(np.mean(gaps))}


def release(srv, *more) -> None:
    """Free the server's device state (and `more`) so the reference runs
    beside nothing of the program's."""
    free(srv.decode.params, srv.decode.cache, srv.prefill.params, *more)
    gc.collect()


def compare(c: Cell, gaps: np.ndarray, short: int = 0) -> Dict[str, Dict[str, float]]:
    """The comparison that decides `correct`, from the sample's per-token
    gaps to the reference's best logit: each compared number beside its
    limit."""
    nums = gap_numbers(gaps)
    checks = {k: {"value": nums[k], "limit": float(v)} for k, v in c.cell["check"]["limits"].items()}
    checks["short_requests"] = {"value": float(short), "limit": 0.0}
    return checks


def is_correct(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(v["value"] <= v["limit"] for v in checks.values())


def check(c: Cell, seed: int, d: Drive, srv, log) -> Dict[str, Dict[str, float]]:
    """Free the program's state, then compare a sample of the served tokens
    with the plain reference. Each compared number beside its limit."""
    from repro.core.request import Phase

    short = sum(1 for r in d.counted if r.phase == Phase.DONE and not d.timed[r.rid].finished)
    pick = choose_sample(d, seed, c.cell["check"].get("served_tokens"))
    release(srv)
    del srv
    t0 = time.perf_counter()
    served = [d.outputs[i] for i in pick]
    gaps = reference.score(c.arch, c.spec, seed, [d.prompts[i] for i in pick], served)
    nums = gap_numbers(gaps)
    log(f"bench: reference over {len(pick)} requests, {gaps.size} served tokens, "
        f"{time.perf_counter() - t0:.1f} s; widest gap {nums['served_token_gap']!r}, "
        f"mean gap {nums['mean_served_gap']!r}")
    checks = compare(c, gaps, short)
    for k, v in checks.items():
        log(f"check {k} {v['value']!r} limit {v['limit']!r}")
    return checks


def control_readings(c: Cell, seed: int, seconds: float, device) -> Dict[str, Any]:
    """One short run of the cell at its own load, then the check twice on
    the same sample: on the program's served tokens, and on the int8
    control's tokens put in their place (the reference computed with int8
    weights and activations, at the same positions)."""
    srv = build_server(c, seed, device)
    bar = warm_up(srv, c, device)
    d = drive(srv, c, seed, seconds, False, bar, CompileCounter())
    pick = choose_sample(d, seed, c.cell["check"].get("served_tokens"))
    release(srv, bar)
    del srv
    prompts = [d.prompts[i] for i in pick]
    served = [d.outputs[i] for i in pick]
    ctl = reference.control_tokens(c.arch, c.spec, seed, prompts, served)
    ref = reference.teacher_logits(c.arch, c.spec, seed, prompts, served)
    program = compare(c, reference.gaps(ref, served))
    control = compare(c, reference.gaps(ref, ctl))
    return {"requests": len(pick), "tokens": sum(len(t) for t in served),
            "program": program, "program_correct": is_correct(program),
            "control": control, "control_correct": is_correct(control)}


def json_line(result: Dict[str, Any]) -> str:
    """The result as one line of JSON (infinite numbers as strings)."""
    def fix(x):
        if isinstance(x, float) and not np.isfinite(x):
            return str(x)
        if isinstance(x, dict):
            return {k: fix(v) for k, v in x.items()}
        if isinstance(x, list):
            return [fix(v) for v in x]
        return x
    return json.dumps(fix(result))
