#!/usr/bin/env python3
"""Bring-up smoke of the disaggregated serving path on a TPU.

    python3 chip_smoke.py [--seed N]       # one chip (the default)
    python3 chip_smoke.py --chips 4        # 2P:2D fleet over four chips

One chip: builds ``minicpm-2b`` at its published widths in bfloat16, with
random weights from ``--seed``, picks the largest engine size whose
compiled steps fit the chip beside everything the engine keeps resident,
and serves a seeded trace through `DisaggServer` + `ServeSession` with the
registry's ``kairos-urgency`` / ``kairos-slack`` on the wall clock. Every
request must finish with its full token count, and its first token must
equal that of `reference_generate` run on the chip. A second phase serves
the same trace with ``attn_impl="pallas"``: the steps must contain compiled
kernels (``tpu_custom_call``) and one chunk-prefill step's logits must
match the jnp path's within `PALLAS_LOGIT_RTOL`.

``--chips 4`` runs only the placement phase: the same 2P:2D
`DisaggSession` on a `ManualClock`, once with each server on its own chip
and once with every server on chip 0. Tokens, handoff and deflection counts
must be identical, and each server's arrays must sit on its device.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU, or when any phase fails, the script exits non-zero before
printing it. The phase functions run on the CPU at smoke size too
(tests/test_chip_smoke.py); only `main` demands the chip.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.request import Phase, Request, SLOSpec  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.sizing import (  # noqa: E402
    CHUNK,
    SIZES,
    choose_size,
    describe,
    device_limit,
    tree_bytes,
)
from repro.models import build_model  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.obs.events import EventType, TraceRecorder  # noqa: E402
from repro.policies import PolicySpec  # noqa: E402
from repro.serving.clock import ManualClock  # noqa: E402
from repro.serving.disagg import DisaggSession  # noqa: E402
from repro.serving.engine import (  # noqa: E402
    DisaggServer,
    EngineConfig,
    PrefillEngine,
    lower_steps,
    reference_generate,
)
from repro.serving.session import ServeSession  # noqa: E402

ARCH = "minicpm-2b"
N_REQUESTS = 8
PROMPT_RANGE = (64, 768)  # prompt tokens, inclusive
OUTPUT_RANGE = (32, 64)  # generated tokens, inclusive
# random weights have no end-of-sequence token: -1 is never sampled, so
# every request runs to its full output length
EOS = -1
# relative L2 gap allowed between the Pallas and jnp chunk-prefill logits:
# both are bf16 end to end, and the flash kernels round their softmax
# weights at a per-block running max instead of the global one
PALLAS_LOGIT_RTOL = 5e-2
# the four-chip placement phase: a fleet small enough that all four
# servers (and their caches) also fit on chip 0 together
FLEET_SIZE = (2, 512)
FLEET_PROMPT_RANGE = (64, 384)
FLEET_OUTPUT_RANGE = (16, 32)
# prompts up to 160 tokens count as short: while the prefill pool holds a
# backlog they prefill on a decode server, so the comparison covers local
# (same-server) handoffs beside cross-chip ones
FLEET_DEFLECTION = PolicySpec("prefill-pressure", dict(short_tokens=160))


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAIL {msg}")


# --------------------------------------------------------------- the trace
def make_trace(
    vocab: int,
    n: int,
    seed: int,
    prompt_range: Tuple[int, int] = PROMPT_RANGE,
    output_range: Tuple[int, int] = OUTPUT_RANGE,
    gap: float = 0.1,
) -> List[Tuple[Request, List[int]]]:
    """n seeded (Request, prompt) pairs arriving `gap` seconds apart.
    Requests are mutated by serving: build a fresh trace for each run."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        n_in = int(rng.integers(prompt_range[0], prompt_range[1] + 1))
        n_out = int(rng.integers(output_range[0], output_range[1] + 1))
        prompt = [int(t) for t in rng.integers(2, vocab, n_in)]
        req = Request(rid=i, arrival=gap * i, input_len=n_in, output_len=n_out,
                      slo=SLOSpec(ttft=5.0, tpot=0.5))
        out.append((req, prompt))
    return out


def engine_config(max_slots: int, max_len: int, chunk: int = CHUNK) -> EngineConfig:
    return EngineConfig(
        max_slots=max_slots, max_len=max_len, chunk_size=chunk, eos_token=EOS,
        prefill_policy="kairos-urgency", decode_policy="kairos-slack",
    )


# ----------------------------------------------------------------- serving
def serve_phase(
    model: Model,
    params,
    ecfg: EngineConfig,
    trace: List[Tuple[Request, List[int]]],
    device=None,
) -> Dict[str, Any]:
    """Warm up (timed as compile seconds), then serve `trace` on the wall
    clock. Returns outputs, timings, step counts and the session summary."""
    rec = TraceRecorder()
    srv = DisaggServer(model, params, ecfg, trace=rec, device=device)
    t0 = time.perf_counter()
    srv.warmup()
    compile_s = time.perf_counter() - t0
    session = ServeSession(srv)
    t0 = time.perf_counter()
    outputs = session.run(trace)
    serve_s = time.perf_counter() - t0
    counts = rec.by_type()
    summary = session.summary()
    del srv, session
    gc.collect()
    return dict(
        outputs=outputs, compile_s=compile_s, serve_s=serve_s,
        decode_steps=counts.get(EventType.DECODE_STEP.value, 0),
        prefill_tokens=summary["prefill_computed_tokens"],
        completed=summary["completed"],
    )


def check_complete(trace: List[Tuple[Request, List[int]]], outputs: Dict[int, List[int]]) -> None:
    """Every request ended DONE with exactly its requested token count."""
    for req, _ in trace:
        got = len(outputs.get(req.rid, []))
        if req.phase != Phase.DONE or got != req.output_len:
            fail(f"rid={req.rid} phase={req.phase.value} tokens={got}/{req.output_len}")


def reference_check(
    model: Model,
    params,
    trace: List[Tuple[Request, List[int]]],
    outputs: Dict[int, List[int]],
    max_len: int,
) -> Dict[str, Any]:
    """First tokens must equal `reference_generate`'s; returns the share of
    all tokens that agree position for position."""
    refs = {
        req.rid: reference_generate(model, params, prompt, req.output_len, max_len, eos=EOS)
        for req, prompt in trace
    }
    for rid, ref in refs.items():
        if outputs[rid][0] != ref[0]:
            log(f"rid={rid} first token {outputs[rid][0]} != reference {ref[0]}")
    same = agreement(outputs, refs)
    return dict(first_token_match=same["first"], requests=len(trace),
                token_agreement=same["share"], tokens=same["tokens"],
                agreeing_prefix=same["prefix"])


def agreement(a: Dict[int, List[int]], b: Dict[int, List[int]]) -> Dict[str, Any]:
    """Position-for-position agreement of two rid -> tokens maps: requests
    whose first tokens are equal, the share of all tokens that are, and per
    request the tokens agreed on before the first divergence."""
    first = agree = total = 0
    prefix = []
    for rid in sorted(a):
        same = [x == y for x, y in zip(a[rid], b[rid], strict=False)]
        first += bool(same) and same[0]
        agree += sum(same)
        total += max(len(a[rid]), len(b[rid]))
        prefix.append(same.index(False) if False in same else len(same))
    return dict(first=first, share=agree / total, tokens=total, prefix=prefix)


def pallas_checks(
    model: Model,
    pallas_model: Model,
    params,
    ecfg: EngineConfig,
    prompt: List[int],
    device=None,
) -> Dict[str, Any]:
    """Compiled-kernel presence in both steps, and one chunk-prefill step's
    logits: Pallas against jnp on the first chunk of `prompt`."""
    lows = lower_steps(pallas_model, ecfg, device)
    custom = {k: "tpu_custom_call" in low.as_text() for k, low in lows.items()}
    n = min(len(prompt), ecfg.chunk_size)
    toks = jnp.asarray([prompt[:n] + [0] * (ecfg.chunk_size - n)], jnp.int32)
    start, valid = jnp.zeros((1,), jnp.int32), jnp.full((1,), n, jnp.int32)
    logits = []
    for m in (model, pallas_model):
        eng = PrefillEngine(m, params, ecfg, device)
        lg, _ = eng.chunk_step(toks, start, valid, eng.new_cache())
        logits.append(np.asarray(lg, np.float32))
    ref, got = logits
    rel = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
    return dict(
        custom_call=custom, logit_rel_l2=rel,
        max_abs_diff=float(np.max(np.abs(got - ref))),
        finite=bool(np.isfinite(got).all()),
    )


# ---------------------------------------------------------- four devices
def fleet_phase(
    model: Model,
    params,
    ecfg: EngineConfig,
    trace: List[Tuple[Request, List[int]]],
    devices: Sequence,
) -> Dict[str, Any]:
    """Serve `trace` through a 2P:2D `DisaggSession` on one `ManualClock`,
    server i on ``devices[i]`` (prefill 0, prefill 1, decode 0, decode 1)."""
    clock = ManualClock(auto_step=1e-3)
    servers = [DisaggServer(model, params, ecfg, clock=clock, device=d) for d in devices]
    session = DisaggSession(servers[:2], servers[2:], deflection=FLEET_DEFLECTION)
    for req, prompt in trace:
        session.submit(req, prompt)
    for _ in range(100_000):
        if not session.has_work:
            break
        session.step()
    else:
        fail("fleet did not drain")

    def on(tree, dev) -> bool:
        return all(x.devices() == {dev} for x in jax.tree.leaves(tree))

    placed = [
        on(srv.decode.params, dev) and on(srv.decode.cache, dev)
        and on(srv.prefill.new_cache(), dev)
        for srv, dev in zip(servers, devices, strict=True)
    ]
    h = session.handoff_summary()
    report = dict(
        outputs={rid: list(t) for rid, t in session.outputs.items()},
        done=sum(r.phase == Phase.DONE for r, _ in trace),
        handoff={k: h[k] for k in ("transfers_completed", "cross_transfers",
                                   "local_transfers", "by_dst")},
        deflected=session.deflected,
        placed=placed,
    )
    del servers, session
    gc.collect()
    return report


def four_chip_comparison(
    model: Model,
    params,
    ecfg: EngineConfig,
    trace_fn: Callable[[], List[Tuple[Request, List[int]]]],
    devices: Sequence,
) -> Dict[str, Any]:
    """The fleet with each server on its own device, against the identical
    fleet and clock with every server on ``devices[0]``."""
    spread = fleet_phase(model, params, ecfg, trace_fn(), devices[:4])
    single = fleet_phase(model, params, ecfg, trace_fn(), [devices[0]] * 4)
    return dict(
        identical_tokens=spread["outputs"] == single["outputs"],
        identical_handoff=spread["handoff"] == single["handoff"],
        identical_deflection=spread["deflected"] == single["deflected"],
        spread_placed=all(spread["placed"]),
        single_placed=all(single["placed"]),
        all_done=spread["done"] == single["done"] == len(spread["outputs"]),
        requests=len(spread["outputs"]),
        handoff=spread["handoff"],
        deflected=spread["deflected"],
    )


# -------------------------------------------------------------------- main
def _peak(dev) -> Optional[int]:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_one_chip(seed: int, dev) -> None:
    cfg = get_config(ARCH)
    if cfg.dtype != "bfloat16":
        fail(f"{ARCH} dtype is {cfg.dtype}, expected bfloat16")
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.jit(model.init)(jax.random.key(seed))
    jax.block_until_ready(params)
    log(
        f"model {cfg.name} published widths: layers={cfg.num_layers} d_model={cfg.d_model} "
        f"heads={cfg.num_heads}x{cfg.resolved_head_dim} kv_heads={cfg.num_kv_heads} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} dtype={cfg.dtype} "
        f"params={tree_bytes(params)} B (seed {seed}, init {time.perf_counter() - t0:.1f} s)"
    )
    if not device_limit(dev):
        fail(f"device {dev.device_kind} reports no bytes_limit")
    t0 = time.perf_counter()
    slots, max_len, size = choose_size(
        model, N_REQUESTS, PROMPT_RANGE[1] + OUTPUT_RANGE[1], dev
    )
    ecfg = engine_config(slots, max_len)
    log(
        f"{describe(size, N_REQUESTS)}; candidates {list(SIZES)} "
        f"(sizing {time.perf_counter() - t0:.1f} s)"
    )

    def trace():
        return make_trace(cfg.vocab_size, N_REQUESTS, seed)

    tr = trace()
    log(
        f"trace: {N_REQUESTS} requests, prompts {[r.input_len for r, _ in tr]}, "
        f"outputs {[r.output_len for r, _ in tr]}, kairos-urgency/kairos-slack, wall clock"
    )
    res = serve_phase(model, params, ecfg, tr, dev)
    check_complete(tr, res["outputs"])
    log(
        f"jnp phase: {res['completed']}/{N_REQUESTS} DONE with full token counts; "
        f"compile {res['compile_s']:.2f} s; serve {res['serve_s']:.2f} s; "
        f"{res['decode_steps']} decode steps, {res['prefill_tokens']} prefill tokens; "
        f"peak_bytes_in_use {_peak(dev)}"
    )
    t0 = time.perf_counter()
    ref = reference_check(model, params, tr, res["outputs"], ecfg.max_len)
    log(
        f"reference: first tokens match {ref['first_token_match']}/{ref['requests']}; "
        f"token agreement {ref['token_agreement']:.4f} of {ref['tokens']} tokens; "
        f"tokens agreeing before the first divergence {ref['agreeing_prefix']} "
        f"({time.perf_counter() - t0:.1f} s)"
    )
    if ref["first_token_match"] != ref["requests"]:
        fail("first tokens differ from reference_generate")

    pallas_model = build_model(cfg.replace(attn_impl="pallas"))
    chk = pallas_checks(model, pallas_model, params, ecfg, tr[0][1], dev)
    log(
        f"pallas: tpu_custom_call in chunk step {chk['custom_call']['chunk']}, decode step "
        f"{chk['custom_call']['decode']}; chunk-prefill logits rel L2 {chk['logit_rel_l2']:.6f} "
        f"(tolerance {PALLAS_LOGIT_RTOL}), max abs diff {chk['max_abs_diff']:.6f}"
    )
    if not all(chk["custom_call"].values()):
        fail("a Pallas step has no tpu_custom_call: the kernels were not compiled")
    if not chk["finite"] or chk["logit_rel_l2"] > PALLAS_LOGIT_RTOL:
        fail("Pallas chunk-prefill logits outside tolerance")
    tr = trace()
    jnp_outputs = res["outputs"]
    res = serve_phase(pallas_model, params, ecfg, tr, dev)
    check_complete(tr, res["outputs"])
    same = agreement(res["outputs"], jnp_outputs)
    log(
        f"pallas phase: {res['completed']}/{N_REQUESTS} DONE; compile {res['compile_s']:.2f} s; "
        f"serve {res['serve_s']:.2f} s; {res['decode_steps']} decode steps; "
        f"peak_bytes_in_use {_peak(dev)}; against the jnp phase: first tokens equal "
        f"{same['first']}/{N_REQUESTS}, token agreement {same['share']:.4f}"
    )


def run_four_chips(seed: int, devices: Sequence) -> None:
    cfg = get_config(ARCH)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.key(seed))
    ecfg = engine_config(*FLEET_SIZE)

    def trace():
        return make_trace(cfg.vocab_size, N_REQUESTS, seed,
                          FLEET_PROMPT_RANGE, FLEET_OUTPUT_RANGE, gap=0.0)

    t0 = time.perf_counter()
    rep = four_chip_comparison(model, params, ecfg, trace, devices)
    log(
        f"2P:2D {FLEET_DEFLECTION.name} {dict(FLEET_DEFLECTION.kwargs)} fleet, "
        f"{FLEET_SIZE[0]} slots x max_len {FLEET_SIZE[1]}, "
        f"ManualClock: spread over {[d.id for d in devices[:4]]} vs all on {devices[0].id}: "
        f"identical tokens {rep['identical_tokens']}, handoff {rep['identical_handoff']}, "
        f"deflection {rep['identical_deflection']}; handoff {rep['handoff']}, "
        f"deflected {rep['deflected']}; arrays on their devices: spread "
        f"{rep['spread_placed']}, single {rep['single_placed']} "
        f"({time.perf_counter() - t0:.1f} s)"
    )
    checks = ("identical_tokens", "identical_handoff", "identical_deflection",
              "spread_placed", "single_placed", "all_done")
    bad = [k for k in checks if not rep[k]]
    if bad:
        fail(f"four-chip comparison: {bad}")


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip placement phase")
    args = ap.parse_args(argv)
    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    log(f"devices: platform={dev.platform} kind={dev.device_kind} count={len(devices)}")
    if dev.platform != "tpu":
        fail(f"no TPU: JAX's default backend is {dev.platform}")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, found {len(devices)}")
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"compilation cache: {cache} ({entries} entries at start)")
    if args.chips == 4:
        run_four_chips(args.seed, devices)
    else:
        run_one_chip(args.seed, dev)
    print(json.dumps(dict(ok=True, device=dict(
        platform=dev.platform, kind=dev.device_kind, count=len(devices),
    ))))


if __name__ == "__main__":
    main()
