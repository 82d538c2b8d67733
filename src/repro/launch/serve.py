"""Serving launcher: disaggregated engine with registry-driven scheduling.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b-smoke \
        --requests 8 [--policy kairos-urgency] [--decode-policy kairos-slack] \
        [--queue-depth 16] [--list-policies]
    PYTHONPATH=src python -m repro.launch.serve --arch minicpm-2b   # on a TPU

The model runs in its config's own dtype, with random weights from
``--seed``. The engine size (slots x max_len) is the widest of
`repro.launch.sizing.SIZES` that fits the device (`choose_size`); prompts
are 64-768 tokens. ``-smoke`` archs run on the CPU; a full-width arch needs
the chip.

``--policy`` / ``--decode-policy`` accept any name registered in
``repro.policies`` (the same registry the simulator uses); ``--list-policies``
prints them. ``--queue-depth`` bounds the admission queue: submits beyond it
are shed and reported in the session metrics.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core.request import Request, SLOSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.sizing import choose_size, describe
from repro.models import build_model
from repro.policies import available_policies
from repro.serving.engine import DisaggServer, EngineConfig
from repro.serving.session import ServeSession


PROMPT_RANGE = (64, 768)  # prompt tokens, inclusive


def main() -> None:
    enable_compile_cache()
    pol = available_policies()
    ap = argparse.ArgumentParser(
        description="Disaggregated serving demo (policies from repro.policies)"
    )
    ap.add_argument("--arch", default="llama3-8b-smoke")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-out", type=int, default=12)
    ap.add_argument(
        "--policy",
        default="kairos-urgency",
        choices=pol["prefill"],
        help=f"prefill policy; registered: {', '.join(pol['prefill'])}",
    )
    ap.add_argument(
        "--decode-policy",
        default="kairos-slack",
        choices=pol["decode"],
        help=f"decode policy; registered: {', '.join(pol['decode'])}",
    )
    ap.add_argument(
        "--queue-depth", type=int, default=0,
        help="admission-control queue depth; 0 = unbounded",
    )
    ap.add_argument(
        "--list-policies", action="store_true",
        help="print registered policies and exit",
    )
    ap.add_argument("--chunk-size", type=int, default=256)
    ap.add_argument("--ttft-slo", type=float, default=60.0)
    ap.add_argument("--tpot-slo", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.list_policies:
        for side, names in pol.items():
            print(f"{side}: {', '.join(names)}")
        return

    cfg = get_config(args.arch)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.key(args.seed))
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"{cfg.name} in {cfg.dtype}")
    slots, max_len, size = choose_size(
        model, args.requests, PROMPT_RANGE[1] + args.max_out, dev, chunk=args.chunk_size
    )
    print(describe(size, args.requests))
    rng = np.random.default_rng(args.seed)

    reqs = []
    for i in range(args.requests):
        n = int(rng.integers(PROMPT_RANGE[0], PROMPT_RANGE[1] + 1))
        prompt = list(map(int, rng.integers(2, cfg.vocab_size, n)))
        reqs.append(
            (
                Request(rid=i, arrival=0.05 * i, input_len=n, output_len=args.max_out,
                        slo=SLOSpec(ttft=args.ttft_slo, tpot=args.tpot_slo)),
                prompt,
            )
        )

    ecfg = EngineConfig(
        max_slots=slots, max_len=max_len, chunk_size=args.chunk_size,
        prefill_policy=args.policy, decode_policy=args.decode_policy,
        admission_queue_depth=args.queue_depth or None,
    )
    server = DisaggServer(model, params, ecfg, device=dev)
    t0 = time.perf_counter()
    server.warmup()
    print(f"compile (warm-up of every step shape): {time.perf_counter() - t0:.2f} s")

    # drive the streaming session directly (what serve() wraps) so the
    # admission metrics stay in hand
    session = ServeSession(server)
    outs = session.run(reqs)
    n_ok = 0
    for r, _ in reqs:
        ok = r.meets_e2e()
        n_ok += ok
        print(
            f"rid={r.rid} phase={r.phase.value} tokens={len(outs.get(r.rid, []))} "
            f"ttft={(r.ttft() or 0):.2f}s mean_itl={1e3*(r.mean_tpot() or 0):.0f}ms e2e_ok={ok}"
        )
    s = session.summary()
    print(
        f"E2E SLO attainment: {n_ok}/{len(reqs)} "
        f"(submitted={s['submitted']} shed={s['rejected']})"
    )


if __name__ == "__main__":
    main()
