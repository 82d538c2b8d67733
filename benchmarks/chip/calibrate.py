#!/usr/bin/env python3
"""Measurements on the chip from which the benchmark's fixed numbers were set.

    python3 benchmarks/chip/calibrate.py size <config> <slots>x<max_len> ...
    python3 benchmarks/chip/calibrate.py slo <config> [--out FILE]
    python3 benchmarks/chip/calibrate.py sweep <cell> --rates R ... --seconds S --seed N
    python3 benchmarks/chip/calibrate.py control <cell> --seeds N ... --seconds S

``size`` runs the program's ``choose_size`` over the candidates and prints
its report (the engine size a configuration file records). ``slo``
measures unloaded single-request TTFT over prompt lengths and batch-1 TPOT
on the configuration's server and prints the fit ``a + b * prompt_tokens``
(the SLO rule a configuration file freezes). ``sweep`` drives a cell at each
rate with one server and prints the end-to-end metrics per rate (the knee).
``control`` runs the cell briefly per seed and makes the check twice on a
sample of the served tokens: on the program's tokens, and on the int8
control's tokens put in their place (the two readings a check's limit is
set between; the control has to come out not correct).
None of these runs in a benchmark run. Each needs a TPU.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import boot  # noqa: E402


def log(msg: str) -> None:
    print(msg, flush=True)


def device():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"calibrate: no TPU: JAX's default backend is {dev.platform}")
    return dev


def cmd_size(args) -> None:
    import harness
    from repro.launch.sizing import choose_size, describe
    from repro.models import build_model

    _, arch, spec = harness.load_config(HERE, args.config)
    sizes = [tuple(int(x) for x in s.split("x")) for s in args.sizes]
    _, _, rep = choose_size(build_model(arch.model_config(spec)), args.requests,
                            args.seq_len, device(), sizes=sizes, chunk=args.chunk)
    log(json.dumps(dict(config=args.config, candidates=sizes, n_requests=args.requests,
                        seq_len=args.seq_len, report=rep)))
    log(describe(rep, args.requests))


def _cell_with(name: str, slo_file):
    import harness

    c = harness.load_cell(HERE, name)
    if slo_file:
        with open(slo_file) as f:
            c.conf["slo"] = json.load(f)["slo"]
    return c


def cmd_slo(args) -> None:
    import numpy as np

    import harness
    from repro.core.request import Request, SLOSpec
    from repro.serving.session import ServeSession

    conf, arch, spec = harness.load_config(HERE, args.config)
    c = harness.Cell("slo", conf, {}, {}, arch, spec)
    dev = device()
    srv = harness.build_server(c, 0, dev)
    harness.warm_up(srv, c, dev)
    rng = np.random.default_rng(0)

    def one(n_in: int, n_out: int):
        """(TTFT, TPOT) of one request alone, on the driver's clock."""
        prompt = rng.integers(2, c.spec.vocab, n_in).tolist()
        req = Request(rid=0, arrival=0.0, input_len=n_in, output_len=n_out, slo=SLOSpec(60, 60))
        times = []
        session = ServeSession(srv, on_token=lambda *_: times.append(time.perf_counter()))
        t0 = time.perf_counter()
        session.run([(req, prompt)])
        return times[0] - t0, (times[-1] - times[0]) / max(1, len(times) - 1)

    ns, ttfts = [], []
    for n in args.lengths:
        vals = sorted(one(n, 2)[0] for _ in range(args.repeats))
        ns.append(n)
        ttfts.append(vals[len(vals) // 2])
        log(f"ttft prompt {n}: median {ttfts[-1]:.6f} s of {[round(v, 6) for v in vals]}")
    tp = sorted(one(args.tpot_prompt, args.tpot_tokens)[1] for _ in range(args.repeats))
    b, a = np.polyfit(ns, ttfts, 1)
    slo = dict(k=args.k, ttft_base_s=float(a), ttft_per_token_s=float(b),
               tpot_s=float(tp[len(tp) // 2]))
    log(f"batch-1 tpot (prompt {args.tpot_prompt}, {args.tpot_tokens} tokens): "
        f"{[round(v, 6) for v in tp]}")
    out = dict(config=args.config, lengths=ns, ttft_s=ttfts, tpot_runs_s=tp, slo=slo)
    log(json.dumps(out))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f)


def cmd_sweep(args) -> None:
    import harness

    c = _cell_with(args.cell, args.slo_file)
    dev = device()
    srv = harness.build_server(c, args.seed, dev)
    bar = harness.warm_up(srv, c, dev)
    k0 = c.conf["slo"]["k"]
    for rate in args.rates:
        c.cell["rate"] = rate
        try:
            d = harness.drive(srv, c, args.seed, args.seconds, False, bar,
                              harness.CompileCounter())
        except Exception as e:  # an overloaded engine can run out of memory
            log(json.dumps(dict(cell=args.cell, rate=rate, error=repr(e)[:300])))
            break
        m = harness.end_to_end(d, args.seconds, 0.0)
        ttft, tpot, _, failed = harness.request_times(d)
        timed = [d.timed[r.rid] for r in d.counted]
        # attainment had the limits been k/k0 times as wide (schedulers
        # unchanged): a guide to k, not a measurement at that k
        at_k = {}
        for k in (3, 4, 5, 6, 8, 10):
            met = [
                t.finished and a <= t.ttft_limit * k / k0 and b <= t.tpot_limit * k / k0
                for t, a, b in zip(timed, ttft, tpot, strict=True)
            ]
            at_k[str(k)] = round(100.0 * sum(met) / max(1, len(met)), 2)
        log(json.dumps(dict(cell=args.cell, rate=rate, seconds=args.seconds,
                            attempted=len(d.counted), failed=failed,
                            late_p90_ms=1e3 * d.late_p90_s, attainment_if_k=at_k,
                            ttft_p50_s=harness.stats.percentile(ttft, 50),
                            tpot_p50_ms=1e3 * harness.stats.percentile(tpot, 50), **m)))
        srv.reset_for_restart()


def cmd_control(args) -> None:
    import harness

    c = _cell_with(args.cell, args.slo_file)
    dev = device()
    for seed in args.seeds:
        t0 = time.perf_counter()
        got = harness.control_readings(c, seed, args.seconds, dev)
        log(json.dumps(dict(cell=args.cell, seed=seed, seconds=time.perf_counter() - t0, **got)))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    p = sub.add_parser("size")
    p.add_argument("config")
    p.add_argument("sizes", nargs="+")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=968)
    p.add_argument("--chunk", type=int, default=256)
    p = sub.add_parser("slo")
    p.add_argument("config")
    p.add_argument("--lengths", type=int, nargs="+",
                   default=[64, 128, 256, 384, 512, 640, 768, 960])
    p.add_argument("--repeats", type=int, default=3)
    p.add_argument("--tpot-prompt", type=int, default=128)
    p.add_argument("--tpot-tokens", type=int, default=64)
    p.add_argument("--k", type=float, default=3.0)
    p.add_argument("--out")
    for name in ("sweep", "control"):
        p = sub.add_parser(name)
        p.add_argument("cell")
        p.add_argument("--seconds", type=float, default=20.0)
        p.add_argument("--slo-file")
        if name == "sweep":
            p.add_argument("--rates", type=float, nargs="+", required=True)
            p.add_argument("--seed", type=int, default=1)
        else:
            p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    if not boot.paths():
        raise SystemExit("calibrate: the program's sources are not in the checkout")
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    boot.compile_cache(Path(env) if env else boot.ROOT / ".jax_cache")
    dict(size=cmd_size, slo=cmd_slo, sweep=cmd_sweep, control=cmd_control)[args.cmd](args)


if __name__ == "__main__":
    main()
