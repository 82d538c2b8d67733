#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print one JSON line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root of
the checkout. With ``--trace 0`` the result carries the cell's end-to-end
metrics; with ``--trace 1`` a few seconds of the window are profiled and it
carries the per-layer metrics, the device's busy and traced seconds, and a
breakdown. The last line of standard output is the result; the compared
numbers, each beside its limit, are the last lines of standard error.
The run needs a TPU with as many chips as the cell asks for; without one,
or without the program's sources beside it, it exits non-zero and prints no
result. JAX's compilation cache is kept in ``.jax_cache/`` in the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))


def fail(msg: str, code: int = 2) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        fail("--seed must be non-negative and --seconds positive")
    try:
        with open(ROOT / "BENCHMARK.json") as f:
            bench = json.load(f)
    except OSError as e:
        fail(f"no BENCHMARK.json at {ROOT}: {e}")
    cells = {w["name"]: w for w in bench["workloads"]}
    w = cells.get(args.workload)
    if w is None:
        fail(f"unknown workload {args.workload!r}; known: {sorted(cells)}")
    import boot

    if not boot.paths():
        fail(f"the program's sources are not at {ROOT / 'src'}")
    boot.compile_cache()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX's default backend is {devices[0].platform}", 3)
    if len(devices) < w["chips"]:
        fail(f"{args.workload} needs {w['chips']} chips, found {len(devices)}", 3)

    import harness

    c = harness.load_cell(HERE, args.workload)
    if (c.cell["config"], c.cell["traffic"]) != (w["config"], w["traffic"]):
        fail(f"cells/{args.workload}.json disagrees with BENCHMARK.json on config or traffic")
    if args.trace:
        metrics = [(m["name"], m["unit"]) for m in bench["per_layer"]
                   if args.workload in m.get("workloads", [args.workload])]
    else:
        metrics = []
    res = harness.run_cell(HERE, args.workload, args.seed, args.seconds, bool(args.trace),
                           devices[0], metrics, T_START)
    if not args.trace:
        keep = {m["name"] for m in bench["end_to_end"]
                if args.workload in m.get("workloads", [args.workload])}
        res["metrics"] = {k: v for k, v in res["metrics"].items() if k in keep}
    print(harness.json_line(res), flush=True)


if __name__ == "__main__":
    main()
