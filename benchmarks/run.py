"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Usage:
    PYTHONPATH=src python -m benchmarks.run [--quick] [--workloads-only]

``--workloads-only`` runs just the workloads scenario matrix and writes the
perf record (the slice CI's bench-gate compares against the committed
``BENCH_workloads.json``); ``--bench-out`` redirects that record so a gate
run never overwrites the baseline it is judging itself against.

Event tracing (`repro.obs`) and these benchmarks: benchmark runs leave
``HarnessConfig.trace`` at its ``None`` default, which keeps every emission
site on its no-recorder fast path — the overhead guard in
``tests/test_obs.py`` pins that a trace-enabled run is bit-identical in
virtual time and adds no metric drift, so perf records stay comparable
whether or not a diagnostic rerun traced the same cells.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="skip kernel microbenches")
    ap.add_argument(
        "--workloads-only", action="store_true",
        help="only the workloads scenario matrix + its perf record",
    )
    ap.add_argument(
        "--bench-out", default=None,
        help="where to write the workloads perf record "
        "(default: the repo's BENCH_workloads.json)",
    )
    args = ap.parse_args()

    print("name,value,derived")
    t0 = time.perf_counter()

    if args.workloads_only:
        from benchmarks import paper_figs

        record = paper_figs.workloads_bench_record()
        bench_path = pathlib.Path(
            args.bench_out
            or pathlib.Path(__file__).resolve().parent.parent / "BENCH_workloads.json"
        )
        bench_path.write_text(json.dumps(record, indent=2, sort_keys=True) + "\n")
        print(f"bench_workloads_wall_s,{record['total_wall_s']:.1f},{bench_path.name}")
        print(f"total_bench_wall_s,{time.perf_counter()-t0:.1f},")
        return

    # the policy surface under test, straight from the registry (the same
    # enumeration the simulator, engine, and CLI consume)
    from repro.policies import available_policies

    pol = available_policies()
    print(f"policy_registry,{len(pol['prefill'])}+{len(pol['decode'])},"
          f"prefill={'/'.join(pol['prefill'])};decode={'/'.join(pol['decode'])}")

    from benchmarks import paper_figs

    for fn in [
        paper_figs.fig1a_trace_distribution,
        paper_figs.fig1b_decode_step_vs_seqlen,
        paper_figs.fig3_e2e_attainment,
        paper_figs.fig4_ttft_attainment,
        paper_figs.fig5_tpot_attainment,
        paper_figs.fig6_decode_throughput,
        paper_figs.fig7_scenario_matrix,
        paper_figs.headline_gains,
    ]:
        for row in fn():
            print(row)
        sys.stdout.flush()

    # perf record: scenario-matrix wall time + decode throughput, one JSON
    # file per run so the bench trajectory is diffable across PRs
    record = paper_figs.workloads_bench_record()
    bench_path = pathlib.Path(
        args.bench_out
        or pathlib.Path(__file__).resolve().parent.parent / "BENCH_workloads.json"
    )
    bench_path.write_text(json.dumps(record, indent=2, sort_keys=True) + "\n")
    print(f"bench_workloads_wall_s,{record['total_wall_s']:.1f},{bench_path.name}")

    if not args.quick:
        from benchmarks.kernel_bench import kernel_rows, scheduler_rows

        for row in scheduler_rows():
            print(row)
        for row in kernel_rows():
            print(row)

    print(f"total_bench_wall_s,{time.perf_counter()-t0:.1f},")


if __name__ == "__main__":
    main()
