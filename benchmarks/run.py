"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--only NAME]
                                            [--json-dir DIR]

Prints ``name,us_per_call,derived`` CSV and writes one machine-readable
``BENCH_<suite>.json`` per suite into --json-dir (default: the repo
root, wherever the harness is launched from, so bench-smoke refreshes
the COMMITTED per-PR perf trajectory in place; pass --json-dir '' to
disable) with us/round + every derived metric (rounds/sec etc.) parsed
into numbers. ``benchmarks/check_regression.py`` diffs a fresh
BENCH_fused_rounds.json against the committed baseline in CI.
Mapping to the paper:
    bench_convergence   -> Figs. 2 & 8 (psi percentiles vs k)
    bench_comm_timing   -> Figs. 3 & 9 (Poisson schedule)
    bench_cop_surface   -> Figs. 4, 5 & 10 (CoP vs n, eps + fitted bound)
    bench_collaboration -> Figs. 6 & 7 (value of collaboration)
    bench_async_vs_sync -> Sec. 2 comparison ([14]-style sync baseline)
                           + beyond-paper capped-rounds composition
                           + deep-path fused vs per-round driver
    bench_fused_rounds  -> beyond-paper: rounds/sec scaling of the fused
                           multi-round driver (device-resident ledger)
    bench_serving       -> beyond-paper: serving-path latency (no paper
                           figure; guards the hybrid-serving example)
    bench_chaos         -> beyond-paper: fault-layer guard overhead +
                           convergence degradation under injected faults
    bench_paged_bank    -> beyond-paper: paged owner bank — full-residency
                           parity overhead + resident-bytes scaling on
                           10k/100k-owner availability traces
    bench_kernels       -> kernel-path microbenches (CPU)
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--fast", action="store_true",
                    help="reduced run counts (CI mode)")
    ap.add_argument("--json-dir",
                    default=os.path.dirname(os.path.dirname(
                        os.path.abspath(__file__))),
                    help="where BENCH_<suite>.json files land "
                         "(default: the repo root; '' disables)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    print(f"# compile cache: {enable_compile_cache()}", file=sys.stderr)

    from benchmarks import (bench_async_vs_sync, bench_chaos,
                            bench_collaboration, bench_comm_timing,
                            bench_convergence, bench_cop_surface,
                            bench_fused_rounds, bench_kernels,
                            bench_paged_bank, bench_serving)

    suites = {
        "comm_timing": bench_comm_timing.run,
        "kernels": bench_kernels.run,
        "serving": bench_serving.run,
        "convergence": (lambda: bench_convergence.run(n_runs=20)) if args.fast
        else bench_convergence.run,
        "cop_surface": bench_cop_surface.run,
        "collaboration": bench_collaboration.run,
        "async_vs_sync": lambda: bench_async_vs_sync.run(fast=args.fast),
        "fused_rounds": lambda: bench_fused_rounds.run(fast=args.fast),
        "chaos": lambda: bench_chaos.run(fast=args.fast),
        "paged_bank": lambda: bench_paged_bank.run(fast=args.fast),
    }
    from benchmarks.common import write_bench_json

    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites.items():
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            rows = list(fn())
            for row in rows:
                print(f"{row[0]},{row[1]:.1f},{row[2]}")
            if args.json_dir:
                write_bench_json(
                    os.path.join(args.json_dir, f"BENCH_{name}.json"),
                    name, rows, time.time() - t0)
        except Exception as e:  # keep the harness going
            failures += 1
            print(f"{name},0.0,ERROR:{type(e).__name__}:{e}")
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
