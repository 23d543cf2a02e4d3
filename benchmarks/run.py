"""Benchmark registry — one function per paper table/figure (plus framework
benches added alongside their subsystems).  Prints ``name,us_per_call,derived``
CSV rows.

Usage:
    PYTHONPATH=src python -m benchmarks.run             # everything
    PYTHONPATH=src python -m benchmarks.run --quick     # reduced sweep
    PYTHONPATH=src python -m benchmarks.run --only fig6 # one group
"""

from __future__ import annotations

import argparse
import importlib
import sys
import traceback

from repro.launch.compile_cache import enable_compile_cache

BENCHES = {
    "fig6": "benchmarks.bench_capacity_sweep",
    "fig7": "benchmarks.bench_migration_trace",
    "fig8": "benchmarks.bench_large_mem",
    "table2": "benchmarks.bench_profile_overhead",
    "kernels": "benchmarks.bench_kernels",
    "serve": "benchmarks.bench_serving",
    "train": "benchmarks.bench_train",
    "roofline": "benchmarks.roofline",
}

# Smallest set that exercises every Algorithm-1 backend (simulator, paged
# KV serving — including the one-shot vs chunked prefill-throughput case —
# trainer arenas) plus the Pallas kernel sweep (grouped-expert GEMM
# included) — the CI job that keeps perf scripts alive.
SMOKE_GROUPS = ("fig7", "serve", "train", "kernels")


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--only", type=str, default=None,
                        help="comma-separated bench group names")
    parser.add_argument("--quick", action="store_true",
                        help="reduced sweeps for CI")
    parser.add_argument("--smoke", action="store_true",
                        help="CI smoke: quick mode over one bench per "
                             "guidance backend; persists each group's rows "
                             "as BENCH_<group>.json (rows + git rev + "
                             "timestamp)")
    args = parser.parse_args()
    enable_compile_cache()

    if args.smoke:
        args.quick = True
        if args.only is None:
            args.only = ",".join(SMOKE_GROUPS)
    names = list(BENCHES) if args.only is None else args.only.split(",")
    print("name,us_per_call,derived")
    failures = []
    for name in names:
        modname = BENCHES.get(name)
        if modname is None:
            print(f"unknown bench group: {name}", file=sys.stderr)
            failures.append(name)
            continue
        try:
            rows = importlib.import_module(modname).run(quick=args.quick)
        except Exception:
            traceback.print_exc()
            failures.append(name)
            continue
        if args.smoke and rows:
            # Persist the trajectory under the module's short name
            # (bench_serving -> BENCH_serving.json) — rows + git rev +
            # timestamp, uploaded as a CI artifact.
            from .common import write_bench_json
            short = modname.rsplit(".", 1)[-1]
            short = short[len("bench_"):] if short.startswith("bench_") \
                else short
            path = write_bench_json(short, rows)
            print(f"# wrote {path}", file=sys.stderr)
    if failures:
        sys.exit(f"benchmark groups failed: {failures}")


if __name__ == "__main__":
    main()
