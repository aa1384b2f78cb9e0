"""Benchmark harness — one function per paper table/figure + roofline, plus
the executor-backend suite.

    PYTHONPATH=src python -m benchmarks.run [--only table5]
    PYTHONPATH=src python -m benchmarks.run --only vectorvm   # writes
        BENCH_vectorvm.json (windowed numpy/jax vs resident executor
        timings; see benchmarks/vectorvm_bench.py env knobs)
    PYTHONPATH=src python -m benchmarks.run --only api        # writes
        BENCH_api.json (front-end dispatch overhead vs direct VectorVM)
    PYTHONPATH=src python -m benchmarks.run --only compile    # writes
        BENCH_compile.json (per-pass wall time + IR node deltas per app)
    PYTHONPATH=src python -m benchmarks.run --only serve      # writes
        BENCH_serve.json (batched vs sequential serving throughput)
    PYTHONPATH=src python -m benchmarks.run --only place      # writes
        BENCH_place.json (placement resource reports + throughput vs
        replica count; see benchmarks/place_bench.py env knobs)
    PYTHONPATH=src python -m benchmarks.run --only traffic    # writes
        BENCH_traffic.json (open-loop Poisson p50/p99 + goodput at an
        SLO, async engine vs closed-loop baseline; see
        benchmarks/traffic_bench.py env knobs)

Prints ``name,us_per_call,derived`` CSV rows per benchmark cell.
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: table3,table4,table5,fig12,fig13,"
                         "fig14,roofline,vectorvm,micro,api,compile,serve,"
                         "place,traffic")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    from . import (api_bench, backends, compile_bench, figures, place_bench,
                   roofline, serve_bench, tables, traffic_bench,
                   vectorvm_bench)
    benches = {
        "table3": tables.table3_apps,
        "table4": tables.table4_resources,
        "table5": tables.table5_throughput,
        "fig12": figures.fig12_opt_ablations,
        "fig13": figures.fig13_hierarchy_removal,
        "fig14": figures.fig14_load_balance,
        "roofline": roofline.roofline_rows,
        "vectorvm": vectorvm_bench.vectorvm_backends,
        "micro": backends.reduce_micro,
        "api": api_bench.api_dispatch,
        "compile": compile_bench.compile_pipeline,
        "serve": serve_bench.serve_batching,
        "place": place_bench.place_replication,
        "traffic": traffic_bench.traffic_open_loop,
    }
    if only:
        unknown = only - set(benches)
        if unknown:
            print(f"unknown bench name(s): {sorted(unknown)}; "
                  f"available: {sorted(benches)}", file=sys.stderr)
            sys.exit(2)
    rows: list[dict] = []
    print("name,us_per_call,derived")
    for bname, fn in benches.items():
        if only and bname not in only:
            continue
        t0 = time.perf_counter()
        try:
            fn(rows)
            dt_us = (time.perf_counter() - t0) * 1e6
            new = [r for r in rows if r.get("bench") == bname]
            for r in new:
                derived = ";".join(f"{k}={v}" for k, v in r.items()
                                   if k not in ("bench", "name"))
                print(f"{bname}/{r.get('name', r.get('variant', '?'))},"
                      f"{dt_us / max(len(new), 1):.0f},{derived}")
        except Exception as e:  # keep the harness running
            print(f"{bname},0,ERROR={e!r}", file=sys.stderr)
            raise


if __name__ == "__main__":
    main()
