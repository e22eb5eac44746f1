"""Benchmark harness: one module per paper table/figure.

``python -m benchmarks.run [--only fig11,...]`` prints name,us_per_call,
derived CSV rows for every experiment (paper §5 scaled to this container).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks.common import CSV


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma list of bench names")
    args = ap.parse_args()

    from repro.compile_cache import setup_compile_cache

    setup_compile_cache()
    from benchmarks import (
        batch_bench,
        cache_bench,
        cluster_bench,
        cursor_bench,
        engine_bench,
        fig11_queries,
        fig13_groupsize,
        fig14_16_stores,
        fig17_ycsb,
        kernels_bench,
        rebuild_bench,
        scrub_bench,
        table1_storage,
    )

    benches = {
        "fig11": lambda c: fig11_queries.run(c, locality="weak"),
        "fig12": lambda c: fig11_queries.run(c, locality="strong"),
        "fig13": fig13_groupsize.run,
        "table1": table1_storage.run,
        "fig14_16": fig14_16_stores.run,
        "fig17": fig17_ycsb.run,
        "kernels": kernels_bench.run,
        "rebuild": rebuild_bench.run,
        # scrub throughput, paced scrub, REMIX repair round trip
        "scrub": scrub_bench.run,
        "cache": cache_bench.run,
        # also emits results/BENCH_queries.json (the perf trajectory file)
        "batch": batch_bench.run,
        # streaming cursor vs re-seeking scans (results/BENCH_cursor.json)
        "cursor": cursor_bench.run,
        # typed op batches through submit() (results/BENCH_engine.json)
        "engine": engine_bench.run,
        # live shard split + replica catch-up (results/BENCH_cluster.json)
        "cluster": cluster_bench.run,
    }
    if args.only:
        names = args.only.split(",")
    else:
        names = list(benches)
    csv = CSV()
    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        t0 = time.time()
        try:
            benches[name](csv)
        except Exception:
            failures += 1
            traceback.print_exc()
            csv.emit(f"{name}_FAILED", -1.0, "exception")
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
