"""Benchmark harness entry point — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [suite ...]

Suites: fig6 (latency-recall), tables (breakdown), throughput, insert,
roofline, serving (offered-load sweep -> BENCH_serving.json), quant
(recall-vs-bytes tier-split sweep -> BENCH_quant.json), pool (modeled
latency vs simulated network parameters -> BENCH_pool.json).
Default: all.  Prints ``name,us_per_call,key=val...`` CSV.
Scale via REPRO_BENCH_SCALE={quick,full} (see benchmarks/common.py).
"""
from __future__ import annotations

import os
import sys
import time
import traceback

SUITES = ["fig6", "tables", "throughput", "insert", "roofline", "serving",
          "quant", "pool"]


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    want = sys.argv[1:] or SUITES
    print(f"# benchmark run: suites={want}", flush=True)
    failures = []
    for suite in want:
        t0 = time.time()
        print(f"# --- {suite} ---", flush=True)
        try:
            if suite == "fig6":
                from benchmarks.latency_recall import run
                run()
            elif suite == "tables":
                from benchmarks.breakdown import run
                run()
            elif suite == "throughput":
                from benchmarks.throughput import run
                run()
            elif suite == "insert":
                from benchmarks.insert import run
                run()
            elif suite == "roofline":
                from benchmarks.roofline import main as rl
                rl()
            elif suite == "serving":
                from benchmarks.serving import run as sv
                sv(smoke=os.environ.get("REPRO_BENCH_SCALE",
                                        "quick") == "quick")
            elif suite == "quant":
                from benchmarks.quant import run as qr
                qr(smoke=os.environ.get("REPRO_BENCH_SCALE",
                                        "quick") == "quick")
            elif suite == "pool":
                from benchmarks.pool import run as pr
                pr(smoke=os.environ.get("REPRO_BENCH_SCALE",
                                        "quick") == "quick")
            else:
                print(f"# unknown suite {suite}")
                continue
        except Exception:
            failures.append(suite)
            print(f"# SUITE FAILED: {suite}")
            traceback.print_exc()
        print(f"# --- {suite} done in {time.time() - t0:.1f}s ---",
              flush=True)
    if failures:
        sys.exit(f"failed suites: {failures}")


if __name__ == "__main__":
    main()
