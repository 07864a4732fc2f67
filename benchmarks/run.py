"""Benchmark aggregator: one module per paper table + kernel bench +
serving bench.

``PYTHONPATH=src python -m benchmarks.run``   prints name,us_per_call,derived
CSV for every row, writes the machine-readable perf artifacts --
BENCH_kernels.json (kernel_* rows), BENCH_serve.json (serve_* rows, the
DESIGN.md §10 serving SLO schema) and BENCH_infer.json (infer_* rows, the
DESIGN.md §14 per-method accuracy/throughput schema; see
benchmarks/common.py) -- and exits nonzero if any table's invariant fails.
"""
from __future__ import annotations

import sys
import time
import traceback

from benchmarks.common import write_bench_json


def main() -> None:
    from repro.core.platform import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (infer_bench, kernel_bench, serve_bench,
                            table1_2x2, table6_error, table7_4x4,
                            table8_dist, table9_scaling, table10_psnr)
    mods = [table1_2x2, table6_error, table7_4x4, table8_dist,
            table9_scaling, table10_psnr, kernel_bench, serve_bench,
            infer_bench]
    print("name,us_per_call,derived")
    failures = []
    for mod in mods:
        t0 = time.perf_counter()
        try:
            mod.main()
            print(f"# {mod.__name__} ok in {time.perf_counter()-t0:.1f}s")
        except Exception:                              # noqa: BLE001
            failures.append(mod.__name__)
            traceback.print_exc()
    if failures:
        # Don't refresh the perf artifact from a broken run -- a partial row
        # set would silently truncate the README table downstream.
        print(f"# FAILED: {failures} (BENCH_kernels.json/BENCH_serve.json/"
              "BENCH_infer.json not written)")
        sys.exit(1)
    write_bench_json()
    write_bench_json("BENCH_serve.json", prefix="serve_")
    write_bench_json("BENCH_infer.json", prefix="infer_")
    print("# all benchmark tables passed")


if __name__ == "__main__":
    main()
