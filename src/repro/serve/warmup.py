"""Warm-start pre-compiler for the serving datapath (DESIGN.md §10).

    PYTHONPATH=src python -m repro.serve.warmup \\
        --shapes 128x128,256x256 --filters gaussian3,gaussian5 \\
        --methods refmlm --execs local --batches 1,8

Each point of the cross product is one warm `serve_key` -- shape bucket ×
filter × exec × traced batch size, the same keying as the tuning cache
(`repro.tuning.config_key`) one level up. Warming runs a zero dummy
batch through the exact `apply_filter_batch` dispatch the server will
issue, so jax's jit cache is populated before the first real request: first-hit
latency collapses to steady-state latency, amortised at deploy time
instead of on a user.

A running server exposes the same sweep as `ImageFilterServer.warmup()`;
this CLI is the deploy-time entry point (run it before admitting
traffic, like `repro.tuning.autotune` is run before benchmarking). The
CLI keeps JAX's persistent compile cache on
(`repro.core.platform.enable_compile_cache`), so the executables it
compiles outlive the process for the server started after it.
"""
from __future__ import annotations

import argparse
import itertools
import time

from repro.core.platform import enable_compile_cache
from repro.filters.bank import FILTER_NAMES
from repro.serve.executor import BatchExecutor


def parse_shapes(text: str) -> list[tuple[int, int]]:
    shapes = []
    for part in text.split(","):
        h, _, w = part.strip().partition("x")
        shapes.append((int(h), int(w)))
    return shapes


def sweep(executor: BatchExecutor, shapes, filters, methods, execs,
          batches, *, nbits: int = 8, priorities=("normal",),
          workload: str = "filter", verbose: bool = False) -> list[str]:
    """Warm the cross product of serve points on `executor`; returns the
    warmed keys. The one sweep definition shared by this CLI and
    `ImageFilterServer.warmup()`. `priorities` widens the warmed-ledger
    cross product (§13 buckets are per-class); the compiled executables
    are priority-blind, so extra classes cost bookkeeping, not compiles.
    `workload` selects the §14 class being warmed ('filter' by default;
    `filters` then names that workload's targets). The pixel container
    follows `nbits` (`repro.serve.executor.warm_container`): a width above
    8 bits warms the uint16 container of wider pixels (DESIGN.md §5)."""
    keys = []
    for (h, w), filt, method, em, n, pri in itertools.product(
            shapes, filters, methods, execs, batches, priorities):
        t0 = time.perf_counter()
        key = executor.warm((int(h), int(w)), filt, method=method,
                            exec_mode=em, nbits=nbits,
                            n=int(n), priority=pri, workload=workload)
        keys.append(key)
        if verbose:
            dt = (time.perf_counter() - t0) * 1e3
            print(f"warmed {key}  ({dt:.0f} ms)")
    return keys


def warm(shapes, filters, methods, execs, batches, *,
         interpret: bool | None = None, verbose: bool = True) -> list[str]:
    """Run the warmup sweep on a fresh executor; returns the warmed keys."""
    return sweep(BatchExecutor(interpret=interpret), shapes, filters,
                 methods, execs, batches, verbose=verbose)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--shapes", default="128x128",
                    help="comma-separated HxW shape buckets")
    ap.add_argument("--filters", default=",".join(FILTER_NAMES))
    ap.add_argument("--methods", default="refmlm")
    ap.add_argument("--execs", default="local",
                    help="comma-separated exec modes (DESIGN.md §9)")
    ap.add_argument("--batches", default="1,8",
                    help="comma-separated traced batch sizes")
    args = ap.parse_args(argv)
    enable_compile_cache()
    keys = warm(parse_shapes(args.shapes),
                args.filters.split(","), args.methods.split(","),
                args.execs.split(","),
                [int(b) for b in args.batches.split(",")])
    print(f"warmed {len(keys)} serve keys")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
