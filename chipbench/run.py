"""Run one cell of BENCHMARK.json on the chip and print its result line.

    python3 chipbench/run.py --workload fvc2004.enrol_backlog --seed 7 \\
        --seconds 20 --trace 0

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics, or with
`--trace 1` its per-layer metrics), `device`, with `--trace 1` a
`breakdown`, then `load` and, last, `checks`: each number compared with
the plain reference beside its limit. The same checks close standard
error. Without a TPU, with fewer chips than the cell needs, or on a device
kind that `peaks.json` lacks, the run exits 2 and prints no result.

JAX's persistent compilation cache lives at `.jax_cache/` in the checkout,
so only the first run of a cell there compiles.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHIPBENCH = Path(__file__).resolve().parent
CACHE_DIR = CHIPBENCH.parent / ".jax_cache"


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: per-layer metrics from a traced run")
    ap.add_argument("--control", action="store_true",
                    help="serve with the configuration's control, a path "
                         "that breaks its exactness: `correct` must come "
                         "out false (never a timed run)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    # the program takes the benchmark's cache directory: fixed, in the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(CHIPBENCH))
    import harness
    import kernelwork

    try:
        cell = harness.load_cell(args.workload)
    except harness.CellError as err:
        print(f"chipbench: {err}", file=sys.stderr)
        return 2
    peaks = kernelwork.load_peaks()
    try:
        harness.set_malloc(cell.traffic.get("malloc", {}))
        devices = harness.check_device(cell.chips, peaks)
    except harness.DeviceError as err:
        print(f"chipbench: {err}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(harness.SRC))
    from repro.core.platform import enable_compile_cache
    enable_compile_cache()
    line = harness.run_cell(cell, args.seed, args.seconds,
                            trace=bool(args.trace), t_process=T_PROCESS,
                            devices=devices, control=args.control,
                            peak=peaks[devices[0].device_kind])
    unmatched = line["load"].get("unmatched_op_share")
    if unmatched is not None:
        print(f"chipbench: device operation time matched by no kernel "
              f"pattern: {unmatched:.6f} of all", flush=True)
    print(json.dumps(line), flush=True)
    for name, check in line["checks"].items():
        bound = (f"<= {check['limit']}" if "limit" in check
                 else f">= {check['min']}")
        print(f"check {name}: {check['value']} {bound}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
