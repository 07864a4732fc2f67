"""Find the knee of an open or burst cell: one set-up, then one window per
offered load, in one process.

    python3 chipbench/sweep.py --workload fvc2004.verify_open --seed 11 \\
        --seconds 8 --param rate_per_s --values 200,400,800,1600

For each value of the traffic parameter it prints one JSON line: the
offered and completed request rates, p50 and p95 latency (due instant to
answer), p95 over the first and the last third of the window (a backlog
that grows shows as the last third's tail pulling away), the drain after
the window, and the generator's lateness. A cell's rate is fixed in its
traffic file from such a sweep, at about four fifths of the knee; the
benchmark's own runs never search for one.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from pathlib import Path

CHIPBENCH = Path(__file__).resolve().parent


def third_p95(reqs, t0, part: int, seconds: float, nearest_rank):
    lo, hi = part * seconds / 3, (part + 1) * seconds / 3
    lat = [(r.done - (t0 + r.due)) * 1e3 for r in reqs
           if r.ok and lo <= r.due < hi]
    return nearest_rank(lat, 95) if lat else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--param", required=True,
                    help="traffic parameter to vary (rate_per_s, burst)")
    ap.add_argument("--values", required=True,
                    help="comma-separated values, in the order to run")
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="fix another traffic parameter for the whole sweep")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CHIPBENCH.parent
                                                  / ".jax_cache")
    sys.path.insert(0, str(CHIPBENCH))
    import harness
    import kernelwork

    cell = harness.load_cell(args.workload)
    for item in args.set:
        key, raw = item.split("=", 1)
        cell.traffic[key] = json.loads(raw)
    try:
        harness.check_device(cell.chips, kernelwork.load_peaks())
    except harness.DeviceError as err:
        print(f"sweep: {err}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(harness.SRC))
    from repro.core.platform import enable_compile_cache
    enable_compile_cache()
    mult = harness.multiplier(cell)
    kinds, pools, srv = harness.prepare(cell, args.seed, mult)
    try:
        for raw in args.values.split(","):
            value = json.loads(raw)
            traffic = dict(cell.traffic, **{args.param: value})
            swept = dataclasses.replace(cell, traffic=traffic)
            answers = harness.Answers(kinds, 0, args.seed)
            t0 = harness.clock()
            reqs = harness.drive(srv, swept, kinds, pools, mult, args.seed,
                                 args.seconds, answers, t0, annotate=False)
            done = [r for r in reqs if r.ok]
            t_last = max((r.done for r in done), default=t0)
            lat = [(r.done - (t0 + r.due)) * 1e3 for r in done]
            late = [(r.sent - (t0 + r.due)) * 1e3 for r in reqs if r.ok]
            nr = harness.nearest_rank
            print(json.dumps({
                "workload": args.workload, args.param: value,
                "offered_per_s": len(reqs) / args.seconds,
                "completed_per_s": len(done) / max(t_last - t0, 1e-9),
                "failed": len(reqs) - len(done),
                "p50_ms": nr(lat, 50) if lat else None,
                "p95_ms": nr(lat, 95) if lat else None,
                "p95_first_third_ms": third_p95(done, t0, 0, args.seconds, nr),
                "p95_last_third_ms": third_p95(done, t0, 2, args.seconds, nr),
                "drain_s": max(t_last - (t0 + args.seconds), 0.0),
                "late_p95_ms": nr(late, 95) if late else None,
            }), flush=True)
    finally:
        srv.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
