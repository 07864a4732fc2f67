#!/usr/bin/env python
"""Regenerate the README "Performance" table from BENCH_kernels.json +
BENCH_serve.json + BENCH_infer.json.

    PYTHONPATH=src python -m benchmarks.run        # writes the artifacts
    python scripts/update_perf_table.py            # splices the README table

The table is the curated DESIGN.md §7/§8 before/after story (two-pass vs
fused, separable vs direct, serial batch axis vs batch-folded parallel
grid) plus the §10 serving rows (sequential vs coalesced submission under
the mixed-shape load generator); the full row set stays in the JSON
artifacts. These are CPU interpreter timings, not speeds: chip numbers
are in PERF.md. Content between the BENCH_TABLE markers
is owned by this script.
"""
from __future__ import annotations

import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
START = "<!-- BENCH_TABLE_START -->"
END = "<!-- BENCH_TABLE_END -->"

#: (json row name, human label) in display order.
ROWS = [
    ("kernel_bank_gaussian5_sep_two_pass",
     "5×5 Gaussian, refmlm, separable, two kernels (HBM intermediate)"),
    ("kernel_bank_gaussian5_sep_fused",
     "5×5 Gaussian, refmlm, separable, **fused kernel** (VMEM halo band)"),
    ("kernel_bank_gaussian5_direct", "5×5 Gaussian, refmlm, direct (kh·kw taps)"),
    ("kernel_bank_gaussian3_n8_nofold",
     "3×3 Gaussian, refmlm, batch n=8, serial batch axis"),
    ("kernel_bank_gaussian3_n8",
     "3×3 Gaussian, refmlm, batch n=8, **batch-folded parallel grid** (§8)"),
    ("kernel_dist_gaussian5_local_n32",
     "5×5 Gaussian, refmlm, batch n=32, exec=local"),
    ("kernel_dist_gaussian5_sharded_n32",
     "5×5 Gaussian, refmlm, batch n=32, **exec=sharded** (8-device mesh, §9)"),
    ("kernel_dist_gaussian5_streamed_n32",
     "5×5 Gaussian, refmlm, batch n=32, exec=streamed (out-of-core 64×64 tiles, §9)"),
    ("serve_seq",
     "online serving, 4-client mixed load, sequential submission (µs = mean request latency)"),
    ("serve_coalesced",
     "online serving, 4-client mixed load, **coalesced micro-batching** (§10)"),
    ("serve_slo_static",
     "overloaded serving, mixed priorities, static flush policy (µs = mean post-admission latency)"),
    ("serve_slo_adaptive",
     "overloaded serving, mixed priorities, **SLO-adaptive batching + priority shedding** (§13)"),
    ("serve_obs_on",
     "online serving, coalesced, **tracing on** (§15; realistic-frame mix)"),
    ("infer_cnn_int8",
     "CNN inference (8×8, n=32), **exact-quantized int8 oracle** (§14; µs = batched forward)"),
    ("infer_cnn_refmlm",
     "CNN inference, **refmlm** -- bit-identical logits to the oracle (§14)"),
    ("infer_cnn_mitchell", "CNN inference, mitchell (approximate LNS)"),
    ("infer_cnn_mitchell_ecc2",
     "CNN inference, mitchell_ecc2 (Babic 2-bit error correction)"),
]
SPEEDUPS = [
    ("kernel_bank_gaussian5_fused_speedup", "fused vs two-pass"),
    ("kernel_bank_gaussian3_fold_speedup", "batch fold vs serial batch (n=8)"),
    ("kernel_bank_gaussian3_batch_scaling", "n=8 vs n=1 throughput"),
    ("kernel_dist_gaussian5_sharded_speedup", "sharded vs local (n=32, §9)"),
    ("serve_coalesce_speedup",
     "coalesced vs sequential serving throughput (§10)"),
    ("serve_slo_high_p99_gain",
     "static vs adaptive high-priority p99 under overload (§13)"),
    ("serve_obs_overhead",
     "observability off vs on throughput (§15; the <1.05× budget)"),
]


def build_table(bench: dict) -> str:
    missing = [n for n, _ in (*ROWS, *SPEEDUPS) if n not in bench]
    if missing:
        raise SystemExit(f"perf artifacts are missing rows {missing} -- "
                         "stale or partial artifact; rerun the benchmarks "
                         "(the kernel_dist_*_sharded rows need the process "
                         "started with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    lines = [
        "| variant (4×128×128 batch, interpret mode) | µs/call | derived |",
        "|---|---|---|",
    ]
    for name, label in ROWS:
        row = bench[name]
        lines.append(f"| {label} | {row['us_per_call']:.0f} | {row['derived']} |")
    parts = [f"{label}: **{bench[name]['us_per_call']:.1f}×**"
             for name, label in SPEEDUPS]
    ts = next(iter(bench.values()))["timestamp"]
    lines.append("")
    lines.append(f"{'; '.join(parts)} (measured {ts}; regenerate with "
                 "`python -m benchmarks.run` + this script).")
    return "\n".join(lines)


def main() -> int:
    readme_path = ROOT / "README.md"
    bench = {}
    for fname in ("BENCH_kernels.json", "BENCH_serve.json",
                  "BENCH_infer.json"):
        path = ROOT / fname
        if not path.exists():
            print(f"{fname} missing -- run `python -m benchmarks.run` "
                  "first (it writes every artifact)", file=sys.stderr)
            return 1
        bench.update(json.loads(path.read_text()))
    readme = readme_path.read_text()
    if START not in readme or END not in readme:
        print("README.md is missing the BENCH_TABLE markers", file=sys.stderr)
        return 1
    head, rest = readme.split(START, 1)
    _, tail = rest.split(END, 1)
    readme_path.write_text(f"{head}{START}\n{build_table(bench)}\n{END}{tail}")
    print("README.md performance table updated")
    return 0


if __name__ == "__main__":
    sys.exit(main())
