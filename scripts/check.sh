#!/usr/bin/env bash
# Repo check: tier-1 test suite + documentation-link lint + perf smoke.
#
#   scripts/check.sh                run everything
#   scripts/check.sh --lint         doc-link lint only (fast)
#   scripts/check.sh --smoke-serve  serving SLO guard only (DESIGN.md §10)
#   scripts/check.sh --smoke-tune   plan-tuning guard only (DESIGN.md §11)
#   scripts/check.sh --smoke-fault  fault-tolerance guard only (DESIGN.md §12)
#   scripts/check.sh --smoke-slo    service-level guard only (DESIGN.md §13)
#   scripts/check.sh --smoke-infer  inference datapath guard only (DESIGN.md §14)
#   scripts/check.sh --smoke-obs    observability guard only (DESIGN.md §15)
#
# The perf smoke runs benchmarks/kernel_bench.py --smoke on a reduced size
# and fails if n=8 batched throughput falls below n=1 for any guarded bank
# filter (the DESIGN.md §8 batch-scaling guard). A generous 1.0x threshold
# so only a real inversion trips it.
#
# The multi-device smoke (--smoke-dist) restarts the bench with 8 host
# platform devices and fails if sharded/streamed output ever differs from
# local, or if sharded n=32 throughput falls below local n=32 on a guarded
# filter (the DESIGN.md §9 scale-out guard).
#
# The serving smoke (--smoke-serve, benchmarks/serve_bench.py --smoke) is
# the DESIGN.md §10 guard: coalesced micro-batching must not run slower
# than sequential submission, coalesced p99 latency must stay inside the
# SLO bound, the coalesced run must actually batch, and a served output is
# spot-checked bit-identical to the direct apply_filter call.
#
# The plan-tuning smoke (--smoke-tune, kernel_bench.py --smoke-tune) is the
# DESIGN.md §11 guard: a pruned replay of an exhaustive plan sweep must
# select the same winner while timing strictly fewer candidates (pruning
# may only save time, never flip the winner). Opt-in -- the exhaustive
# pass times every candidate, so it takes a few minutes.
#
# The fault smoke (--smoke-fault, serve_bench.py --smoke-fault) is the
# DESIGN.md §12 guard: a deterministically poisoned request must be
# isolated by the bisection retry with every neighbor served
# bit-identically, an expired per-request deadline must shed before any
# dispatch, a stream killed mid-run must resume from its tile journal to
# the exact cold-run bytes, and a drained server must end reporting
# healthy.
#
# The service-level smoke (--smoke-slo, serve_bench.py --smoke-slo) is the
# DESIGN.md §13 guard: under an overload run the highest priority class is
# never shed, the adaptive controller must hold the high-priority p99
# inside the SLO bound (and beat the throughput-tuned static deadline)
# without collapsing aggregate throughput, every served output must equal
# the direct apply_filter call byte for byte, and a pool member whose
# scale-out mesh is killed must drain to the survivor with zero
# client-visible failures.
#
# The inference smoke (--smoke-infer, benchmarks/infer_bench.py --smoke) is
# the DESIGN.md §14 guard: refmlm logits must be byte-equal to the
# exact-quantized int8 oracle on both the MLP head and the CNN classifier
# (the paper's zero-error theorem carried end to end through a network),
# mitchell_ecc2 top-1 agreement vs the oracle must clear the floor, and
# inference served through repro.serve at several flush sizes must return
# bytes equal to the direct forward call.
#
# The observability smoke (--smoke-obs, serve_bench.py --smoke-obs) is the
# DESIGN.md §15 guard: tracing must cost under 5% of coalesced
# throughput on realistic frames, a 50-request mixed-priority load must
# leave a complete well-formed trace (exactly one fulfil/shed/fail
# terminal per submitted request, stage timestamps monotone), the
# stats()/metrics snapshot schema keys must stay stable, and a served
# output must remain bit-identical with tracing on.
#
# The doc lint asserts that every `DESIGN.md §N` reference in src/ and
# benchmarks/ resolves to a real `## §N` section of DESIGN.md, so the code's
# design citations can never dangle again.
set -euo pipefail
cd "$(dirname "$0")/.."

lint() {
  python - <<'EOF'
import pathlib, re, sys

root = pathlib.Path(".")
design = root / "DESIGN.md"
if not design.exists():
    sys.exit("FAIL: DESIGN.md is missing but src/ cites it")
sections = set(re.findall(r"^##\s+§(\d+)", design.read_text(), re.M))

bad = []
refs = 0
for base in ("src", "benchmarks"):
    for path in sorted(root.glob(f"{base}/**/*.py")):
        for i, line in enumerate(path.read_text().splitlines(), 1):
            for sec in re.findall(r"DESIGN\.md\s+§(\d+)", line):
                refs += 1
                if sec not in sections:
                    bad.append(f"{path}:{i}: DESIGN.md §{sec} (have: "
                               f"{sorted(sections, key=int)})")
if bad:
    sys.exit("FAIL: dangling DESIGN.md section references:\n" + "\n".join(bad))
print(f"doc-link lint OK: {refs} DESIGN.md §-references resolve "
      f"({len(sections)} sections)")
EOF
}

if [[ "${1:-}" == "--smoke-serve" ]]; then
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.serve_bench --smoke
  exit 0
fi

if [[ "${1:-}" == "--smoke-tune" ]]; then
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.kernel_bench --smoke-tune
  exit 0
fi

if [[ "${1:-}" == "--smoke-fault" ]]; then
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.serve_bench --smoke-fault
  exit 0
fi

if [[ "${1:-}" == "--smoke-slo" ]]; then
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.serve_bench --smoke-slo
  exit 0
fi

if [[ "${1:-}" == "--smoke-infer" ]]; then
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.infer_bench --smoke
  exit 0
fi

if [[ "${1:-}" == "--smoke-obs" ]]; then
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.serve_bench --smoke-obs
  exit 0
fi

lint
if [[ "${1:-}" == "--lint" ]]; then
  exit 0
fi

PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q

echo "== perf smoke (kernel_bench --smoke) =="
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.kernel_bench --smoke

echo "== multi-device smoke (kernel_bench --smoke-dist, 8 host devices) =="
XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}" \
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
  python -m benchmarks.kernel_bench --smoke-dist

echo "== serving smoke (serve_bench --smoke) =="
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.serve_bench --smoke

echo "== fault-tolerance smoke (serve_bench --smoke-fault) =="
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.serve_bench --smoke-fault

echo "== service-level smoke (serve_bench --smoke-slo) =="
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.serve_bench --smoke-slo

echo "== inference smoke (infer_bench --smoke) =="
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.infer_bench --smoke

echo "== observability smoke (serve_bench --smoke-obs) =="
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.serve_bench --smoke-obs
