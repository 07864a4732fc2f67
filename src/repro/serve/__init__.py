"""`repro.serve` -- online image-filter serving on the REFMLM datapath
(DESIGN.md §10): a request queue with admission control, a shape-bucketed
micro-batcher coalescing concurrent same-shape requests into one batched
`apply_filter` call (riding the §8 batch fold), exec-mode routing through
`repro.distribute` (§9), a warm-start compile cache, and the §13
service-level machinery (SLO-adaptive batching, priorities/quotas, the
elastic executor pool).

Layers:
  request.py    -- `FilterRequest` / `FilterFuture`, the coalescing
                   `bucket_key` and the warm-cache `serve_key`, the §13
                   priority classes and weighted admission accounting;
  admission.py  -- weighted in-flight bound + per-tenant quotas +
                   backpressure (`AdmissionGate`, `ServerOverloaded`,
                   `TenantOverQuota`);
  batcher.py    -- the pure flush-policy state machine
                   (`ShapeBucketedBatcher`: size / deadline / drain,
                   priority-ordered flushes, deadline/overload shedding);
  controller.py -- `AdaptiveBatchController`, the §13 target-latency
                   feedback loop picking per-bucket flush size/deadline
                   from the warm plan-cost ledger;
  workload.py   -- the §14 pluggable `Workload` classes (validation,
                   dispatch, warmup, cost model); 'filter' is built in,
                   `repro.infer.serving.InferWorkload` adds 'infer';
  executor.py   -- micro-batch -> workload dispatch with the LRU plan
                   memo, pow-2 batch rounding, and the §12 bisection /
                   degraded-fallback machinery;
  pool.py       -- `ExecutorPool`, rendezvous-routed executors over
                   device subsets with probe-and-rebuild failover;
  server.py     -- `ImageFilterServer` (worker thread, `submit`, stats;
                   the §15 `trace=` observability knob and
                   the one-lock consistent `stats()` snapshot over the
                   shared `repro.obs.MetricsRegistry`);
  warmup.py     -- `python -m repro.serve.warmup` deploy-time pre-compiler.

    from repro.serve import ImageFilterServer, ServerConfig
    with ImageFilterServer(ServerConfig(max_batch=8, adaptive=True)) as srv:
        fut = srv.submit(img, "gaussian5", method="refmlm",
                         priority="high", slo_ms=50.0)
        out = fut.result()   # bit-identical to apply_filter(img, ...)

The load-bearing guarantee is paper faithfulness end to end: a request's
output is bit-identical no matter which coalesced batch, bucket, exec
mode, or pool member served it (tests/test_serve.py,
tests/test_serve_slo.py).
"""
from __future__ import annotations

from repro.serve.admission import (
    AdmissionGate,
    ServerClosed,
    ServerDegraded,
    ServerOverloaded,
    TenantOverQuota,
)
from repro.serve.batcher import (
    FLUSH_REASONS,
    SHED_CAUSES,
    FlushPolicy,
    MicroBatch,
    ShapeBucketedBatcher,
    ShedRequest,
)
from repro.serve.controller import AdaptiveBatchController
from repro.serve.executor import SCALE_OUT_MODES, BatchExecutor, next_pow2
from repro.serve.pool import ExecutorPool, PoolMember
from repro.serve.request import (
    PRIORITIES,
    DeadlineExceeded,
    FilterFuture,
    FilterRequest,
    bucket_key,
    request_weight,
    serve_key,
)
from repro.serve.server import ImageFilterServer, ServerConfig
from repro.serve.workload import FilterWorkload, Workload, resolve_workloads

__all__ = [
    "FLUSH_REASONS",
    "PRIORITIES",
    "SCALE_OUT_MODES",
    "SHED_CAUSES",
    "AdaptiveBatchController",
    "AdmissionGate",
    "BatchExecutor",
    "DeadlineExceeded",
    "ExecutorPool",
    "FilterFuture",
    "FilterRequest",
    "FilterWorkload",
    "FlushPolicy",
    "ImageFilterServer",
    "MicroBatch",
    "PoolMember",
    "ServerClosed",
    "ServerConfig",
    "ServerDegraded",
    "ServerOverloaded",
    "ShapeBucketedBatcher",
    "ShedRequest",
    "TenantOverQuota",
    "Workload",
    "bucket_key",
    "next_pow2",
    "request_weight",
    "resolve_workloads",
    "serve_key",
]
